"""Continuous batching: slot reuse, per-request exactness, eos handling,
pipelined-vs-sequential equivalence, bucketed/batched admission, and the
closed-batch-over-open-loop-engine equivalence pin."""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tony_tpu.models import transformer as T
from tony_tpu.models.decode import generate
from tony_tpu.models.serve import (ContinuousBatcher, ServeEngine,
                                   SpeculativeContinuousBatcher)
from tony_tpu.runtime.metrics import MetricsRegistry

CFG = T.PRESETS["tiny"].scaled(dtype=jnp.float32, remat=False)


@pytest.fixture(scope="module")
def params():
    return T.init_params(jax.random.PRNGKey(0), CFG)


def _reference(params, prompt, max_new):
    out = generate(params, jnp.asarray(prompt, jnp.int32)[None], CFG,
                   max_new_tokens=max_new, rng=jax.random.PRNGKey(0),
                   temperature=0.0)
    return [int(t) for t in np.asarray(out.tokens[0, len(prompt):])]


class TestContinuousBatching:
    def test_token_identical_with_slot_reuse(self, params):
        """6 requests of mixed lengths through 3 slots: every request's
        output equals its solo greedy generate — including requests
        admitted into a REUSED slot whose cache still holds the previous
        occupant's stale K/V beyond the frontier."""
        rng = np.random.RandomState(0)
        prompts = [list(rng.randint(0, CFG.vocab_size, size=n))
                   for n in (5, 3, 7, 4, 6, 3)]
        batcher = ContinuousBatcher(params, CFG, batch=3, max_len=32,
                                    chunk=4)
        outs = batcher.serve(prompts, max_new_tokens=6)
        for i, p in enumerate(prompts):
            assert outs[i] == _reference(params, p, 6), f"request {i}"

    def test_quantized_cache_token_identical_to_quant_generate(self,
                                                               params):
        """int8 KV serving: the batcher with a quantized cache equals
        per-request generate under the SAME quantized config (quant-to-
        quant is deterministic — per-row math is batch-independent on
        CPU; quant-to-float agreement is approximate by design). Slot
        reuse included."""
        qcfg = CFG.scaled(kv_cache_dtype="int8")
        rng = np.random.RandomState(7)
        prompts = [list(rng.randint(0, qcfg.vocab_size, size=n))
                   for n in (5, 3, 6, 4)]
        batcher = ContinuousBatcher(params, qcfg, batch=2, max_len=32,
                                    chunk=4)
        outs = batcher.serve(prompts, max_new_tokens=6)
        for i, p in enumerate(prompts):
            want = generate(params, jnp.asarray(p, jnp.int32)[None],
                            qcfg, max_new_tokens=6,
                            rng=jax.random.PRNGKey(0), temperature=0.0)
            assert outs[i] == [int(t) for t in
                               np.asarray(want.tokens[0, len(p):])], \
                f"request {i}"

    def test_single_slot_serializes_correctly(self, params):
        """batch=1 degenerates to sequential serving — same outputs."""
        rng = np.random.RandomState(1)
        prompts = [list(rng.randint(0, CFG.vocab_size, size=n))
                   for n in (4, 6)]
        batcher = ContinuousBatcher(params, CFG, batch=1, max_len=32,
                                    chunk=3)
        outs = batcher.serve(prompts, max_new_tokens=5)
        for i, p in enumerate(prompts):
            assert outs[i] == _reference(params, p, 5)

    def test_eos_stops_a_row_early(self, params):
        """A request whose greedy chain hits eos stops there (eos token
        included), freeing the slot; others run to their budget."""
        rng = np.random.RandomState(2)
        prompts = [list(rng.randint(0, CFG.vocab_size, size=n))
                   for n in (5, 4)]
        ref0 = _reference(params, prompts[0], 6)
        eos = ref0[2]            # third generated token of request 0
        batcher = ContinuousBatcher(params, CFG, batch=2, max_len=32,
                                    eos_id=eos, chunk=2)
        outs = batcher.serve(prompts, max_new_tokens=6)
        assert outs[0] == ref0[:3]          # stopped AT the eos token
        ref1 = _reference(params, prompts[1], 6)
        cut = (ref1.index(eos) + 1) if eos in ref1 else 6
        assert outs[1] == ref1[:cut]

    def test_prompt_too_long_rejected(self, params):
        batcher = ContinuousBatcher(params, CFG, batch=1, max_len=16)
        with pytest.raises(ValueError, match="exceeds max_len"):
            batcher.serve([[1] * 14], max_new_tokens=8)

    def test_per_request_budgets(self, params):
        """Mixed generation budgets (the case continuous batching exists
        for): each request stops at ITS budget and slots recycle."""
        rng = np.random.RandomState(3)
        prompts = [list(rng.randint(0, CFG.vocab_size, size=4))
                   for _ in range(4)]
        budgets = [2, 7, 3, 5]
        batcher = ContinuousBatcher(params, CFG, batch=2, max_len=32,
                                    chunk=3)
        outs = batcher.serve(prompts, budgets)
        for i, (p, b) in enumerate(zip(prompts, budgets)):
            assert outs[i] == _reference(params, p, b), f"request {i}"
        assert batcher.steps_executed >= max(budgets)

    def test_idle_slots_do_not_march(self, params, monkeypatch):
        """Queue drained with a straggler still running: freed slots are
        reset EVERY chunk (not just once), so an idle slot's garbage
        frontier cannot walk toward the cache end. Asserted on the
        retire masks themselves (a final-state length check is vacuous
        — serve()'s last iteration resets all rows anyway)."""
        import tony_tpu.models.serve as S
        masks = []
        orig = S.retire_rows

        def spy(cache, mask):
            masks.append(np.asarray(mask))
            return orig(cache, mask)

        monkeypatch.setattr(S, "retire_rows", spy)
        rng = np.random.RandomState(4)
        prompts = [list(rng.randint(0, CFG.vocab_size, size=4))
                   for _ in range(3)]
        batcher = ContinuousBatcher(params, CFG, batch=3, max_len=32,
                                    chunk=2)
        outs = batcher.serve(prompts, [2, 2, 12])
        for i, (p, b) in enumerate(zip(prompts, [2, 2, 12])):
            assert outs[i] == _reference(params, p, b)
        # rows 0 and 1 free after ~1 chunk; the straggler needs ~6 — the
        # idle rows must be re-reset on EVERY subsequent chunk
        both_idle = [m for m in masks if m[0] and m[1]]
        assert len(both_idle) >= 3, [list(m) for m in masks]

    def test_invalid_request_rejected_before_serving(self, params):
        """A bad request ANYWHERE in the list fails up front — no partial
        serve that would discard completed outputs mid-flight."""
        batcher = ContinuousBatcher(params, CFG, batch=1, max_len=16)
        with pytest.raises(ValueError, match="request 1"):
            batcher.serve([[1, 2], [1] * 14], max_new_tokens=8)
        with pytest.raises(ValueError, match="must be positive"):
            batcher.serve([[1, 2]], max_new_tokens=0)
        with pytest.raises(ValueError, match="empty prompt"):
            batcher.serve([[1, 2], []], max_new_tokens=4)


class TestSharedPrefix:
    """Shared-prefix caching: the prefix prefills once into a K/V
    template; admission copies it and runs only the request's suffix."""

    def _refs(self, params, prefix, suffixes, budgets):
        out = []
        for sfx, b in zip(suffixes, budgets):
            full = jnp.asarray(prefix + sfx, jnp.int32)[None]
            g = generate(params, full, CFG, max_new_tokens=b,
                         rng=jax.random.PRNGKey(0), temperature=0.0)
            out.append([int(t) for t in
                        np.asarray(g.tokens[0, full.shape[1]:])])
        return out

    def test_greedy_prefix_serving_token_identical(self, params):
        """Serving suffixes against a shared prefix equals per-request
        greedy decode of prefix+suffix — including slot reuse, where a
        new occupant's template copy overwrites the previous request's
        K/V."""
        rs = np.random.RandomState(7)
        prefix = [int(t) for t in rs.randint(0, CFG.vocab_size, size=9)]
        suffixes = [list(rs.randint(0, CFG.vocab_size,
                                    size=rs.randint(2, 6)))
                    for _ in range(5)]
        budgets = [int(b) for b in rs.randint(4, 9, size=5)]
        batcher = ContinuousBatcher(params, CFG, batch=2, max_len=48,
                                    chunk=3, shared_prefix=prefix)
        outs = batcher.serve(suffixes, budgets)
        assert outs == self._refs(params, prefix, suffixes, budgets)

    def test_speculative_prefix_serving_token_identical(self, params):
        """The speculative batcher's prefix admission fills BOTH models'
        caches from their own templates; greedy rounds stay token-exact."""
        draft = T.init_params(jax.random.PRNGKey(99), CFG)
        rs = np.random.RandomState(8)
        prefix = [int(t) for t in rs.randint(0, CFG.vocab_size, size=7)]
        suffixes = [list(rs.randint(0, CFG.vocab_size, size=3))
                    for _ in range(4)]
        budgets = [5, 7, 4, 6]
        batcher = SpeculativeContinuousBatcher(
            params, CFG, draft, CFG, batch=2, max_len=48,
            num_speculative=3, chunk=2, shared_prefix=prefix)
        outs = batcher.serve(suffixes, budgets)
        assert outs == self._refs(params, prefix, suffixes, budgets)

    def test_prefix_budget_validation(self, params):
        batcher = ContinuousBatcher(params, CFG, batch=1, max_len=16,
                                    shared_prefix=[1, 2, 3, 4])
        with pytest.raises(ValueError, match="shared prefix 4"):
            batcher.serve([[5] * 6], max_new_tokens=8)
        with pytest.raises(ValueError, match="non-empty"):
            ContinuousBatcher(params, CFG, batch=1, max_len=16,
                              shared_prefix=[])


class TestSampledServing:
    """temperature/top_k/top_p on the continuous batcher: valid tokens,
    seed-reproducible workloads, seed-sensitive outputs."""

    def test_sampled_serve_reproducible_by_seed(self, params):
        rng = np.random.RandomState(5)
        prompts = [list(rng.randint(0, CFG.vocab_size, size=4))
                   for _ in range(5)]

        def run(seed):
            b = ContinuousBatcher(params, CFG, batch=2, max_len=32,
                                  chunk=3, temperature=0.8, top_k=50,
                                  top_p=0.9, seed=seed)
            return b.serve(prompts, max_new_tokens=6)

        outs = run(0)
        for o in outs:
            assert len(o) == 6
            assert all(0 <= t < CFG.vocab_size for t in o)
        assert outs == run(0)          # same seed, same workload
        assert outs != run(1)          # overwhelmingly likely

    def test_greedy_default_unchanged_by_seed(self, params):
        rng = np.random.RandomState(6)
        prompts = [list(rng.randint(0, CFG.vocab_size, size=4))
                   for _ in range(3)]
        a = ContinuousBatcher(params, CFG, batch=2, max_len=32,
                              chunk=3, seed=0).serve(prompts, 5)
        b = ContinuousBatcher(params, CFG, batch=2, max_len=32,
                              chunk=3, seed=7).serve(prompts, 5)
        assert a == b
        for i, p in enumerate(prompts):
            assert a[i] == _reference(params, p, 5)


class TestSpeculativeContinuousBatching:
    """Continuous batching composed with speculative decoding: every
    slot runs draft-propose/target-verify rounds at its own frontier
    and commits its own acceptance; slot reuse/retirement identical to
    the greedy batcher."""

    def test_token_identical_with_slot_reuse(self, params):
        """7 mixed-length requests with mixed budgets through 3 slots,
        self-draft and rejecting draft: every request equals its solo
        greedy generate, and the self-draft (full acceptance) finishes
        in strictly fewer speculative rounds."""
        draft = T.init_params(jax.random.PRNGKey(99), CFG)
        rng = np.random.RandomState(3)
        prompts = [list(rng.randint(0, CFG.vocab_size,
                                    size=rng.randint(3, 9)))
                   for _ in range(7)]
        budgets = [int(b) for b in rng.randint(4, 14, size=7)]
        rounds = {}
        for d, name in ((params, "self"), (draft, "rej")):
            batcher = SpeculativeContinuousBatcher(
                params, CFG, d, CFG, batch=3, max_len=64,
                num_speculative=3, chunk=2)
            outs = batcher.serve(prompts, budgets)
            for i, (p, b) in enumerate(zip(prompts, budgets)):
                assert outs[i] == _reference(params, p, b), (name, i)
            rounds[name] = batcher.rounds_executed
        assert rounds["self"] < rounds["rej"]

    def test_eos_frees_slot_early(self, params):
        """A request hitting eos mid-speculative-chunk stops there (eos
        included, surplus committed tokens discarded) and its slot is
        recycled."""
        rng = np.random.RandomState(2)
        prompts = [list(rng.randint(0, CFG.vocab_size, size=n))
                   for n in (5, 4, 6)]
        ref0 = _reference(params, prompts[0], 8)
        eos = ref0[2]
        batcher = SpeculativeContinuousBatcher(
            params, CFG, params, CFG, batch=2, max_len=64,
            num_speculative=4, eos_id=eos, chunk=2)
        outs = batcher.serve(prompts, max_new_tokens=8)
        assert outs[0] == ref0[:3]
        for i in (1, 2):
            ref = _reference(params, prompts[i], 8)
            cut = (ref.index(eos) + 1) if eos in ref else 8
            assert outs[i] == ref[:cut]

    def test_bad_num_speculative_rejected(self, params):
        with pytest.raises(ValueError, match="num_speculative"):
            SpeculativeContinuousBatcher(params, CFG, params, CFG,
                                         batch=2, max_len=32,
                                         num_speculative=0)

    def test_vocab_mismatch_rejected(self, params):
        """A draft with a different vocabulary is silent corruption in
        greedy mode and a shape error in sampled mode — rejected up
        front, at the batcher AND at the generate-path entry points."""
        from tony_tpu.models.decode import (speculative_generate,
                                            speculative_generate_device)

        bad_cfg = CFG.scaled(vocab_size=CFG.vocab_size // 2)
        bad = T.init_params(jax.random.PRNGKey(1), bad_cfg)
        with pytest.raises(ValueError, match="vocab"):
            SpeculativeContinuousBatcher(params, CFG, bad, bad_cfg,
                                         batch=2, max_len=32)
        prompt = jnp.asarray([[1, 2, 3]], jnp.int32)
        with pytest.raises(ValueError, match="vocab"):
            speculative_generate_device(params, bad, prompt, CFG, bad_cfg,
                                        max_new_tokens=4,
                                        num_speculative=2)
        with pytest.raises(ValueError, match="vocab"):
            speculative_generate(params, bad, prompt, CFG, bad_cfg,
                                 max_new_tokens=4, num_speculative=2)

    @pytest.mark.slow
    def test_sampled_speculative_serving_matches_target_distribution(self):
        """Sampled speculative serving (rejection-sampling rounds inside
        the continuous batcher): each served request's tokens are
        distributed as direct target sampling, for a MISMATCHED draft —
        measured on the 2-token joint over many served requests, with a
        draft-only baseline proving the tolerance discriminates. Also
        pins seed-reproducibility of a whole served workload."""
        from tony_tpu.models.decode import generate as gen

        cfg = T.TransformerConfig(vocab_size=11, d_model=24, n_layers=2,
                                  n_heads=2, d_ff=48, max_seq=1024,
                                  dtype=jnp.float32,
                                  logits_dtype=jnp.float32, remat=False)
        params = T.init_params(jax.random.PRNGKey(0), cfg)
        draft = T.init_params(jax.random.PRNGKey(99), cfg)
        prompt = [3, 7, 1, 5]
        n_req, n = 192, 2

        def joint_serve(seed):
            b = SpeculativeContinuousBatcher(
                params, cfg, draft, cfg, batch=48, max_len=32,
                num_speculative=3, chunk=1, temperature=1.1, top_k=6,
                seed=seed)
            outs = b.serve([prompt] * n_req, n)
            c = np.zeros((cfg.vocab_size, cfg.vocab_size))
            for o in outs:
                c[o[0], o[1]] += 1
            return c

        counts = sum(joint_serve(s) for s in range(8))
        spec_p = counts / counts.sum()

        pm = jnp.asarray([prompt], jnp.int32).repeat(n_req, 0)

        def joint_gen(model, seed0):
            c = np.zeros((cfg.vocab_size, cfg.vocab_size))
            for i in range(8):
                a = np.asarray(gen(model, pm, cfg, max_new_tokens=n,
                                   rng=jax.random.PRNGKey(seed0 + i),
                                   temperature=1.1,
                                   top_k=6).tokens[:, -n:])
                for r in a:
                    c[r[0], r[1]] += 1
            return c / c.sum()

        ref_p = joint_gen(params, 40)
        ref2_p = joint_gen(params, 400)      # independent same-dist run
        draft_p = joint_gen(draft, 80)
        tv_spec = 0.5 * np.abs(spec_p - ref_p).sum()
        tv_noise = 0.5 * np.abs(ref2_p - ref_p).sum()
        tv_draft = 0.5 * np.abs(draft_p - ref_p).sum()
        # self-calibrated: within ~2x of same-distribution sampling
        # noise at this sample count (and far under the draft's gap)
        assert tv_spec < max(0.1, 2.0 * tv_noise), (tv_spec, tv_noise)
        assert tv_draft > 0.3, tv_draft

        # whole-workload reproducibility by seed
        b1 = SpeculativeContinuousBatcher(
            params, cfg, draft, cfg, batch=3, max_len=32,
            num_speculative=3, chunk=2, temperature=1.1, top_k=6, seed=7)
        o1 = b1.serve([prompt] * 5, 6)
        b2 = SpeculativeContinuousBatcher(
            params, cfg, draft, cfg, batch=3, max_len=32,
            num_speculative=3, chunk=2, temperature=1.1, top_k=6, seed=7)
        assert o1 == b2.serve([prompt] * 5, 6)

    def test_spec_sampled_pipelined_equals_sequential(self, params):
        """Sampled speculative serving: per-request round-key streams
        make the pipelined loop's shifted admissions invisible — both
        loops produce identical sampled streams."""
        draft = T.init_params(jax.random.PRNGKey(99), CFG)
        rng = np.random.RandomState(11)
        prompts = [list(rng.randint(0, CFG.vocab_size, size=n))
                   for n in (4, 6, 3)]
        budgets = [5, 3, 4]

        def run(pipeline):
            b = SpeculativeContinuousBatcher(
                params, CFG, draft, CFG, batch=2, max_len=48,
                num_speculative=2, chunk=2, temperature=0.9, top_k=6,
                seed=3, pipeline=pipeline)
            return b.serve(prompts, budgets)

        assert run(True) == run(False)

    def test_distinct_draft_config(self, params):
        """The draft may have a different architecture (the production
        shape: a much smaller model) — caches sized per-config."""
        dcfg = CFG.scaled(n_layers=1, d_model=32, n_heads=2, d_ff=64)
        draft = T.init_params(jax.random.PRNGKey(5), dcfg)
        rng = np.random.RandomState(7)
        prompts = [list(rng.randint(0, CFG.vocab_size, size=5))
                   for _ in range(4)]
        batcher = SpeculativeContinuousBatcher(
            params, CFG, draft, dcfg, batch=2, max_len=48,
            num_speculative=3, chunk=3)
        outs = batcher.serve(prompts, max_new_tokens=7)
        for i, p in enumerate(prompts):
            assert outs[i] == _reference(params, p, 7), f"request {i}"


class TestPipelinedServing:
    """Double-buffered dispatch: chunk N+1 is issued before chunk N's
    tokens are fetched. Outputs must be token-identical to the
    sequential loop in EVERY mode — the eos workloads force the
    catch-up path (a speculatively issued chunk crossing an
    unpredictable completion, whose garbage rows are discarded and
    whose admission lands late).

    Compile frugality: these tests deliberately REUSE the workloads and
    static shapes of the earlier equivalence tests (same RandomState
    seeds, batch/max_len/chunk/sampling combos), so the pipelined and
    sequential runs hit the already-compiled device programs and the
    solo-generate references hit generate()'s jit cache — the suite
    pays serve-loop wall time, not a second compile bill."""

    def test_greedy_pipelined_equals_sequential_and_reference(self,
                                                              params):
        # the test_token_identical_with_slot_reuse workload, verbatim
        rng = np.random.RandomState(0)
        prompts = [list(rng.randint(0, CFG.vocab_size, size=n))
                   for n in (5, 3, 7, 4, 6, 3)]

        def run(pipeline):
            b = ContinuousBatcher(params, CFG, batch=3, max_len=32,
                                  chunk=4, pipeline=pipeline)
            return b.serve(prompts, max_new_tokens=6)

        pipelined, sequential = run(True), run(False)
        assert pipelined == sequential
        for i, p in enumerate(prompts):
            assert pipelined[i] == _reference(params, p, 6), i

    def test_greedy_eos_catchup_path(self, params):
        """eos completions are invisible to host budget bookkeeping, so
        the pipelined loop speculates across them and must catch up —
        discarding the freed rows' speculatively-decoded garbage and
        admitting late — without changing any output. (The
        test_eos_stops_a_row_early workload plus a third request so an
        admission rides the catch-up.)"""
        rng = np.random.RandomState(2)
        prompts = [list(rng.randint(0, CFG.vocab_size, size=n))
                   for n in (5, 4, 5)]
        ref0 = _reference(params, prompts[0], 6)
        eos = ref0[2]

        def run(pipeline):
            b = ContinuousBatcher(params, CFG, batch=2, max_len=32,
                                  eos_id=eos, chunk=2,
                                  pipeline=pipeline)
            return b.serve(prompts, max_new_tokens=6)

        pipelined = run(True)
        assert pipelined == run(False)
        for i, p in enumerate(prompts):
            ref = _reference(params, p, 6)
            cut = (ref.index(eos) + 1) if eos in ref else 6
            assert pipelined[i] == ref[:cut], i

    def test_sampled_pipelined_equals_sequential_with_eos(self, params):
        """Sampled serving under eos: admission timing CAN shift between
        the loops here, so equality hangs entirely on the per-request
        key streams. (Sampling params match
        test_sampled_serve_reproducible_by_seed — same compiled step
        program.)"""
        rng = np.random.RandomState(5)
        prompts = [list(rng.randint(0, CFG.vocab_size, size=4))
                   for _ in range(5)]

        def run(pipeline, eos=None):
            b = ContinuousBatcher(params, CFG, batch=2, max_len=32,
                                  chunk=3, temperature=0.8, top_k=50,
                                  top_p=0.9, seed=0, eos_id=eos,
                                  pipeline=pipeline)
            return b.serve(prompts, max_new_tokens=6)

        no_eos = run(True)
        assert no_eos == run(False)
        eos = no_eos[0][0]                   # a token that DOES occur
        assert run(True, eos=eos) == run(False, eos=eos)

    def test_sampled_output_independent_of_slot_count(self, params):
        """The per-request stream guarantee, stated directly: a sampled
        request's output is a function of (seed, request index, prompt)
        alone — re-serving the same workload through a different slot
        count (completely different admission timing) reproduces every
        output. The pre-pipelining shared-stream scheme could not do
        this."""
        rng = np.random.RandomState(5)
        prompts = [list(rng.randint(0, CFG.vocab_size, size=4))
                   for _ in range(5)]

        def run(batch):
            b = ContinuousBatcher(params, CFG, batch=batch, max_len=32,
                                  chunk=3, temperature=0.8, top_k=50,
                                  top_p=0.9, seed=0)
            return b.serve(prompts, max_new_tokens=6)

        assert run(1) == run(2)

    def test_speculative_pipelined_equals_sequential(self, params):
        """Greedy speculative serving with eos mid-chunk (the spec
        test_token_identical workload shapes): catch-up discards a freed
        slot's speculatively-run ROUNDS, not just steps."""
        draft = T.init_params(jax.random.PRNGKey(99), CFG)
        rng = np.random.RandomState(3)
        prompts = [list(rng.randint(0, CFG.vocab_size,
                                    size=rng.randint(3, 9)))
                   for _ in range(5)]
        budgets = [int(b) for b in rng.randint(4, 14, size=5)]
        ref0 = _reference(params, prompts[0], budgets[0])
        eos = ref0[-1]

        def run(pipeline):
            b = SpeculativeContinuousBatcher(
                params, CFG, draft, CFG, batch=3, max_len=64,
                num_speculative=3, chunk=2, eos_id=eos,
                pipeline=pipeline)
            return b.serve(prompts, budgets)

        pipelined = run(True)
        assert pipelined == run(False)
        for i, (p, bud) in enumerate(zip(prompts, budgets)):
            ref = _reference(params, p, bud)
            cut = (ref.index(eos) + 1) if eos in ref else bud
            assert pipelined[i] == ref[:cut], i

    def test_shared_prefix_pipelined_equals_sequential(self, params):
        # the test_greedy_prefix_serving workload, verbatim (same
        # template/admission/step programs and cached references)
        rs = np.random.RandomState(7)
        prefix = [int(t) for t in rs.randint(0, CFG.vocab_size, size=9)]
        suffixes = [list(rs.randint(0, CFG.vocab_size,
                                    size=rs.randint(2, 6)))
                    for _ in range(5)]
        budgets = [int(b) for b in rs.randint(4, 9, size=5)]

        def run(pipeline):
            b = ContinuousBatcher(params, CFG, batch=2, max_len=48,
                                  chunk=3, shared_prefix=prefix,
                                  pipeline=pipeline)
            return b.serve(suffixes, budgets)

        pipelined = run(True)
        assert pipelined == run(False)
        full0 = jnp.asarray(prefix + suffixes[0], jnp.int32)[None]
        g = generate(params, full0, CFG, max_new_tokens=budgets[0],
                     rng=jax.random.PRNGKey(0), temperature=0.0)
        assert pipelined[0] == [
            int(t) for t in np.asarray(g.tokens[0, full0.shape[1]:])]

    def test_budget_only_workload_matches_sequential_steps(self, params):
        """With no eos, completions are budget-predictable, so the
        pipelined loop defers issuing across admission events and pays
        ZERO extra device steps — step utilization is identical to the
        sequential loop, not merely close."""
        rng = np.random.RandomState(3)
        prompts = [list(rng.randint(0, CFG.vocab_size, size=4))
                   for _ in range(4)]
        budgets = [2, 7, 3, 5]

        def steps(pipeline):
            b = ContinuousBatcher(params, CFG, batch=2, max_len=32,
                                  chunk=3, pipeline=pipeline)
            outs = b.serve(prompts, budgets)
            assert [len(o) for o in outs] == budgets
            return b.steps_executed

        assert steps(True) == steps(False)

    def test_phase_times_recorded(self, params):
        rng = np.random.RandomState(3)
        prompts = [list(rng.randint(0, CFG.vocab_size, size=4))
                   for _ in range(3)]
        b = ContinuousBatcher(params, CFG, batch=2, max_len=32, chunk=3)
        b.serve(prompts, max_new_tokens=5)
        s = b.phase_times.summary()
        for phase in ("dispatch", "fetch", "admit"):
            assert s[phase]["count"] > 0, s
            assert s[phase]["total_s"] >= 0.0
        # every fetched chunk was first dispatched (the loop may drop at
        # most the final speculative chunk unfetched)
        assert 0 <= (b.phase_times.count("dispatch")
                     - b.phase_times.count("fetch")) <= 1


class TestBucketedAdmission:
    """Admission pads prompts to power-of-two length buckets and lands
    every slot freed in a chunk in one batched dispatch: at most ONE
    compiled program per bucket, however many distinct prompt lengths
    the workload carries."""

    def test_one_program_per_bucket(self, params, retrace_guard):
        """8 distinct prompt lengths spanning two buckets (<=16 and
        <=32) through repeated slot reuse: at most the two bucket
        programs may trace, and the ring's per-length program must not
        trace at all."""
        rng = np.random.RandomState(30)
        lengths = [3, 4, 5, 7, 9, 17, 20, 23]
        prompts = [list(rng.randint(0, CFG.vocab_size, size=n))
                   for n in lengths]
        batcher = ContinuousBatcher(params, CFG, batch=2, max_len=48,
                                    chunk=3)
        outs = batcher.serve(prompts, max_new_tokens=4)
        retrace_guard.assert_max("admit_rows", 2)     # one per bucket
        retrace_guard.assert_max("admit_row_ring", 0)  # no ring here
        # spot-check one short and one long (bucket-32) request against
        # solo generate; full-coverage exactness is pinned elsewhere
        assert outs[0] == _reference(params, prompts[0], 4)
        assert outs[6] == _reference(params, prompts[6], 4)
        assert all(len(o) == 4 for o in outs)

    def test_distinct_lengths_same_bucket_share_one_program(
            self, params, retrace_guard):
        """The core claim in isolation: lengths 3..10 all pad to one
        16-token bucket — at most one trace total."""
        rng = np.random.RandomState(31)
        prompts = [list(rng.randint(0, CFG.vocab_size, size=n))
                   for n in (3, 4, 5, 6, 7, 8, 9, 10)]
        batcher = ContinuousBatcher(params, CFG, batch=2, max_len=48,
                                    chunk=3)
        outs = batcher.serve(prompts, max_new_tokens=4)
        retrace_guard.assert_max("admit_rows", 1)
        assert outs[0] == _reference(params, prompts[0], 4)
        assert all(len(o) == 4 for o in outs)

    def test_batched_admission_multiple_slots_one_chunk(self, params):
        """Equal budgets retire every slot in the SAME chunk, so each
        admission wave lands multiple requests in one admit_rows
        dispatch — outputs stay per-request exact."""
        rng = np.random.RandomState(0)
        prompts = [list(rng.randint(0, CFG.vocab_size, size=n))
                   for n in (5, 3, 7, 4, 6, 3)]
        batcher = ContinuousBatcher(params, CFG, batch=3, max_len=32,
                                    chunk=4)
        outs = batcher.serve(prompts, max_new_tokens=6)
        for i, p in enumerate(prompts):
            assert outs[i] == _reference(params, p, 6), i

    def test_ring_cache_falls_back_to_per_length_admission(
            self, params, retrace_guard):
        """Rolling caches cannot take padded prompts (wrapped writes
        would land padding on live ring rows): the batcher routes
        admission through admit_row_ring, one program a distinct prompt
        length, and still serves correctly (pipelined == sequential
        under the ring too)."""
        rcfg = CFG.scaled(attn_window=8, kv_cache_capacity=8)
        rng = np.random.RandomState(34)
        prompts = [list(rng.randint(0, CFG.vocab_size, size=n))
                   for n in (5, 3)]

        def run(pipeline):
            b = ContinuousBatcher(params, rcfg, batch=2, max_len=32,
                                  chunk=3, pipeline=pipeline)
            return b.serve(prompts, max_new_tokens=4)

        outs = run(True)
        retrace_guard.assert_max("admit_rows", 0)
        assert set(retrace_guard.new_traces("admit_row_ring")) == {
            (1, 5), (1, 3)}
        assert outs == run(False)
        for o in outs:
            assert len(o) == 4
            assert all(0 <= t < rcfg.vocab_size for t in o)

    def test_custom_admission_bucket_ladder(self, params):
        rng = np.random.RandomState(0)
        prompts = [list(rng.randint(0, CFG.vocab_size, size=n))
                   for n in (5, 3, 7)]
        batcher = ContinuousBatcher(params, CFG, batch=3, max_len=32,
                                    chunk=4, admission_buckets=(8,))
        outs = batcher.serve(prompts, max_new_tokens=6)
        for i, p in enumerate(prompts):
            assert outs[i] == _reference(params, p, 6), i
        with pytest.raises(ValueError, match="admission_buckets"):
            ContinuousBatcher(params, CFG, batch=2, max_len=32,
                              admission_buckets=(0, 8))

    def test_speculative_bucketed_admission_one_program_per_bucket(
            self, params, retrace_guard):
        draft = T.init_params(jax.random.PRNGKey(99), CFG)
        rng = np.random.RandomState(3)
        prompts = [list(rng.randint(0, CFG.vocab_size,
                                    size=rng.randint(3, 9)))
                   for _ in range(6)]
        batcher = SpeculativeContinuousBatcher(
            params, CFG, draft, CFG, batch=3, max_len=64,
            num_speculative=3, chunk=2)
        outs = batcher.serve(prompts, max_new_tokens=5)
        retrace_guard.assert_max("spec_admit_rows", 1)
        assert outs[0] == _reference(params, prompts[0], 5)
        assert all(len(o) == 5 for o in outs)


class TestClosedBatchEngineEquivalence:
    """The engine-refactor pin: ``serve()`` rebuilt as a thin wrapper
    over the open-loop :class:`ServeEngine` stays BIT-identical in
    outputs — and, for the single-token-per-step modes on budget-only
    workloads, identical in ``steps_executed`` — to the pre-refactor
    fixed-queue loop, across greedy / sampled / speculative /
    shared-prefix modes. The pre-refactor contract is the per-mode
    solo-generate references (PR 1's pins, all asserted above) plus
    pipelined==sequential equality; this class additionally pins that
    an OPEN-LOOP run (incremental submission from another thread, per-
    request rng streams doing the heavy lifting) produces the same
    tokens as the closed batch.

    Workloads/shapes deliberately REUSE the earlier tests' (same seeds,
    batch/max_len/chunk combos) so everything here hits already-
    compiled programs."""

    def _open_loop(self, batcher, prompts, budgets):
        outs: dict = {i: [] for i in range(len(prompts))}
        reg = MetricsRegistry()
        padded0 = batcher.prefill_padded_tokens   # a template's forward
        eng = ServeEngine(
            batcher, on_delta=lambda r, t: outs[r].extend(t),
            on_retired=lambda r, reason, n, final: outs[r].extend(final),
            registry=reg)
        th = threading.Thread(target=eng.run, daemon=True)
        th.start()
        for i, (p, b) in enumerate(zip(prompts, budgets)):
            eng.submit(i, p, b)
            if i == 0:
                time.sleep(0.05)      # a genuinely LIVE queue: later
                #                       submits land mid-serve
        eng.drain()
        th.join(timeout=300)
        assert not th.is_alive(), "engine did not drain"
        # the positions the admission programs ran, beside the real
        # prompts': in stats() and on the metrics plane
        st = eng.stats()
        assert (st["prefill_padded_tokens"] == batcher.prefill_padded_tokens
                >= st["prefill_tokens"] > 0)
        assert reg.counter("tony_prefill_padded_tokens_total").value == \
            batcher.prefill_padded_tokens - padded0
        return [outs[i] for i in range(len(prompts))]

    def _pin(self, make, prompts, budgets, pin_steps=True):
        bp = make(True)
        outs_p = bp.serve(prompts, budgets)
        bs = make(False)
        outs_s = bs.serve(prompts, budgets)
        assert outs_p == outs_s
        if pin_steps:
            # budget-only workloads pipeline losslessly — the engine
            # must execute the exact chunk schedule of the sequential
            # (pre-refactor-equivalent) loop
            assert bp.steps_executed == bs.steps_executed
        assert self._open_loop(make(True), prompts, budgets) == outs_p
        return outs_p

    def test_greedy(self, params):
        rng = np.random.RandomState(0)
        prompts = [list(rng.randint(0, CFG.vocab_size, size=n))
                   for n in (5, 3, 7, 4, 6, 3)]
        outs = self._pin(
            lambda pipeline: ContinuousBatcher(
                params, CFG, batch=3, max_len=32, chunk=4,
                pipeline=pipeline),
            prompts, [6] * 6)
        for i, p in enumerate(prompts):
            assert outs[i] == _reference(params, p, 6), i

    def test_sampled(self, params):
        rng = np.random.RandomState(5)
        prompts = [list(rng.randint(0, CFG.vocab_size, size=4))
                   for _ in range(5)]
        self._pin(
            lambda pipeline: ContinuousBatcher(
                params, CFG, batch=2, max_len=32, chunk=3,
                temperature=0.8, top_k=50, top_p=0.9, seed=0,
                pipeline=pipeline),
            prompts, [6] * 5)

    def test_shared_prefix(self, params):
        rs = np.random.RandomState(7)
        prefix = [int(t) for t in rs.randint(0, CFG.vocab_size, size=9)]
        suffixes = [list(rs.randint(0, CFG.vocab_size,
                                    size=rs.randint(2, 6)))
                    for _ in range(5)]
        budgets = [int(b) for b in rs.randint(4, 9, size=5)]
        self._pin(
            lambda pipeline: ContinuousBatcher(
                params, CFG, batch=2, max_len=48, chunk=3,
                shared_prefix=prefix, pipeline=pipeline),
            suffixes, budgets)

    def test_speculative(self, params):
        draft = T.init_params(jax.random.PRNGKey(99), CFG)
        rng = np.random.RandomState(3)
        prompts = [list(rng.randint(0, CFG.vocab_size,
                                    size=rng.randint(3, 9)))
                   for _ in range(7)]
        budgets = [int(b) for b in rng.randint(4, 14, size=7)]
        outs = self._pin(
            lambda pipeline: SpeculativeContinuousBatcher(
                params, CFG, draft, CFG, batch=3, max_len=64,
                num_speculative=3, chunk=2, pipeline=pipeline),
            prompts, budgets,
            # speculative completions are acceptance-driven, not
            # host-predictable, so the chunk schedule (unlike tokens)
            # may legally differ pipelined-vs-sequential
            pin_steps=False)
        for i, (p, b) in enumerate(zip(prompts, budgets)):
            assert outs[i] == _reference(params, p, b), i


@pytest.mark.slow
class TestPipelinedServingSmoke:
    """End-to-end smoke: the pipelined batcher under a realistic mixed
    workload — many distinct prompt lengths across several buckets,
    per-request budgets, eos, sampled variants — on CPU."""

    def test_mixed_length_mixed_budget_smoke(self, params):
        rng = np.random.RandomState(40)
        n = 24
        prompts = [list(rng.randint(0, CFG.vocab_size,
                                    size=rng.randint(3, 40)))
                   for _ in range(n)]
        budgets = [int(b) for b in rng.randint(2, 12, size=n)]
        batcher = ContinuousBatcher(params, CFG, batch=4, max_len=64,
                                    chunk=4)
        outs = batcher.serve(prompts, budgets)
        for i, (p, b) in enumerate(zip(prompts, budgets)):
            assert outs[i] == _reference(params, p, b), i
        # admission compiled per bucket (16/32/64), not per length —
        # filtered to THIS batcher's batch-4 programs (the module-global
        # counter also holds other tests' batch-2/3 shapes)
        from tony_tpu.models.serve import TRACE_COUNTS
        admit_shapes = {k[1] for k in TRACE_COUNTS
                        if k[0] == "admit_rows" and k[1][0] == 4}
        assert len(admit_shapes) <= 3, admit_shapes

    def test_sampled_and_eos_smoke(self, params):
        rng = np.random.RandomState(41)
        prompts = [list(rng.randint(0, CFG.vocab_size,
                                    size=rng.randint(3, 20)))
                   for _ in range(12)]
        budgets = [int(b) for b in rng.randint(3, 10, size=12)]
        b1 = ContinuousBatcher(params, CFG, batch=3, max_len=48,
                               chunk=4, temperature=0.8, top_k=30,
                               seed=1)
        outs = b1.serve(prompts, budgets)
        eos = outs[0][0]
        b2 = ContinuousBatcher(params, CFG, batch=3, max_len=48,
                               chunk=4, temperature=0.8, top_k=30,
                               seed=1, eos_id=eos, pipeline=False)
        b3 = ContinuousBatcher(params, CFG, batch=3, max_len=48,
                               chunk=4, temperature=0.8, top_k=30,
                               seed=1, eos_id=eos)
        assert b3.serve(prompts, budgets) == b2.serve(prompts, budgets)


def test_admit_width_is_the_token_budget_over_the_bucket():
    """The rule and its one constant, at the benchmark's own shapes: the
    Phi-3 cells' 6 slots over buckets 64-1,024, the Kimi cell's 32 over
    32-512. A bucket at or over the budget dispatches one row; short
    buckets keep the slot count."""
    from tony_tpu.models import serve as S
    assert S._ADMIT_TOKEN_BUDGET == 256
    assert [S.admit_width(b, 6) for b in (16, 32, 64, 128, 256, 512, 1024)
            ] == [6, 6, 4, 2, 1, 1, 1]
    assert [S.admit_width(b, 32) for b in (16, 32, 64, 128, 256, 512)
            ] == [16, 8, 4, 2, 1, 1]
    assert S.admit_width(16, 2) == 2 and S.admit_width(4096, 2) == 1


#: (batcher kind, slots, prompt lengths — suffix lengths for "prefix" —
#: and a bucket whose share of the first wave outnumbers its width)
_WAVES = [
    # PR 28's wave: 12 slots filled at once from ONE short bucket, whose
    # width is the slot count — padding is free there, one dispatch
    ("greedy", 12, (5, 3, 7, 4, 6, 3, 9, 12, 5, 8, 11, 4, 6, 13), None),
    # buckets 32 / 64 / 128 at widths 8 / 4 / 2: ten of the first
    # twelve share bucket 32 and go through in two dispatches
    ("greedy", 12, (20, 25, 30, 20, 25, 30, 20, 25, 30, 20, 40, 50,
                    40, 50, 40, 70, 100, 70, 5), 32),
    # the Phi-3 cells' slot count: 6 / 6 / 4 / 2 / 1 over 16 - 256
    ("greedy", 6, (40, 50, 40, 50, 40, 50, 70, 100, 70, 130, 200, 5, 20),
     64),
    # a bucket at the budget dispatches ONE row, whatever arrived
    ("greedy", 2, (130, 200, 130, 70, 100, 5), 256),
    ("speculative", 6, (40, 50, 40, 50, 40, 50, 70, 100, 5, 20), 64),
    ("prefix", 6, (40, 50, 40, 50, 40, 50, 70, 100, 5, 20), 64),
]


@pytest.mark.parametrize(
    "kind, slots, lengths, split", _WAVES,
    ids=[f"{k}-{s}slots-{len(n)}req" for k, s, n, _ in _WAVES])
def test_admission_dispatch_is_sized_in_tokens(params, retrace_guard,
                                               kind, slots, lengths,
                                               split):
    """Every bucketed admission dispatch is ``admit_width(bucket)`` rows
    wide — one traced program a bucket — a wave that holds more
    requests of a bucket than its width goes through in several
    dispatches, every request still equals its solo greedy generate, and
    ``prefill_padded_tokens`` counts rows x bucket of every dispatch."""
    from tony_tpu.models import serve as S
    rng = np.random.RandomState(11)
    prefix = ([int(t) for t in rng.randint(0, CFG.vocab_size, size=9)]
              if kind == "prefix" else [])
    prompts = [prefix + [int(t) for t in
                         rng.randint(0, CFG.vocab_size, size=n)]
               for n in lengths]
    if kind == "speculative":
        batcher = SpeculativeContinuousBatcher(
            params, CFG, params, CFG, batch=slots, max_len=288,
            num_speculative=3, chunk=2)
    else:
        batcher = ContinuousBatcher(params, CFG, batch=slots, max_len=288,
                                    chunk=4)
    padded0 = 0
    if prefix:
        assert batcher.install_prefix("sys", prefix)
        padded0 = len(prefix)
    program = {"greedy": "admit_rows", "speculative": "spec_admit_rows",
               "prefix": "prefix_admit_rows"}[kind]
    dispatched = []                          # (rows, bucket, real rows)
    admit = batcher._admit_rows

    def recording(rows, toks, lens, keys, entry=None):
        dispatched.append(tuple(toks.shape)
                          + (int(np.sum(np.asarray(rows) < slots)),))
        return admit(rows, toks, lens, keys, entry=entry)
    batcher._admit_rows = recording
    outs = batcher.serve(prompts, max_new_tokens=5)
    for i, p in enumerate(prompts):
        assert outs[i] == _reference(params, p, 5), f"request {i}"

    budget = S._ADMIT_TOKEN_BUDGET
    for rows, bucket, real in dispatched:
        assert rows == max(1, min(slots, budget // bucket)), dispatched
        assert 1 <= real <= rows
    assert sum(real for _, _, real in dispatched) == len(prompts)
    assert batcher.prefill_padded_tokens == padded0 + sum(
        rows * bucket for rows, bucket, _ in dispatched)
    assert batcher.prefill_forward_tokens == len(prefix) + sum(lengths)
    # one program a bucket: the shapes dispatched are the shapes traced
    # (a shape an earlier case compiled is not traced again)
    buckets = {bucket for _, bucket, _ in dispatched}
    traced = retrace_guard.new_traces(program)
    assert all(n == 1 for n in traced.values()), traced
    assert set(traced) <= {(S.admit_width(b, slots), b) for b in buckets}
    if split is not None:
        # the first wave fills every slot at once: its requests of
        # bucket ``split`` outnumber the width, so they took several
        # dispatches, the first of them full
        first = [S.bucket_for(n, 288 - len(prefix))
                 for n in lengths[:slots]]
        want = -(-first.count(split) // S.admit_width(split, slots))
        assert want > 1
        wave = dispatched[:len(set(first)) - 1 + want]
        assert [d[1] for d in wave].count(split) == want, dispatched
        assert (S.admit_width(split, slots), split,
                S.admit_width(split, slots)) in wave


#: the five jitted programs that land a prompt in a slot
_ADMITTERS = {"admit_rows", "admit_row_ring", "prefix_admit_rows",
              "spec_admit_rows", "spec_prefix_admit_rows"}


def _batcher_of(kind, params, max_len):
    """(batcher, the admission program its cache type selects)."""
    if kind == "ring":
        return (ContinuousBatcher(
            params, CFG.scaled(attn_window=8, kv_cache_capacity=8),
            batch=2, max_len=max_len, chunk=3), "admit_row_ring")
    if kind == "shared-prefix":
        return (ContinuousBatcher(params, CFG, batch=2, max_len=max_len,
                                  chunk=3, shared_prefix=[7, 8, 9]),
                "prefix_admit_rows")
    if kind == "speculative":
        return (SpeculativeContinuousBatcher(
            params, CFG, params, CFG, batch=2, max_len=max_len,
            num_speculative=2, chunk=2), "spec_admit_rows")
    return (ContinuousBatcher(params, CFG, batch=2, max_len=max_len,
                              chunk=3), "admit_rows")


@pytest.mark.parametrize("kind", ["dense", "ring", "shared-prefix",
                                  "speculative"])
def test_admission_program_follows_the_cache_type(params, retrace_guard,
                                                  kind):
    """Which program lands a prompt is decided by what the batcher was
    built on — a linear cache, a ring, a prefix template, a draft
    model — and by nothing a caller sets: each batcher traces its own
    admission program and none of the other four. (``max_len`` 43 is
    this test's alone, so the program is traced here, not found in an
    earlier test's jit cache.)"""
    rng = np.random.RandomState(41)
    prompts = [list(rng.randint(0, CFG.vocab_size, size=n))
               for n in (5, 3, 6)]
    batcher, program = _batcher_of(kind, params, 43)
    outs = batcher.serve(prompts, max_new_tokens=4)
    assert all(len(o) == 4 for o in outs)
    assert retrace_guard.total_new(program) >= 1
    for other in _ADMITTERS - {program}:
        retrace_guard.assert_max(other, 0)


@pytest.mark.parametrize("lengths, budget", [((5, 3, 4), 3),
                                             ((5, 11, 7), 14)],
                         ids=["short", "wrapping"])
def test_ring_admission_is_exact(params, lengths, budget):
    """A ring-cache batcher's tokens equal ``decode.generate``'s for each
    request alone under the same ring configuration — while every
    request stays inside the 8-row ring (short), and with prompts longer
    than the ring and answers that wrap it (wrapping), slot reuse
    included."""
    rcfg = CFG.scaled(attn_window=8, kv_cache_capacity=8)
    rng = np.random.RandomState(42)
    prompts = [list(rng.randint(0, CFG.vocab_size, size=n))
               for n in lengths]
    batcher = ContinuousBatcher(params, rcfg, batch=2, max_len=16,
                                chunk=3)
    outs = batcher.serve(prompts, max_new_tokens=budget)
    for i, p in enumerate(prompts):
        want = generate(params, jnp.asarray(p, jnp.int32)[None], rcfg,
                        max_new_tokens=budget, rng=jax.random.PRNGKey(0),
                        temperature=0.0)
        assert outs[i] == [int(t) for t in
                           np.asarray(want.tokens[0, len(p):])], i
    assert batcher.prefill_padded_tokens == sum(lengths)
    assert batcher.prefill_forward_tokens == sum(lengths)


def test_serving_programs_are_the_five_admitters():
    """The jitted admission entry points ``serve`` exports are exactly
    the five, and no constructor argument selects among them."""
    import inspect
    from tony_tpu.models import serve as S
    jitted = {name for name, fn in vars(S).items()
              if "admit" in name and hasattr(fn, "lower")}
    assert jitted == _ADMITTERS
    for cls in (ContinuousBatcher, SpeculativeContinuousBatcher):
        assert "bucketed_admission" not in inspect.signature(
            cls.__init__).parameters


def test_every_bench_arm_is_a_test_fixture():
    """``bench.py`` at the repo root is what the tests use of it: every
    ``_*_arm`` it defines is called by some file under ``tests/`` (the
    benchmark is ``benchmark/``)."""
    import ast
    import os
    import re
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "..", "bench.py"), encoding="utf-8") as f:
        tree = ast.parse(f.read())
    arms = {n.name for n in tree.body if isinstance(n, ast.FunctionDef)
            and re.fullmatch(r"_\w+_arm", n.name)}
    assert arms, "bench.py defines no arm"
    assert not any(isinstance(n, ast.FunctionDef) and n.name == "main"
                   for n in tree.body)
    called = set()
    for name in os.listdir(here):
        if name.endswith(".py"):
            with open(os.path.join(here, name), encoding="utf-8") as f:
                called |= set(re.findall(r"bench\.(_\w+_arm)\(", f.read()))
    assert arms == called


# ---------------------------------------------------------------------------
# Rows the decode chunks' cached reads visited against rows live (PR 36)
# ---------------------------------------------------------------------------

#: name -> (cfg changes, max_len, prompt lengths on 2 slots, the rows one
#: step reads a slot at a position given the chunk's longest, the rows
#: live at a position). Budgets of 8 at chunk 4: both slots hold their
#: request from the first chunk to the second and last, so every read is
#: a request's and the totals follow from the lengths alone
ROWS_CASES = {
    # under _BLOCKWISE_MIN_LEN rows: the dense einsum reads the buffer
    "dense": ({}, 32, (5, 11), lambda pos, longest: 32,
              lambda pos: pos + 1),
    # the walk off the chip: every slot to the LONGEST row's last block
    "walk": ({}, 600, (5, 300),
             lambda pos, longest: (longest + 256) // 256 * 256,
             lambda pos: pos + 1),
    # a ring off the chip is read whole; live is what the window admits
    "ring": ({"attn_window": 8, "kv_cache_capacity": 8}, 32, (5, 11),
             lambda pos, longest: 8, lambda pos: min(pos + 1, 8)),
    # the chip's arm (``on_chip_arm``): each slot its OWN blocks, of 128
    "kernel": ({}, 700, (5, 300), lambda pos, longest: pos // 128 * 128 + 128,
               lambda pos: pos + 1),
    "kernel-ring": ({"attn_window": 600, "kv_cache_capacity": 600}, 900,
                    (5, 300), lambda pos, longest: pos // 128 * 128 + 128,
                    lambda pos: pos + 1),
}


@pytest.fixture
def on_chip_arm(monkeypatch):
    """``decode._read_arm`` answers as on the chip — the kernel, at the
    128 rows a block that ``cached_attn_block`` never goes under — while
    the launch itself stays the interpreter's (``ops.mosaic`` is not
    touched): the engine serves THROUGH ``tony_cached_attn`` here. The
    arm is chosen while a program is traced, so no traced program may
    cross the patch in either direction."""
    import types

    from tony_tpu.models import decode as D
    jax.clear_caches()
    monkeypatch.setattr(D, "mosaic",
                        types.SimpleNamespace(interpret=lambda: False))
    monkeypatch.setattr(D, "cached_attn_block", lambda rows, row_bytes: 128)
    yield
    jax.clear_caches()


@pytest.mark.parametrize("case", sorted(ROWS_CASES))
def test_cache_rows_read_and_live_follow_the_requests_lengths(
        params, case, request):
    scaled, max_len, lengths, rows_read, rows_live = ROWS_CASES[case]
    if case.startswith("kernel"):
        request.getfixturevalue("on_chip_arm")
    cfg = CFG.scaled(**scaled)
    rng = np.random.RandomState(3)
    prompts = [list(rng.randint(0, cfg.vocab_size, size=n))
               for n in lengths]
    b = ContinuousBatcher(params, cfg, batch=2, max_len=max_len, chunk=4)
    reg = MetricsRegistry()
    eng = ServeEngine(b, registry=reg)
    for rid, p in enumerate(prompts):
        eng.submit(rid, p, 8)
    eng.drain()
    eng.run()
    assert b.steps_executed == 8
    kind = "ring" if scaled else "linear"
    # step j of 8: slot r's query holds position lengths[r] + j
    read = cfg.n_layers * sum(
        rows_read(n + j, max(lengths) + j) for n in lengths
        for j in range(8))
    live = cfg.n_layers * sum(
        rows_live(n + j) for n in lengths for j in range(8))
    stats = eng.stats()
    assert stats["cache_rows_read"] == {kind: read}
    assert stats["cache_rows_live"] == {kind: live}
    assert reg.counter("tony_cache_rows_read_total",
                       kind=kind).value == read
    assert reg.counter("tony_cache_rows_live_total",
                       kind=kind).value == live


@pytest.mark.parametrize("scaled, max_len", [
    ({}, 700), ({"attn_window": 600, "kv_cache_capacity": 600}, 900)],
    ids=["linear", "ring"])
def test_engine_through_the_kernel_serves_the_jnp_reads_tokens(
        params, scaled, max_len, request):
    """Slots of unlike lengths, reused, one idle at the end: the tokens
    served through ``tony_cached_attn`` (``on_chip_arm``) are the ones
    the ``jnp`` reads serve."""
    cfg = CFG.scaled(**scaled)
    rng = np.random.RandomState(5)
    prompts = [list(rng.randint(0, cfg.vocab_size, size=n))
               for n in (300, 7, 130, 40)]
    budgets = [9, 14, 5, 6]
    want = ContinuousBatcher(params, cfg, batch=2, max_len=max_len,
                             chunk=4).serve(prompts, budgets)
    request.getfixturevalue("on_chip_arm")
    b = ContinuousBatcher(params, cfg, batch=2, max_len=max_len, chunk=4)
    assert b.serve(prompts, budgets) == want
    kind = "ring" if scaled else "linear"
    assert b.cache_rows_live[kind] / b.cache_rows_read[kind] > 0.3


def test_speculative_batcher_counts_no_cached_reads(params):
    """Its rounds read through ``extend_step``, which this count does
    not reckon: no total, and no counter registered at a constant 0."""
    from tony_tpu.models.serve import SpeculativeContinuousBatcher
    b = SpeculativeContinuousBatcher(params, CFG, params, CFG, batch=2,
                                     max_len=32, num_speculative=2)
    reg = MetricsRegistry()
    stats = ServeEngine(b, registry=reg).stats()
    assert stats["cache_rows_read"] == stats["cache_rows_live"] == {}
    assert "tony_cache_rows" not in reg.to_wire_json()


def test_host_frontiers_are_the_devices_through_slot_reuse(params):
    """The counts rest on the host's copy of ``cache["length"]``: after
    5 requests of unlike lengths and budgets through 2 slots (slots
    reused, one idle while the last request finishes) it still IS the
    device's, and no more rows were live than read."""
    rng = np.random.RandomState(4)
    prompts = [list(rng.randint(0, CFG.vocab_size, size=n))
               for n in (5, 3, 9, 4, 7)]
    b = ContinuousBatcher(params, CFG, batch=2, max_len=64, chunk=4)
    seen = []
    issue = b._issue

    def checked_issue():
        np.testing.assert_array_equal(np.asarray(b.cache["length"]),
                                      b._row_len)
        seen.append(b._row_len.copy())
        return issue()
    b._issue = checked_issue
    b.serve(prompts, [6, 50, 3, 4, 5])
    np.testing.assert_array_equal(np.asarray(b.cache["length"]),
                                  b._row_len)
    assert len(seen) >= 5 and any(0 in s for s in seen)    # an idle slot
    assert 0 < b.cache_rows_live["linear"] < b.cache_rows_read["linear"]
    assert b.cache_rows_read["linear"] == \
        CFG.n_layers * b.steps_executed * 2 * 64
