"""A state-space layer kind on the serving path, at toy widths on the CPU
(float32 both sides): hidden 64, three Mamba-2 mixers (8 heads of 16, a
state of 16, one group, a conv of 4 taps, prompt chunks of 8) around one
full-attention layer without positions (4/2 heads of 16, softmax scale
1/16 — not 16^-1/2), a SwiGLU of 128 in every layer, the four multipliers
(12, 0.22, 1/16, 1/8) and a tied head — ``tests/data/tiny-ssm-hybrid.json``,
read by the benchmark's family ``benchmark/families/ssm_hybrid_decoder.py``,
whose float32 reference runs the recurrence ONE POSITION AFTER ANOTHER and
imports nothing from the program: the program's chunked prompt scan and
its one-step update are both held by it.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import modelcfg, reference
from tony_tpu.models import decode as D
from tony_tpu.models import serve as S
from tony_tpu.models import transformer as T
from tony_tpu.ops import ssm as ssm_ops

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIG = os.path.join(HERE, "data", "tiny-ssm-hybrid.json")
SEED = 2**31 + 41
#: float32 both sides, logits of order 1: what is left is rounding order —
#: the chunked scan sums a chunk's decayed products where the reference
#: steps through them, the cached read is the reference's one softmax.
#: Read: at most 4e-6 over every position below. A bfloat16 run (2e-2), a
#: lost state, a padded tail run through, a conv window from the tail, a
#: multiplier left at its default each miss it by more than ten times
#: (the tests below): the tolerance of the other kinded models' tests
ATOL = 5e-5


@pytest.fixture(scope="module")
def tiny():
    c = modelcfg.load(CONFIG)
    fam = modelcfg.family(c)
    return (c, fam, fam.program_config(c, dtype=jnp.float32, remat=False),
            fam.make_params(SEED, c, jnp.float32))


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _tokens(n_rows, length, vocab, salt=0):
    return np.random.default_rng(SEED + salt).integers(
        0, vocab, (n_rows, length)).astype(np.int32)


def _ref_logits(c, toks, dtype=jnp.float32):
    return np.asarray(reference.Reference(c, SEED, None, dtype).logits(toks))


def _admit(params, cfg, toks, lengths, bucket, slots=None, rows=64):
    """Rows of ``toks`` admitted at ``lengths`` through ONE padded bucket
    into a fresh cache (slot r unless ``slots`` says otherwise). Returns
    (the admission's logits, the cache)."""
    k = len(lengths)
    prompts = np.zeros((k, bucket), np.int32)
    for r, n in enumerate(lengths):
        prompts[r, :n] = toks[r, :n]
    lg, mini = D.prefill_rows(params, jnp.asarray(prompts),
                              jnp.asarray(lengths, jnp.int32), cfg)
    cache = dict(D.init_kv_cache(cfg, k, rows),
                 length=jnp.zeros((k,), jnp.int32))
    return lg, D.place_rows(
        cache, mini, jnp.asarray(np.arange(k) if slots is None else slots),
        jnp.asarray(lengths, jnp.int32))


def _decode_gap(params, cfg, cache, toks, lengths, ref, steps):
    """Teacher-forced decode of ``steps`` positions for every row; the
    widest gap of any step's logits from the reference's."""
    step = jax.jit(lambda tok, cache: D.decode_step(
        params, tok, cache, cache["length"], cfg))
    worst = 0.0
    for t in range(steps):
        tok = np.asarray([toks[r, n + t] for r, n in enumerate(lengths)])
        lg, cache = step(jnp.asarray(tok), cache)
        worst = max(worst, max(
            float(np.abs(np.asarray(lg[r], np.float32) - ref[r, n + t]).max())
            for r, n in enumerate(lengths)))
    return worst, cache


# ------------------------------------------------------------------ (a)
def test_one_padded_admission_then_decode_past_a_chunk_is_the_reference(
        tiny):
    """Prompts of lengths 1, 2, 3 (shorter than the conv's window),
    ``bucket - 1`` and ``bucket`` in ONE padded program: every row's
    state stops at its OWN length and its conv window holds its own last
    inputs (zeros before position 0), so the admission's logits and
    twelve decode steps through the state — past a chunk boundary of 8 —
    are the sequential float32 reference's at every position."""
    c, fam, cfg, params = tiny
    bucket, steps = 16, 12
    lengths = [1, 2, 3, bucket - 1, bucket]
    toks = _tokens(5, bucket + steps, c["vocab_size"])
    ref = _ref_logits(c, toks)
    lg, cache = _admit(params, cfg, toks, lengths, bucket)
    for r, n in enumerate(lengths):
        np.testing.assert_allclose(lg[r], ref[r, n - 1], atol=ATOL, rtol=0)
    worst, cache = _decode_gap(params, cfg, cache, toks, lengths, ref, steps)
    assert worst < ATOL
    # no rows: a mixer's state is the same size at every length
    assert cache["ssm"].shape == (3, 5, 16, 8 * 16)
    assert cache["conv"].shape == (3, 5, 3, 8 * 16 + 2 * 16)
    assert cache["k"].shape == (1, 5, 64, 2 * 16)


def test_a_prompt_over_several_chunks_and_the_whole_prompt_prefill(tiny):
    """37 positions in a bucket of 64 — five chunks of 8 carry the state,
    the last one ragged, three more are padding — and ``prefill`` (the
    ``generate`` path) of the same prompt: both hand decode the
    reference's state."""
    c, fam, cfg, params = tiny
    toks = _tokens(1, 50, c["vocab_size"], salt=1)
    ref = _ref_logits(c, toks)
    lg, cache = _admit(params, cfg, toks, [37], 64)
    np.testing.assert_allclose(lg[0], ref[0, 36], atol=ATOL, rtol=0)
    assert _decode_gap(params, cfg, cache, toks, [37], ref, 13)[0] < ATOL
    lg, whole = D.prefill(params, jnp.asarray(toks[:, :37]), cfg, max_len=64)
    np.testing.assert_allclose(lg[0], ref[0, 36], atol=ATOL, rtol=0)
    for name in ("ssm", "conv"):
        np.testing.assert_allclose(whole[name], cache[name], atol=1e-5)
    out = D.generate(params, jnp.asarray(toks[:, :37]), cfg, 6,
                     jax.random.PRNGKey(0)).tokens[0, 37:]
    seq = np.concatenate([toks[0, :37], np.asarray(out)])[None]
    lg = _ref_logits(c, seq[:, :-1])[0, 36:]
    assert (lg.max(-1) - lg[np.arange(6), np.asarray(out)] < ATOL).all()


def test_a_reused_and_an_idle_slot_give_a_fresh_engines_logits(tiny):
    """Nothing masks a recurrence's residue: only the admission's
    whole-state landing stands between a slot's last occupant — or the
    garbage an idle slot decoded for many steps — and its next one. Slot
    0 serves a long request and slot 1 idles through its 24 steps; both
    are then retired and admitted anew: logits and state are, to the
    bit, those of the same admission into a fresh cache."""
    c, fam, cfg, params = tiny
    toks = _tokens(3, 40, c["vocab_size"], salt=2)
    step, admit, retire = S.step_rows, S.admit_rows, S.retire_rows

    def fresh():
        return (dict(D.init_kv_cache(cfg, 2, 64),
                     length=jnp.zeros((2,), jnp.int32)),
                jnp.zeros((2, cfg.vocab_size), jnp.float32))

    def land(cache, logits, rows, which, lengths):
        prompts = np.zeros((2, 16), np.int32)
        for i, (r, n) in enumerate(zip(which, lengths)):
            prompts[i, :n] = toks[r, :n]
        return admit(params, cache, logits, jnp.asarray(rows),
                     jnp.asarray(prompts), jnp.asarray(lengths, jnp.int32),
                     cfg)[:2]

    keys, offs = jnp.zeros((2, 2), jnp.uint32), jnp.zeros((2,), jnp.int32)
    cache, logits = fresh()
    # a long occupant in slot 0 alone (row 1's sentinel 2 drops)
    cache, logits = land(cache, logits, [0, 2], [0, 0], [16, 1])
    for _ in range(3):
        _, cache, logits, _ = step(params, cache, logits, keys, offs, 8, cfg)
    assert float(jnp.abs(cache["ssm"][:, 1]).max()) > 0    # idle: garbage
    cache = retire(cache, jnp.asarray([True, True]))
    used = land(cache, logits, [0, 1], [1, 2], [5, 11])
    new = land(*fresh(), [0, 1], [1, 2], [5, 11])
    for name in ("ssm", "conv"):
        np.testing.assert_array_equal(used[0][name], new[0][name])
    np.testing.assert_array_equal(used[1], new[1])
    toks_used, _, lg_used, _ = step(params, used[0], used[1], keys, offs, 8,
                                    cfg)
    toks_new, _, lg_new, _ = step(params, new[0], new[1], keys, offs, 8, cfg)
    np.testing.assert_array_equal(toks_used, toks_new)
    np.testing.assert_array_equal(lg_used, lg_new)


# ------------------------------------------------------- ops/ssm.py alone
def _scan_inputs(groups, dtype=jnp.float32, s=21):
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    b, h, p, n = 2, 4, 8, 16
    return (jax.random.normal(ks[0], (b, s, h, p), dtype),
            jax.nn.softplus(jax.random.normal(ks[1], (b, s, h))),
            -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.5),
            jax.random.normal(ks[3], (b, s, groups, n), dtype),
            jax.random.normal(ks[4], (b, s, groups, n), dtype))


@pytest.mark.parametrize("groups", [1, 2])
def test_the_one_step_arm_is_the_chunked_form_at_the_same_positions(groups):
    """``ssm_step_reference`` stepped 21 times from a zero state gives
    the ``y`` of ``ssm_chunked`` (chunks of 8: two whole, one ragged) at
    every position and its final state; ``dt = 0`` past position 13
    leaves the state where it stood."""
    x, dt, a, b, c = _scan_inputs(groups)
    y, last = ssm_ops.ssm_chunked(x, dt, a, b, c, 8)
    state = jnp.zeros((1, 2, 16, 32), jnp.float32)
    for t in range(x.shape[1]):
        inp = (dt[:, t, :, None] * x[:, t]).reshape(2, -1)
        yt, state = ssm_ops.ssm_step_reference(
            state, 0, jnp.repeat(jnp.exp(dt[:, t] * a), 8, axis=1), inp,
            b[:, t], c[:, t])
        np.testing.assert_allclose(yt.reshape(2, 4, 8), y[:, t], atol=2e-5)
        if t == 13:
            at_13 = state
    np.testing.assert_allclose(state[0], last, atol=2e-5)
    stop = jnp.where(jnp.arange(21)[None, :, None] <= 13, dt, 0.0)
    np.testing.assert_allclose(
        ssm_ops.ssm_chunked(x, stop, a, b, c, 8)[1], at_13[0], atol=2e-5)


@pytest.mark.parametrize("groups,dtype", [(1, jnp.float32),
                                          (2, jnp.bfloat16)])
def test_the_kernel_in_interpret_mode_is_the_jnp_arm(groups, dtype):
    """``tony_ssm_step`` (Pallas interpreter) against the ``jnp`` arm: the
    same ``y`` from the float32 state, the same stored state to the bit,
    the layers beside it untouched (the update is in place)."""
    ks = jax.random.split(jax.random.PRNGKey(4), 5)
    layers, slots, n, cols = 3, 4, 16, 256
    state = jax.random.normal(ks[0], (layers, slots, n, cols)).astype(dtype)
    decay = jax.random.uniform(ks[1], (slots, cols))
    inp = jax.random.normal(ks[2], (slots, cols))
    b = jax.random.normal(ks[3], (slots, groups, n))
    c = jax.random.normal(ks[4], (slots, groups, n))
    y0, s0 = ssm_ops.ssm_step_reference(state, 1, decay, inp, b, c)
    y1, s1 = ssm_ops.ssm_step(state, 1, decay, inp, b, c, interpret=True)
    np.testing.assert_allclose(y1, y0, atol=1e-5)
    # the two arms may contract a multiply-add differently: a last bit
    np.testing.assert_allclose(
        np.asarray(s1, np.float32), np.asarray(s0, np.float32), atol=1e-6,
        rtol=2.0 ** -7 if dtype == jnp.bfloat16 else 1e-6)
    np.testing.assert_array_equal(np.asarray(s1[0], np.float32),
                                  np.asarray(state[0], np.float32))
    assert ssm_ops.ssm_step_block(128, 4096, 1, 2) == 4096   # a whole slot
    assert ssm_ops.ssm_step_block(128, 4096, 1, 4) == 2048
    assert ssm_ops.ssm_step_block(128, 4096, 2, 2) == 2048   # inside a group


# --------------------------------------------------------- what it tells
def _served_gap(cfg, params, toks, ref, lengths=(11,), bucket=16,
                steps=6, spoil=None):
    lg, cache = _admit(params, cfg, toks, list(lengths), bucket)
    if spoil is not None:
        cache = spoil(cache)
    first = max(float(np.abs(np.asarray(lg[r], np.float32)
                             - ref[r, n - 1]).max())
                for r, n in enumerate(lengths))
    return max(first, _decode_gap(params, cfg, cache, toks, list(lengths),
                                  ref, steps)[0])


@pytest.mark.parametrize("fault", [
    "bf16", "bf16_state", "lost_state", "lost_conv", "tail_run_through",
    "embed_scale", "residual_scale", "attn_scale"])
def test_the_tolerance_tells_a_lower_precision_a_lost_state_or_a_multiplier(
        tiny, fault):
    """What ATOL is tight enough for: the program in bfloat16; the state
    alone STORED in bfloat16; the state, or the conv's window, zeroed
    where decode takes over; the padded tail run through (the rows'
    lengths given as the bucket: no step masked, the window taken from
    the tail); and each of the three multipliers left at its default
    (``attn_scale`` None is 16^-1/2 where the model says 1/16) — each
    misses it, all but the stored state by more than ten times, where
    the sound program is inside it."""
    c, fam, cfg, params = tiny
    toks = _tokens(1, 24, c["vocab_size"], salt=3)
    ref = _ref_logits(c, toks)
    assert _served_gap(cfg, params, toks, ref) < ATOL
    spoil, lengths = None, (11,)
    if fault == "bf16":
        cfg = fam.program_config(c, dtype=jnp.bfloat16, remat=False)
        params = fam.make_params(SEED, c, jnp.bfloat16)
        ref = _ref_logits(c, toks, jnp.bfloat16)
    elif fault == "bf16_state":
        cfg = dataclasses.replace(cfg, ssm=dataclasses.replace(
            cfg.ssm, state_dtype=jnp.bfloat16))
    elif fault in ("lost_state", "lost_conv"):
        name = "ssm" if fault == "lost_state" else "conv"
        spoil = lambda cache: dict(cache, **{                # noqa: E731
            name: jnp.zeros_like(cache[name])})
    elif fault == "tail_run_through":
        # the row's real 11 tokens, admitted as if all 16 were real: the
        # logits read position 15 and decode starts from the tail's state
        lg, mini = D.prefill_rows(
            params, jnp.asarray(np.pad(toks[:, :11], ((0, 0), (0, 5)))),
            jnp.asarray([16]), cfg)
        cache = dict(D.init_kv_cache(cfg, 1, 64),
                     length=jnp.zeros((1,), jnp.int32))
        cache = D.place_rows(cache, mini, jnp.asarray([0]),
                             jnp.asarray([11]))
        gap = _decode_gap(params, cfg, cache, toks, [11], ref, 6)[0]
        assert gap > 10 * ATOL
        return
    else:
        cfg = dataclasses.replace(cfg, **{
            fault: None if fault == "attn_scale" else 1.0})
    # the state alone stored in bfloat16, six steps old: three times
    # the tolerance; everything else more than ten
    assert _served_gap(cfg, params, toks, ref, lengths, spoil=spoil) > (
        2 if fault == "bf16_state" else 10) * ATOL


def test_the_softmax_scale_is_folded_into_q_once(tiny):
    """``attn_scale`` x sqrt(head_dim) goes onto q in ``_gqa_qkv`` and
    every read keeps its ``head_dim ** -0.5``: at the scale every model
    had (None) nothing is traced, and the latent kinds take theirs in
    ``_latent_scale``."""
    c, fam, cfg, params = tiny
    p = D._layer_params(params, cfg, 1)
    h = jnp.asarray(np.random.default_rng(0).normal(size=(1, 3, 64)),
                    jnp.float32)
    q, k, v = D._gqa_qkv(h, p, None, cfg)
    plain = dataclasses.replace(cfg, attn_scale=None)
    q0, k0, v0 = D._gqa_qkv(h, p, None, plain)
    np.testing.assert_allclose(q, q0 * (c["attention_multiplier"] * 4.0),
                               rtol=1e-6)
    np.testing.assert_array_equal(k, k0)
    np.testing.assert_array_equal(v, v0)


# -------------------------------------------------------- batcher, engine
def test_served_through_the_batcher_with_the_state_in_stats(tiny):
    """``ContinuousBatcher`` -> ``ServeEngine`` through the SAME
    ``admit_rows`` / ``step_rows`` / ``place_rows`` as every kinded model,
    five requests on three slots (slots are reused): every served token
    is the reference's best at its position; ``cache_bytes`` carries the
    ``ssm`` and ``conv`` state beside the full kind's rows, and the four
    counters arrive in ``engine.stats()`` and the registry."""
    from tony_tpu.runtime import metrics as M
    c, fam, cfg, params = tiny
    rs = np.random.default_rng(7)
    prompts = [rs.integers(0, c["vocab_size"], n).tolist()
               for n in (5, 17, 33, 9, 21)]
    budgets = [10, 7, 12, 9, 6]
    b = S.ContinuousBatcher(params, cfg, batch=3, max_len=96, chunk=4)
    reg = M.MetricsRegistry()
    got = {}
    eng = S.ServeEngine(
        b, registry=reg,
        on_delta=lambda rid, toks: got.setdefault(rid, []).extend(toks),
        on_retired=lambda rid, why, n, final: got.setdefault(
            rid, []).extend(final))
    for rid, (p, n) in enumerate(zip(prompts, budgets)):
        eng.submit(rid, p, n)
    eng.drain()
    eng.run()
    ref = reference.Reference(c, SEED, None, jnp.float32)
    for rid, (p, n) in enumerate(zip(prompts, budgets)):
        assert len(got[rid]) == n
        seq = np.asarray([p + got[rid]], np.int32)
        lg = np.asarray(ref.logits(seq[:, :-1]))[0, len(p) - 1:]
        assert (lg.max(-1) - lg[np.arange(n), got[rid]] < ATOL).all(), rid
    st = eng.stats()
    state = 3 * 3 * 16 * 128 * 4            # mixers x slots x [N, H·P] f32
    assert st["cache_bytes"] == {"full": 2 * 1 * 3 * 96 * 32 * 4,
                                 "ssm": state,
                                 "conv": 3 * 3 * 3 * 160 * 4}
    assert reg.gauge("tony_cache_bytes", kind="ssm").value == state
    ssm = st["ssm"]
    assert ssm["state_bytes"] == 2 * state * st["steps_executed"]
    assert 0 < ssm["state_live_bytes"] < ssm["state_bytes"]
    # one row a dispatch here, each bucket's positions through 3 mixers
    assert ssm["scan_live_positions"] == 3 * sum(len(p) for p in prompts)
    assert ssm["scan_positions"] == 3 * sum(
        S.bucket_for(len(p), 96) * S.admit_width(
            S.bucket_for(len(p), 96), 3) for p in prompts)
    for what, have in ssm.items():
        assert reg.counter(f"tony_ssm_{what}_total").value == have
    # the four full-attention rows keep counting under their own kind
    assert set(st["cache_rows_read"]) == {"full"}


def test_a_preempted_request_resumes_by_re_prefill_with_no_snapshot(tiny):
    """Preemption re-prefills from prompt + emitted tokens
    (``_preempt_locked``), so a recurrent state needs no snapshot: an
    interactive admission evicts a decoding batch row, and all three
    streams finish with the uninterrupted tokens."""
    from tests.test_qos import _Harness, _SlowFetch
    from tony_tpu.runtime import metrics as M
    c, fam, cfg, params = tiny
    rs = np.random.default_rng(8)
    prompts = [rs.integers(0, c["vocab_size"], n).tolist() for n in (5, 4, 6)]
    budgets = (12, 12, 6)
    want = [np.asarray(D.generate(
        params, jnp.asarray([p]), cfg, n,
        jax.random.PRNGKey(0)).tokens[0, len(p):]).tolist()
        for p, n in zip(prompts, budgets)]
    reg = M.MetricsRegistry()
    h = _Harness(_SlowFetch(params, cfg, batch=2, max_len=32, chunk=3),
                 registry=reg)
    try:
        h.engine.submit(0, prompts[0], 12, request_class="batch")
        h.engine.submit(1, prompts[1], 12, request_class="batch")
        h.wait_first_tokens([0, 1])
        h.engine.submit(2, prompts[2], 6, request_class="interactive")
    finally:
        h.finish()
    assert reg.counter("tony_serve_preemptions_total").value == 1
    assert [h.got[r] for r in range(3)] == want
    assert {r for r, _ in h.retired.values()} == {"budget"}


def test_int8_weights_reach_the_mixers_projections(tiny):
    """``quantize_weights_int8`` (the serving cells' control) rounds a
    mixer's two matrices beside the attention's and the SwiGLUs'; the
    conv, the step and decay leaves and the tied embedding stay."""
    from tony_tpu.models.quantize import QuantizedWeight, \
        quantize_weights_int8
    c, fam, cfg, params = tiny
    q = quantize_weights_int8(params)
    group = q["blocks"]["ssm_dense"]
    for name in ("w_in", "w_out", "w_gate", "w_up", "w_down"):
        assert isinstance(group[name], QuantizedWeight), name
    for name in ("conv_w", "conv_b", "dt_bias", "A_log", "D", "gate_norm"):
        assert not isinstance(group[name], QuantizedWeight), name
    assert isinstance(q["blocks"]["full_dense"]["wq"], QuantizedWeight)
    assert not isinstance(q["embed"], QuantizedWeight)
    toks = jnp.asarray(_tokens(2, 24, c["vocab_size"], salt=4))
    a, _ = D.prefill(params, toks, cfg, max_len=32)
    b, _ = D.prefill(q, toks, cfg, max_len=32)
    assert 20 * ATOL < float(jnp.abs(a - b).mean()) < 0.3


# ------------------------------------------------------------ what is refused
def test_what_is_refused_and_where(tiny):
    c, fam, cfg, params = tiny
    kinds = dict(vocab_size=64, d_model=32, n_layers=2, n_heads=4, d_ff=64,
                 dtype=jnp.float32, remat=False)
    mixer = T.StateSpace(4, 8, 16)
    with pytest.raises(ValueError, match="mixes through `ssm`"):
        T.TransformerConfig(layer_kinds=("ssm_dense", "full_dense"), **kinds)
    with pytest.raises(ValueError, match="`ssm` is set, and no kind"):
        T.TransformerConfig(layer_kinds=("full_dense", "full_dense"),
                            ssm=mixer, **kinds)
    with pytest.raises(ValueError, match="no rows by position"):
        T.TransformerConfig(layer_kinds=("ssm_dense", "ssm_dense"),
                            ssm=mixer, **kinds)
    with pytest.raises(ValueError, match="n_groups dividing n_heads"):
        T.TransformerConfig(layer_kinds=("ssm_dense", "full_dense"),
                            ssm=T.StateSpace(4, 8, 16, n_groups=3), **kinds)
    for scalar in (dict(embed_scale=12.0), dict(residual_scale=0.22),
                   dict(attn_scale=0.125), dict(ssm=mixer)):
        with pytest.raises(ValueError, match="a model with `layer_kinds`"):
            T.TransformerConfig(**kinds, **scalar)
    # a chunk wider than one position cannot rewind a recurrence
    cache = D.init_kv_cache(cfg, 1, 32)
    with pytest.raises(ValueError, match="single-position steps only"):
        D.extend_step(params, jnp.zeros((1, 2), jnp.int32), cache, 0, cfg)
    # every kinded model's refusals, at construction, with the reason
    with pytest.raises(NotImplementedError, match="training forward"):
        T.forward(params, jnp.zeros((1, 4), jnp.int32), cfg)
    with pytest.raises(NotImplementedError, match="shared-prefix"):
        S.ContinuousBatcher(params, cfg, batch=2, max_len=32,
                            shared_prefix=[1, 2])
    with pytest.raises(NotImplementedError, match="KV shipping"):
        D.kv_wire_layout(cfg)
    with pytest.raises(NotImplementedError, match="speculative"):
        S.SpeculativeContinuousBatcher(params, cfg, params, cfg, batch=2,
                                       max_len=32)


def test_the_named_scopes_are_in_the_step_and_the_admission(tiny):
    """``ssm_mixer`` > ``ssm_conv`` / ``ssm_state`` in both programs'
    lowered text: what a device trace is read by."""
    c, fam, cfg, params = tiny
    cache = dict(D.init_kv_cache(cfg, 2, 32),
                 length=jnp.zeros((2,), jnp.int32))
    logits = jnp.zeros((2, cfg.vocab_size), jnp.float32)
    step = S.step_rows.lower(
        params, cache, logits, jnp.zeros((2, 2), jnp.uint32),
        jnp.zeros((2,), jnp.int32), 2, cfg).as_text(debug_info=True)
    admit = S.admit_rows.lower(
        params, cache, logits, jnp.arange(2), jnp.zeros((2, 16), jnp.int32),
        jnp.ones((2,), jnp.int32), cfg).as_text(debug_info=True)
    for text in (step, admit):
        for scope in ("ssm_mixer/ssm_conv", "ssm_mixer/ssm_state"):
            assert scope in text, scope


# ----------------------------------------------------- the cell, toy size
@pytest.mark.parametrize("fault,correct", [
    ("", True), ("wrong_token_one_slot", False)])
def test_toy_cell_end_to_end(tmp_path, monkeypatch, fault, correct):
    """The new cell's whole run at toy size on the CPU: replica child,
    the wire, the cell's own driver (``drivers/serve_drain_rows.py``: the
    reference a row at a time), the family's sequential reference over
    the served tokens — correct; with a token altered in one slot
    underneath, not."""
    from benchmark import run
    monkeypatch.setenv("XLA_FLAGS", "")
    from benchmark.tests.test_run_faults import SERVE_LIMITS
    bench = {"workloads": [{
        "name": "toy", "chips": 1, "config": CONFIG,
        "traffic": os.path.join(HERE, "data",
                                "saturated-wide-rows-tiny.json")}],
        "end_to_end": [{"name": n, "unit": "x"} for n in
                       ("serve_tokens_per_s", "itl_p95_ms", "setup_s")],
        "per_layer": []}
    got = run.run_cell(bench, "toy", 2**31 + 42, 3.0, 0, platform="cpu",
                       root=str(tmp_path), fault=fault, limits=SERVE_LIMITS)
    assert got["correct"] is correct
    assert got["failed"] == 0 and got["attempted"] > 0
