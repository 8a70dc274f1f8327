"""Latent attention and sparse experts on the serving path, at toy widths
on the CPU (float32 both sides): hidden 128, 4 heads of 32+16 / 32, ranks
48 / 32, 16 experts of width 64 (8 held from the 4th on), 4 a token, one
dense and two expert layers — ``tests/data/tiny-mla-moe.json``, read by
the benchmark's family ``benchmark/families/mla_moe_decoder.py``, whose
float32 reference (EXPANDED attention, every held expert for every token)
is the yardstick and imports nothing from the program.
"""

import json
import math
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import modelcfg, reference
from tony_tpu.models import decode as D
from tony_tpu.models import serve as S
from tony_tpu.models import transformer as T
from tony_tpu.parallel import moe

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIG = os.path.join(HERE, "data", "tiny-mla-moe.json")
SEED = 2**31 + 28


@pytest.fixture(scope="module")
def tiny():
    c = modelcfg.load(CONFIG)
    fam = modelcfg.family(c)
    return (c, fam, fam.program_config(c, dtype=jnp.float32, remat=False),
            fam.make_params(SEED, c, jnp.float32))


def _tokens(n_rows, length, vocab, salt=0):
    return np.random.default_rng(SEED + salt).integers(
        0, vocab, (n_rows, length)).astype(np.int32)


# ------------------------------------------------------------------ (a)
def test_prefill_then_absorbed_decode_is_the_expanded_reference(tiny):
    """Rows admitted at DIFFERENT lengths into one bucket, then decoded
    through the latent cache in the absorbed form, give the logits of the
    family's expanded float32 reference at every position. Tolerance
    2e-4 on logits of order 1: float32 both sides, but the absorbed form
    associates (q W_uk^T) c_kv where the reference has q (c_kv W_uk), the
    cache read is an online softmax over blocks, and the program's
    routed sum runs sorted by expert — rounding order, nothing else."""
    c, fam, cfg, params = tiny
    lengths = np.array([5, 17, 11], np.int32)
    steps, bucket, rows = 12, 32, 64
    toks = _tokens(3, int(lengths.max()) + steps, c["vocab_size"])
    ref = np.asarray(reference.Reference(c, SEED, None,
                                         jnp.float32).logits(toks))
    prompts = np.zeros((3, bucket), np.int32)
    for r, n in enumerate(lengths):
        prompts[r, :n] = toks[r, :n]
    lg, mini = D.prefill_rows(params, jnp.asarray(prompts),
                              jnp.asarray(lengths), cfg)
    for r, n in enumerate(lengths):
        np.testing.assert_allclose(lg[r], ref[r, n - 1], atol=2e-4, rtol=0)
    # the bucket's padding is not routed: at most every real token's picks
    # in both expert layers landed, not the 3 x 32 positions'
    assert 0 < int(mini[D.MOE_COUNTS][0]) <= int(lengths.sum()) * 4 * 2
    cache = dict(D.init_kv_cache(cfg, 4, rows),
                 length=jnp.zeros((4,), jnp.int32))
    cache = D.place_rows(cache, mini, jnp.asarray([2, 0, 3]),
                         jnp.asarray(lengths))
    slot_of = {0: 2, 1: 0, 2: 3}
    step = jax.jit(lambda tok, cache: D.decode_step(
        params, tok, cache, cache["length"], cfg))
    for t in range(steps):
        tok = np.zeros((4,), np.int32)
        for r, n in enumerate(lengths):
            tok[slot_of[r]] = toks[r, n + t]
        lg, cache = step(jnp.asarray(tok), cache)
        for r, n in enumerate(lengths):
            np.testing.assert_allclose(lg[slot_of[r]], ref[r, n + t],
                                       atol=2e-4, rtol=0)
    # the cache holds ONE row a token a layer, kv_rank + rope wide in
    # whole lane tiles, and the counters rode along
    assert cache["ckv"].shape == (3, 4, rows, 128)
    assert cfg.latent.row == 48 and int(cache[D.MOE_COUNTS][0]) > 0


def test_blockwise_latent_read_past_one_block(tiny):
    """A cache longer than a read block (256): the online softmax over
    two and three live blocks is the reference's one softmax."""
    c, fam, cfg, params = tiny
    toks = _tokens(1, 600, c["vocab_size"], salt=1)
    ref = np.asarray(reference.Reference(c, SEED, None,
                                         jnp.float32).logits(toks))
    lg, cache = D.prefill(params, jnp.asarray(toks[:, :250]), cfg,
                          max_len=640)
    np.testing.assert_allclose(lg[0], ref[0, 249], atol=3e-4, rtol=0)
    step = jax.jit(lambda tok, cache: D.decode_step(
        params, tok, cache, cache["length"], cfg))
    for t in range(250, 600):
        lg, cache = step(jnp.asarray(toks[:, t]), cache)
        if t in (255, 256, 511, 512, 599):
            np.testing.assert_allclose(lg[0], ref[0, t], atol=3e-4, rtol=0)


# ------------------------------------------------------------------ (b)
def test_the_shares_add_up_to_the_uncut_layer(tiny):
    """The routed parts of all ``E / held`` shares, with what every chip
    computes alike — the shared expert — counted ONCE, equal the
    reference's UNCUT layer (all 16 experts held): the cut is a share of
    the model, not another model."""
    c, fam, cfg, _ = tiny
    whole = dict(c, n_routed_experts=16, first_expert=0)
    p = {n: np.asarray(w) for n, w in fam.layer_weights(
        np.uint32(SEED), np.int32(1), whole, jnp.float32, "moe").items()}
    h = jax.random.normal(jax.random.PRNGKey(3), (2, 19, 128), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = fam.experts(h.reshape(-1, 128), p, whole)
        shared = fam._swiglu(h.reshape(-1, 128), p["shared_gate"],
                             p["shared_up"], p["shared_down"])
        got, landed = 0.0, 0
        for first in range(0, 16, 4):
            share = dataclass_replace(cfg, first, 4)
            mine = dict(p, routed=tuple(
                jnp.asarray(p[n][None, first:first + 4])
                for n in ("w_gate", "w_up", "w_down")), routed_layer=0)
            out, counts = D._sparse_mlp(h, mine, share)
            got = got + out.reshape(-1, 128) - shared
            landed += int(counts[0])
        got = got + shared
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
    # every (token, pick) landed on exactly one share
    assert landed == 2 * 19 * c["num_experts_per_tok"]


def dataclass_replace(cfg, first, held):
    import dataclasses
    return cfg.scaled(experts=dataclasses.replace(cfg.experts, first=first,
                                                  held=held))


# ------------------------------------------------------------------ (c)
def test_no_token_dropped_and_the_bias_picks_but_does_not_weigh():
    """A selection bias large enough to send EVERY token to expert 3:
    all of them are computed (no capacity, no drop) — 700 tokens through
    three chunks of the sorted layout — the pick follows ``z + b`` and
    the weights follow ``z`` alone."""
    t, d, f, e, k = 700, 128, 64, 16, 4
    keys = jax.random.split(jax.random.PRNGKey(5), 5)
    h = jax.random.normal(keys[0], (t, d), jnp.float32)
    router = jax.random.normal(keys[1], (d, e)) * d ** -0.5
    gate, up = (jax.random.normal(kk, (1, e, d, f)) * d ** -0.5
                for kk in keys[2:4])
    down = jax.random.normal(keys[4], (1, e, f, d)) * f ** -0.5
    bias = jnp.zeros((e,)).at[3].set(10.0)
    with jax.default_matmul_precision("highest"):
        z = jax.nn.sigmoid(h @ router)
        plain, _ = moe.sigmoid_route(h, router, jnp.zeros((e,)), k, 2.5)
        picks, w = moe.sigmoid_route(h, router, bias, k, 2.5)
        assert bool((picks == 3).any(-1).all())
        assert not bool((plain == 3).any(-1).all())      # the bias did it
        zp = jnp.take_along_axis(z, picks, -1)
        np.testing.assert_allclose(
            w, zp / zp.sum(-1, keepdims=True) * 2.5, rtol=1e-6)
        out, landed, touched, zeros = jax.jit(
            lambda h, p, w: moe.held_experts_ffn(
                h, p, w, gate[:, 2:4], up[:, 2:4], down[:, 2:4], 0,
                moe.HeldExperts(2, 2, e)))(h, picks, w)
        assert zeros == 0                   # a layer without zero experts
        want = 0.0
        for ex in (2, 3):
            y = (jax.nn.silu(h @ gate[0, ex]) * (h @ up[0, ex])) @ down[0, ex]
            want = want + jnp.sum(jnp.where(picks == ex, w, 0), -1,
                                  keepdims=True) * y
    assert int(landed) == int(((picks == 2) | (picks == 3)).sum()) >= t
    assert int(touched) == 2
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=0)


def test_grouped_matmul_kernel_is_the_ragged_product():
    """The Mosaic kernel in the Pallas interpreter against plain products:
    each live tile meets its group's weights; skipped tiles are the
    caller's to mask."""
    from tony_tpu.ops.grouped_matmul import grouped_matmul
    tm = 16
    lhs = jax.random.normal(jax.random.PRNGKey(1), (6 * tm, 128))
    rhs = jax.random.normal(jax.random.PRNGKey(2), (5, 128, 256))
    tile_group = jnp.asarray([0, 2, 2, 4, 1, 3])
    got = grouped_matmul(lhs, rhs, tile_group, jnp.int32(4), tm=tm,
                         interpret=True)
    with jax.default_matmul_precision("highest"):
        want = jnp.concatenate([lhs[i * tm:(i + 1) * tm] @ rhs[g]
                                for i, g in enumerate([0, 2, 2, 4])])
    np.testing.assert_allclose(got[:4 * tm], want, atol=1e-4, rtol=0)


# ------------------------------------------------------------------ (d)
def test_yarn_tables_are_the_closed_form_past_the_original_context():
    """base 50,000 on 64 dims, factor 64 over 4,096 positions: dimensions
    that turn more than 32 times in the original context (i < 8) keep
    theta_i, those that turn less than once (i >= 20) take theta_i / 64,
    a linear ramp between; cos/sin carry mscale / mscale_all_dim = 1 and
    the softmax m^2, m = 0.1 ln 64 + 1. float64 closed form here against
    the program's float32 tables: angles reach 2e4 rad, where float32
    resolves 2e-3."""
    y = T.RopeYarn(factor=64.0, beta_fast=32, beta_slow=1,
                   original_max=4096, mscale=1.0, mscale_all_dim=1.0)
    dim, base = 64, 50000.0

    def turns(r):
        return dim * math.log(4096 / (r * 2 * math.pi)) / (2 * math.log(base))
    low, high = math.floor(turns(32)), math.ceil(turns(1))
    assert (low, high) == (8, 20)
    i = np.arange(dim // 2, dtype=np.float64)
    theta = base ** (-2 * i / dim)
    ramp = np.clip((i - low) / (high - low), 0, 1)
    freq = theta * (1 - ramp) + theta / 64 * ramp
    assert np.all(freq[:9] == theta[:9]) and np.allclose(
        freq[20:], theta[20:] / 64, rtol=1e-15)
    pos = np.array([[0, 1, 4095, 4096, 5000, 8191, 20000]])
    cos, sin = T.rope_tables(jnp.asarray(pos), dim, base, y)
    ang = pos[0][:, None] * freq[None, :]
    np.testing.assert_allclose(cos[0, :, 0], np.cos(ang), atol=4e-3)
    np.testing.assert_allclose(sin[0, :, 0], np.sin(ang), atol=4e-3)
    assert y.table_scale == 1.0
    assert y.softmax_scale == pytest.approx((0.1 * math.log(64) + 1) ** 2)
    # unscaled tables are today's: base 10,000, nothing blended
    c0, _ = T.rope_tables(jnp.asarray(pos), dim)
    np.testing.assert_allclose(
        c0[0, :, 0], np.cos(pos[0][:, None] * 10000.0 ** (-2 * i / dim)),
        atol=4e-3)


# ------------------------------------------------------------------ (e)
REFUSALS = {
    "train step": lambda cfg, p: T.lm_loss(
        p, {"tokens": jnp.zeros((1, 8), jnp.int32)}, cfg),
    "1f1b train step": lambda cfg, p: T.lm_value_and_grad(
        p, {"tokens": jnp.zeros((1, 8), jnp.int32)}, cfg, None),
    "speculative batcher": lambda cfg, p: S.SpeculativeContinuousBatcher(
        p, cfg, p, cfg, 2, 64),
    "speculative generate": lambda cfg, p: D.speculative_generate(
        p, p, jnp.zeros((1, 4), jnp.int32), cfg, cfg, 4),
    "shared prefix": lambda cfg, p: S.ContinuousBatcher(
        p, cfg, 2, 64, shared_prefix=[1, 2, 3]),
    "resident prefix": lambda cfg, p: S.ContinuousBatcher(
        p, cfg, 2, 64).install_prefix("sys", [1, 2, 3]),
    "prefix template": lambda cfg, p: S.prefix_template(p, [1, 2, 3], cfg),
    "KV shipping": lambda cfg, p: D.kv_wire_layout(cfg),
    "int8 cache": lambda cfg, p: cfg.scaled(kv_cache_dtype="int8"),
    "ring cache": lambda cfg, p: cfg.scaled(attn_window=16,
                                            kv_cache_capacity=16),
    "beam search": lambda cfg, p: D.beam_search(
        p, jnp.zeros((1, 4), jnp.int32), cfg, 4, beam_width=2),
}


@pytest.mark.parametrize("what", sorted(REFUSALS))
def test_refused_at_construction_with_the_reason(tiny, what):
    _, _, cfg, params = tiny
    with pytest.raises((NotImplementedError, ValueError),
                       match="layer_kinds"):
        REFUSALS[what](cfg, params)


def test_a_kind_list_needs_its_layers_described():
    with pytest.raises(ValueError, match="set both"):
        T.TransformerConfig(n_layers=2, layer_kinds=("dense", "moe"))
    with pytest.raises(ValueError, match="layer_kinds"):
        T.TransformerConfig(latent=T.LatentAttention(48, 32, 32, 16, 32))
    with pytest.raises(ValueError, match="inside the 16"):
        T.TransformerConfig(
            n_layers=2, layer_kinds=("dense", "moe"),
            latent=T.LatentAttention(48, 32, 32, 16, 32),
            experts=T.SparseExperts(16, 4, 64, first=12, held=8))


# ----------------------------------------------- serving path, counters
def test_served_through_the_batcher_with_counters_in_stats(tiny):
    """``ContinuousBatcher`` -> ``ServeEngine`` on the kinded model: the
    greedy tokens are ``generate``'s, the expert layers' device counters
    arrive with the chunks' tokens — per program kind, in
    ``engine.stats()`` and in the metrics registry — and a dense model's
    stats carry zeros."""
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
    for rid, (p, n) in enumerate(zip(prompts, budgets)):
        want = D.generate(params, jnp.asarray(p)[None], cfg, n,
                          jax.random.PRNGKey(0)).tokens[0, len(p):]
        assert got[rid] == [int(t) for t in want]
    st = eng.stats()
    layers = 2                                   # expert layers
    steps = st["steps_executed"]
    assert st["moe_assignments"]["decode"] > 0
    # 3 slots x 4 picks a step a layer, of which the held half or so
    assert st["moe_assignments"]["decode"] <= steps * layers * 3 * 4
    assert 0 < st["moe_expert_touches"]["decode"] <= steps * layers * 8
    assert st["moe_assignments"]["admit"] > 0
    for what, have in (("assignments", st["moe_assignments"]),
                       ("expert_touches", st["moe_expert_touches"])):
        for program in ("decode", "admit"):
            series = reg.counter(f"tony_moe_{what}_total", program=program)
            assert series.value == have[program]
    assert "tony_moe_expert_touches_total" in reg.to_wire_json()


def test_int8_weights_serve_the_kinded_tree(tiny):
    """``quantize_weights_int8`` (the serving cells' control) reaches the
    new leaves — the projections, the dense SwiGLU, the shared expert,
    the head — and the served tokens stay close to the float model's."""
    from tony_tpu.models.quantize import QuantizedWeight, \
        quantize_weights_int8
    c, fam, cfg, params = tiny
    q = quantize_weights_int8(params)
    assert isinstance(q["blocks"]["moe"]["shared_gate"], QuantizedWeight)
    assert isinstance(q["blocks"]["dense"]["w_down"], QuantizedWeight)
    assert not isinstance(q["blocks"]["moe"]["w_gate"], QuantizedWeight)
    toks = jnp.asarray(_tokens(2, 24, c["vocab_size"], salt=2))
    a, _ = D.prefill(params, toks, cfg, max_len=32)
    b, _ = D.prefill(q, toks, cfg, max_len=32)
    # rounding, not another model: a routing flip may move one logit row
    # by most of a unit, the mean stays small
    assert 0 < float(jnp.abs(a - b).mean()) < 0.3


# ------------------------------------------------------------------ (g)
@pytest.mark.parametrize("fault,correct", [
    ("", True), ("wrong_token_one_slot", False)])
def test_toy_cell_end_to_end(tmp_path, monkeypatch, fault, correct):
    """The new cell's whole run at toy size on the CPU: replica child,
    the wire, the cell's own driver (``drivers/serve_drain.py``: a closed
    loop left to drain), the family's reference over the served tokens —
    correct; with a token altered in one slot underneath, not."""
    from benchmark import run
    # the replica wants ONE device; this suite's 8 virtual ones are for
    # the mesh tests (conftest.py), and a child inherits the flag
    monkeypatch.setenv("XLA_FLAGS", "")
    from benchmark.tests.test_run_faults import SERVE_LIMITS
    bench = {"workloads": [{
        "name": "toy", "chips": 1, "config": CONFIG,
        "traffic": os.path.join(HERE, "data", "saturated-long-tiny.json")}],
        "end_to_end": [{"name": n, "unit": "x"} for n in
                       ("serve_tokens_per_s", "itl_p95_ms", "setup_s")],
        "per_layer": []}
    got = run.run_cell(bench, "toy", 2**31 + 29, 3.0, 0, platform="cpu",
                       root=str(tmp_path), fault=fault, limits=SERVE_LIMITS)
    assert got["correct"] is correct
    assert got["failed"] == 0 and got["attempted"] > 0


@pytest.mark.parametrize("fault", ["fp8_routed", "fp8_experts",
                                   "zero_routed", "swapped_routed"])
def test_a_fault_in_the_routed_part_alone_is_not_correct(tiny, fault):
    """``benchmark/tools/control_routed.py``: the reference with ONLY its
    router and routed experts at fault, in the program's place, crosses
    the toy cell's limits — ``correct`` holds the routed product, which
    the int8 control's quantizer never reaches. (Published widths: PERF.md
    section 2.)"""
    from benchmark.tests.test_run_faults import SERVE_LIMITS
    from benchmark.tools import control_routed as tool
    c, fam = tiny[:2]
    tokens = jnp.asarray(_tokens(4, 32, c["vocab_size"], salt=5))
    sound = tool.logits_of(c, SEED, fam, None, tokens)
    low = tool.logits_of(c, SEED, *tool._faulty(fam, fault), tokens)
    gaps = sound.max(-1) - np.take_along_axis(
        sound, low.argmax(-1)[..., None], -1)[..., 0]
    assert (gaps > 0).mean() > SERVE_LIMITS["served_token_mismatch_share"]
    assert gaps.mean() > SERVE_LIMITS["served_token_mean_gap"]
    # the sound reference against itself reads nothing
    assert not (sound.max(-1) - np.take_along_axis(
        sound, sound.argmax(-1)[..., None], -1)[..., 0]).any()


def test_a_request_queued_at_the_close_is_left_to_finish():
    """What ``drivers/serve_drain.py`` changes: a request sent inside the
    window whose slot comes long after the close waits ``drain_seconds``
    for each event, and ends ``budget``; past that it is given up as
    ``drivers/serve.py`` gives it up. The swap lasts one run."""
    import queue

    from benchmark.drivers import serve, serve_drain

    class Slow:
        """A server whose every answer starts ``wait`` s after it was
        sent: one TOKENS frame, then RETIRED."""

        def __init__(self, wait):
            self.wait, self.sent = wait, {}

        def submit(self, prompt, n):
            rid = len(self.sent)
            self.sent[rid] = (time.perf_counter(), n)
            return rid

        def next_event(self, rid, timeout=None):
            t, n = self.sent[rid]
            if n is None:
                return ("retired", "budget", 0)
            left = t + self.wait - time.perf_counter()
            if left > timeout:
                time.sleep(timeout)
                raise queue.Empty
            time.sleep(max(0.0, left))
            self.sent[rid] = (t, None)
            return ("tokens", list(range(n)))

    reqs = [{"prompt": [1], "max_new_tokens": 3}] * 8
    # window 0.2 s, answers after 1.5 s: sent in the window, done after it
    done = serve_drain.closed_loop(Slow(1.5), reqs, 2, time.perf_counter(),
                                   0.2, drain_s=3.0)
    assert len(done) == 2 and all(s.ok for s in done)
    done = serve_drain.closed_loop(Slow(1.5), reqs, 2, time.perf_counter(),
                                   0.2, drain_s=0.5)
    assert [s.reason for s in done] == ["timeout"] * 2
    kept = serve.closed_loop
    with pytest.raises(ValueError, match="closed loops"):
        serve_drain.run(mix={"loop": "open"})
    assert serve.closed_loop is kept


# ------------------------------------------- the family and the cell's files
def test_family_counts_are_the_trees_at_published_widths():
    """``kimi-k2.5-l6-ep32`` (shapes only): the family's parameter count
    is the size of the tree it makes and of the program's own init, leaf
    for leaf; 4.173 B; a token's forward FLOPs are twice the parameters
    it MEETS (8 x 12 / 384 routed experts, not the 12 held)."""
    c = modelcfg.load("kimi-k2.5-l6-ep32")
    fam = modelcfg.family(c)
    made = jax.eval_shape(lambda: fam.make_params(7, c, jnp.bfloat16))
    cfg = fam.program_config(c, dtype=jnp.bfloat16)
    own = jax.eval_shape(lambda: T.init_params(jax.random.PRNGKey(0), cfg))
    shapes = lambda t: jax.tree.map(lambda x: (x.shape, x.dtype), t)  # noqa: E731
    assert shapes(made) == shapes(own)
    size = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(made))
    assert fam.param_count(c) == size
    assert round(size / 1e9, 3) == 4.173
    assert fam.layer_kinds(c) == ["dense"] + ["moe"] * 5
    one_by_one = sum(
        int(np.prod(x.shape)) for li, kind in enumerate(fam.layer_kinds(c))
        for x in jax.tree.leaves(jax.eval_shape(
            lambda li=li, kind=kind: fam.layer_weights(
                np.uint32(7), np.int32(li), c, jnp.bfloat16, kind))))
    outer = jax.eval_shape(lambda: fam.outer_weights(np.uint32(7), c,
                                                     jnp.bfloat16))
    assert one_by_one + sum(int(np.prod(x.shape))
                            for x in jax.tree.leaves(outer)) == size
    expert = 3 * 7168 * 2048
    met = size - 20480 * 7168 - 5 * (12 - 0.25) * expert
    flops = fam.forward_flops_per_token(c, 1)
    assert 2 * met * 0.99 < flops < 2 * met * 1.01
    # a decode step: everything but the embedding once when every held
    # expert is touched (no run given), the router in float32
    every = fam.decode_step_bytes(c, 0.0, None)
    assert every == 2 * (size - 20480 * 7168) + 2 * 5 * (7168 * 384 + 384)
    ctx = {"mix": {"slots": 32}}
    _, touched = fam.expert_load(c, ctx)
    assert touched == pytest.approx(12 * (1 - (47 / 48) ** 32))   # 5.9
    assert every - fam.decode_step_bytes(c, 0.0, ctx) == pytest.approx(
        5 * (12 - touched) * expert * 2)
    assert fam.decode_step_bytes(c, 100.0, ctx) - \
        fam.decode_step_bytes(c, 0.0, ctx) == 100 * 6 * 576 * 2


def test_configuration_file_states_its_cut():
    with open(os.path.join(HERE, os.pardir, "benchmark", "configs",
                           "kimi-k2.5-l6-ep32.json")) as f:
        c = json.load(f)
    with open(os.path.join(HERE, os.pardir, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(e for e in bench["configs"]
                 if e["name"] == "kimi-k2.5-l6-ep32")
    assert sorted(entry["reduced"]) == sorted(c["reduced"]) == [
        "n_routed_experts", "num_hidden_layers", "vocab_size"]
    for key, cut in c["reduced"].items():
        assert c[key] == cut["run"] != cut["published"]
    assert (c["router_experts"], c["num_experts_per_tok"],
            c["routed_scaling_factor"]) == (384, 8, 2.827)
    for key in ("assumed", "departures", "deployment"):
        assert c[key]
    cell = next(w for w in bench["workloads"]
                if w["name"] == "serve-kimik25-saturated")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "kimi-k2.5-l6-ep32", "saturated-long-answers", 1)
