"""Window and full attention layers in one model, each owning the cache it
needs, on the serving path at toy widths on the CPU (float32 both sides):
hidden 64, 8 query / 2 K/V heads of 16 (q is 128 wide: heads wider than
hidden / heads), 3 sliding layers (window 12, so a RING of 16 rows a
slot) then 1 full layer, every layer a parallel block with 16 experts of
width 32 (4 held from the 4th on), 4 a token, 4 averaged shared experts,
a tied head — ``tests/data/tiny-window-full-moe.json``, read by the
benchmark's family ``benchmark/families/window_full_moe_decoder.py``,
whose float32 reference (full forward, every held expert for every
token) is the yardstick and imports nothing from the program.

Tolerance. Both sides are float32 at ``highest`` matmul precision; the
logits are of order sqrt(64) = 8 (a tied head over unit-variance rows).
They differ by rounding ORDER alone: the flash-style online softmax of
the full kind's blockwise read, the ring's rows contracted in ring order,
the program's routed sum sorted by expert against the reference's expert
by expert. 2e-3 absolute on logits of order 8 holds that (2.5e-4 of the
scale); the same model run in bfloat16 misses it by more than ten times
(``test_bfloat16_would_fail_the_tolerance``).
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import modelcfg, reference
from tony_tpu.models import decode as D
from tony_tpu.models import serve as S
from tony_tpu.models import transformer as T

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIG = os.path.join(HERE, "data", "tiny-window-full-moe.json")
SEED = 2**31 + 35
ATOL = 2e-3
RING = 16           # ring_rows(window 12)


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


def _admit(params, cfg, cache, logits, slots, toks, lengths, bucket):
    """``serve.admit_rows`` of rows ``toks[r, :lengths[r]]`` into
    ``slots``, padded to ``bucket``."""
    prompts = np.zeros((len(slots), bucket), np.int32)
    for r, n in enumerate(lengths):
        prompts[r, :n] = toks[r, :n]
    cache, logits, _ = S.admit_rows(
        params, cache, logits, jnp.asarray(slots, jnp.int32),
        jnp.asarray(prompts), jnp.asarray(lengths, jnp.int32), cfg)
    return cache, logits


def _empty(cfg, slots, rows):
    return (dict(D.init_kv_cache(cfg, slots, rows),
                 length=jnp.zeros((slots,), jnp.int32)),
            jnp.zeros((slots, cfg.vocab_size), jnp.float32))


def _decode_against(params, cfg, cache, toks, ref, lengths, slot_of, steps,
                    atol=ATOL):
    step = jax.jit(lambda tok, cache: D.decode_step(
        params, tok, cache, cache["length"], cfg))
    n_slots = cache["length"].shape[0]
    for t in range(steps):
        tok = np.zeros((n_slots,), np.int32)
        for r, n in enumerate(lengths):
            tok[slot_of[r]] = toks[r, n + t]
        lg, cache = step(jnp.asarray(tok), cache)
        for r, n in enumerate(lengths):
            np.testing.assert_allclose(lg[slot_of[r]], ref[r, n + t],
                                       atol=atol, rtol=0)
    return cache


# ------------------------------------------------- prefill, ring, decode
def test_padded_prefill_lands_in_ring_and_linear_cache_then_decodes(tiny):
    """One bucketed admission of prompts SHORTER than the ring (7),
    EQUAL to it (16) and LONGER (29: no multiple of anything), padded to
    32, then 24 teacher-forced decode steps — the short row runs on past
    the window and past the ring's rows, the long one wraps a second
    time — give the reference's full-forward logits at every position.
    The padding tail (positions 29..31 of the long row are ring rows
    13..15 of its LIVE window) must not land."""
    c, fam, cfg, params = tiny
    lengths, steps = [7, 16, 29], 24
    toks = _tokens(3, max(lengths) + steps, c["vocab_size"])
    ref = _ref_logits(c, toks)
    cache, logits = _empty(cfg, 4, 96)
    slots = [2, 0, 3]
    cache, logits = _admit(params, cfg, cache, logits, slots, toks, lengths,
                           32)
    for r, n in enumerate(lengths):
        np.testing.assert_allclose(logits[slots[r]], ref[r, n - 1],
                                   atol=ATOL, rtol=0)
    assert list(np.asarray(cache["length"])) == [16, 0, 7, 29]
    cache = _decode_against(params, cfg, cache, toks, ref, lengths,
                            dict(enumerate(slots)), steps)
    assert int(cache[D.MOE_COUNTS][0]) > 0


def test_each_kind_owns_its_rows(tiny):
    """``init_kv_cache`` of the mixed model: the window kinds hold a ring
    of ``ring_rows`` (16: window 12 in whole 16-row tiles) a slot, the
    full kind ``max_len``; a prefill's mini cache is linear in both; and
    the ring's bytes do not grow with ``max_len``."""
    _, _, cfg, _ = tiny
    assert D.ring_rows(cfg) == RING
    assert D.ring_rows(cfg.scaled(attn_window=4096)) == 4096
    shapes = {n: a.shape for n, a in D.init_kv_cache(cfg, 5, 96).items()}
    assert shapes["k_ring"] == shapes["v_ring"] == (3, 5, RING, 2 * 16)
    assert shapes["k"] == shapes["v"] == (1, 5, 96, 2 * 16)
    long = D.init_kv_cache(cfg, 5, 4096)
    assert long["k_ring"].shape == (3, 5, RING, 32)
    assert long["k"].shape == (1, 5, 4096, 32)
    assert D.cache_rows(long) == 4096
    mini = D.init_kv_cache(cfg, 2, 64, ring=False)
    assert mini["k_ring"].shape == (3, 2, 64, 32)
    assert D.cache_layout(cfg, 96) == {
        "k_ring": (3, RING, 32, jnp.float32),
        "v_ring": (3, RING, 32, jnp.float32),
        "k": (1, 96, 32, jnp.float32), "v": (1, 96, 32, jnp.float32)}


@pytest.mark.parametrize("length", [1, 11, 15, 16, 17, 31, 32, 33, 47, 64])
def test_ring_rows_of_a_linear_prefill(length):
    """``decode._ring_rows_of``: ring row r holds the LAST position p <
    length with p = r (mod C); rows no position reaches are never read,
    so only the reached ones are compared."""
    c_rows, s = 16, 64
    mini = jnp.arange(s, dtype=jnp.float32)[None, None, :, None] \
        * jnp.ones((2, 1, s, 3))
    got = np.asarray(D._ring_rows_of(mini, jnp.asarray([length]), c_rows))
    for r in range(c_rows):
        want = [p for p in range(length) if p % c_rows == r]
        if want:
            assert (got[:, 0, r] == want[-1]).all(), (r, got[0, 0, r])
    assert got.shape == (2, 1, c_rows, 3)


def test_a_slot_reused_after_a_longer_occupant(tiny):
    """A slot that held a 29-token prompt decoded to 40 takes, after
    ``retire_rows``, a 5-token one: the ring's residue (rows 5..15 still
    hold the old occupant's K and V) and the linear buffer's are behind
    every mask, and the new occupant's logits are the reference's."""
    c, fam, cfg, params = tiny
    toks = _tokens(2, 48, c["vocab_size"], salt=1)
    ref = _ref_logits(c, toks)
    cache, logits = _empty(cfg, 2, 64)
    cache, logits = _admit(params, cfg, cache, logits, [1], toks[:1], [29],
                           32)
    cache = _decode_against(params, cfg, cache, toks[:1], ref[:1], [29],
                            {0: 1}, 11)
    cache = S.retire_rows(cache, jnp.asarray([False, True]))
    cache, logits = _admit(params, cfg, cache, logits, [1], toks[1:], [5],
                           8)
    np.testing.assert_allclose(logits[1], ref[1, 4], atol=ATOL, rtol=0)
    _decode_against(params, cfg, cache, toks[1:], ref[1:], [5], {0: 1}, 30)


def test_full_kind_reads_its_live_blocks_past_one_block(tiny):
    """A linear buffer longer than a read block (256): the full kind
    walks its live blocks (``_cached_attention_blockwise``) while the
    window kinds read their 16-row ring, 300 positions on."""
    c, fam, cfg, params = tiny
    toks = _tokens(1, 330, c["vocab_size"], salt=2)
    ref = _ref_logits(c, toks)
    lg, cache = D.prefill(params, jnp.asarray(toks[:, :250]), cfg,
                          max_len=640)
    assert cache["k"].shape[2] == 640 and cache["k_ring"].shape[2] == RING
    np.testing.assert_allclose(lg[0], ref[0, 249], atol=ATOL, rtol=0)
    step = jax.jit(lambda tok, cache: D.decode_step(
        params, tok, cache, cache["length"], cfg))
    for t in range(250, 330):
        lg, cache = step(jnp.asarray(toks[:, t]), cache)
        if t in (255, 256, 257, 300, 329):
            np.testing.assert_allclose(lg[0], ref[0, t], atol=ATOL, rtol=0)


def test_bfloat16_would_fail_the_tolerance(tiny):
    """The same prefill in bfloat16 weights and activations misses ATOL
    by more than ten times: the tolerance tells a lower precision."""
    c, fam, _, _ = tiny
    cfg = fam.program_config(c, dtype=jnp.bfloat16, remat=False)
    params = fam.make_params(SEED, c, jnp.bfloat16)
    toks = _tokens(2, 24, c["vocab_size"], salt=3)
    ref = _ref_logits(c, toks, jnp.bfloat16)     # the SAME rounded weights
    lg, _ = D.prefill(params, jnp.asarray(toks), cfg, max_len=32)
    assert float(np.abs(np.asarray(lg, np.float32) - ref[:, -1]).max()) \
        > 10 * ATOL


# ------------------------------------------------------------ the share
def test_the_shares_add_up_to_the_uncut_layer(tiny):
    """The routed parts of all ``router_experts / held`` ranks (4 here, 8
    in the cell's deployment), with what every chip computes alike — the
    AVERAGED shared experts — counted ONCE, equal the reference's UNCUT
    layer (all 16 experts held): the cut is a share of the model."""
    c, fam, cfg, _ = tiny
    d = c["hidden_size"]
    whole = dict(c, num_experts=16, first_expert=0)
    p = {n: np.asarray(w) for n, w in fam.layer_weights(
        np.uint32(SEED), np.int32(1), whole, jnp.float32, "moe").items()}
    h = jax.random.normal(jax.random.PRNGKey(3), (2, 19, d), jnp.float32)
    flat = h.reshape(-1, d)
    want = fam.experts(flat, p, whole)
    shared = sum(fam._swiglu(flat, p["shared_gate"][s], p["shared_up"][s],
                             p["shared_down"][s]) for s in range(4)) / 4
    got, landed = 0.0, 0
    for first in range(0, 16, 4):
        share = cfg.scaled(experts=dataclasses.replace(
            cfg.experts, first=first, held=4))
        mine = dict(p, routed=tuple(
            jnp.asarray(p[n][None, first:first + 4])
            for n in ("w_gate", "w_up", "w_down")), routed_layer=0)
        out, counts = D._sparse_mlp(h, mine, share)
        got = got + out.reshape(-1, d) - shared
        landed += int(counts[0])
    np.testing.assert_allclose(got + shared, want, atol=2e-5, rtol=0)
    assert landed == 2 * 19 * c["num_experts_per_tok"]
    # the reference's own shares add up too
    parts = sum(fam.experts(flat, dict(
        p, **{n: p[n][first:first + 4] for n in ("w_gate", "w_up",
                                                  "w_down")}),
        dict(c, first_expert=first)) - shared for first in range(0, 16, 4))
    np.testing.assert_allclose(parts + shared, want, atol=2e-5, rtol=0)


def test_a_constant_bias_moves_no_pick(tiny):
    """The family carries a layer's type in ``router_bias`` — 0 over a
    sliding layer's experts, 1 over a full layer's: the router's picks
    and weights are those of a zero bias."""
    from tony_tpu.parallel import moe
    c, fam, cfg, params = tiny
    assert float(params["blocks"]["window_moe"]["router_bias"].max()) == 0
    assert float(params["blocks"]["full_moe"]["router_bias"].min()) == 1
    h = jax.random.normal(jax.random.PRNGKey(4), (64, c["hidden_size"]))
    r = params["blocks"]["full_moe"]["router"][0]
    with_bias = moe.sigmoid_route(h, r, jnp.ones((16,)), 4, 1.0)
    without = moe.sigmoid_route(h, r, jnp.zeros((16,)), 4, 1.0)
    np.testing.assert_array_equal(np.sort(with_bias[0], -1),
                                  np.sort(without[0], -1))
    np.testing.assert_allclose(np.sort(with_bias[1], -1),
                               np.sort(without[1], -1), atol=1e-6)


# --------------------------------------------------- refusals, settings
def _kinded(**kw):
    base = dict(vocab_size=64, d_model=32, n_layers=2, n_heads=4,
                n_kv_heads=2, head_dim=16, attn_window=8, d_ff=48,
                layer_kinds=("window_dense", "full_dense"),
                dtype=jnp.float32, remat=False)
    base.update(kw)
    return T.TransformerConfig(**base)


REFUSALS = {
    "training": lambda cfg, p: T.forward(p, jnp.zeros((1, 4), jnp.int32),
                                         cfg),
    "speculation": lambda cfg, p: D.speculative_generate(
        p, p, jnp.zeros((1, 4), jnp.int32), cfg, cfg, 4),
    "beams": lambda cfg, p: D.beam_search(
        p, jnp.zeros((1, 4), jnp.int32), cfg, 4, beam_width=2),
    "prefix templates": lambda cfg, p: S.prefix_template(p, [1, 2], cfg),
    "shared prefix": lambda cfg, p: S.ContinuousBatcher(
        p, cfg, batch=2, max_len=32, shared_prefix=[1, 2]),
    "KV shipping": lambda cfg, p: D.kv_wire_layout(cfg),
    "int8 cache": lambda cfg, p: cfg.scaled(kv_cache_dtype="int8"),
    "whole-model ring": lambda cfg, p: cfg.scaled(kv_cache_capacity=16),
}


@pytest.mark.parametrize("what", sorted(REFUSALS))
def test_refused_with_the_reason(what):
    cfg = _kinded()
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    with pytest.raises((NotImplementedError, ValueError),
                       match="layer_kinds"):
        REFUSALS[what](cfg, params)


def test_kinds_name_their_settings():
    with pytest.raises(ValueError, match="attn_window"):
        _kinded(attn_window=0)
    with pytest.raises(ValueError, match="no kind"):
        _kinded(layer_kinds=("full_dense", "full_dense"))
    with pytest.raises(ValueError, match="layer_kinds"):
        T.TransformerConfig(parallel_block=True)
    with pytest.raises(ValueError, match="unknown norm"):
        _kinded(norm="batch")
    # head_dim: a field, d_model // n_heads unless set, derived anew
    # when a derived one's terms change
    assert T.PRESETS["tiny"].head_dim == 32
    assert T.PRESETS["tiny"].scaled(n_heads=8).head_dim == 16
    assert _kinded().head_dim == 16 and _kinded().scaled(
        d_model=64).head_dim == 16


@pytest.mark.parametrize("parallel,norm,tied", [
    (False, "rms", False), (True, "layer", True), (True, "rms", False)])
def test_block_settings_are_orthogonal(parallel, norm, tied):
    """Window and full kinds with a DENSE feed-forward, in a sequential
    or a parallel block, under either norm and head: prefill through
    ring and linear cache then decode equals one long prefill's last
    logits (the program against itself: the reference family covers the
    published combination)."""
    cfg = _kinded(parallel_block=parallel, norm=norm, tie_embeddings=tied,
                  logit_scale=0.5 if tied else 1.0)
    params = T.init_params(jax.random.PRNGKey(1), cfg)
    assert ("lm_head" in params) != tied
    assert ("mlp_norm" in params["blocks"]["full_dense"]) != parallel
    toks = jnp.asarray(_tokens(2, 30, 64, salt=6))
    want, _ = D.prefill(params, toks, cfg, max_len=32)
    lg, cache = D.prefill(params, toks[:, :13], cfg, max_len=32)
    for t in range(13, 30):
        lg, cache = D.decode_step(params, toks[:, t], cache,
                                  cache["length"], cfg)
    np.testing.assert_allclose(lg, want, atol=2e-4, rtol=0)


# ----------------------------------------- serving path, counters, stats
def test_served_through_the_batcher_with_cache_bytes_in_stats(tiny):
    """``ContinuousBatcher`` -> ``ServeEngine`` on the mixed model
    through ``admit_rows`` / ``step_rows`` / ``retire_rows`` (never
    ``admit_row_ring``): prompts below, at and beyond the ring in one
    queue on 3 slots, slots reused; every served token is the
    reference's best at its position (teacher-forced), the cache's bytes
    are reported by kind and the ring's overwritten rows counted."""
    from tony_tpu.runtime import metrics as M
    c, fam, cfg, params = tiny
    rs = np.random.default_rng(7)
    prompts = [rs.integers(0, c["vocab_size"], n).tolist()
               for n in (5, 17, 33, 9, 21, 40)]
    budgets = [20, 7, 12, 9, 6, 15]
    S.TRACE_COUNTS.clear()
    b = S.ContinuousBatcher(params, cfg, batch=3, max_len=96, chunk=4)
    assert not b._ring
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
    assert not any(k[0] == "admit_row_ring" for k in S.TRACE_COUNTS)
    assert any(k[0] == "admit_rows" for k in S.TRACE_COUNTS)
    ref = reference.Reference(c, SEED, None, jnp.float32)
    for rid, (p, n) in enumerate(zip(prompts, budgets)):
        assert len(got[rid]) == n
        seq = np.asarray([p + got[rid]], np.int32)
        lg = np.asarray(ref.logits(seq[:, :-1]))[0, len(p) - 1:]
        picked = lg[np.arange(n), got[rid]]
        assert (lg.max(-1) - picked < ATOL).all(), rid
    stats = eng.stats()
    f32 = 4
    assert stats["cache_bytes"] == {
        "window": 2 * 3 * 3 * RING * 32 * f32,
        "full": 2 * 1 * 3 * 96 * 32 * f32}
    # rows a ring overwrote: positions at or past its 16 rows, a window
    # layer (3) a position; every request's final length is prompt +
    # budget - 1 rows written (the last token is never fed back)
    want = 3 * sum(max(0, len(p) + n - 1 - RING)
                   for p, n in zip(prompts, budgets))
    assert stats["ring_rows_overwritten"] == want
    assert stats["moe_assignments"]["decode"] > 0
    assert reg.counter("tony_ring_rows_overwritten_total").value == want
    for kind, n in stats["cache_bytes"].items():
        assert reg.gauge("tony_cache_bytes", kind=kind).value == n
    assert "tony_cache_bytes" in reg.to_wire_json()
    # a request may not outgrow the FULL kind's rows; the ring never
    # limits a length
    with pytest.raises(ValueError, match="exceeds max_len"):
        b._validate_request([1] * 90, 10)
    b._validate_request([1] * 80, 10)


def test_int8_weights_serve_the_mixed_tree(tiny):
    """``quantize_weights_int8`` (the serving cells' control) reaches the
    window / full kinds' projections and the STACKED shared experts; the
    tied head stays the embedding; router and routed experts stay."""
    from tony_tpu.models.quantize import QuantizedWeight, \
        quantize_weights_int8
    c, fam, cfg, params = tiny
    q = quantize_weights_int8(params)
    group = q["blocks"]["window_moe"]
    for n in ("wq", "wk", "wv", "wo", "shared_gate", "shared_down"):
        assert isinstance(group[n], QuantizedWeight), n
    assert group["shared_gate"].scale.shape == (3, 4, 32)
    assert group["shared_down"].scale.shape == (3, 64)
    assert not isinstance(group["w_gate"], QuantizedWeight)
    assert "lm_head" not in q and not isinstance(q["embed"],
                                                 QuantizedWeight)
    toks = jnp.asarray(_tokens(2, 24, c["vocab_size"], salt=2))
    a, _ = D.prefill(params, toks, cfg, max_len=32)
    b, _ = D.prefill(q, toks, cfg, max_len=32)
    assert 0 < float(jnp.abs(a - b).mean()) < 0.5


# --------------------------------------------- compiled program, copies
def test_step_rows_copies_nothing_cache_sized(tiny):
    """The compiled ``step_rows`` of the mixed model (CPU backend) holds
    no ``copy`` of a cache buffer's size: ring and linear buffers are
    written in place. (The chip's compiler: tests/test_chip_compile.py.)"""
    c, fam, cfg, params = tiny
    slots, rows = 4, 640
    cache, logits = _empty(cfg, slots, rows)
    text = S.step_rows.lower(
        params, cache, logits, jnp.zeros((slots, 2), jnp.uint32),
        jnp.zeros((slots,), jnp.int32), 4, cfg).compile().as_text()
    sizes = {f"f32[{','.join(map(str, a.shape))}]"
             for n, a in cache.items() if n in D._KV_BUFS}
    copies = [ln for ln in text.splitlines()
              if " copy(" in ln and any(s in ln.split(" copy(")[0]
                                        for s in sizes)]
    assert not copies, copies[:3]


# ------------------------------------------------ the cell at toy size
@pytest.mark.parametrize("fault,correct", [
    ("", True), ("wrong_token_one_slot", False)])
def test_toy_cell_end_to_end(tmp_path, monkeypatch, fault, correct):
    """The new cell's whole run at toy size on the CPU: replica child
    (``jobs/serve_replica_rows.py``), the wire, the cell's own driver
    (``drivers/serve_drain_rows.py``: the drained closed loop, the
    reference 2 rows a block), prompts below, at and past the ring in
    one queue, the family's reference over the served tokens — correct;
    with a token altered in one slot underneath, not."""
    from benchmark import run
    from benchmark.drivers import serve
    monkeypatch.setenv("XLA_FLAGS", "")     # the replica wants ONE device
    from benchmark.tests.test_run_faults import SERVE_LIMITS
    bench = {"workloads": [{
        "name": "toy", "chips": 1, "config": CONFIG,
        "traffic": os.path.join(HERE, "data", "saturated-mixed-tiny.json")}],
        "end_to_end": [{"name": n, "unit": "x"} for n in
                       ("serve_tokens_per_s", "itl_p95_ms", "setup_s")],
        "per_layer": []}
    kept = serve.Replica
    # logits of order 8 here, where SERVE_LIMITS' toy has order 1
    limits = dict(SERVE_LIMITS, served_token_widest_gap=8e-3,
                  served_token_mean_gap=8e-4)
    got = run.run_cell(bench, "toy", 2**31 + 36, 3.0, 0, platform="cpu",
                       root=str(tmp_path), fault=fault, limits=limits)
    assert got["correct"] is correct
    assert got["failed"] == 0 and got["attempted"] > 0
    assert serve.Replica is kept            # the swap lasted one run


def test_the_reference_walks_check_rows_at_a_time(monkeypatch):
    """What ``jobs/serve_replica_rows.py`` changes: the reference's pass
    runs in blocks of the mix's ``check_rows``, and
    ``lib/reference.served_token_gaps`` is itself again after it."""
    from benchmark.jobs import serve_replica, serve_replica_rows
    from benchmark.lib import reference as R
    seen = {}

    def fake(c, seed, samples, widths, rows=8, weight_dtype=None):
        seen["rows"] = rows
        return [np.zeros(len(t)) for _, t in samples]

    monkeypatch.setattr(R, "served_token_gaps", fake)
    monkeypatch.setattr(serve_replica.Replica, "stop", lambda self: None)
    rep = object.__new__(serve_replica_rows.Replica)
    rep.mix = {"check_rows": 2, "check_widths": [8]}
    rep.c, rep.seed, rep.dtype = {}, 1, "float32"
    rep.args = type("A", (), {"trace": 0})()
    out = rep.check([[[1, 2], [3]]])
    assert seen["rows"] == 2 and out["gaps"] == [[0.0]]
    assert R.served_token_gaps is fake
    from benchmark.drivers import serve_drain_rows
    with pytest.raises(ValueError, match="check_rows"):
        serve_drain_rows.run(mix={"loop": "closed"})


# ------------------------------------------- the family and the cell's files
def test_family_counts_are_the_trees_at_published_widths():
    """``command-a-plus-l4-ep8`` (shapes only): the family's parameter
    count is the size of the tree it makes and of the program's own
    init, leaf for leaf; 4.733 B; a layer outside its routed experts is
    the 344.5 M the catalog's widths give; a token's forward FLOPs are
    twice the parameters it MEETS (8 x 16 / 128 routed experts = 1, not
    the 16 held)."""
    c = modelcfg.load("command-a-plus-l4-ep8")
    fam = modelcfg.family(c)
    made = jax.eval_shape(lambda: fam.make_params(7, c, jnp.bfloat16))
    cfg = fam.program_config(c, dtype=jnp.bfloat16)
    own = jax.eval_shape(lambda: T.init_params(jax.random.PRNGKey(0), cfg))
    shapes = lambda t: jax.tree.map(lambda x: (x.shape, x.dtype), t)  # noqa: E731
    assert shapes(made) == shapes(own)
    size = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(made))
    assert fam.param_count(c) == size
    assert round(size / 1e9, 3) == 4.733
    expert = 3 * 4096 * 4096
    outside = (size - 32768 * 4096 - 4096) / 4 - 16 * expert
    assert round(outside / 1e6, 1) == 344.5
    assert cfg.layer_kinds == ("window_moe",) * 3 + ("full_moe",)
    assert (cfg.head_dim, cfg.n_heads, cfg.kv_heads, cfg.attn_window,
            cfg.rope_base, cfg.rms_eps) == (128, 128, 8, 4096, 50000.0, 1e-5)
    assert fam.layer_kinds(c) == ["moe"] * 4
    met = size - 32768 * 4096 - 4 * (16 - 1) * expert + 32768 * 4096
    flops = fam.forward_flops_per_token(c, 1)
    assert 2 * met * 0.99 < flops < 2 * met * 1.01
    # the cache the cell pays for (bf16): a ring of 4,096 rows in three
    # layers beside 16,384 rows in one, at 32 slots
    layout = D.cache_layout(cfg, 16384)
    by = {n: l * 32 * r * w * 2 for n, (l, r, w, _) in layout.items()}
    assert layout["k_ring"][:3] == (3, 4096, 1024)
    assert layout["k"][:3] == (1, 16384, 1024)
    assert round((by["k_ring"] + by["v_ring"]) / 1e9, 2) == 1.61
    assert round((by["k"] + by["v"]) / 1e9, 2) == 2.15
    # a decode step: the touched experts and the window's share of rows
    with open(os.path.join(HERE, os.pardir, "benchmark", "traffic",
                           "saturated-mixed-lengths.json")) as f:
        mix = json.load(f)
    ctx = {"mix": mix}
    _, touched = fam.expert_load(c, ctx)
    assert touched == pytest.approx(16 * (1 - (15 / 16) ** 32))     # 13.97
    # with a run's counters: the rows that differ — its mean live slots
    # and one for the idle ones, which route alike
    run = dict(ctx, counters={"tokens_kept": 2400, "steps_executed": 100})
    assert fam.expert_load(c, run)[1] == pytest.approx(
        16 * (1 - (15 / 16) ** 25))                                 # 12.81
    share = fam.window_share(c, mix)
    assert 0.5 < share < 0.9
    per_row = fam.decode_step_bytes(c, 1000.0, ctx) \
        - fam.decode_step_bytes(c, 0.0, ctx)
    assert per_row == pytest.approx(1000 * (1 + 3 * share) * 2 * 1024 * 2)


def test_configuration_file_states_its_cut():
    with open(os.path.join(HERE, os.pardir, "benchmark", "configs",
                           "command-a-plus-l4-ep8.json")) as f:
        c = json.load(f)
    with open(os.path.join(HERE, os.pardir, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(e for e in bench["configs"]
                 if e["name"] == "command-a-plus-l4-ep8")
    assert sorted(entry["reduced"]) == sorted(c["reduced"]) == [
        "layer_types", "num_experts", "num_hidden_layers", "vocab_size"]
    for key, cut in c["reduced"].items():
        assert c[key] == cut["run"] != cut["published"]
    assert (c["router_experts"], c["num_experts_per_tok"],
            c["num_shared_experts"], c["sliding_window"], c["head_dim"],
            c["hidden_size"], c["intermediate_size"]) == (
                128, 8, 4, 4096, 128, 4096, 4096)
    for key in ("assumed", "departures", "deployment"):
        assert c[key]
    cell = next(w for w in bench["workloads"]
                if w["name"] == "serve-commandaplus-mixedlen")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "command-a-plus-l4-ep8", "saturated-mixed-lengths", 1)
    lists = [m["name"] for m in bench["per_layer"]
             if "serve-commandaplus-mixedlen" in m.get("workloads", [])]
    assert sorted(lists) == sorted([
        "engine_host_ms.serve", "step_utilization.serve",
        "decode_bw_pct.serve", "wire_emit_ms.serve",
        "moe_experts_roofline.serve", "admit_device_share_pct.serve",
        "admit_device_ms.serve", "admit_attention_share_pct.serve",
        "cached_attn_share_pct.serve",
        "moe_gmm_share_pct.serve",          # PR 37's, read here too
        # PR 39's: the engine's timeline, read in every saturated cell
        "chunk_turn_ms.serve", "admit_stall_ms.serve",
        "admit_stall_share_pct.serve", "device_starved_pct.serve",
        "slot_vacant_ms.serve"])
