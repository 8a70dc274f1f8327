"""The double layer with its shortcut-connected expert block, softmax
routing and zero experts on the serving path, at toy widths on the CPU
(float32 both sides): hidden 128, 4 heads of 32+16 / 32 through ranks 48 /
32 with the bottleneck scales on, a SwiGLU of 256 a half, 16 routed
experts of width 64 (4 held from the 4th on) and 8 zero ones, 4 a token
x 6, two layers — ``tests/data/tiny-scmoe.json``, read by the benchmark's
family ``benchmark/families/scmoe_mla_decoder.py``, whose float32
reference (EXPANDED attention, every held expert for every token, one
whole double layer at a time) is the yardstick and imports nothing from
the program.
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
from tony_tpu.parallel import moe

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIG = os.path.join(HERE, "data", "tiny-scmoe.json")
SEED = 2**31 + 37
#: float32 both sides, logits of order 1: what is left is rounding order —
#: the absorbed read associates (q W_uk^T) c_kv where the reference has q
#: (c_kv W_uk), the cache read is an online softmax over blocks, the
#: routed sum runs sorted by expert. Read: at most 3e-6 over 40 positions
#: (1.1e-5 before W_qb / W_kvb were drawn for their scaled inputs). A
#: bf16 router (8e-4), a dropped s_kv or s_q, a renormalised pick or a
#: routed block fed the SECOND half's norm each miss it by more than ten
#: times (the tests below)
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


# ------------------------------------------------------------------ (a)
def test_prefill_then_decode_through_both_row_sets_is_the_reference(tiny):
    """Rows admitted at DIFFERENT lengths into one bucket
    (``prefill_rows`` + ``place_rows``), then decoded through the two
    latent row-sets a layer in the absorbed form, give the logits of the
    family's expanded float32 reference at every position."""
    c, fam, cfg, params = tiny
    lengths = np.array([5, 17, 11], np.int32)
    steps, bucket, rows = 12, 32, 64
    toks = _tokens(3, int(lengths.max()) + steps, c["vocab_size"])
    ref = _ref_logits(c, toks)
    prompts = np.zeros((3, bucket), np.int32)
    for r, n in enumerate(lengths):
        prompts[r, :n] = toks[r, :n]
    lg, mini = D.prefill_rows(params, jnp.asarray(prompts),
                              jnp.asarray(lengths), cfg)
    for r, n in enumerate(lengths):
        np.testing.assert_allclose(lg[r], ref[r, n - 1], atol=ATOL, rtol=0)
    # the bucket's padding is not routed: every real token's 4 picks in
    # both layers are a held expert, an absent one or a zero one
    landed, touched, zeros = (int(v) for v in mini[D.MOE_COUNTS])
    assert 0 < landed + zeros <= int(lengths.sum()) * 4 * 2
    assert 0 < touched <= 2 * 4 and zeros > 0
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
                                       atol=ATOL, rtol=0)
    # TWO rows a token a layer, kv_rank + rope wide in whole lane tiles
    assert cache["ckv"].shape == (4, 4, rows, 128)


def test_whole_prompt_prefill_and_a_read_past_one_block(tiny):
    """``prefill`` of one prompt, then decode past a read block (256):
    the online softmax over two live blocks of each of the four row-sets
    is the reference's one softmax."""
    c, fam, cfg, params = tiny
    toks = _tokens(1, 300, c["vocab_size"], salt=1)
    ref = _ref_logits(c, toks)
    lg, cache = D.prefill(params, jnp.asarray(toks[:, :250]), cfg,
                          max_len=320)
    np.testing.assert_allclose(lg[0], ref[0, 249], atol=ATOL, rtol=0)
    step = jax.jit(lambda tok, cache: D.decode_step(
        params, tok, cache, cache["length"], cfg))
    for t in range(250, 300):
        lg, cache = step(jnp.asarray(toks[:, t]), cache)
        if t in (255, 256, 257, 299):
            np.testing.assert_allclose(lg[0], ref[0, t], atol=ATOL, rtol=0)


def _prefill_gap(cfg, params, toks, ref):
    lg, _ = D.prefill(params, jnp.asarray(toks), cfg, max_len=32)
    return float(np.abs(np.asarray(lg, np.float32) - ref[:, -1]).max())


@pytest.mark.parametrize("fault", ["bf16_router", "no_kv_scale",
                                   "no_q_scale", "renormalised", "bf16"])
def test_the_tolerance_tells_a_lower_precision_or_a_dropped_term(tiny,
                                                                 fault):
    """What ATOL is tight enough for: the router's matrix rounded to
    bfloat16, ``s_kv`` or ``s_q`` left off the normed bottlenecks, the
    weights renormalised over the pick (sigmoid routing's rule), or the
    whole program in bfloat16 against the reference on the same rounded
    weights — each misses it by more than ten times."""
    c, fam, cfg, params = tiny
    toks = _tokens(2, 24, c["vocab_size"], salt=3)
    ref = _ref_logits(c, toks)
    assert _prefill_gap(cfg, params, toks, ref) < ATOL
    if fault == "bf16_router":
        blocks = dict(params["blocks"]["latent2_scmoe"])
        blocks["router"] = blocks["router"].astype(jnp.bfloat16).astype(
            jnp.float32)
        params = dict(params, blocks={"latent2_scmoe": blocks})
    elif fault == "no_kv_scale":
        cfg = cfg.scaled(latent=dataclasses.replace(cfg.latent,
                                                    kv_scale=1.0))
    elif fault == "no_q_scale":
        cfg = cfg.scaled(latent=dataclasses.replace(cfg.latent,
                                                    q_scale=1.0))
    elif fault == "renormalised":
        real = moe.softmax_route

        def renormalised(h, w, b, k, scale):
            picks, wts = real(h, w, b, k, scale)
            return picks, wts / wts.sum(-1, keepdims=True) * scale
        D.softmax_route = renormalised
    else:
        cfg = fam.program_config(c, dtype=jnp.bfloat16, remat=False)
        params = fam.make_params(SEED, c, jnp.bfloat16)
        ref = _ref_logits(c, toks, jnp.bfloat16)
    try:
        assert _prefill_gap(cfg, params, toks, ref) > 10 * ATOL
    finally:
        D.softmax_route = moe.softmax_route


def test_the_routed_block_reads_the_first_half_and_lands_after_the_second(
        tiny):
    """The shortcut itself: the program's layer equals the six lines with
    ``M`` fed ``h1`` — and NOT the sequential layer (``M`` fed the second
    half's normed stream), which the same leaves also express."""
    c, fam, cfg, params = tiny
    p = {n: np.asarray(w) for n, w in fam.layer_weights(
        np.uint32(SEED), np.int32(0), c, jnp.float32, "moe").items()}
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 9, 128), jnp.float32)
    want = fam.layer_forward(x, p, c, "moe")
    p0, p1 = fam.half_of(p, 0), fam.half_of(p, 1)

    def mlp(h, q):
        return fam._swiglu(h.reshape(-1, 128), q["mlp_gate"], q["mlp_up"],
                           q["mlp_down"]).reshape(h.shape)
    x1 = x + fam.latent_attention(fam.rms(x, p0["attn_norm"], c), p0, c)
    x2 = x1 + mlp(fam.rms(x1, p0["mlp_norm"], c), p0)
    x3 = x2 + fam.latent_attention(fam.rms(x2, p1["attn_norm"], c), p1, c)
    h3 = fam.rms(x3, p1["mlp_norm"], c)
    sequential = x3 + mlp(h3, p1) + fam.experts(
        h3.reshape(-1, 128), p, c).reshape(x.shape)
    layer = {n: jnp.asarray(w)[None] for n, w in p.items()}
    one = cfg.scaled(n_layers=1, layer_kinds=("latent2_scmoe",))
    bufs = D._kv_state(D.init_kv_cache(one, 2, 16))
    positions = jnp.broadcast_to(jnp.arange(9), (2, 9))
    got, bufs = D._kinded_prompt_block(
        x, D._layer_params({"blocks": {"latent2_scmoe": layer}}, one, 0),
        bufs, 0, one, D._rope_tables(positions, one), 9,
        jnp.ones((2, 9), bool))
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
    assert float(jnp.abs(sequential - want).max()) > 100 * 2e-5


# ------------------------------------------------------------------ (b)
def test_the_shares_add_up_to_the_uncut_block(tiny):
    """The held-experts parts of all ``router_experts / held`` = 4 shares
    (32 in the cell's deployment), with what every chip computes alike —
    the zero experts' term — counted ONCE, equal the reference's UNCUT
    routed block (all 16 routed experts held): the cut is a share of the
    model, not another model."""
    c, fam, cfg, _ = tiny
    whole = dict(c, n_routed_experts=16, first_expert=0)
    p = {n: np.asarray(w) for n, w in fam.layer_weights(
        np.uint32(SEED), np.int32(1), whole, jnp.float32, "moe").items()}
    h = jax.random.normal(jax.random.PRNGKey(3), (2, 19, 128), jnp.float32)
    flat = h.reshape(-1, 128)
    want = fam.experts(flat, p, whole)
    picks, w = fam.route(flat, p, whole)
    zero_term = jnp.sum(jnp.where(picks >= 16, w, 0.0), -1)[:, None] * flat
    got, landed, zeros = 0.0, 0, set()
    for first in range(0, 16, 4):
        share = cfg.scaled(experts=dataclasses.replace(
            cfg.experts, first=first, held=4))
        mine = dict(router=p["router"], router_bias=p["router_bias"],
                    routed=tuple(jnp.asarray(p[n][None, first:first + 4])
                                 for n in ("w_gate", "w_up", "w_down")),
                    routed_layer=0)
        out, counts = D._sparse_mlp(h, mine, share)
        got = got + out.reshape(-1, 128) - zero_term
        landed += int(counts[0])
        zeros.add(int(counts[2]))
    got = got + zero_term
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
    # every (token, pick) landed on exactly one share or is a zero
    # expert, which every share counts alike
    assert len(zeros) == 1
    assert landed + zeros.pop() == 2 * 19 * c["moe_topk"]


# ------------------------------------------------------------------ (c)
def test_a_token_of_zero_picks_only_is_its_weights_times_itself():
    """A token whose 4 picks are all zero experts gets exactly ``(sum w)
    x h``, touches no held expert (no weight is read) and counts 4 zero
    assignments; a token that is not ``live`` gets nothing and counts
    nothing."""
    t, d, f, total, n_zero, k = 6, 128, 64, 16, 8, 4
    keys = jax.random.split(jax.random.PRNGKey(5), 4)
    h = jax.random.normal(keys[0], (t, d), jnp.float32)
    gate, up = (jax.random.normal(kk, (1, 4, d, f)) * d ** -0.5
                for kk in keys[1:3])
    down = jax.random.normal(keys[3], (1, 4, f, d)) * f ** -0.5
    picks = jnp.asarray([[16, 19, 23, 17]] * t, jnp.int32)
    w = jnp.asarray(np.random.default_rng(1).uniform(0.1, 0.5, (t, k)),
                    jnp.float32)
    live = jnp.asarray([True] * 5 + [False])
    out, landed, touched, zeros = jax.jit(
        lambda h, p, w: moe.held_experts_ffn(
            h, p, w, gate, up, down, 0,
            moe.HeldExperts(4, 4, total, n_zero), live=live))(h, picks, w)
    np.testing.assert_array_equal(
        out[:5], w[:5].sum(-1, keepdims=True) * h[:5])
    assert not np.asarray(out[5]).any()
    assert (int(landed), int(touched), int(zeros)) == (0, 0, 5 * k)
    # one pick of a held expert beside three zero ones: both terms
    mixed = picks.at[0, 1].set(5)
    out, landed, touched, zeros = moe.held_experts_ffn(
        h, mixed, w, gate, up, down, 0,
        moe.HeldExperts(4, 4, total, n_zero), live=live)
    y = (jax.nn.silu(h[0] @ gate[0, 1]) * (h[0] @ up[0, 1])) @ down[0, 1]
    np.testing.assert_allclose(
        out[0], w[0, 1] * y + (w[0].sum() - w[0, 1]) * h[0], atol=2e-5)
    assert (int(landed), int(touched), int(zeros)) == (1, 1, 5 * k - 1)


def test_the_zero_scope_is_in_the_step_and_absent_without_zero_experts(
        tiny):
    """``moe_zero`` names the zero experts' term in the decode program of
    a model that has them; a model without traces none."""
    c, fam, cfg, params = tiny

    def scopes(cfg):
        shapes = jax.eval_shape(
            lambda: T.init_params(jax.random.PRNGKey(0), cfg))
        cache = jax.eval_shape(lambda: dict(
            D.init_kv_cache(cfg, 2, 32), length=jnp.zeros((2,), jnp.int32)))
        sds = jax.ShapeDtypeStruct
        return S.step_rows.lower(
            shapes, cache, sds((2, cfg.vocab_size), cfg.logits_storage_dtype),
            sds((2, 2), jnp.uint32), sds((2,), jnp.int32), 2,
            cfg).as_text(debug_info=True)
    text = scopes(cfg)
    for name in ("moe_zero", "moe_route", "moe_experts", "mla_attention_0",
                 "mla_attention_1", "mlp_0", "mlp_1"):
        assert name in text, name
    assert "moe_shared" not in text             # no shared expert: skipped
    plain = cfg.scaled(experts=dataclasses.replace(cfg.experts, n_zero=0))
    assert "moe_zero" not in scopes(plain)


# ------------------------------------------------------------------ (d)
def test_softmax_route_is_not_renormalised_and_the_bias_only_picks():
    t, d, e, k = 50, 128, 24, 4
    keys = jax.random.split(jax.random.PRNGKey(9), 2)
    h = jax.random.normal(keys[0], (t, d), jnp.float32)
    router = jax.random.normal(keys[1], (d, e)) * d ** -0.5
    z = jax.nn.softmax(h @ router, axis=-1)
    picks, w = moe.softmax_route(h, router, jnp.zeros((e,)), k, 6.0)
    np.testing.assert_array_equal(np.sort(picks, -1),
                                  np.sort(jax.lax.top_k(z, k)[1], -1))
    np.testing.assert_allclose(w, jnp.take_along_axis(z, picks, -1) * 6.0,
                               rtol=1e-6)
    # the weights are the scores over ALL 24 outputs: they do not sum to
    # the scale over the pick
    assert float(w.sum(-1).max()) < 6.0 * 0.9
    bias = jnp.zeros((e,)).at[21].set(10.0)
    biased, wb = moe.softmax_route(h, router, bias, k, 6.0)
    assert bool((biased == 21).any(-1).all())
    assert not bool((picks == 21).any(-1).all())        # the bias did it
    np.testing.assert_allclose(wb, jnp.take_along_axis(z, biased, -1) * 6.0,
                               rtol=1e-6)


# ------------------------------------------------------------------ (e)
def test_a_layer_owns_two_row_sets_and_its_halves_never_share_one(tiny):
    c, fam, cfg, params = tiny
    assert T.kind_attentions("latent2_scmoe") == 2
    assert T.kind_attentions("moe") == 1
    assert D.cache_layout(cfg, 64) == {"ckv": (4, 64, 128, jnp.float32)}
    assert cfg.attention_layers() == {"latent": 4}
    assert [cfg.attention_of(li) for li in range(2)] == [("latent", 0),
                                                         ("latent", 2)]
    assert D.cache_bytes_by_kind(cfg, 3, 64) == {
        "latent": 4 * 3 * 64 * 128 * 4}
    # a dense latent layer before and after a double one: 1 + 2 + 1
    mixed = cfg.scaled(n_layers=3, d_ff=256,
                       layer_kinds=("dense", "latent2_scmoe", "dense"))
    assert [mixed.attention_of(li) for li in range(3)] == [
        ("latent", 0), ("latent", 1), ("latent", 3)]
    assert mixed.attention_layers() == {"latent": 4}
    # every row-set is written by exactly one half: after a prefill the
    # four of them differ pairwise, and a decode step writes one row in
    # each
    toks = jnp.asarray(_tokens(1, 9, c["vocab_size"], salt=4))
    _, cache = D.prefill(params, toks[:, :8], cfg, max_len=16)
    sets = np.asarray(cache["ckv"][:, 0, :8])
    assert all(np.abs(sets[i]).sum() > 0 for i in range(4))
    assert all(not np.allclose(sets[i], sets[j])
               for i in range(4) for j in range(i))
    _, after = D.decode_step(params, toks[:, 8], cache, cache["length"], cfg)
    changed = np.asarray(after["ckv"] != cache["ckv"]).any(-1)[:, 0]
    assert changed[:, 8].all() and not changed[:, :8].any() \
        and not changed[:, 9:].any()


# ------------------------------------------- settings, refusals, counters
def test_experts_without_a_shared_one_and_what_is_refused(tiny):
    c, fam, cfg, params = tiny
    assert cfg.experts.n_shared == 0 and cfg.experts.n_scored == 24
    assert "shared_gate" not in params["blocks"]["latent2_scmoe"]
    with pytest.raises(ValueError, match="0: none"):
        cfg.scaled(experts=dataclasses.replace(cfg.experts, n_shared=-1))
    with pytest.raises(ValueError, match="zero experts"):
        cfg.scaled(experts=dataclasses.replace(cfg.experts, n_zero=-2))
    with pytest.raises(ValueError, match="experts.route"):
        cfg.scaled(experts=dataclasses.replace(cfg.experts, route="topk"))
    with pytest.raises(ValueError, match="parallel_block"):
        cfg.scaled(parallel_block=True)
    # top_k may reach into the zero experts, not past them
    cfg.scaled(experts=dataclasses.replace(cfg.experts, top_k=24))
    with pytest.raises(ValueError, match="top_k"):
        cfg.scaled(experts=dataclasses.replace(cfg.experts, top_k=25))
    with pytest.raises(NotImplementedError, match="layer_kinds"):
        T.lm_loss(params, {"tokens": jnp.zeros((1, 8), jnp.int32)}, cfg)
    with pytest.raises(NotImplementedError, match="layer_kinds"):
        S.ContinuousBatcher(params, cfg, 2, 64, shared_prefix=[1, 2, 3])
    # sigmoid routing beside a shared expert with zero experts too: the
    # classes of pick are orthogonal to the routing rule
    both = T.TransformerConfig(
        vocab_size=64, d_model=32, n_layers=1, n_heads=2, d_ff=64,
        dtype=jnp.float32, remat=False, layer_kinds=("moe",),
        latent=T.LatentAttention(16, 16, 8, 8, 8),
        experts=T.SparseExperts(8, 3, 16, n_zero=4))
    p = T.init_params(jax.random.PRNGKey(1), both)
    assert p["blocks"]["moe"]["router"].shape == (1, 32, 12)
    lg, cache = D.prefill(p, jnp.zeros((1, 5), jnp.int32), both, max_len=8)
    assert cache[D.MOE_COUNTS].shape == (3,) and bool(
        jnp.isfinite(lg).all())


def test_served_through_the_batcher_with_zero_assignments_in_stats(tiny):
    """``ContinuousBatcher`` -> ``ServeEngine`` on the double layer
    through ``admit_rows`` / ``step_rows``: every served token is the
    reference's best at its position (teacher-forced), the zero experts'
    assignments arrive with the chunks' tokens — per program kind, in
    ``engine.stats()`` and in the metrics registry, about a third of all
    (8 of 24 outputs) — and the cache's bytes count two row-sets a
    layer."""
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
    steps, layers, k = st["steps_executed"], 2, 4
    for program in ("decode", "admit"):
        zeros = st["moe_zero_assignments"][program]
        landed = st["moe_assignments"][program]
        assert zeros > 0 and landed > 0
        for what, have in (("zero_assignments", zeros),
                           ("assignments", landed)):
            assert reg.counter(f"tony_moe_{what}_total",
                               program=program).value == have
    every = steps * layers * 3 * k          # 3 slots, idle ones routed too
    assert 0.2 * every < st["moe_zero_assignments"]["decode"] < 0.5 * every
    assert st["moe_assignments"]["decode"] \
        + st["moe_zero_assignments"]["decode"] <= every
    assert 0 < st["moe_expert_touches"]["decode"] <= steps * layers * 4
    assert st["cache_bytes"] == {"latent": 4 * 3 * 96 * 128 * 4}
    # off the chip the latent read is the walk: every slot to the longest
    # live row's last block (one block of 96 here), in all four row-sets
    assert st["cache_rows_read"] == {"latent": steps * 3 * 96 * 4}
    assert 0 < st["cache_rows_live"]["latent"] < st["cache_rows_read"][
        "latent"]
    assert "tony_moe_zero_assignments_total" in reg.to_wire_json()


def test_int8_weights_serve_the_double_layer(tiny):
    """``quantize_weights_int8`` (the serving cells' control) reaches both
    halves' projections and dense SwiGLUs behind their axis of 2 and the
    head; the router and the routed experts stay; the served logits stay
    close to the float model's."""
    from tony_tpu.models.quantize import QuantizedWeight, \
        quantize_weights_int8
    c, fam, cfg, params = tiny
    q = quantize_weights_int8(params)
    group = q["blocks"]["latent2_scmoe"]
    for name in ("wq_a", "wq_b", "wkv_a", "wo", "mlp_gate", "mlp_up",
                 "mlp_down"):
        assert isinstance(group[name], QuantizedWeight), name
    assert group["mlp_down"].scale.shape == (2, 2, 128)
    assert group["wo"].scale.shape == (2, 2, 128)
    for name in ("router", "w_gate", "w_down", "wkv_b"):
        assert not isinstance(group[name], QuantizedWeight), name
    assert isinstance(q["lm_head"], QuantizedWeight)
    toks = jnp.asarray(_tokens(2, 24, c["vocab_size"], salt=2))
    a, _ = D.prefill(params, toks, cfg, max_len=32)
    b, _ = D.prefill(q, toks, cfg, max_len=32)
    assert 100 * ATOL < float(jnp.abs(a - b).mean()) < 0.3


# ----------------------------------------------------- the cell, toy size
@pytest.mark.parametrize("fault,correct", [
    ("", True), ("wrong_token_one_slot", False)])
def test_toy_cell_end_to_end(tmp_path, monkeypatch, fault, correct):
    """The new cell's whole run at toy size on the CPU: replica child,
    the wire, the cell's own driver (``drivers/serve_drain.py``), the
    family's reference over the served tokens — correct; with a token
    altered in one slot underneath, not."""
    from benchmark import run
    monkeypatch.setenv("XLA_FLAGS", "")
    from benchmark.tests.test_run_faults import SERVE_LIMITS
    bench = {"workloads": [{
        "name": "toy", "chips": 1, "config": CONFIG,
        "traffic": os.path.join(HERE, "data", "saturated-wide-tiny.json")}],
        "end_to_end": [{"name": n, "unit": "x"} for n in
                       ("serve_tokens_per_s", "itl_p95_ms", "setup_s")],
        "per_layer": []}
    got = run.run_cell(bench, "toy", 2**31 + 38, 3.0, 0, platform="cpu",
                       root=str(tmp_path), fault=fault, limits=SERVE_LIMITS)
    assert got["correct"] is correct
    assert got["failed"] == 0 and got["attempted"] > 0


@pytest.mark.parametrize("fault", ["zero_routed", "swapped_routed",
                                   "no_zero_term"])
def test_a_fault_in_the_routed_block_alone_is_not_correct(tiny, fault):
    """``benchmark/tools/control_routed.py``'s faults and this
    configuration's own — the zero experts' term left out
    (``benchmark/tools/control_zero.py``) — in the reference, in the
    program's place, cross the toy cell's limits. (Published widths:
    ``benchmark/limits/serve-longcatflash-wide-decode.json``.)"""
    from benchmark.tests.test_run_faults import SERVE_LIMITS
    from benchmark.tools import control_routed, control_zero
    c, fam = tiny[:2]
    tokens = jnp.asarray(_tokens(4, 32, c["vocab_size"], salt=5))
    sound = control_routed.logits_of(c, SEED, fam, None, tokens)
    faulty = control_zero.without_zero_term(fam) if fault == "no_zero_term" \
        else control_routed._faulty(fam, fault)[0]
    low = control_routed.logits_of(c, SEED, faulty, None, tokens)
    gaps = sound.max(-1) - np.take_along_axis(
        sound, low.argmax(-1)[..., None], -1)[..., 0]
    assert (gaps > 0).mean() > SERVE_LIMITS["served_token_mismatch_share"]
    assert gaps.mean() > SERVE_LIMITS["served_token_mean_gap"]


# ------------------------------------------- the family and the cell's files
def test_family_counts_are_the_trees_at_published_widths():
    """``longcat-flash-l4-ep32`` (shapes only): the family's parameter
    count is the size of the tree it makes and of the program's own init,
    leaf for leaf; 5.173 B (ISSUE 37's 5,172.6 M counts the matrices;
    the norms and the selection bias are the other 0.12 M); a token's
    forward FLOPs are twice the parameters it MEETS (12 x 16 / 768 of a
    routed expert, not the 16 held); a decode step reads a touched
    expert's bytes only, two row-sets a layer, and no byte for a zero
    pick."""
    c = modelcfg.load("longcat-flash-l4-ep32")
    fam = modelcfg.family(c)
    made = jax.eval_shape(lambda: fam.make_params(7, c, jnp.bfloat16))
    cfg = fam.program_config(c, dtype=jnp.bfloat16)
    own = jax.eval_shape(lambda: T.init_params(jax.random.PRNGKey(0), cfg))
    shapes = lambda t: jax.tree.map(lambda x: (x.shape, x.dtype), t)  # noqa: E731
    assert shapes(made) == shapes(own)
    size = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(made))
    assert fam.param_count(c) == size == 5_172_749_312
    attention = 6144 * 1536 + 1536 * 64 * 192 + 6144 * 576 \
        + 512 * 64 * 256 + 64 * 128 * 6144
    dense, expert = 3 * 6144 * 12288, 3 * 6144 * 2048
    assert (attention, dense, expert) == (90_570_752, 226_492_416,
                                          37_748_736)
    matrices = 4 * (2 * (attention + dense) + 6144 * 768 + 16 * expert) \
        + 2 * 16384 * 6144
    assert round(matrices / 1e6, 1) == 5172.6
    assert size - matrices == 4 * (2 * (2 * 6144 + 1536 + 512) + 768) + 6144
    assert fam.layer_kinds(c) == ["moe"] * 4
    assert (cfg.latent.q_scale, round(cfg.latent.kv_scale, 4)) == (2.0,
                                                                   3.4641)
    assert D.cache_layout(cfg, 4096)["ckv"][:3] == (8, 4096, 640)
    assert D.cache_bytes_by_kind(cfg, 64, 4096) == {
        "latent": 64 * 4096 * 8 * 1280}                         # 2.68 GB
    met = size - 16384 * 6144 - 4 * (16 - 0.25) * expert
    flops = fam.forward_flops_per_token(c, 1)
    assert 2 * met * 0.99 < flops < 2 * met * 1.01
    routers = 4 * (6144 * 768 + 768)
    every = fam.decode_step_bytes(c, 0.0, None)
    assert every == 2 * (size - 16384 * 6144 - routers) + 4 * routers
    ctx = {"mix": {"slots": 64}}
    assigned, touched = fam.expert_load(c, ctx)
    assert assigned == 16.0                     # 64 x 12 x 16 / 768
    assert touched == pytest.approx(16 * (1 - (63 / 64) ** 64))     # 10.2
    assert every - fam.decode_step_bytes(c, 0.0, ctx) == pytest.approx(
        4 * (16 - touched) * expert * 2)
    assert fam.decode_step_bytes(c, 100.0, ctx) - \
        fam.decode_step_bytes(c, 0.0, ctx) == 100 * 8 * 1280
    fl, by = fam.moe_experts_flops_bytes(c, assigned, touched)
    assert (fl, by) == (2 * 16 * expert, touched * expert * 2)


def test_configuration_file_states_its_cut():
    with open(os.path.join(HERE, os.pardir, "benchmark", "configs",
                           "longcat-flash-l4-ep32.json")) as f:
        c = json.load(f)
    with open(os.path.join(HERE, os.pardir, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(e for e in bench["configs"]
                 if e["name"] == "longcat-flash-l4-ep32")
    assert entry["source"] == c["source"]
    assert sorted(entry["reduced"]) == sorted(c["reduced"]) == [
        "n_routed_experts", "num_layers", "vocab_size"]
    for key, cut in c["reduced"].items():
        assert c[key] == cut["run"] != cut["published"]
    # every published width, the router's outputs, picks and factor
    for key, value in {
            "hidden_size": 6144, "ffn_hidden_size": 12288,
            "expert_ffn_hidden_size": 2048, "num_attention_heads": 64,
            "q_lora_rank": 1536, "kv_lora_rank": 512,
            "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
            "v_head_dim": 128, "router_experts": 512,
            "zero_expert_num": 256, "moe_topk": 12,
            "routed_scaling_factor": 6, "rope_theta": 10000000,
            "rms_norm_eps": 1e-05, "max_position_embeddings": 131072,
            "mla_scale_q_lora": True, "mla_scale_kv_lora": True,
            "zero_expert_type": "identity", "attention_method": "MLA",
            "attention_bias": False}.items():
        assert c[key] == value, key
    for key in ("assumed", "departures", "deployment"):
        assert c[key]
    assert c["chips"] == 1 and "32 chips share each layer" in c["deployment"]
    cell = next(w for w in bench["workloads"]
                if w["name"] == "serve-longcatflash-wide-decode")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "longcat-flash-l4-ep32", "saturated-wide-long-answers", 1)
    assert "1/32" in cell["why"]
