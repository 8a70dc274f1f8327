"""``tony_cached_attn`` in the Pallas interpreter against the ``jnp`` arms
of ``models/decode.py`` (the walk over live blocks, the dense ring read,
the latent walk over the stored ``[c_kv; k_r]`` rows).

The cells' ``correct`` does not hold the dense family's cache (PERF.md
section 7, PR 35 (e)): at fan-in scale seeded attention is a flat average
and a wrong row moves the output by a thousandth. So the queries here
are drawn 8 x wider than fan-in — the softmax is PEAKED, and a row left
out, read twice or masked wrongly moves the output by its whole value —
and this file is the guard of the kernel's arithmetic.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tony_tpu.models import decode as D
from tony_tpu.ops import attention as A

#: (query heads, K/V heads, head_dim): heads of 96 fall off the 128-lane
#: tiles (whole stored rows leave the kernel, the select is outside);
#: heads of 128 are cut inside it
HEADS = {"mha4x96": (4, 4, 96), "gqa16/2x128": (16, 2, 128)}

#: name -> (rows, block, window, ring, positions a slot). Every case is
#: ragged: an idle slot at 0, a slot ending mid-block, one on a block's
#: last row, the longest near the buffer's end
KINDS = {
    "linear": (80, 16, None, False, (0, 21, 47, 78)),
    "linear-rows-off-the-block": (72, 16, None, False, (0, 21, 47, 71)),
    "linear-window": (80, 16, 24, False, (0, 21, 47, 78)),
    "ring-not-wrapped": (48, 16, 40, True, (0, 5, 31, 46)),
    "ring-wrapped": (48, 16, 40, True, (0, 50, 95, 1000)),
    "ring-rows-off-the-block": (40, 16, 40, True, (0, 17, 39, 83)),
    "ring-wider-than-window": (64, 16, 20, True, (0, 30, 63, 200)),
    # a slot reused at a short length over an older occupant's residue:
    # the buffer is full of rows, the positions say which are the
    # occupant's
    "ring-reused-slot": (48, 16, 40, True, (3, 9, 0, 20)),
}


def _case(heads, kind, seed=0):
    h, kv, d = HEADS[heads]
    rows, block, window, ring, pos = KINDS[kind]
    kq, kk, kv_ = jax.random.split(jax.random.PRNGKey(seed), 3)
    b = len(pos)
    # 8 x fan-in scale: scores of order 8, a peaked softmax
    q = 8.0 * jax.random.normal(kq, (b, 1, h, d), jnp.float32)
    bufs = {"k": jax.random.normal(kk, (2, b, rows, kv * d), jnp.float32),
            "v": jax.random.normal(kv_, (2, b, rows, kv * d), jnp.float32)}
    return q, bufs, jnp.asarray(pos, jnp.int32), rows, block, window, ring


def _oracle(q, bufs, li, pos, block, window, ring):
    if ring:
        return D._ring_cached_attention(q, bufs, li, pos, window)
    return D._cached_attention_blockwise(q, bufs, li, pos, block=block,
                                         attn_window=window)


@pytest.fixture
def blocks_of(monkeypatch):
    """Give the kernel's read (``decode._kernel_cached_attention``: the
    interpreter's, off the chip) blocks of the case's height; the one a
    serving buffer gets, ``cached_attn_block``, is never under 128
    rows."""
    return lambda block: monkeypatch.setattr(
        D, "cached_attn_block", lambda rows, row_bytes: block)


@pytest.mark.parametrize("kind", sorted(KINDS))
@pytest.mark.parametrize("heads", sorted(HEADS))
def test_kernel_is_the_jnp_arm(heads, kind, blocks_of):
    q, bufs, pos, _, block, window, ring = _case(heads, kind)
    blocks_of(block)
    for li in (0, 1):
        want = _oracle(q, bufs, li, pos, block, window, ring)
        got = D._kernel_cached_attention(q, bufs, li, pos, window=window,
                                         ring=ring)
        assert got.shape == want.shape and got.dtype == want.dtype
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    # peaked: the largest weight of a long row is far from 1 / rows
    assert float(jnp.abs(want).max()) > 0.5


@pytest.mark.parametrize("kind", ["linear", "linear-window",
                                  "ring-not-wrapped"])
@pytest.mark.parametrize("heads", sorted(HEADS))
def test_blocks_past_a_rows_last_are_never_read(heads, kind, blocks_of):
    """Every WHOLE block past a slot's last live block holds NaN: the
    output is finite and the clean buffers' — the work list names no
    such block (a block read and masked would still put 0 x NaN into
    the value product)."""
    q, bufs, pos, rows, block, window, ring = _case(heads, kind, seed=1)
    blocks_of(block)
    first_dead = (np.asarray(pos) // block + 1) * block         # [B]
    dead = np.arange(rows)[None, :] >= first_dead[:, None]      # [B, rows]
    assert dead.any(axis=1)[:-1].all()
    poisoned = {n: jnp.where(dead[None, :, :, None], jnp.nan, a)
                for n, a in bufs.items()}
    run = lambda c: D._kernel_cached_attention(         # noqa: E731
        q, c, 1, pos, window=window, ring=ring)
    got = run(poisoned)
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_array_equal(got, run(bufs))


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_work_list_counts_each_slots_live_blocks(kind):
    rows, block, window, ring, _ = KINDS[kind]
    rng = np.random.default_rng(7)
    pos = rng.integers(0, (3 * rows) if ring else rows, size=9)
    pos[0] = 0
    slot, blk, lo, hi, n_work = A.cached_attn_work(
        jnp.asarray(pos, jnp.int32), rows, block, window, ring)
    if ring:
        live = np.minimum(pos + 1, rows)
        first = np.zeros_like(pos)
    else:
        first = (np.maximum(pos - window + 1, 0) // block * block
                 if window else np.zeros_like(pos))
        live = pos + 1 - first
    blocks = -(-live // block)
    assert int(n_work) == blocks.sum()
    # a slot's blocks consecutive and ascending, slots in turn
    want = [(s, first[s] // block + i) for s in range(len(pos))
            for i in range(blocks[s])]
    got = list(zip(np.asarray(slot)[:int(n_work)].tolist(),
                   np.asarray(blk)[:int(n_work)].tolist()))
    assert got == want
    np.testing.assert_array_equal(np.asarray(hi) - np.asarray(lo) + 1,
                                  blocks)


#: mesh axes -> what the island does with 4 slots of 4 K/V heads: slots
#: over dp, heads over tp; a batch axis that does not divide the slots
#: (fsdp = 8) and an axis that is neither (ep) repeat the work
MESHES = {"dp4-tp2": {"dp": 4, "tp": 2}, "dp2-tp4": {"dp": 2, "tp": 4},
          "fsdp8": {"fsdp": 8}, "dp2-ep2-tp2": {"dp": 2, "ep": 2, "tp": 2}}


@pytest.mark.parametrize("kind", ["linear-window", "ring-wrapped"])
@pytest.mark.parametrize("axes", sorted(MESHES))
def test_kernel_under_a_mesh_runs_a_device_on_its_own_heads(axes, kind,
                                                            blocks_of):
    """Tensor-parallel serving (``jax.set_mesh``): the launch is a Mosaic
    call, which the partitioner cannot split, so it runs inside a
    ``shard_map`` island — each device its slots, its K/V heads' columns
    of every stored row, the queries that read them. The split is exact:
    the same numbers as one device, and no collective in the island."""
    from tony_tpu.parallel import make_mesh
    q, bufs, pos, _, block, window, ring = _case("mha4x96", kind, seed=2)
    blocks_of(block)
    read = jax.jit(lambda q, bufs, pos: D._kernel_cached_attention(
        q, bufs, 1, pos, window=window, ring=ring))
    want = read(q, bufs, pos)
    mesh = make_mesh(MESHES[axes])
    with jax.set_mesh(mesh):
        lowered = read.lower(q, bufs, pos)
        got = read(q, bufs, pos)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    text = lowered.as_text()
    assert "shard_map" in text or "manual" in text.lower()
    compiled = lowered.compile().as_text()
    assert "all-gather" not in compiled and "all-reduce" not in compiled


def test_heads_the_mesh_would_cut_inside_keep_the_jnp_read(monkeypatch):
    """2 K/V heads on tp = 4: a device's share of a stored row is half a
    head, which no launch a device can read — ``_read_arm`` leaves the
    buffer to the walk, which XLA partitions as it did."""
    from tony_tpu.ops import mosaic
    from tony_tpu.parallel import make_mesh
    monkeypatch.setattr(mosaic, "interpret", lambda: False)
    assert D._read_arm(1024, 2, False) == "kernel"
    with jax.set_mesh(make_mesh({"dp": 2, "tp": 4})):
        assert D._read_arm(1024, 4, False) == "kernel"
        assert D._read_arm(1024, 2, False) == "walk"
        assert D._read_arm(1024, 2, False, ring=True) == "dense"
    with jax.set_mesh(make_mesh({"dp": 8})):
        assert D._read_arm(1024, 2, False) == "kernel"


def test_decode_step_under_a_mesh_through_the_kernel(monkeypatch):
    """``tests/test_decode.py::test_tp_sharded_long_cache_decode`` with
    the chip's arm: ``decode._read_arm`` answers as on the chip (the
    launch itself stays the interpreter's), so the whole step — sharded
    projections, the cache write, the island, the output projection —
    runs under ``jax.set_mesh`` with the kernel in it, and gives the
    logits of the unsharded ``jnp`` read."""
    import types

    from tony_tpu.models import transformer as T
    from tony_tpu.parallel import make_mesh, shard_pytree
    cfg = T.PRESETS["tiny"].scaled(dtype=jnp.float32, remat=False)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(31), (2, 6), 0,
                                cfg.vocab_size)
    nxt = jnp.array([1, 2])
    _, cache = D.prefill(params, prompt, cfg, max_len=600)
    want, _ = D.decode_step(params, nxt, cache, cache["length"], cfg)
    jax.clear_caches()
    monkeypatch.setattr(D, "mosaic",
                        types.SimpleNamespace(interpret=lambda: False))
    mesh = make_mesh({"tp": 4, "dp": 2})
    sharded = shard_pytree(params, T.logical_axes(cfg), mesh)
    try:
        with jax.set_mesh(mesh):
            assert D._read_arm(600, cfg.kv_heads, False) == "kernel"
            got, new = D.decode_step(sharded, nxt, cache, cache["length"],
                                     cfg)
    finally:
        jax.clear_caches()
    np.testing.assert_allclose(got, want, rtol=3e-4, atol=3e-4)
    assert new["k"].shape == cache["k"].shape


# ---------------------------------------------------------------------------
# The latent arm: no V operand — the stored row [c_kv; k_r; tail] is the one
# K/V head every query head shares and its own value
# ---------------------------------------------------------------------------

#: name -> (query heads, LatentAttention): the toy's value (32 of a
#: 128-wide stored row) is cut outside the kernel, the published row's
#: (512 of 640: Kimi-K2.5's and LongCat-Flash's) on a lane tile inside it
LATENT = {"toy4x(32+16)": (4, (48, 32, 32, 16, 32)),
          "published64x(512+64)": (64, (1536, 512, 128, 64, 128))}

#: name -> (rows, block, positions a slot): a slot at 0, one ending
#: mid-block, one on a block's last row, one in the buffer's last block
LATENT_ROWS = {"linear": (80, 16, (0, 21, 47, 78)),
               "rows-off-the-block": (72, 16, (0, 21, 47, 71))}


def _latent_case(width, rows, positions, n_q=1, seed=0):
    from tony_tpu.models import transformer as T
    heads, dims = LATENT[width]
    la = T.LatentAttention(*dims)
    cfg = T.TransformerConfig(
        vocab_size=64, d_model=64, n_layers=1, n_heads=heads, d_ff=64,
        dtype=jnp.float32, remat=False, layer_kinds=("dense",), latent=la)
    b = len(positions)
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    # peaked, as the K/V cases: scores of order 8
    gain = 8.0 / (D._latent_scale(cfg) * la.qk_dim ** 0.5)
    q_n = gain * jax.random.normal(ks[0], (b, n_q, heads, la.nope_dim))
    q_r = gain * jax.random.normal(ks[1], (b, n_q, heads, la.rope_dim))
    buf = jax.random.normal(ks[2], (2, b, rows, la.stored_row))
    buf = buf.at[..., la.row:].set(0.0)                 # the tail is zeros
    p = {"wkv_b": jax.random.normal(
        ks[3], (la.kv_rank, heads, la.nope_dim + la.v_dim))
        * la.kv_rank ** -0.5}
    return cfg, q_n, q_r, buf, jnp.asarray(positions, jnp.int32), p


@pytest.fixture
def latent_on_chip(monkeypatch, blocks_of):
    """``decode._read_arm`` answers as on the chip for buffers of the
    cases' few rows, at the case's block height; the launch itself stays
    the interpreter's (``ops.mosaic`` is not touched)."""
    import types

    def arm(block):
        monkeypatch.setattr(
            D, "mosaic", types.SimpleNamespace(interpret=lambda: False))
        monkeypatch.setattr(D, "_BLOCKWISE_MIN_LEN", 32)
        blocks_of(block)
    return arm


@pytest.mark.parametrize("kind", sorted(LATENT_ROWS))
@pytest.mark.parametrize("width", sorted(LATENT))
def test_latent_arm_is_the_walk(width, kind, latent_on_chip):
    rows, block, positions = LATENT_ROWS[kind]
    cfg, q_n, q_r, buf, pos, p = _latent_case(width, rows, positions)
    want = [D._latent_cached_attention(q_n, q_r, buf, li, pos, p, cfg,
                                       block=block) for li in (0, 1)]
    latent_on_chip(block)
    assert D._read_arm(rows, 1, False) == "kernel"
    for li in (0, 1):
        got = D._latent_cached_attention(q_n, q_r, buf, li, pos, p, cfg)
        assert got.shape == want[li].shape and got.dtype == want[li].dtype
        np.testing.assert_allclose(got, want[li], atol=5e-5, rtol=5e-5)
    assert float(jnp.abs(want[1]).max()) > 0.5


@pytest.mark.parametrize("width", sorted(LATENT))
def test_latent_blocks_past_a_rows_last_are_never_read(width,
                                                       latent_on_chip):
    """As ``test_blocks_past_a_rows_last_are_never_read``: NaN in every
    whole block past a slot's last live one reaches no output — the one
    operand is key AND value, so a block read and masked would still put
    0 x NaN into the value product."""
    rows, block, positions = LATENT_ROWS["linear"]
    cfg, q_n, q_r, buf, pos, p = _latent_case(width, rows, positions,
                                              seed=1)
    latent_on_chip(block)
    first_dead = (np.asarray(pos) // block + 1) * block
    dead = np.arange(rows)[None, :] >= first_dead[:, None]
    assert dead.any(axis=1)[:-1].all()
    poisoned = jnp.where(dead[None, :, :, None], jnp.nan, buf)
    run = lambda c: D._latent_cached_attention(         # noqa: E731
        q_n, q_r, c, 1, pos, p, cfg)
    got = run(poisoned)
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_array_equal(got, run(buf))


def test_latent_chunks_of_several_positions_keep_the_walk(latent_on_chip,
                                                          monkeypatch):
    """``extend_step``'s chunks and speculation's verify (``n_q > 1``)
    never reach the launch: ``_read_arm`` leaves them the walk, with the
    numbers the walk gave before the arm existed."""
    rows, block, positions = LATENT_ROWS["linear"]
    cfg, q_n, q_r, buf, pos, p = _latent_case("toy4x(32+16)", rows,
                                              (0, 21, 47, 70), n_q=3)
    want = D._latent_cached_attention(q_n, q_r, buf, 0, pos, p, cfg,
                                      block=block)
    latent_on_chip(block)
    assert D._read_arm(rows, 1, False) == "kernel"
    assert D._read_arm(rows, 1, False, n_q=3) == "walk"

    def never(*a, **k):
        raise AssertionError("the launch was reached")
    monkeypatch.setattr(D, "_kernel_latent_attention", never)
    got = D._latent_cached_attention(q_n, q_r, buf, 0, pos, p, cfg,
                                     block=block)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("arm", ["kernel", "walk"])
def test_latent_rows_visited_follow_the_arm(arm, latent_on_chip):
    """``cache_rows_visited``'s ``latent`` entry asks ``_read_arm`` as
    the read does: on the chip the work list's blocks (each slot's own,
    the buffer's last block cut to its rows), otherwise the walk's
    arithmetic — every slot to the longest row's last block of 256 (the
    whole buffer here) — an attention a row."""
    rows, block, positions = LATENT_ROWS["rows-off-the-block"]
    cfg = _latent_case("toy4x(32+16)", rows, positions)[0]
    steps = np.asarray(positions)[:, None] + np.arange(2)       # [B, n]
    steps = np.minimum(steps, rows - 1)
    live = int((steps + 1).sum())
    if arm == "kernel":
        latent_on_chip(block)
        n_work = sum(int(A.cached_attn_work(
            jnp.asarray(steps[:, j], jnp.int32), rows, block)[4])
            for j in range(steps.shape[1]))
        # the slot in the buffer's last block reads 72 - 64 = 8 rows of it
        read = n_work * block - 2 * (-rows % block)
    else:
        read = steps.size * rows
    assert D.cache_rows_visited(cfg, rows, steps) == {
        "latent": (read, live)}


@pytest.mark.parametrize("axes", ["dp4-ep2", "fsdp8", "dp2-tp4"])
def test_latent_arm_under_a_mesh(axes, latent_on_chip, monkeypatch):
    """The latent cache has ONE head, so nothing of it splits over
    ``tp``: under a mesh whose "heads" axis is live ``_read_arm`` leaves
    the read to the walk, which XLA partitions as it did; under batch
    axes alone the launch runs a device inside the ``shard_map`` island,
    on its own slots, with one device's numbers and no collective."""
    from tony_tpu.parallel import make_mesh
    mesh = make_mesh({"dp4-ep2": {"dp": 4, "ep": 2}, "fsdp8": {"fsdp": 8},
                      "dp2-tp4": {"dp": 2, "tp": 4}}[axes])
    rows, block, positions = LATENT_ROWS["linear"]
    cfg, q_n, q_r, buf, pos, p = _latent_case("toy4x(32+16)", rows,
                                              positions, seed=2)
    want = D._latent_cached_attention(q_n, q_r, buf, 1, pos, p, cfg,
                                      block=block)
    latent_on_chip(block)
    launches = []
    monkeypatch.setattr(D, "cached_attention", lambda *a, **k: (
        launches.append(a[0].shape), A.cached_attention(*a, **k))[1])
    read = jax.jit(lambda q_n, q_r, buf, pos: D._latent_cached_attention(
        q_n, q_r, buf, 1, pos, p, cfg, block=block))
    with jax.set_mesh(mesh):
        arm = D._read_arm(rows, 1, False)
        lowered = read.lower(q_n, q_r, buf, pos)
        got = read(q_n, q_r, buf, pos)
    assert arm == ("walk" if "tp" in axes else "kernel")
    np.testing.assert_allclose(got, want, atol=5e-5, rtol=5e-5)
    if arm == "walk":
        assert not launches
    else:
        # a device's own slots: 4 over dp = 4, all 4 where fsdp = 8 does
        # not divide them
        assert launches[0] == (1 if axes.startswith("dp") else 4, 4, 128)
        text = lowered.as_text()
        assert "shard_map" in text or "manual" in text.lower()
        compiled = lowered.compile().as_text()
        assert "all-gather" not in compiled and "all-reduce" not in compiled


@pytest.mark.parametrize("config,reads", [("tiny-mla-moe.json", 3),
                                          ("tiny-scmoe.json", 4)])
def test_engine_through_the_latent_arm_serves_the_walks_tokens(
        config, reads, latent_on_chip):
    """Kimi's kinds (a dense and two expert layers) and the double layer
    (two attentions a layer) served by ``ContinuousBatcher`` with the
    chip's arm in ``step_rows``: slots of unlike lengths, reused, one
    idle at the end — the greedy tokens are the walk's, and the host's
    ``latent`` count says each slot read its own blocks of 16 rows
    where the walk read every slot to the longest row's last block."""
    import os

    from benchmark.lib import modelcfg
    from tony_tpu.models import serve as S
    c = modelcfg.load(os.path.join(os.path.dirname(__file__), "data",
                                   config))
    fam = modelcfg.family(c)
    cfg = fam.program_config(c, dtype=jnp.float32, remat=False)
    params = fam.make_params(2**31 + 38, c, jnp.float32)
    assert cfg.attention_layers() == {"latent": reads}
    rs = np.random.default_rng(9)
    prompts = [rs.integers(0, c["vocab_size"], n).tolist()
               for n in (40, 5, 61, 18)]
    budgets = [9, 14, 5, 6]

    def served():
        jax.clear_caches()          # the arm is chosen while tracing
        b = S.ContinuousBatcher(params, cfg, batch=2, max_len=96, chunk=4)
        try:
            return b.serve(prompts, budgets), b
        finally:
            jax.clear_caches()
    want, walked = served()
    latent_on_chip(16)
    got, b = served()
    assert got == want
    assert b.cache_rows_live == walked.cache_rows_live
    steps = b.steps_executed * 2 * reads            # a slot an attention
    assert walked.cache_rows_read == {"latent": steps * 96}
    assert b.cache_rows_live["latent"] < b.cache_rows_read["latent"] \
        <= b.cache_rows_live["latent"] + steps * 16
