"""``tony_cached_attn`` in the Pallas interpreter against the ``jnp`` arms
of ``models/decode.py`` (the walk over live blocks, the dense ring read).

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
