"""Ring attention: context parallelism over the mesh's ``cp`` axis.

Green-field for the TPU build (SURVEY.md §2.3 / §5: the reference scales
nodes, not sequence length). Sequence is sharded over ``cp``; each device
holds a Q/K/V chunk, computes blockwise attention against the K/V chunk it
currently holds, then rotates K/V one hop around the ring with
``lax.ppermute`` (ICI neighbor exchange) while accumulating an online
softmax — so peak memory is O(seq/cp) and the full sequence is never
materialized on one chip. Differentiable as-is: the backward pass is the
transposed ring (ppermute has a transpose rule), driven by JAX AD through
the scan.

Numerics follow flash attention: f32 running max ``m``, normalizer ``l`` and
unnormalized output ``o``; fully-masked blocks (causal, future chunks) are
handled with a -1e30 additive mask so ``m`` never becomes -inf.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh

from tony_tpu.ops import mosaic
from tony_tpu.parallel.sharding import shard_attention

_NEG_INF = -1.0e30


def _block_attn(q, k, v, m, l, o, scale, q_off, kv_off, causal):
    """One blockwise-attention accumulation step (online softmax).

    q: [B, Sq, H, D]; k/v: [B, Sk, H_kv, D] (H_kv | H — GQA chunks stay
    unexpanded on the ring so the ppermute payload is H/H_kv× smaller;
    grouped einsums read the shared head directly, no materialized
    expansion); m/l: [B, H, Sq]; o: [B, Sq, H, D]. q_off/kv_off are the
    global sequence offsets of the chunks (for causal masking across
    ring hops).
    """
    b, sq, h, d = q.shape
    hk = k.shape[2]
    if hk != h:
        # blocked grouping (query head j ↔ kv head j // rep), same layout
        # as the flash kernels; [b, hk, rep, q, k] reshapes to the
        # contiguous [b, h, q, k]
        rep = h // hk
        qg = q.reshape(b, sq, hk, rep, d)
        s = jnp.einsum("bqhrd,bkhd->bhrqk", qg, k,
                       preferred_element_type=jnp.float32)
        s = s.reshape(b, h, sq, k.shape[1]) * scale
    else:
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                       preferred_element_type=jnp.float32) * scale
    if causal:
        q_pos = q_off + lax.broadcasted_iota(jnp.int32, s.shape, 2)
        kv_pos = kv_off + lax.broadcasted_iota(jnp.int32, s.shape, 3)
        s = jnp.where(q_pos >= kv_pos, s, _NEG_INF)
    m_new = jnp.maximum(m, s.max(axis=-1))
    # exp of masked lanes underflows to 0; correction stays finite because
    # m is floored at _NEG_INF rather than -inf.
    p = jnp.exp(s - m_new[..., None])
    corr = jnp.exp(m - m_new)
    l_new = l * corr + p.sum(axis=-1)
    if hk != h:
        pg = p.reshape(b, hk, h // hk, sq, k.shape[1])
        pv = jnp.einsum("bhrqk,bkhd->bqhrd", pg.astype(v.dtype), v,
                        preferred_element_type=jnp.float32)
        pv = pv.reshape(b, sq, h, d)
    else:
        pv = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                        preferred_element_type=jnp.float32)
    o_new = o * corr.transpose(0, 2, 1)[..., None] + pv
    return m_new, l_new, o_new


# Per-chunk attention engine: None = auto (flash kernels on TPU, dense
# online-softmax elsewhere); tests force True to run the flash arm in
# interpret mode.
_USE_FLASH_CHUNKS: bool | None = None


def _flash_chunks() -> bool:
    if _USE_FLASH_CHUNKS is not None:
        return _USE_FLASH_CHUNKS
    return not mosaic.interpret()


def ring_attention_local(q, k, v, *, axis_name: str = "cp",
                         causal: bool = True, scale: float | None = None):
    """Per-shard ring attention body — call inside ``shard_map`` (or any
    SPMD context where ``axis_name`` is bound and the sequence dim is the
    shard axis).

    q, k, v: [B, S_local, H, D] local chunks. Returns [B, S_local, H, D].
    On TPU each hop's chunk runs the flash kernels
    (:func:`tony_tpu.ops.attention.flash_attention_with_lse`) and hops are
    merged by logsumexp — O(S_local) memory per chunk instead of the dense
    [B, H, S_local, S_local] score tensor.
    """
    b, s_loc, h, d = q.shape
    scale = (d ** -0.5) if scale is None else scale
    cp = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    q_off = idx * s_loc

    if _flash_chunks() and _flash_block(s_loc) is not None:
        return _ring_flash(q, k, v, axis_name=axis_name, causal=causal,
                           scale=scale, cp=cp, q_off=q_off)

    q32 = q.astype(jnp.float32) if q.dtype == jnp.float64 else q
    m0 = jnp.full((b, h, s_loc), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, s_loc), jnp.float32)
    o0 = jnp.zeros((b, s_loc, h, d), jnp.float32)

    if cp == 1:
        m, l, o = _block_attn(q32, k, v, m0, l0, o0, scale, q_off, q_off,
                              causal)
        return (o / l.transpose(0, 2, 1)[..., None]).astype(q.dtype)

    perm = [(i, (i + 1) % cp) for i in range(cp)]

    def step(carry, hop):
        k_cur, v_cur, m, l, o = carry
        # chunk held at this hop originated on device (idx - hop) mod cp
        kv_off = ((idx - hop) % cp) * s_loc
        m, l, o = _block_attn(q32, k_cur, v_cur, m, l, o, scale, q_off,
                              kv_off, causal)
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return (k_nxt, v_nxt, m, l, o), None

    (_, _, m, l, o), _ = lax.scan(step, (k, v, m0, l0, o0),
                                  jnp.arange(cp, dtype=jnp.int32))
    # causal + f32: every query attends at least to itself, so l > 0
    return (o / l.transpose(0, 2, 1)[..., None]).astype(q.dtype)


def _flash_block(s_loc: int) -> int | None:
    """Largest flash block size tiling the local chunk, or None when no
    usable block exists. On REAL TPU the floor is 128: below the
    128-lane tile the Mosaic lowering of the 2-D lse layout is untested,
    and per-grid-step overhead makes flash slower than the dense arm
    there anyway. Interpret mode (the CPU test path) keeps the small
    blocks so the flash-chunk arm stays bit-testable at tiny shapes."""
    blocks = ((512, 256, 128, 64, 32, 16, 8) if mosaic.interpret()
              else (512, 256, 128))
    for b in blocks:
        if s_loc % b == 0:
            return b
    return None


def _ring_flash(q, k, v, *, axis_name, causal, scale, cp, q_off):
    """Ring body with flash-kernel chunks merged by logsumexp.

    Each hop's chunk falls into one of three causal cases, selected at
    runtime (the kv offset rotates with the hop): entirely in the past
    (full attention, no mask), the diagonal chunk (causal flash), or
    entirely in the future (skipped — contributes o = 0, lse = -1e30,
    which the finite-arithmetic logaddexp merge weights to exactly zero).
    The lse outputs are DIFFERENTIATED (flash_attention_with_lse), so
    JAX AD through the merge + scan yields the transposed ring backward
    with flash backward kernels per chunk."""
    from tony_tpu.ops.attention import flash_attention_with_lse

    out_dtype = q.dtype
    if q.dtype == jnp.float64:      # pallas kernels have no f64 path
        q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    b, s_loc, h, d = q.shape
    blk = _flash_block(s_loc)
    # narrow-q × wide-kv is the kernels' measured sweet spot (see
    # ops/attention.py); fall back to the square tiling block for chunk
    # lengths the preferred shapes don't divide
    bq = min(256, blk) if s_loc % min(256, blk) == 0 else blk
    bk = next((w for w in (1024, 512, 256) if w >= blk and s_loc % w == 0),
              blk)

    def full_chunk(q, k, v):
        o, lse = flash_attention_with_lse(q, k, v, causal=False, scale=scale,
                                          block_q=bq, block_k=bk)
        return o.astype(jnp.float32), lse

    def diag_chunk(q, k, v):
        o, lse = flash_attention_with_lse(q, k, v, causal=True, scale=scale,
                                          block_q=bq, block_k=bk)
        return o.astype(jnp.float32), lse

    def future_chunk(q, k, v):
        # output/lse are q-shaped: unaffected by GQA K/V widths
        return (jnp.zeros((b, s_loc, h, d), jnp.float32),
                jnp.full((b, h, s_loc), _NEG_INF, jnp.float32))

    o0 = jnp.zeros((b, s_loc, h, d), jnp.float32)
    lse0 = jnp.full((b, h, s_loc), _NEG_INF, jnp.float32)

    if cp == 1:
        o, lse = (diag_chunk if causal else full_chunk)(q, k, v)
        return o.astype(out_dtype)

    perm = [(i, (i + 1) % cp) for i in range(cp)]
    idx = lax.axis_index(axis_name)

    def step(carry, hop):
        k_cur, v_cur, o_acc, lse_acc = carry
        kv_off = ((idx - hop) % cp) * s_loc
        if causal:
            case = jnp.where(kv_off > q_off, 2,
                             jnp.where(kv_off == q_off, 1, 0))
            o_c, lse_c = lax.switch(
                case, (full_chunk, diag_chunk, future_chunk), q, k_cur, v_cur)
        else:
            # every hop is a full chunk — no switch, no dead branches
            o_c, lse_c = full_chunk(q, k_cur, v_cur)
        lse_new = jnp.logaddexp(lse_acc, lse_c)         # [B, H, S]
        w_acc = jnp.exp(lse_acc - lse_new)
        w_c = jnp.exp(lse_c - lse_new)
        to_bshd = lambda w: w.transpose(0, 2, 1)[..., None]
        o_acc = o_acc * to_bshd(w_acc) + o_c * to_bshd(w_c)
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return (k_nxt, v_nxt, o_acc, lse_new), None

    (_, _, o, _), _ = lax.scan(step, (k, v, o0, lse0),
                               jnp.arange(cp, dtype=jnp.int32))
    return o.astype(out_dtype)


def ring_attention(q, k, v, mesh: Mesh, *, causal: bool = True,
                   scale: float | None = None,
                   batch_axes: Sequence[str] = ("dp", "fsdp"),
                   seq_axis: str = "cp", head_axis: str = "tp"):
    """Context-parallel attention over global [B, S, H, D] arrays.

    A ``shard_map`` island intended for use inside a jitted model: batch over
    dp/fsdp, sequence over cp, heads over tp. Axes missing from ``mesh`` (or
    of size 1) are dropped from the specs automatically.

    GQA K/V (fewer heads than Q) ride the ring UNEXPANDED — the ring's
    inter-chip traffic IS the K/V rotation, so grouped heads cut the
    ppermute payload by H/H_kv. Head-sharding discipline: the local
    arms pair local query head j with local kv head j // rep, which is
    only the GLOBAL pairing when K/V heads shard over the SAME axis as
    Q's (or none is live). So kv heads shard over ``head_axis`` when
    they divide it; otherwise (H_kv < tp) K/V expand to full width
    first — correctness over the payload saving.
    """
    if mesh.shape.get(seq_axis, 1) > 1:
        fn = functools.partial(ring_attention_local, axis_name=seq_axis,
                               causal=causal, scale=scale)
    else:
        # no cp axis: plain (still blockwise/online-softmax) local attention
        fn = functools.partial(_single_chunk, causal=causal, scale=scale)
    return shard_attention(fn, q, k, v, mesh, batch_axes=batch_axes,
                           seq_axis=seq_axis, head_axis=head_axis)


def _single_chunk(q, k, v, *, causal, scale):
    b, s, h, d = q.shape
    scale = (d ** -0.5) if scale is None else scale
    blk = _flash_block(s)
    if _flash_chunks() and blk is not None:
        # same engine selection (and f64→f32 cast) as the ring hops
        from tony_tpu.ops.attention import flash_attention
        out_dtype = q.dtype
        if q.dtype == jnp.float64:
            q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
        return flash_attention(q, k, v, causal=causal, scale=scale,
                               block_q=blk, block_k=blk).astype(out_dtype)
    m = jnp.full((b, h, s), _NEG_INF, jnp.float32)
    l = jnp.zeros((b, h, s), jnp.float32)
    o = jnp.zeros((b, s, h, d), jnp.float32)
    m, l, o = _block_attn(q, k, v, m, l, o, scale, 0, 0, causal)
    return (o / l.transpose(0, 2, 1)[..., None]).astype(q.dtype)
