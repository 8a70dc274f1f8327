"""Flash attention: fused blockwise attention as Pallas TPU kernels.

Green-field for the TPU build — the reference delegates all compute to user
TF/PyTorch code (SURVEY.md §2.3); here the hot op the MXU lives on is a
first-class framework kernel. Design follows the flash-attention recipe on
the TPU memory hierarchy: Q/K/V tiles stream HBM→VMEM once, scores never
materialize in HBM, the online softmax keeps f32 running max/sum in VMEM
scratch across the innermost (kv) grid dimension, and the MXU sees only
[block_q, d] × [d, block_k] matmuls with ``preferred_element_type=f32``.

Two measured-on-v5e refinements over the textbook kernel (the per-grid-step
cost on this hardware is ~2-4µs, so step count matters as much as FLOPs):

- **Head grouping** (``block_h``): each grid step processes ``block_h``
  batch-heads (an in-kernel unrolled loop of 2-D matmuls), cutting the grid
  from ``b·h × nq × nk`` to ``b·h/block_h × nq × nk`` steps. At LM shapes
  (head_dim 64, seq 1k) the per-head blocks are far below MXU-saturating
  sizes, so amortizing the fixed step cost dominates.
- **GQA-native K/V** (round 3): when K/V carry fewer heads than Q
  (grouped-query attention), the kernels take them UNEXPANDED. Queries are
  laid out ``[b·h_kv, rep·sq, d]`` — each kv head's ``rep`` query heads
  form contiguous row bands sharing that head's K/V blocks in-kernel — and
  the causal mask uses the position within the band (``qi mod sq/bq``).
  K/V HBM traffic drops by h/h_kv and the ``jnp.repeat`` materialization
  disappears; dK/dV need no extra handling (the per-q-block partial sum
  already reduces across the bands).
- **Shared causal mask**: the block's position mask is an iota+compare
  computed once per grid step and reused by every head in the group, and
  kv-blocks entirely above the diagonal are skipped, so the VPU cost of
  masking amortizes to ~1 op/element instead of ~4.

Round-4 refinements, each measured on one v5e with xprof device time:

- **Base-2 online softmax**: ``scale·log2e`` folds into Q once outside the
  kernels; the kernels call ``exp2`` (VPU ``exp`` is exp2 plus a
  multiply) and convert lse to natural log only at finalize. Backward
  picks up a single ln2 on the [*, d]-shaped outputs.
- **Skip-block DMA elision**: causal index maps clamp the K/V (or q-side)
  block coordinate for above/below-diagonal skipped steps, so the
  pipeline never fetches blocks the kernel won't read.
- **Narrow-q × wide-kv blocks** (256×1024 fwd, 128×512 bwd): the
  [block_q, block_k] f32 score intermediates are the kernel-stack VMEM
  budget; shrinking block_q 4× is what affords kv blocks past 256 and
  with them fewer grid steps and less K/V re-fetch.

Backward recomputes scores (no O(S²) residuals) in a single fused pass by
default, on a KV-MAJOR grid: dK/dV accumulate in f32 VMEM scratch across
the inner q sweep (written once per kv block — no partials), and only the
per-kv-block dQ contributions ([nk, b·h, S, D], input dtype) are summed
by XLA outside — one score/exp recompute instead of the classic two-pass
split's two, which is what matters in this VPU-bound regime, and half the
partial-tensor traffic of the previous q-major layout. When the partials
would exceed the ``_FUSED_PARTIALS_BYTES`` budget (their HBM footprint
scales with nk), the backward falls back to the two-pass split: one pass
gridded over q-blocks accumulating dQ, one over kv-blocks accumulating
dK/dV. Wired together with ``jax.custom_vjp``.

On non-TPU backends (the 8-device CPU test mesh) the same kernels run in
Pallas interpret mode — bit-accurate, slow — or callers use
:func:`reference_attention`. Layouts are [batch, seq, heads, head_dim] at
the API, [batch·heads, seq, head_dim] inside; the layout
:mod:`tony_tpu.parallel.ring_attention` chunks over ``cp`` — this kernel is
the intra-chunk compute.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tony_tpu.ops import mosaic

_NEG_INF = -1.0e30
_LANES = 128
# The online softmax runs in BASE-2 (flash-2-style transcendental
# thinning): `scale · log2(e)` is folded into Q once outside the kernels
# (a [*, d] multiply amortized over every kv block, instead of the
# per-block [bq, bk] `s * scale`), the kernels call `exp2` directly
# (VPU `exp` is exp2 plus an x·log2e multiply — dropped), and lse
# converts back to natural log only at finalize. Backward picks up a
# single ln2 factor on the score gradient (∂2^x/∂x = ln2·2^x), applied
# to the [*, d]-shaped dq/dk outputs rather than the score matrix.
_LOG2E = 1.4426950408889634
_LN2 = 0.6931471805599453


def _cdiv(a: int, b: int) -> int:
    return (a + b - 1) // b


def _pick_group(bh: int, block_h: int) -> int:
    """Heads-per-grid-step. Must divide batch·heads, and — because the 2-D
    [g, bq] lse blocks hit Mosaic's (8, 128)-divisibility rule on the
    second-minor dim — must be a multiple of 8. Callers pad bh to a
    multiple of 8 first (:func:`flash_attention`), so a multiple-of-8
    divisor always exists."""
    best = 8
    for g in range(8, bh + 1, 8):
        if bh % g == 0 and g <= max(block_h, 8):
            best = g
    return best


def _causal_mask(qi, ki, bq: int, bk: int, window: int | None = None):
    """[bq, bk] bool mask for the (qi, ki) block — computed once per grid
    step and shared by all heads in the group. ``qi`` is the BAND-relative
    q-block index (callers take program_id(..) mod blocks-per-band; for
    plain MHA the band is the whole sequence and the mod is identity).
    ``window`` adds the sliding-window bound: query attends only the
    ``window`` most recent positions (qpos - kpos < window)."""
    qpos = qi * bq + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = ki * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = qpos >= kpos
    if window is not None:
        mask = jnp.logical_and(mask, qpos - kpos < window)
    return mask


def _block_work(qi, ki, bq: int, bk: int, window: int | None):
    """Whether block (qi, ki) holds ANY attended (q, k) pair: below-or-on
    the diagonal, and — with a sliding window — not entirely older than
    the window (youngest k in the block within ``window`` of the oldest
    q)."""
    work = (qi + 1) * bq > ki * bk
    if window is not None:
        work = jnp.logical_and(work,
                               qi * bq - ((ki + 1) * bk - 1) < window)
    return work


def _causal_dispatch(qi, ki, bq: int, bk: int, accumulate, on_skip=None,
                     window: int | None = None):
    """Causal (+ sliding-window) block triage, shared by every kernel:
    blocks with no attended pair — entirely above the diagonal, or (with
    ``window``) entirely older than the window — are skipped (``on_skip``
    runs if given — e.g. zeroing partial outputs); blocks whose every
    pair is attended run ``accumulate(False)`` (no per-element
    compare/select — measurable in these VPU-bound kernels, increasingly
    so at long sequence where such blocks dominate); boundary-crossing
    blocks run ``accumulate(True)``."""
    work = _block_work(qi, ki, bq, bk, window)
    unmasked = qi * bq >= (ki + 1) * bk - 1
    if window is not None:
        unmasked = jnp.logical_and(
            unmasked, (qi + 1) * bq - 1 - ki * bk < window)

    @pl.when(jnp.logical_and(work, unmasked))
    def _():
        accumulate(False)

    @pl.when(jnp.logical_and(work, jnp.logical_not(unmasked)))
    def _():
        accumulate(True)

    if on_skip is not None:
        @pl.when(jnp.logical_not(work))
        def _():
            on_skip()


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, ml_scr, acc_scr,
                *, causal: bool, g: int, bq: int, bk: int,
                nk: int, band_nq: int, window: int | None):
    # Q arrives PRE-SCALED by scale·log2e (:func:`_prep_flat`), so the
    # raw MXU dot is already the base-2 score and the kernel never
    # touches a [bq, bk] scale multiply; all max/sum bookkeeping below
    # is in the exp2 domain, converted to natural lse only at finalize.
    qi = pl.program_id(1) % band_nq     # GQA band-relative (identity: MHA)
    ki = pl.program_id(2)
    # ml_scr packs the running max (lane 0) and running sum (lane 1) into
    # one [g, bq, _LANES] buffer — each lives in its own 128-lane tile
    # anyway, so separate buffers would double the VMEM footprint.

    @pl.when(ki == 0)
    def _init():
        ml_scr[:] = jnp.full_like(ml_scr, _NEG_INF)

    def _accumulate(masked: bool):
        mask = _causal_mask(qi, ki, bq, bk, window) if masked else None
        for gi in range(g):
            q = q_ref[gi]                              # [bq, d], pre-scaled
            k = k_ref[gi]                              # [bk, d]
            v = v_ref[gi]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)    # [bq, bk], base-2
            if masked:
                s = jnp.where(mask, s, _NEG_INF)
            m_prev = ml_scr[gi, :, 0:1]                # [bq, 1]
            l_prev = ml_scr[gi, :, 1:2]
            first = m_prev <= _NEG_INF                 # nothing seen yet
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            p = jnp.exp2(s - m_new)                    # [bq, bk]
            corr = jnp.where(first, 0.0, jnp.exp2(m_prev - m_new))  # [bq, 1]
            l_new = l_prev * corr + p.sum(axis=-1, keepdims=True)
            if nk == 1 and not (causal and bq < bk):
                # single kv block: the accumulator rescale is dead code
                acc_scr[gi] = jax.lax.dot(
                    p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            else:
                acc = jnp.where(first, 0.0, acc_scr[gi])
                acc_scr[gi] = acc * corr + jax.lax.dot(
                    p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            ml_scr[gi, :, 0:1] = m_new
            ml_scr[gi, :, 1:2] = l_new

    if causal:
        _causal_dispatch(qi, ki, bq, bk, _accumulate, window=window)
    else:
        _accumulate(False)

    @pl.when(ki == nk - 1)
    def _finalize():
        for gi in range(g):
            m = ml_scr[gi, :, 0:1]
            l = ml_scr[gi, :, 1:2]
            o_ref[gi] = (acc_scr[gi] / jnp.maximum(l, 1e-30)).astype(
                o_ref.dtype)
            # natural-log lse: ln(2^m · l) = ln2 · (m + log2 l)
            lse_ref[gi] = (_LN2 * (m + jnp.log2(jnp.maximum(l, 1e-30))))[:, 0]


def _kv_index_map(causal: bool, bq: int, bk: int, band_nq: int,
                  window: int | None = None):
    """K/V block index map for q-major grids ``(b, qi, ki)``. For causal
    kernels the ki coordinate is CLAMPED to the last diagonal-touching
    block of the (band-relative) q row: skipped above-diagonal steps then
    repeat the previous step's block index, and the Pallas pipeline elides
    the HBM→VMEM copy for an unchanged index — at long sequence nearly
    half the K/V DMA traffic was being fetched for blocks the kernel
    never reads. A sliding ``window`` clamps from BELOW too: kv blocks
    entirely older than the window repeat the first in-window block's
    index, so their DMA is elided the same way — what makes windowed
    cost scale with the window, not the sequence."""
    if not causal:
        return lambda b, i, j: (b, j, 0)

    def _map(b, i, j):
        rel = i % band_nq
        last = ((rel + 1) * bq - 1) // bk
        if window is not None:
            first = jnp.maximum(rel * bq - window + 1, 0) // bk
            return (b, jnp.clip(j, first, last), 0)
        return (b, jnp.minimum(j, last), 0)

    return _map


def _flash_forward(q, k, v, *, causal, g, bq, bk, band, window=None):
    bh, sq, d = q.shape                 # sq = rep·band under GQA
    sk = k.shape[1]
    nq, nk = _cdiv(sq, bq), _cdiv(sk, bk)
    kernel = functools.partial(_fwd_kernel, causal=causal,
                               g=g, bq=bq, bk=bk, nk=nk,
                               band_nq=_cdiv(band, bq), window=window)
    kv_map = _kv_index_map(causal, bq, bk, _cdiv(band, bq), window)
    o, lse = pl.pallas_call(
        kernel,
        grid=(bh // g, nq, nk),
        in_specs=[
            pl.BlockSpec((g, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((g, bk, d), kv_map),
            pl.BlockSpec((g, bk, d), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((g, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((g, bq), lambda b, i, j: (b, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((g, bq, _LANES), jnp.float32),   # max (l0) + sum (l1)
            pltpu.VMEM((g, bq, d), jnp.float32),        # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=mosaic.interpret(),
        # the four launches carry stable names: ``name=`` becomes the HLO
        # instruction's name (``%tony_flash_fwd.N``, what a device trace
        # is read by — XLA's own ``checkpoint.N`` / ``closed_call.N``
        # renumber on any refactor) and opens a ``jax.named_scope`` of
        # the same name around the launch
        name="tony_flash_fwd",
    )(q, k, v)
    return o, lse


# ---------------------------------------------------------------------------
# Backward, fused single pass (default): KV-MAJOR grid (bh/g, nk, nq) —
# ki outer, qi inner. dK/dV accumulate in f32 VMEM scratch across the qi
# sweep and are written ONCE per kv block (no dK/dV partials at all); the
# only partial tensor is per-kv-block dQ contributions [nk, bh, sq, d],
# summed by XLA outside. Compared to the round-2/3 q-major layout (which
# wrote TWO partial tensors, dK and dV), this halves partial HBM traffic
# and replaces two XLA reduces with one. One compiled body (mask applied
# on every active block — measured free next to exp2) keeps Mosaic's
# kernel stack small enough for 512-wide kv blocks; 128-row q blocks
# shrink the [bq, bk] f32 intermediates 4×, which is what buys the wide
# kv blocks under the ~16 MB VMEM limit. Measured (device-time via xprof,
# one v5e, seq 8k b4): 14.2 ms vs 17.1 ms for the q-major layout (1.21×);
# seq 1k b32: 3.32 vs 3.78 ms (1.14×). This recomputes scores/exp ONCE
# per backward instead of the two-pass split's twice, which matters
# because the kernel is VPU-bound (softmax ops, not MXU FLOPs, set the
# wall-clock at LM head dims). delta = rowsum(dO·O) is one fused XLA
# pass outside, fed (like lse) as 2-D [g, bq] blocks — no [.., _LANES]
# broadcasts ever touch HBM.
# ---------------------------------------------------------------------------

# Partial-tensor budget gating the fused backward (the dQ partials are
# nk × the q tensor size). Overridable: TONY_FLASH_FUSED_PARTIALS_MB.
# Measured on one v5e (bf16, 8 heads, d64, xprof device time): with the
# kv-major layout fused beats two-pass 14.2 vs 17.1 ms at seq 8k b4
# (512 MB partials) and 26.6 vs 32.6 ms at seq 16k b2 (1 GB partials) —
# the default covers both; raise further when HBM has headroom. Set 0
# to force two-pass: the fused path stores dQ partials in bf16 (error
# ~ √nk·eps_bf16), while two-pass accumulates dQ in f32 VMEM — the
# knob is the precision escape hatch.
import os as _os

_FUSED_PARTIALS_BYTES = int(_os.environ.get(
    "TONY_FLASH_FUSED_PARTIALS_MB", "1024")) * 1024 * 1024

# Backward block shape on real TPUs (interpret mode keeps caller blocks
# so tiny CPU test shapes stay bit-testable): 128-row q blocks × 512-wide
# kv blocks won the v5e sweep — [128, 512] f32 stack intermediates are
# small enough for the single-body kernel to fit VMEM with headroom.
_BWD_BQ = 128
_BWD_BK = 512


def _bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      *refs, causal: bool, g: int, bq: int, bk: int,
                      nq: int, has_dlse: bool, band_nq: int,
                      window: int | None):
    # refs = ([dlse_ref,] dqp_ref, dk_ref, dv_ref, dk_scr, dv_scr): the
    # dlse input exists only for the with-lse entry point, so the hot
    # plain-attention path compiles the exact same kernel.
    if has_dlse:
        dlse_ref, dqp_ref, dk_ref, dv_ref, dk_scr, dv_scr = refs
    else:
        dlse_ref = None
        dqp_ref, dk_ref, dv_ref, dk_scr, dv_scr = refs
    ki = pl.program_id(1)
    qi_g = pl.program_id(2)             # inner: restarts per kv block
    qi = qi_g % band_nq                 # GQA band-relative (identity: MHA)

    @pl.when(qi_g == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _accumulate():
        # single body: the causal mask runs on every active block (its
        # iota+compare is in the noise next to exp2), which keeps one
        # copy of the [bq, bk] f32 intermediates on the kernel stack —
        # the VMEM room that pays for 512-wide kv blocks.
        mask = _causal_mask(qi, ki, bq, bk, window) if causal else None
        for gi in range(g):
            q = q_ref[gi]                               # [bq, d], pre-scaled
            k = k_ref[gi]                               # [bk, d]
            v = v_ref[gi]
            do = do_ref[gi]
            lse2 = lse_ref[gi][:, None]                 # [bq, 1], base-2
            # d(lse) enters the score gradient additively:
            # ds = p · (dp - delta + dlse); delta_eff folds it in
            delta = delta_ref[gi][:, None]              # [bq, 1]
            if has_dlse:
                delta = delta - dlse_ref[gi][:, None]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)     # [bq, bk], base-2
            if causal:
                s = jnp.where(mask, s, _NEG_INF)
            p = jnp.exp2(s - lse2)                      # [bq, bk]
            dv_scr[gi] += jax.lax.dot_general(
                p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)     # [bk, d]
            dp = jax.lax.dot_general(
                do, v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)     # [bq, bk]
            # base-2 score grad is ln2·p·(dp - delta); the ln2 lands on
            # the [*, d]-shaped dk/dq outputs, never the score matrix
            ds = p * (dp - delta)                       # [bq, bk]
            dk_scr[gi] += jax.lax.dot_general(
                ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)     # [bk, d]
            dqp_ref[0, gi] = (_LN2 * jax.lax.dot(
                ds.astype(k.dtype), k,
                preferred_element_type=jnp.float32)).astype(dqp_ref.dtype)

    if causal:
        work = _block_work(qi, ki, bq, bk, window)

        @pl.when(work)
        def _():
            _accumulate()

        @pl.when(jnp.logical_not(work))
        def _():
            # blocks with no attended pair (above the diagonal, or older
            # than the sliding window) contribute nothing, but their dq
            # partial blocks still exist and must be zeroed
            dqp_ref[:] = jnp.zeros_like(dqp_ref)
    else:
        _accumulate()

    @pl.when(qi_g == nq - 1)
    def _finalize():
        dk_ref[:] = (_LN2 * dk_scr[:]).astype(dk_ref.dtype)
        dv_ref[:] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_q_maps(causal: bool, bq: int, bk: int, band_nq: int,
                window: int | None = None):
    """Index maps for q-side operands on the kv-major grid ``(b, ki, qi)``.
    For causal kernels the leading (band-relative) q blocks of each kv
    sweep sit above the diagonal and are skipped — clamp them to the
    first diagonal-touching block so the pipeline doesn't DMA blocks the
    kernel never reads (mirror of :func:`_kv_index_map`). With a sliding
    ``window``, trailing q blocks entirely NEWER than window-past-this-kv
    are skipped too — clamp from above symmetrically."""
    if not causal:
        return (lambda b, j, i: (b, i, 0)), (lambda b, j, i: (b, i))

    def _clamp(j, i):
        rel = i % band_nq
        first = (j * bk) // bq
        if window is not None:
            last = jnp.minimum((j + 1) * bk - 1 + window - 1, band_nq
                               * bq - 1) // bq
            return i - rel + jnp.clip(rel, first, jnp.maximum(last, first))
        return i - rel + jnp.maximum(rel, first)

    return (lambda b, j, i: (b, _clamp(j, i), 0),
            lambda b, j, i: (b, _clamp(j, i)))


def _flash_backward_fused(q, k, v, o, lse, do, dlse, *, causal, g,
                          bq, bk, band, window=None):
    bh, sq, d = q.shape
    sk = k.shape[1]
    has_dlse = dlse is not None
    # Swap to the measured-best backward blocks when they tile the
    # shapes (always true at the power-of-two LM lengths); interpret
    # mode keeps caller blocks so tiny CPU test shapes exercise the
    # same kernel. The head group is clamped independently of the
    # forward's: the backward holds 2× f32 kv-block scratch per head,
    # so the forward's g=16 short-kv choice blows its VMEM (any g=16
    # implies 8 | bh, so the clamp always divides).
    if not mosaic.interpret():
        g = min(g, 8)
        if sq % _BWD_BQ == 0 and band % _BWD_BQ == 0:
            bq = _BWD_BQ
        if sk % _BWD_BK == 0:
            bk = _BWD_BK
        elif bk > 256 and sk % 256 == 0:
            bk = 256
    nq, nk = _cdiv(sq, bq), _cdiv(sk, bk)
    band_nq = _cdiv(band, bq)
    # ds = p · (dp - delta + dlse): delta = rowsum(dO·O) is one fused XLA
    # elementwise+reduce pass; base-2 lse feeds the exp2-domain kernel.
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)                            # [bh, sq]
    lse2 = lse * _LOG2E
    q_map, q_map2 = _bwd_q_maps(causal, bq, bk, band_nq, window)
    in_specs = [
        pl.BlockSpec((g, bq, d), q_map),
        pl.BlockSpec((g, bk, d), lambda b, j, i: (b, j, 0)),
        pl.BlockSpec((g, bk, d), lambda b, j, i: (b, j, 0)),
        pl.BlockSpec((g, bq, d), q_map),
        pl.BlockSpec((g, bq), q_map2),
        pl.BlockSpec((g, bq), q_map2),
    ]
    operands = [q, k, v, do, lse2, delta]
    if has_dlse:
        in_specs.append(pl.BlockSpec((g, bq), q_map2))
        operands.append(dlse)
    dqp, dk, dv = pl.pallas_call(
        functools.partial(_bwd_fused_kernel, causal=causal,
                          g=g, bq=bq, bk=bk, nq=nq, has_dlse=has_dlse,
                          band_nq=band_nq, window=window),
        grid=(bh // g, nk, nq),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, g, bq, d), lambda b, j, i: (j, b, i, 0)),
            pl.BlockSpec((g, bk, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((g, bk, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            # dQ partials are stored at input precision, not f32: each
            # element is a complete f32 MXU accumulation over the kv-block
            # columns rounded ONCE, and the partials are summed in f32
            # below. Worst-case error ~ √nk · eps_bf16 (covered by
            # test_gradients_bfloat16_long_seq) — for half the partial
            # HBM traffic.
            jax.ShapeDtypeStruct((nk, bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sk, d), k.dtype),
            jax.ShapeDtypeStruct((bh, sk, d), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((g, bk, d), jnp.float32),
                        pltpu.VMEM((g, bk, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=mosaic.interpret(),
        name="tony_flash_bwd_fused",
    )(*operands)
    if nk == 1:
        return dqp[0], dk, dv
    dq = dqp.astype(jnp.float32).sum(0).astype(q.dtype)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# Backward, two-pass fallback for long sequences: dQ pass (grid over q
# blocks, inner loop over kv blocks)
# ---------------------------------------------------------------------------

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_scr, *, causal: bool, g: int, bq: int,
               bk: int, nk: int, band_nq: int, window: int | None):
    qi = pl.program_id(1) % band_nq     # GQA band-relative (identity: MHA)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def _accumulate(masked: bool):
        mask = _causal_mask(qi, ki, bq, bk, window) if masked else None
        for gi in range(g):
            q = q_ref[gi]                               # [bq, d], pre-scaled
            k = k_ref[gi]
            v = v_ref[gi]
            do = do_ref[gi]                             # [bq, d]
            lse2 = lse_ref[gi][:, None]                 # [bq, 1], base-2
            delta = delta_ref[gi][:, None]              # [bq, 1]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)     # base-2
            if masked:
                s = jnp.where(mask, s, _NEG_INF)
            p = jnp.exp2(s - lse2)                      # [bq, bk]
            dp = jax.lax.dot_general(
                do, v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)     # [bq, bk]
            ds = p * (dp - delta)
            dq_scr[gi] += jax.lax.dot(ds.astype(k.dtype), k,
                                      preferred_element_type=jnp.float32)

    if causal:
        _causal_dispatch(qi, ki, bq, bk, _accumulate, window=window)
    else:
        _accumulate(False)

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[:] = (_LN2 * dq_scr[:]).astype(dq_ref.dtype)


# ---------------------------------------------------------------------------
# Backward: dK/dV pass (grid over kv blocks, inner loop over q blocks)
# ---------------------------------------------------------------------------

def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *,
                causal: bool, g: int, bq: int, bk: int, nq: int,
                band_nq: int, window: int | None):
    ki = pl.program_id(1)
    qi_g = pl.program_id(2)             # global: init/finalize sequencing
    qi = qi_g % band_nq                 # GQA band-relative: causal triage

    @pl.when(qi_g == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _accumulate(masked: bool):
        mask = _causal_mask(qi, ki, bq, bk, window) if masked else None
        for gi in range(g):
            q = q_ref[gi]                               # [bq, d], pre-scaled
            k = k_ref[gi]                               # [bk, d]
            v = v_ref[gi]
            do = do_ref[gi]
            lse2 = lse_ref[gi][:, None]                 # base-2
            delta = delta_ref[gi][:, None]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)     # [bq, bk], base-2
            if masked:
                s = jnp.where(mask, s, _NEG_INF)
            p = jnp.exp2(s - lse2)                      # [bq, bk]
            dv_scr[gi] += jax.lax.dot_general(
                p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)     # [bk, d]
            dp = jax.lax.dot_general(
                do, v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)     # [bq, bk]
            ds = p * (dp - delta)                       # [bq, bk]
            dk_scr[gi] += jax.lax.dot_general(
                ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)     # [bk, d]

    if causal:
        _causal_dispatch(qi, ki, bq, bk, _accumulate, window=window)
    else:
        _accumulate(False)

    @pl.when(qi_g == nq - 1)
    def _finalize():
        dk_ref[:] = (_LN2 * dk_scr[:]).astype(dk_ref.dtype)
        dv_ref[:] = dv_scr[:].astype(dv_ref.dtype)


def _flash_backward(q, k, v, o, lse, do, dlse=None, *, causal, g,
                    bq, bk, band, window=None):
    bh, sq, d = q.shape
    sk = k.shape[1]
    nq, nk = _cdiv(sq, bq), _cdiv(sk, bk)
    # dQ partials are [nk, bh, sq, d] at the blocks the fused path will
    # actually pick — mirror its clamp chain exactly.
    bk_eff = bk
    if not mosaic.interpret():
        if sk % _BWD_BK == 0:
            bk_eff = _BWD_BK
        elif bk > 256 and sk % 256 == 0:
            bk_eff = 256
    partial_bytes = _cdiv(sk, bk_eff) * bh * sq * d * q.dtype.itemsize
    if partial_bytes <= _FUSED_PARTIALS_BYTES:
        return _flash_backward_fused(q, k, v, o, lse, do, dlse,
                                     causal=causal, g=g, bq=bq, bk=bk,
                                     band=band, window=window)
    # Mosaic allocates kernel stack for BOTH _causal_dispatch bodies, so the
    # [bq, bk] f32 intermediates count twice; 256-wide blocks keep the
    # two-pass kernels inside the ~16 MB VMEM budget (long sequences have
    # hundreds of grid steps either way). Same independent head-group
    # clamp as the fused path (the forward may have picked g=16).
    if not mosaic.interpret():
        g = min(g, 8)
    if bq > 256 and sq % 256 == 0 and band % 256 == 0:
        bq = 256
        nq = _cdiv(sq, bq)
    if bk > 256 and sk % 256 == 0:
        bk = 256
        nk = _cdiv(sk, bk)
    # ds = p · (dp - delta + dlse): fold the lse cotangent into delta;
    # base-2 lse for the exp2-domain kernels. Both ride as 2-D [g, bq]
    # blocks — no [.., _LANES] HBM broadcasts.
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)                            # [bh, sq]
    if dlse is not None:
        delta = delta - dlse
    lse2 = lse * _LOG2E
    kv_map = _kv_index_map(causal, bq, bk, _cdiv(band, bq), window)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, causal=causal, g=g,
                          bq=bq, bk=bk, nk=nk, band_nq=_cdiv(band, bq),
                          window=window),
        grid=(bh // g, nq, nk),
        in_specs=[
            pl.BlockSpec((g, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((g, bk, d), kv_map),
            pl.BlockSpec((g, bk, d), kv_map),
            pl.BlockSpec((g, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((g, bq), lambda b, i, j: (b, i)),
            pl.BlockSpec((g, bq), lambda b, i, j: (b, i)),
        ],
        out_specs=pl.BlockSpec((g, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((g, bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=mosaic.interpret(),
        name="tony_flash_bwd_dq",
    )(q, k, v, do, lse2, delta)

    band_nq = _cdiv(band, bq)
    q_map, q_map2 = _bwd_q_maps(causal, bq, bk, band_nq, window)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, causal=causal, g=g,
                          bq=bq, bk=bk, nq=nq, band_nq=band_nq,
                          window=window),
        grid=(bh // g, nk, nq),
        in_specs=[
            pl.BlockSpec((g, bq, d), q_map),
            pl.BlockSpec((g, bk, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((g, bk, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((g, bq, d), q_map),
            pl.BlockSpec((g, bq), q_map2),
            pl.BlockSpec((g, bq), q_map2),
        ],
        out_specs=[
            pl.BlockSpec((g, bk, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((g, bk, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sk, d), k.dtype),
            jax.ShapeDtypeStruct((bh, sk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((g, bk, d), jnp.float32),
            pltpu.VMEM((g, bk, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=mosaic.interpret(),
        name="tony_flash_bwd_dkv",
    )(q, k, v, do, lse2, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# Public op
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_attention_bhsd(q, k, v, causal, g, bq, bk, band, window):
    # q arrives pre-scaled by scale·log2e (:func:`_prep_flat`); the fold
    # sits OUTSIDE this custom_vjp boundary, so plain AD of the multiply
    # routes the scale factor into dq for free.
    o, _ = _flash_forward(q, k, v, causal=causal, g=g, bq=bq,
                          bk=bk, band=band, window=window)
    return o


def _flash_fwd_rule(q, k, v, causal, g, bq, bk, band, window):
    o, lse = _flash_forward(q, k, v, causal=causal, g=g, bq=bq,
                            bk=bk, band=band, window=window)
    # checkpoint_name on the kernel's outputs and operands: what
    # models/remat.LADDER may keep for the backward. With o/lse kept the
    # remat replay drops the forward kernel (DCE); with the flat operands
    # kept too — in the layout this kernel and its backward read, so
    # nothing is transposed on the way in or out of the saved stack — it
    # drops the projections behind them. Outside that policy the names
    # are inert.
    o = checkpoint_name(o, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    q, k, v = (checkpoint_name(q, "flash_q"), checkpoint_name(k, "flash_k"),
               checkpoint_name(v, "flash_v"))
    return o, (q, k, v, o, lse)


def _flash_bwd_rule(causal, g, bq, bk, band, window, residuals, grad):
    q, k, v, o, lse = residuals
    return _flash_backward(q, k, v, o, lse, grad, causal=causal,
                           g=g, bq=bq, bk=bk, band=band, window=window)


_flash_attention_bhsd.defvjp(_flash_fwd_rule, _flash_bwd_rule)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_attention_lse_bhsd(q, k, v, causal, g, bq, bk, band, window):
    """(o, lse) variant with lse as a DIFFERENTIATED output — what
    cross-chunk softmax merging (ring attention) needs: the merge weights
    are exp(lse_chunk - lse_total), so d(lse) must flow back into the
    score gradient (ds gains a +p·dlse term, folded into delta)."""
    return _flash_forward(q, k, v, causal=causal, g=g, bq=bq,
                          bk=bk, band=band, window=window)


def _flash_lse_fwd_rule(q, k, v, causal, g, bq, bk, band, window):
    o, lse = _flash_forward(q, k, v, causal=causal, g=g, bq=bq,
                            bk=bk, band=band, window=window)
    o = checkpoint_name(o, "flash_out")       # see _flash_fwd_rule
    lse = checkpoint_name(lse, "flash_lse")
    q, k, v = (checkpoint_name(q, "flash_q"), checkpoint_name(k, "flash_k"),
               checkpoint_name(v, "flash_v"))
    return (o, lse), (q, k, v, o, lse)


def _flash_lse_bwd_rule(causal, g, bq, bk, band, window, residuals,
                        grads):
    q, k, v, o, lse = residuals
    do, dlse = grads
    return _flash_backward(q, k, v, o, lse, do,
                           dlse.astype(jnp.float32),
                           causal=causal, g=g, bq=bq, bk=bk, band=band,
                           window=window)


_flash_attention_lse_bhsd.defvjp(_flash_lse_fwd_rule, _flash_lse_bwd_rule)


def _resolve_window(window, causal: bool, sq: int) -> int | None:
    """Validate/normalize the sliding-window size: None or >= sq means
    full causal attention (no window term compiled into the kernels);
    windowed non-causal attention is undefined here (the window is
    anchored on the causal diagonal)."""
    if window is None:
        return None
    if not causal:
        raise ValueError("sliding-window attention requires causal=True "
                         "(the window is anchored on the diagonal)")
    if window < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    return None if window >= sq else int(window)


def flash_attention(q, k, v, *, causal: bool = True,
                    scale: float | None = None,
                    block_q: int = 256, block_k: int = 1024,
                    block_h: int = 4, window: int | None = None):
    """Fused attention over [batch, seq, heads, head_dim] inputs.

    K/V may carry FEWER heads than Q (grouped-query attention, h_kv | h):
    they are consumed unexpanded — query head i reads kv head
    i // (h/h_kv), the same blocked layout as
    ``models.transformer.expand_kv`` — so GQA cuts the kernels' K/V HBM
    traffic by h/h_kv instead of materializing a repeated tensor.

    Block sizes are clamped to the input shapes (tiny test shapes).
    Defaults were swept on a v5e chip at LM shapes (seq 1k-8k, head_dim
    64): narrow q blocks × wide kv blocks (256×1024 forward, 128×512
    backward) won — the [block_q, block_k] f32 score intermediates are
    the VMEM budget, and shrinking block_q is what affords wide kv
    blocks, fewer grid steps, and less K/V re-fetch per output row.
    ``block_h`` is a hint for heads-per-grid-step, resolved by
    :func:`_pick_group` (a multiple of 8 dividing batch·heads, or all of
    them); grouping amortizes the fixed ~2-4 µs per-grid-step cost,
    bounded by VMEM — the binding term is the single compiled body's
    [block_q, block_k] f32 score intermediates times the g-scaled
    input/output/scratch blocks. Differentiable via the fused kv-major
    flash backward (two-pass kernels for long sequences).

    ``window`` enables SLIDING-WINDOW attention (causal only): each
    query attends its ``window`` most recent positions. Blocks entirely
    older than the window are triaged out exactly like above-diagonal
    blocks — skipped compute AND elided DMA (index maps clamp from
    below) — so fwd+bwd cost scales with ``seq × window``, not seq²;
    the boundary blocks take the masked body with the window bound
    folded into the same [bq, bk] compare the causal mask already pays.
    """
    if _sub_tile(q, block_q):
        return reference_attention(q, k, v, causal=causal, scale=scale,
                                   window=window)
    window = _resolve_window(window, causal, q.shape[1])
    qf, kf, vf, g, bq, bk, band = _prep_flat(q, k, v, scale, block_q,
                                             block_k, block_h)
    b, sq, h, d = q.shape
    hk = k.shape[2]
    o = _flash_attention_bhsd(qf, kf, vf, causal, g, bq, bk, band,
                              window)
    return (o[:b * hk].reshape(b, h, sq, d).transpose(0, 2, 1, 3))


def _sub_tile(q, block_q: int) -> bool:
    """True when the resolved q-block would be below the 128-lane tile on
    a REAL TPU — the 2-D [g, bq] lse layout makes bq the lane dim, and
    sub-128 lanes are an untested Mosaic regime (interpret mode — the CPU
    test path — keeps small blocks so the kernels stay bit-testable).
    Callers fall back to the dense arm, which has no tiling demands."""
    if mosaic.interpret():
        return False
    return min(block_q, q.shape[1]) % _LANES != 0


def _prep_flat(q, k, v, scale, block_q: int, block_k: int, block_h: int):
    """Shared entry prep: validate blocks, flatten [B,S,H,D] →
    [B·H_kv, (H/H_kv)·S, D] — under GQA each kv head's queries form
    contiguous row BANDS of length S sharing that head's K/V; plain MHA is
    the 1-band case — pad batch·kv-heads to a multiple of 8 (Mosaic needs
    the 2-D lse block's leading dim divisible by 8; zero heads give zero
    scores → uniform softmax over zero values → o = 0, finite lse, zero
    grads — callers slice the padding off), and resolve the head group.
    Q is scaled by ``scale · log2(e)`` HERE — one [*, d] multiply XLA
    fuses into the layout change — so the kernels' raw MXU dot is the
    base-2 score and no [bq, bk] scale multiply ever runs; the fold sits
    outside the custom_vjp, so AD routes the factor into dq.
    Returns the flat operands plus the band length S."""
    b, sq, h, d = q.shape
    sk, hk = k.shape[1], k.shape[2]
    if hk <= 0 or h % hk:
        raise ValueError(f"kv heads ({hk}) must divide query heads ({h})")
    rep = h // hk
    if sq % min(block_q, sq) or sk % min(block_k, sk):
        raise ValueError(f"seq lengths ({sq}, {sk}) must divide into blocks")
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    on_chip = not mosaic.interpret()
    if on_chip and bk == sk and bq < bk and sk % 256 == 0 and sk >= 1024:
        # single-kv-block grids at wide bk lose the revolving-buffer
        # VMEM reuse and blow the ~16 MB budget by a hair (measured:
        # [256, 1024] at nk=1 is 68 KB over); two kv blocks fit.
        bk = sk // 2
    if on_chip and bk <= 512 and bq > 128 and sq % 128 == 0:
        # short-kv regime (the wide-kv choice above didn't engage): the
        # v5e sweep at seq 1k picked 128-row q blocks with a DOUBLE head
        # group (2.00 ms vs 2.44 for 256×512 g8, vs 2.08 for the old
        # 512×512 g8) — the narrow stack buys the bigger g, and g is
        # what amortizes per-step cost when kv blocks can't widen.
        bq = 128
        block_h = max(block_h, 16)
    scale = (d ** -0.5) if scale is None else scale
    # fold in f32 and round ONCE: casting the constant itself to bf16
    # would bake a systematic ~0.2% temperature error into every logit
    q = (q.astype(jnp.float32) * (scale * _LOG2E)).astype(q.dtype)
    # [B,S,H,D] → [B,H,S,D] → group rep query heads per kv head into one
    # row dim (blocked head order: query head i ↔ kv head i // rep)
    qf = q.transpose(0, 2, 1, 3).reshape(b * hk, rep * sq, d)
    to_flat = lambda x: x.transpose(0, 2, 1, 3).reshape(b * hk, x.shape[1], d)
    kf, vf = to_flat(k), to_flat(v)
    bh = b * hk
    if bh % 8:
        pad = 8 * _cdiv(bh, 8) - bh
        qf, kf, vf = (jnp.pad(x, ((0, pad), (0, 0), (0, 0)))
                      for x in (qf, kf, vf))
    g = _pick_group(qf.shape[0], block_h)
    return qf, kf, vf, g, bq, bk, sq


def flash_attention_with_lse(q, k, v, *, causal: bool = True,
                             scale: float | None = None,
                             block_q: int = 256, block_k: int = 1024,
                             block_h: int = 4, window: int | None = None):
    """Like :func:`flash_attention` but also returns the row logsumexp
    ([batch, heads, seq], f32) as a DIFFERENTIATED output — the primitive
    for cross-chunk softmax merging (ring attention): merged
    results are ``o = Σ_c o_c · exp(lse_c - logaddexp_c lse_c)``, and the
    lse cotangent flows back into the score gradients. GQA K/V (fewer
    heads than Q) and sliding windows are supported exactly as in
    :func:`flash_attention`."""
    if _sub_tile(q, block_q):
        return _dense_with_lse(q, k, v, causal=causal, scale=scale,
                               window=window)
    window = _resolve_window(window, causal, q.shape[1])
    qf, kf, vf, g, bq, bk, band = _prep_flat(q, k, v, scale, block_q,
                                             block_k, block_h)
    b, sq, h, d = q.shape
    hk = k.shape[2]
    o, lse = _flash_attention_lse_bhsd(qf, kf, vf, causal, g, bq, bk,
                                       band, window)
    return (o[:b * hk].reshape(b, h, sq, d).transpose(0, 2, 1, 3),
            lse[:b * hk].reshape(b, h, sq))


def _dense_with_lse(q, k, v, *, causal: bool, scale: float | None,
                    window: int | None = None):
    """Dense (o, lse): the sub-tile fallback for the with-lse entry and
    the body of :func:`reference_attention` (plain jnp, so AD provides
    the dlse flow for free). GQA K/V (fewer heads than Q) is expanded —
    this is the oracle/CPU arm, where clarity beats the bandwidth saving
    the kernels exist for."""
    d = q.shape[-1]
    h, hk = q.shape[2], k.shape[2]
    window = _resolve_window(window, causal, q.shape[1])
    if h != hk:
        if hk <= 0 or h % hk:
            raise ValueError(f"kv heads ({hk}) must divide heads ({h})")
        k = jnp.repeat(k, h // hk, axis=2)
        v = jnp.repeat(v, h // hk, axis=2)
    scale = (d ** -0.5) if scale is None else scale
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        qpos = jnp.arange(q.shape[1])[:, None]
        kpos = jnp.arange(k.shape[1])[None, :]
        mask = qpos >= kpos
        if window is not None:
            mask = mask & (qpos - kpos < window)
        s = jnp.where(mask[None, None], s, _NEG_INF)
    lse = jax.scipy.special.logsumexp(s, axis=-1)
    p = jnp.exp(s - lse[..., None])
    o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)
    return o.astype(q.dtype), lse


def reference_attention(q, k, v, *, causal: bool = True,
                        scale: float | None = None,
                        window: int | None = None):
    """Dense O(S²) attention in plain jnp — the correctness oracle for
    the kernels and the fallback for odd shapes (GQA-aware, sliding-
    window-aware; see :func:`_dense_with_lse`, whose output this is)."""
    o, _ = _dense_with_lse(q, k, v, causal=causal, scale=scale,
                           window=window)
    return o


# ---------------------------------------------------------------------------
# Cached attention of a decode step: one query position a slot against the
# slot's OWN live blocks of the stacked cache
# ---------------------------------------------------------------------------

#: HLO instruction name of the launch (``%tony_cached_attn.N``): the name
#: a device trace is read by
CACHED_ATTN_NAME = "tony_cached_attn"

#: what a block of K (and one of V) should weigh: a grid step costs about
#: 0.35 us beside its DMA, so a block wants to be large, and a slot reads
#: its last block whole however few live rows it holds, so it wants to be
#: small (:func:`cached_attn_block`)
_CACHED_BLOCK_BYTES = 1 << 20


def cached_attn_block(rows: int, row_bytes: int) -> int:
    """Rows of one K/V block of :func:`cached_attention` over a buffer of
    ``rows`` rows of ``row_bytes`` stored bytes each: the power of two
    whose block lies nearest ``_CACHED_BLOCK_BYTES`` from below, at least
    128 rows, the whole buffer where that is smaller. A function of what
    the buffer is, not a setting."""
    block = 128
    while block * 2 * row_bytes <= _CACHED_BLOCK_BYTES:
        block *= 2
    return min(block, rows)


def live_blocks(xp, pos, rows: int, block: int, window: int | None,
                ring: bool):
    """(first, last) block of a ``rows``-row buffer that holds a row the
    mask admits for ONE query at position ``pos`` (any shape; ``xp`` is
    ``jax.numpy`` for the traced work list, ``numpy`` for the host's
    count of what a step visits). Linear: rows ``<= pos``, from the
    window's first row where there is a window. Ring (rows written
    modulo ``rows``): the rows written so far, ``0 .. min(pos, rows-1)``
    — every block once the ring has wrapped. A slot at position 0 has
    its one block: the step has just written the row at ``pos``."""
    last = xp.minimum(pos, rows - 1) // block
    if ring or window is None:
        return xp.zeros_like(last), last
    return xp.maximum(pos - window + 1, 0) // block, last


def cached_attn_work(q_pos, rows: int, block: int,
                     window: int | None = None, ring: bool = False):
    """The work list of :func:`cached_attention`, from the slots'
    positions ``q_pos`` [B] int32: ``(slot[t], block[t])`` for ``t <
    n_work`` — every slot's live blocks in turn, a slot's consecutive and
    ascending — beside each slot's first and last block and ``n_work =
    sum_b blocks_b`` (traced: the kernel's grid bound). Entries at or
    past ``n_work`` are never visited."""
    q_pos = q_pos.astype(jnp.int32)
    lo, hi = live_blocks(jnp, q_pos, rows, block, window, ring)
    cnt = hi - lo + 1
    ends = jnp.cumsum(cnt)
    b = q_pos.shape[0]
    t = jnp.arange(b * _cdiv(rows, block), dtype=jnp.int32)
    # slot[t] = slots whose blocks all lie before t; the same compare
    # gives where the slot's run began and its first block (no gather)
    past = (t[:, None] >= ends[None, :]).astype(jnp.int32)      # [W, B]
    slot = jnp.minimum(past.sum(axis=1), b - 1)
    step = jnp.concatenate([lo[1:] - lo[:-1], jnp.zeros((1,), jnp.int32)])
    blk = lo[0] + (past * step).sum(axis=1) + t - (past * cnt).sum(axis=1)
    return (slot, jnp.clip(blk, 0, _cdiv(rows, block) - 1), lo, hi,
            ends[-1])


def _cached_attn_kernel(layer, q_pos, slot, blk, lo, hi, qx_ref, k_ref,
                        *refs, scale: float, block: int, rows: int,
                        window: int | None, ring: bool,
                        heads: tuple[int, int] | None):
    del layer
    # a K/V pair, or a latent row that is its own value: no V operand,
    # the value product takes the K block that is already in VMEM
    v_ref, o_ref, ml_scr, acc_scr = refs if len(refs) == 4 else (None, *refs)
    t = pl.program_id(0)
    b, j = slot[t], blk[t]
    pos = q_pos[b]

    @pl.when(j == lo[b])
    def _first():
        ml_scr[:, 0:1] = jnp.full_like(ml_scr[:, 0:1], -jnp.inf)
        ml_scr[:, 1:2] = jnp.zeros_like(ml_scr[:, 1:2])
        acc_scr[...] = jnp.zeros_like(acc_scr)

    k = k_ref[...]                                      # [S, KV·hd]
    v = k[:, :acc_scr.shape[1]] if v_ref is None else v_ref[...]
    s = jax.lax.dot_general(
        qx_ref[...], k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale     # [N, S]
    r = j * block + lax.broadcasted_iota(jnp.int32, (1, block), 1)
    if ring:
        # (pos - r) mod rows, for r < rows, without a vector remainder
        off = pos % rows - r
        off = jnp.where(off < 0, off + rows, off)
        mask = off < jnp.minimum(window, pos + 1)
    else:
        mask = r <= pos
        if window is not None:
            mask = mask & (pos - r < window)
    if rows % block:
        # the buffer's last block runs past its rows: what lies there is
        # no row (whatever bits the block's buffer held), and 0 x NaN in
        # the value product would reach every output
        mask = mask & (r < rows)
        live = j * block + lax.broadcasted_iota(
            jnp.int32, (block, 1), 0) < rows
        v = jnp.where(live, v, jnp.zeros_like(v))
    s = jnp.where(mask, s, -jnp.inf)
    m_prev, l_prev = ml_scr[:, 0:1], ml_scr[:, 1:2]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    # a block the mask empties (a wrapped ring wider than its window)
    # keeps m = -inf: subtract 0 there, exp(-inf - 0) = 0 and not nan
    safe_m = jnp.where(m_new == -jnp.inf, 0.0, m_new)
    alpha = jnp.exp(m_prev - safe_m)
    p = jnp.exp(s - safe_m)
    ml_scr[:, 0:1] = m_new
    ml_scr[:, 1:2] = l_prev * alpha + p.sum(axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    @pl.when(j == hi[b])
    def _last():
        # l > 0: every query attends the row its step wrote
        o = acc_scr[...] / ml_scr[:, 1:2]
        if heads is None:
            o_ref[...] = o.astype(o_ref.dtype)
        else:
            # lane-aligned heads: each K/V head's queries keep their own
            # head's columns here, and the row never leaves as stored
            kv, d = heads
            g = o.shape[0] // kv
            for h in range(kv):
                o_ref[h * g:(h + 1) * g, :] = o[
                    h * g:(h + 1) * g, h * d:(h + 1) * d].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "scale", "head_dim", "block", "window", "ring", "interpret"))
def _cached_attention(qx, k_all, v_all, layer, q_pos, work, *, scale,
                      head_dim, block, window, ring, interpret):
    slot, blk, lo, hi, n_work = work
    b, n, f = qx.shape
    rows = k_all.shape[2]
    aligned = head_dim % _LANES == 0
    if v_all is None:
        # the row is its own value, its first head_dim columns: cut on a
        # lane tile inside the kernel, else the whole row leaves and is
        # cut here
        heads, caches = None, (k_all,)
        out_w = acc_w = head_dim if aligned else f
    else:
        kv, caches = f // head_dim, (k_all, v_all)
        heads = (kv, head_dim) if aligned else None
        out_w, acc_w = head_dim if aligned else f, f

    def kv_map(t, layer, q_pos, slot, blk, lo, hi):
        return layer[0], slot[t], blk[t], 0

    def slot_map(t, layer, q_pos, slot, blk, lo, hi):
        return slot[t], 0, 0

    out = pl.pallas_call(
        functools.partial(
            _cached_attn_kernel, scale=scale, block=block, rows=rows,
            window=window, ring=ring, heads=heads),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(n_work,),
            in_specs=[pl.BlockSpec((None, n, f), slot_map)] + [
                pl.BlockSpec((None, None, block, f), kv_map)
                for _ in caches],
            out_specs=pl.BlockSpec((None, n, out_w), slot_map),
            scratch_shapes=[pltpu.VMEM((n, _LANES), jnp.float32),
                            pltpu.VMEM((n, acc_w), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, n, out_w), qx.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=CACHED_ATTN_NAME,
    )(jnp.asarray(layer, jnp.int32).reshape(1), q_pos.astype(jnp.int32),
      slot, blk, lo, hi, qx, *caches)
    if aligned:
        return out
    if v_all is None:
        return out[..., :head_dim]
    # heads off the lane tiles (96): the kernel hands back whole stored
    # rows and each K/V head keeps its own columns here (a select, as
    # decode._head_values: no arithmetic)
    own = jnp.eye(kv, dtype=bool)[None, :, None, :, None]
    return jnp.where(own, out.reshape(b, kv, n // kv, kv, head_dim),
                     0).sum(axis=3).reshape(b, n, head_dim)


def cached_attention(qx, k_all, v_all, layer, q_pos, work, *, scale: float,
                     head_dim: int, block: int, window: int | None = None,
                     ring: bool = False, interpret: bool | None = None):
    """One query position a slot against the slot's live blocks of a
    stacked K/V cache: the decode step's cached read as ONE Mosaic kernel
    (``tony_cached_attn``) whose grid is the work list — (slot, block)
    pairs, ``n_work`` of them, a traced bound — so a slot's read follows
    its OWN length: blocks past its last live row are never visited (no
    DMA, no product, not an empty grid step), whatever the longest row of
    the batch holds.

    ``qx`` [B, H, KV·hd]: the step's queries laid block-diagonally over
    the K/V heads (``decode._spread_queries``), so a stored row is
    contracted whole, as stored; ``k_all`` / ``v_all`` [L, B, rows,
    KV·hd]: the STACKED buffers, whose ``layer`` (traced) goes into the
    index map — nothing slices or copies a layer; ``q_pos`` [B]; ``work``:
    :func:`cached_attn_work` at this ``block``. The mask follows what
    the buffer is: linear rows ``<= q_pos`` (within ``window`` of it
    where given), or a ``ring`` written modulo its rows, masked by each
    row's offset from the query. Operands in the cache's dtype, float32
    scores, softmax state and accumulator (VMEM scratch, reset at a
    slot's first block and written out at its last), ``p`` cast to the
    cache's dtype before the value product: the arithmetic of
    ``decode._cached_attention_blockwise``, which stays the CPU arm and
    the oracle. Returns [B, H, hd] in ``qx``'s dtype.

    The value operand follows what the buffer is, too. ``v_all`` None: a
    LATENT cache, whose stored row ``[c_kv; k_r; tail]`` is the one K/V
    head every query head shares and its own value — ``qx`` [B, H, row]
    is ``[q~; q_r; 0]`` a head (``decode._latent_cached_attention``),
    ``head_dim`` the value's width (``kv_rank``), and the value product
    takes the first ``head_dim`` columns of the K block that is already
    in VMEM: no second operand, no second DMA of the rows.

    Traced once a shape: every layer of a model calls the same jitted
    wrapper with its own ``layer``."""
    if interpret is None:
        interpret = mosaic.interpret()
    return _cached_attention(
        qx, k_all, v_all, layer, q_pos, work, scale=scale,
        head_dim=head_dim, block=block, window=window, ring=ring,
        interpret=interpret)
