"""Fused AdamW: the whole optimizer update in one HBM pass per leaf.

The optax chain (clip_by_global_norm → scale_by_adam → add_decayed_weights →
scale_by_learning_rate → apply_updates) lowers to several elementwise HLOs
whose fusion boundaries XLA does not always collapse — measured ~2 ms/step
at 60 M params on one v5e (docs/performance.md "Known headroom"). This
kernel reads (param, grad, m, v) once, does all the moment/bias-correction/
decay math in VMEM at f32, and writes (param, m, v) once — the HBM-bandwidth
floor for the update. Aliasing (param, m, v) in→out keeps it allocation-free
under donation.

Numerics: moments are stored f32 (optax inherits the grads' dtype, so bf16
params would otherwise get bf16 moments — a precision regression this path
fixes for free); params round to their storage dtype once per step, exactly
like optax.apply_updates. The global-norm clip stays an XLA reduction over
the grads (a cross-leaf global value cannot fuse into a per-leaf kernel) —
its result enters the kernel as a scalar scale.

The reference delegates optimization entirely to user TF/PyTorch code; this
is part of the compute layer the TPU build owns (SURVEY.md §2.3).
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tony_tpu.ops import mosaic

_LANES = 128
_BLOCK_ROWS = 2048          # (2048, 128) f32×5 + bf16×2 ≈ 5.5 MB of VMEM


def _adamw_kernel(sc_ref, p_ref, g_ref, m_ref, v_ref,
                  po_ref, mo_ref, vo_ref):
    """One block of the fused update. sc: SMEM scalars
    [lr, b1, b2, eps, wd, 1/bias_corr1, 1/bias_corr2, clip_scale]."""
    lr, b1, b2, eps = sc_ref[0], sc_ref[1], sc_ref[2], sc_ref[3]
    wd, inv_bc1, inv_bc2, clip = sc_ref[4], sc_ref[5], sc_ref[6], sc_ref[7]
    p = p_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32) * clip
    m = b1 * m_ref[...].astype(jnp.float32) + (1.0 - b1) * g
    v = b2 * v_ref[...].astype(jnp.float32) + (1.0 - b2) * g * g
    m_hat = m * inv_bc1
    v_hat = v * inv_bc2
    update = m_hat / (jnp.sqrt(v_hat) + eps) + wd * p
    po_ref[...] = (p - lr * update).astype(po_ref.dtype)
    mo_ref[...] = m.astype(mo_ref.dtype)
    vo_ref[...] = v.astype(vo_ref.dtype)


def _leaf_view(shape: tuple[int, ...]) -> tuple[int, ...] | None:
    """A relayout-free 2-D/3-D view for the kernel, or None for the XLA
    fallback. TPU arrays are tiled on their last two dims, so any reshape
    that regroups them forces a physical copy — which costs more than the
    kernel saves. The view therefore always PRESERVES the trailing dims:
    [..., minor%128==0] collapses to (rows, minor); [..., sub, minor<128]
    (the d_model→heads×head_dim projection leaves) keeps (rows, sub,
    minor) so the kernel reads the array's native half-lane tiles."""
    if len(shape) >= 2 and shape[-1] % _LANES == 0:
        return (-1, shape[-1])
    if (len(shape) >= 3 and shape[-1] < _LANES
            and shape[-1] % 8 == 0 and shape[-2] % 8 == 0):
        return (-1, shape[-2], shape[-1])
    return None


def _view_rows(shape: tuple[int, ...]):
    """(view, tail, rows) for a leaf — the single source of the blocking
    geometry, shared by the kernel gate and the kernel call."""
    view = _leaf_view(shape)
    if view is None:
        return None, (), 0
    tail = shape[len(shape) - len(view) + 1:]
    rows = _prod(shape) // _prod(tail)
    return view, tail, rows


_VMEM_BUDGET = 4 << 20       # per-operand-set block bytes (7 arrays ≈ 18B/el)


def _fused_leaf_update(p: jax.Array, g: jax.Array, m: jax.Array,
                       v: jax.Array, scalars: jax.Array):
    """Apply the kernel to one leaf via its relayout-free view."""
    view, tail, rows = _view_rows(p.shape)   # rows%8==0: caller-gated
    per_row = _prod(tail)
    # VMEM sizing uses the PADDED row: a sub-128 minor dim occupies full
    # 128-lane tiles in VMEM, so (8, 64) tails cost 2× their logical bytes
    padded_row = (per_row // tail[-1]) * (-(-tail[-1] // _LANES) * _LANES)
    br = max(8, min(_BLOCK_ROWS, _VMEM_BUDGET // (padded_row * 18)))
    br = min(br - br % 8, rows)
    p2, g2, m2, v2 = (x.reshape(view) for x in (p, g, m, v))
    nd = len(tail) + 1
    block = (br,) + tail
    idx = (lambda i: (i, 0)) if nd == 2 else (lambda i: (i, 0, 0))
    spec = pl.BlockSpec(block, idx)
    out = pl.pallas_call(
        _adamw_kernel,
        grid=(pl.cdiv(rows, br),),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  spec, spec, spec, spec],
        out_specs=[pl.BlockSpec(block, idx, memory_space=pltpu.VMEM)] * 3,
        out_shape=[jax.ShapeDtypeStruct(p2.shape, p.dtype),
                   jax.ShapeDtypeStruct(m2.shape, m.dtype),
                   jax.ShapeDtypeStruct(v2.shape, v.dtype)],
        # alias p/m/v through: the update is in-place under donation
        input_output_aliases={1: 0, 3: 1, 4: 2},
        interpret=mosaic.interpret(),
    )(scalars, p2, g2, m2, v2)
    new_p, new_m, new_v = out
    return (new_p.reshape(p.shape), new_m.reshape(p.shape),
            new_v.reshape(p.shape))


def _xla_leaf_update(p, g, m, v, scalars):
    """Plain-XLA fallback for leaves whose size doesn't tile 128 lanes
    (rare: a stray odd-width norm). Same math, same dtypes."""
    lr, b1, b2, eps, wd, inv_bc1, inv_bc2, clip = [scalars[i]
                                                   for i in range(8)]
    g = g.astype(jnp.float32) * clip
    new_m = b1 * m.astype(jnp.float32) + (1.0 - b1) * g
    new_v = b2 * v.astype(jnp.float32) + (1.0 - b2) * g * g
    update = (new_m * inv_bc1) / (jnp.sqrt(new_v * inv_bc2) + eps) \
        + wd * p.astype(jnp.float32)
    return ((p.astype(jnp.float32) - lr * update).astype(p.dtype),
            new_m.astype(m.dtype), new_v.astype(v.dtype))


class FusedAdamWState(NamedTuple):
    count: jax.Array          # int32 step counter
    mu: Any                   # f32 first-moment pytree
    nu: Any                   # f32 second-moment pytree


class FusedAdamW:
    """Fused clip-by-global-norm + AdamW + schedule.

    Matches ``optax.chain(optax.clip_by_global_norm(clip_norm),
    optax.adamw(lr, b1, b2, eps, weight_decay, mu_dtype=f32))`` to fp
    tolerance (tests/test_ops.py parity test), executed as one kernel pass
    per leaf. Consumed by ``make_train_step`` through the ``fused_apply``
    protocol: ``(grads, state, params) -> (new_params, new_state, gnorm)``
    — the params update happens inside, so no separate apply_updates pass.
    """

    def __init__(self, learning_rate: float | Callable[[jax.Array], Any],
                 b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8,
                 weight_decay: float = 1e-4, clip_norm: float | None = 1.0,
                 mu_dtype: Any = jnp.float32):
        self._lr = learning_rate
        self.b1, self.b2, self.eps = b1, b2, eps
        self.weight_decay = weight_decay
        self.clip_norm = clip_norm
        #: moment storage dtype. f32 (default) is the safe choice; bf16
        #: halves the optimizer-state HBM traffic (~0.5 GB/step at 66 M
        #: params) and matches what optax gives bf16 models implicitly.
        self.mu_dtype = mu_dtype

    def init(self, params: Any) -> FusedAdamWState:
        zeros = jax.tree.map(
            lambda p: jnp.zeros(p.shape, self.mu_dtype), params)
        return FusedAdamWState(count=jnp.zeros((), jnp.int32),
                               mu=zeros,
                               nu=jax.tree.map(jnp.copy, zeros))

    def fused_apply(self, grads: Any, state: FusedAdamWState, params: Any):
        count = state.count + 1
        cf = count.astype(jnp.float32)
        # schedules see the PRE-increment count, matching optax's
        # scale_by_schedule (first step evaluates the schedule at 0)
        lr = (self._lr(state.count) if callable(self._lr) else self._lr)
        gnorm = _global_norm(grads)
        if self.clip_norm is not None:
            clip = jnp.where(gnorm < self.clip_norm, 1.0,
                             self.clip_norm / jnp.maximum(gnorm, 1e-20))
        else:
            clip = jnp.ones((), jnp.float32)
        scalars = jnp.stack([
            jnp.asarray(lr, jnp.float32),
            jnp.float32(self.b1), jnp.float32(self.b2),
            jnp.float32(self.eps), jnp.float32(self.weight_decay),
            1.0 / (1.0 - jnp.float32(self.b1) ** cf),
            1.0 / (1.0 - jnp.float32(self.b2) ** cf),
            clip.astype(jnp.float32),
        ])

        leaves_p, tdef = jax.tree.flatten(params)
        leaves_g = tdef.flatten_up_to(grads)
        leaves_m = tdef.flatten_up_to(state.mu)
        leaves_v = tdef.flatten_up_to(state.nu)
        new_p, new_m, new_v = [], [], []
        for p, g, m, v in zip(leaves_p, leaves_g, leaves_m, leaves_v):
            view, _, rows = _view_rows(p.shape)
            # small leaves (norms) go to XLA — it fuses them with the
            # global-norm reduction for free, and a kernel dispatch costs
            # more than their entire update
            use_kernel = (view is not None and p.size >= (1 << 16)
                          and rows % 8 == 0)
            fn = _fused_leaf_update if use_kernel else _xla_leaf_update
            np_, nm, nv = fn(p, g, m, v, scalars)
            new_p.append(np_)
            new_m.append(nm)
            new_v.append(nv)
        return (tdef.unflatten(new_p),
                FusedAdamWState(count=count, mu=tdef.unflatten(new_m),
                                nu=tdef.unflatten(new_v)),
                gnorm)


def _prod(dims) -> int:
    out = 1
    for d in dims:
        out *= d
    return out


def _global_norm(tree: Any) -> jax.Array:
    leaves = jax.tree.leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in leaves))
