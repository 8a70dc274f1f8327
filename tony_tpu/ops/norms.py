"""Fused normalization ops: RMSNorm / LayerNorm.

Bandwidth-bound row reductions: the Pallas forward keeps each row tile in
VMEM for exactly one HBM read and one write, with f32 accumulation (the
bf16 params/activations path the models use). Backwards are plain-jnp
custom-VJP rules — elementwise math XLA fuses into the surrounding backward
graph anyway, so a hand kernel would only add dispatch overhead.

On non-TPU backends the kernels run in interpret mode (tests) — callers on
the hot CPU path should use the ``*_reference`` versions.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tony_tpu.ops import mosaic


def _rms_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    rms = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    o_ref[...] = (x * rms * w_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def _ln_kernel(x_ref, w_ref, b_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mu
    inv = jax.lax.rsqrt(jnp.mean(xc * xc, axis=-1, keepdims=True) + eps)
    o_ref[...] = (xc * inv * w_ref[...].astype(jnp.float32)
                  + b_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def _row_call(kernel, x, *params, block_rows: int = 256):
    """Run a row-wise kernel over x reshaped to [rows, d]."""
    shape = x.shape
    d = shape[-1]
    rows = x.size // d
    x2 = x.reshape(rows, d)
    br = min(block_rows, rows)
    if rows % br:
        br = 1  # degenerate fallback for odd row counts
    out = pl.pallas_call(
        kernel,
        grid=(rows // br,),
        in_specs=[pl.BlockSpec((br, d), lambda i: (i, 0))] +
                 [pl.BlockSpec((d,), lambda i: (0,))] * len(params),
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype),
        interpret=mosaic.interpret(),
    )(x2, *params)
    return out.reshape(shape)


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------

def rms_norm_reference(x, w, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    rms = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * rms * w.astype(jnp.float32)).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def rms_norm(x, w, eps: float = 1e-6):
    """RMSNorm over the last dim (fused on TPU)."""
    return _row_call(functools.partial(_rms_kernel, eps=eps), x, w)


def _rms_fwd(x, w, eps):
    return rms_norm(x, w, eps), (x, w)


def _rms_bwd(eps, res, g):
    x, w = res
    xf = x.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    wf = w.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    xhat = xf * inv
    gw = gf * wf
    dx = inv * (gw - xhat * jnp.mean(gw * xhat, axis=-1, keepdims=True))
    dw = jnp.sum(gf * xhat, axis=tuple(range(x.ndim - 1)))
    return dx.astype(x.dtype), dw.astype(w.dtype)


rms_norm.defvjp(_rms_fwd, _rms_bwd)


# ---------------------------------------------------------------------------
# LayerNorm
# ---------------------------------------------------------------------------

def layer_norm_reference(x, w, b=None, eps: float = 1e-6):
    """LayerNorm over the last dim, statistics in float32. ``b=None``:
    the weight-only form (mean subtracted, no bias)."""
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    xc = xf - mu
    inv = jax.lax.rsqrt(jnp.mean(xc * xc, axis=-1, keepdims=True) + eps)
    y = xc * inv * w.astype(jnp.float32)
    if b is not None:
        y = y + b.astype(jnp.float32)
    return y.astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def layer_norm(x, w, b, eps: float = 1e-6):
    """LayerNorm over the last dim (fused on TPU)."""
    return _row_call(functools.partial(_ln_kernel, eps=eps), x, w, b)


def _ln_fwd(x, w, b, eps):
    return layer_norm(x, w, b, eps), (x, w)


def _ln_bwd(eps, res, g):
    x, w = res
    xf = x.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    wf = w.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    xc = xf - mu
    inv = jax.lax.rsqrt(jnp.mean(xc * xc, axis=-1, keepdims=True) + eps)
    xhat = xc * inv
    gw = gf * wf
    dx = inv * (gw - jnp.mean(gw, axis=-1, keepdims=True)
                - xhat * jnp.mean(gw * xhat, axis=-1, keepdims=True))
    reduce_axes = tuple(range(x.ndim - 1))
    dw = jnp.sum(gf * xhat, axis=reduce_axes)
    db = jnp.sum(gf, axis=reduce_axes)
    return dx.astype(x.dtype), dw.astype(w.dtype), db.astype(gf.dtype)


layer_norm.defvjp(_ln_fwd, _ln_bwd)
