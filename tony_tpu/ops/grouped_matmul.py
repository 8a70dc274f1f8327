"""Grouped matrix product: each row tile of the left operand meets the
weights of ONE group. The routed-expert product of a dropless expert
layer (:func:`tony_tpu.parallel.moe.held_experts_ffn`): rows sorted by
expert, each expert's rows padded to whole tiles, so the work — MXU tiles
and, what decides a decode step, the weight bytes read — follows the
assignments that landed, never ``tokens x experts``.

    out[t*tm:(t+1)*tm] = lhs[t*tm:(t+1)*tm] @ rhs[tile_group[t]]   t < num_tiles

``num_tiles`` is a traced value and the grid's first bound: tiles at or
past it are never visited — no product, no weight read, not even an
empty grid step (a decode step fills 6 of its 28 tiles) — and their
output rows are left unwritten: the caller masks them.

On the chip this is one Mosaic kernel named ``tony_moe_gmm`` (the name a
device trace is read by); off it (``mosaic.interpret()``) callers take
``jax.lax.ragged_dot`` over the same padded layout, and the kernel itself
runs in the Pallas interpreter only where a test asks for it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tony_tpu.ops import mosaic

#: HLO instruction name of the launch (``%tony_moe_gmm.N``)
KERNEL_NAME = "tony_moe_gmm"


def _block(n: int, want: int) -> int:
    """Largest power-of-two fraction of ``want`` (down to 128) that
    divides ``n``; the whole axis where none does (toy widths)."""
    b = want
    while b >= 128:
        if n % b == 0:
            return b
        b //= 2
    return n


def _kernel(tile_group, lhs, rhs, out, acc, *, nk):
    del tile_group
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)

    acc[...] += jnp.dot(lhs[...], rhs[...],
                        preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _():
        out[...] = acc[...].astype(out.dtype)


def grouped_matmul(lhs, rhs, tile_group, num_tiles, *, tm: int,
                   out_dtype=None, interpret: bool | None = None):
    """lhs [M, K] with M a multiple of ``tm``; rhs [G, K, N];
    ``tile_group`` [M // tm] int32, the group of each row tile;
    ``num_tiles`` int32 scalar, the tiles that hold rows: the grid's
    first bound, so the rest are never visited and their output rows stay
    unwritten. Returns [M, N] in ``out_dtype`` (lhs's by default),
    accumulated in float32."""
    m, kdim = lhs.shape
    n = rhs.shape[2]
    if m % tm:
        raise ValueError(f"rows {m} are not whole tiles of {tm}")
    out_dtype = out_dtype or lhs.dtype
    tk, tn = _block(kdim, 1024), _block(n, 1024)
    nn, nk = n // tn, kdim // tk
    return pl.pallas_call(
        functools.partial(_kernel, nk=nk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(num_tiles, nn, nk),
            in_specs=[
                pl.BlockSpec((tm, tk), lambda t, j, k, tg: (t, k)),
                pl.BlockSpec((None, tk, tn),
                             lambda t, j, k, tg: (tg[t], k, j))],
            out_specs=pl.BlockSpec((tm, tn), lambda t, j, k, tg: (t, j)),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=mosaic.interpret() if interpret is None else interpret,
        name=KERNEL_NAME,
    )(tile_group.astype(jnp.int32), lhs, rhs)
