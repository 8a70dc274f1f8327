"""The state-space recurrence of a selective scan (Mamba-2's SSD form;
Dao & Gu, arXiv:2405.21060), in the two shapes a serving path runs it.

Per head ``h`` with state ``S`` [P, N] (``P`` the head's width, ``N`` the
state's), step ``dt_t`` > 0, decay rate ``a`` < 0, input ``x_t`` [P], and
the token's ``B_t``, ``C_t`` [N] (shared by the heads of a group):

    S_t = exp(dt_t a) S_{t-1} + dt_t x_t (x) B_t        y_t = S_t C_t

- :func:`ssm_chunked`: a whole (padded) prompt, in chunks — matrix
  products inside a chunk, one carried state between chunks; ``dt = 0``
  is the identity step (decay 1, input 0), which is how a caller stops a
  row's state at its own length. Plain ``jnp``: einsums for the MXU.
- :func:`ssm_step`: ONE position for every slot against the STACKED state
  buffer, updated in place. On the chip one Mosaic kernel,
  ``tony_ssm_step``: the state block is read once, updated, written back
  through ``input_output_aliases`` and ``y`` leaves from the float32
  state before it is rounded — no second pass and no state-sized copy.
  Off it (``mosaic.interpret()``) :func:`ssm_step_reference`, the tests'
  oracle, as ``decode._cached_attention_blockwise`` is the cached read's.

THE STORED LAYOUT, ``[layers, slots, N, H·P]``: the state's N on the
sublanes and heads x head width MERGED on the lanes (as K/V heads are
stored merged, ``decode.init_kv_cache``). A step's decay and input are
per (head, column) — rows of lanes, broadcast over sublanes for free —
``B`` and ``C`` are per state row, and ``y = S C`` reduces over N: adds
of whole registers and one sublane fold a column, where N on the lanes
would make every one of a slot's 4,096 outputs a cross-lane reduction.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tony_tpu.ops import mosaic

#: HLO instruction name of the launch (``%tony_ssm_step.N``)
SSM_STEP_NAME = "tony_ssm_step"
#: bytes of one state block in VMEM: in and out, double-buffered, beside
#: the float32 working copy — 1 MiB is a whole slot of 128 x 4,096 bf16
_BLOCK_BYTES = 1 << 20
_VMEM_LIMIT = 48 << 20


def ssm_chunked(x, dt, a, b, c, chunk: int):
    """The recurrence over a sequence. x [B, S, H, P]; dt [B, S, H]
    float32 (0 = the identity step); a [H] float32; b, c [B, S, G, N];
    ``chunk``: positions a chunk (``Q``; a shorter sequence is one
    chunk, a ragged tail is padded with identity steps). With ``L_t`` the
    running sum of ``dt a`` inside a chunk:

        y_t   = sum_{s<=t} exp(L_t - L_s) (C_t . B_s) dt_s x_s
                + exp(L_t) S_prev C_t
        S_end = exp(L_end) S_prev + sum_s exp(L_end - L_s) dt_s x_s (x) B_s

    Decays and the carried state in float32; the products take x's dtype
    in and accumulate in float32. Returns (y [B, S, H, P] float32, the
    final state [B, N, H·P] float32 in the stored layout)."""
    bsz, s, h, p = x.shape
    g, n = b.shape[2:]
    q = min(chunk, s)
    pad = -s % q
    if pad:
        x, dt, b, c = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                       for t in (x, dt, b, c))
    nc, k = (s + pad) // q, h // g
    dt = dt.reshape(bsz, nc, q, g, k)
    lam = jnp.cumsum(dt * a.reshape(g, k), axis=2)           # [B,C,Q,G,K]
    dtx = (dt[..., None] * x.reshape(bsz, nc, q, g, k, p)
           .astype(jnp.float32)).astype(x.dtype)
    b = b.reshape(bsz, nc, q, g, n)
    c = c.reshape(bsz, nc, q, g, n)
    f32 = jnp.float32

    # inside a chunk: the decayed, causal C.B scores against the inputs
    diff = lam[:, :, :, None] - lam[:, :, None, :]           # [B,C,T,S,G,K]
    causal = jnp.tril(jnp.ones((q, q), bool))[None, None, :, :, None, None]
    decay = jnp.exp(jnp.where(causal, diff, -jnp.inf))
    cb = jnp.einsum("bctgn,bcsgn->bctsg", c, b, preferred_element_type=f32)
    m = (cb[..., None] * decay).astype(x.dtype)
    y = jnp.einsum("bctsgk,bcsgkp->bctgkp", m, dtx,
                   preferred_element_type=f32)

    # what each chunk adds to the state, and the state each chunk starts
    # from: the one sequential part, nc steps of a [N, H·P] carry
    to_end = jnp.exp(lam[:, :, -1:] - lam)                   # [B,C,Q,G,K]
    adds = jnp.einsum("bcsgn,bcsgkp->bcngkp", b,
                      (to_end[..., None] * dtx).astype(x.dtype),
                      preferred_element_type=f32)
    whole = jnp.exp(lam[:, :, -1])                           # [B,C,G,K]

    def carry(state, xs):
        add, w = xs
        return state * w[:, None, :, :, None] + add, state

    last, starts = jax.lax.scan(
        carry, jnp.zeros((bsz, n, g, k, p), f32),
        (jnp.moveaxis(adds, 1, 0), jnp.moveaxis(whole, 1, 0)))
    starts = jnp.moveaxis(starts, 0, 1)                      # [B,C,N,G,K,P]
    y = y + jnp.exp(lam)[..., None] * jnp.einsum(
        "bctgn,bcngkp->bctgkp", c.astype(f32), starts)
    return (y.reshape(bsz, nc * q, h, p)[:, :s],
            last.reshape(bsz, n, h * p))


def ssm_step_reference(state_all, layer, decay, inp, b, c):
    """One step for every slot against layer ``layer`` of the stacked
    state ``state_all`` [L, B, N, H·P], in ``jnp``: ``decay`` and ``inp``
    [B, H·P] float32 (``exp(dt a)`` and ``dt x``, a head's value over its
    columns), ``b`` and ``c`` [B, G, N] float32. The state is loaded,
    updated and read in float32 and rounded ONCE, at the store. Returns
    (y [B, H·P] float32, state_all)."""
    g = b.shape[1]
    s = state_all[layer].astype(jnp.float32)                 # [B, N, HP]
    cols = s.shape[2] // g
    b, c = (jnp.repeat(t.transpose(0, 2, 1), cols, axis=2) for t in (b, c))
    s = s * decay[:, None, :] + b * inp[:, None, :]
    return (jnp.sum(s * c, axis=1),
            state_all.at[layer].set(s.astype(state_all.dtype)))


def _ssm_step_kernel(layer, decay, inp, bc, s_in, y, s_out):
    del layer
    rows = bc[...]                                           # [8, N]
    n = rows.shape[1]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))

    def column(r):
        # a row of lanes as a column of sublanes: [1, N] -> [N, 1]
        return jnp.sum(jnp.where(eye, rows[r:r + 1], 0.0), axis=1,
                       keepdims=True)

    s = s_in[...].astype(jnp.float32) * decay[...] + column(0) * inp[...]
    s_out[...] = s.astype(s_out.dtype)
    y[...] = jnp.sum(s * column(1), axis=0, keepdims=True)


def ssm_step_block(n: int, cols: int, groups: int, itemsize: int) -> int:
    """Columns (of a slot's ``cols`` = H·P) one grid step holds: the most
    whole 128-lane tiles that divide a group's columns and keep the
    [N, block] state block within ``_BLOCK_BYTES``; the whole group where
    none does (toy widths)."""
    per = cols // groups
    best = 0
    for blk in range(128, per + 1, 128):
        if per % blk == 0 and n * blk * itemsize <= _BLOCK_BYTES:
            best = blk
    return best or per


@functools.partial(jax.jit, static_argnames=("interpret",))
def _ssm_step(state_all, layer, decay, inp, bc, *, interpret):
    _, slots, n, cols = state_all.shape
    groups = bc.shape[1]
    blk = ssm_step_block(n, cols, groups, state_all.dtype.itemsize)
    per = cols // groups // blk             # blocks a group

    def vec_map(i, j, layer):
        return i, 0, j

    def state_map(i, j, layer):
        return layer[0], i, 0, j

    y, state_all = pl.pallas_call(
        _ssm_step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(slots, cols // blk),
            in_specs=[
                pl.BlockSpec((None, 1, blk), vec_map),
                pl.BlockSpec((None, 1, blk), vec_map),
                pl.BlockSpec((None, None, 8, n),
                             lambda i, j, layer: (i, j // per, 0, 0)),
                pl.BlockSpec((None, None, n, blk), state_map)],
            out_specs=[pl.BlockSpec((None, 1, blk), vec_map),
                       pl.BlockSpec((None, None, n, blk), state_map)]),
        out_shape=[jax.ShapeDtypeStruct((slots, 1, cols), jnp.float32),
                   jax.ShapeDtypeStruct(state_all.shape, state_all.dtype)],
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=SSM_STEP_NAME,
    )(jnp.asarray(layer, jnp.int32).reshape(1), decay[:, None, :],
      inp[:, None, :], bc, state_all)
    return y[:, 0], state_all


def ssm_step(state_all, layer, decay, inp, b, c, *,
             interpret: bool | None = None):
    """:func:`ssm_step_reference` as ONE Mosaic kernel, ``tony_ssm_step``:
    a grid over (slot, block of columns), each step one [N, block] block
    of layer ``layer`` (traced: it goes into the index map, nothing
    slices a layer) of the STACKED state — read once, updated in float32,
    written back in place (``input_output_aliases``: the blocks of the
    other layers are never touched), ``y`` taken from the float32 state
    before the store rounds it. ``b`` and ``c`` travel as two rows of an
    [8, N] tile a (slot, group) and are turned to columns in the kernel;
    a block never crosses a group. Traced once a shape: every layer calls
    the same jitted wrapper with its own ``layer``."""
    if interpret is None:
        interpret = mosaic.interpret()
    pad = jnp.zeros(b.shape[:2] + (6, b.shape[2]), jnp.float32)
    bc = jnp.concatenate([b[:, :, None], c[:, :, None], pad], axis=2)
    return _ssm_step(state_all, layer, decay, inp, bc, interpret=interpret)
