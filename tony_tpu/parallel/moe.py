"""Expert parallelism: gshard-style mixture-of-experts dispatch.

Green-field for the TPU build (SURVEY.md §2.3: EP absent from the reference).
TPU-first design: dispatch/combine are *dense einsums* against a capacity-
bounded one-hot routing tensor, with experts sharded over the mesh's ``ep``
axis via logical-axis constraints — XLA then lowers the resharding to
all_to_all collectives over ICI. No per-token gather/scatter loops (which
would defeat MXU tiling and force dynamic shapes).

Static shapes everywhere: each expert processes a fixed ``capacity`` of
tokens; overflow tokens are dropped (their combine weight is zero), the
standard gshard/switch trade for compile-time-known shapes.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from tony_tpu.parallel.sharding import constrain


class MoEMetrics(NamedTuple):
    """Router health numbers (load-balance aux loss per Switch-Transformer)."""
    aux_loss: jax.Array        # scalar: E * sum(frac_tokens * frac_probs)
    dropped_fraction: jax.Array


def router_dispatch(logits: jax.Array, num_experts: int, *, top_k: int = 2,
                    capacity: int):
    """Top-k routing with capacity. logits: [B, S, E].

    Returns (dispatch [B,S,E,C] one-hot, combine [B,S,E,C] weights, metrics).
    """
    b, s, e = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gate_vals, gate_idx = lax.top_k(probs, top_k)          # [B,S,k]
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)

    onehot = jax.nn.one_hot(gate_idx, e, dtype=jnp.float32)  # [B,S,k,E]
    # token-major priority: earlier sequence positions win capacity slots
    oh = onehot.reshape(b, s * top_k, e)
    pos = jnp.cumsum(oh, axis=1) - oh                       # slot within expert
    keep = (pos < capacity).astype(jnp.float32) * oh        # [B,S*k,E]
    pos_oh = jax.nn.one_hot(pos.astype(jnp.int32), capacity,
                            dtype=jnp.float32) * keep[..., None]
    combine = pos_oh * gate_vals.reshape(b, s * top_k, 1, 1)
    dispatch = pos_oh.reshape(b, s, top_k, e, capacity).sum(2)
    combine = combine.reshape(b, s, top_k, e, capacity).sum(2)

    # Switch-Transformer load-balance loss: E * Σ_e f_e * p_e
    frac_tokens = onehot[:, :, 0, :].mean(axis=(0, 1))      # top-1 assignment
    frac_probs = probs.mean(axis=(0, 1))
    aux = e * jnp.sum(frac_tokens * frac_probs)
    routed = keep.sum() / jnp.maximum(oh.sum(), 1.0)
    return dispatch, combine, MoEMetrics(aux, 1.0 - routed)


def default_capacity(tokens_per_group: int, num_experts: int, top_k: int,
                     capacity_factor: float = 1.25) -> int:
    c = int(math.ceil(top_k * tokens_per_group / num_experts
                      * capacity_factor))
    return max(c, 1)


def moe_ffn(x: jax.Array, router_w: jax.Array, w_in: jax.Array,
            w_out: jax.Array, *, top_k: int = 2,
            capacity_factor: float = 1.25,
            activation=jax.nn.gelu) -> tuple[jax.Array, MoEMetrics]:
    """Mixture-of-experts feed-forward block.

    x: [B, S, D]; router_w: [D, E]; w_in: [E, D, H]; w_out: [E, H, D].
    Experts carry logical axis "expert" → mesh ``ep``; the two big einsums
    below keep data in [E, B, C, D] layout so the ep resharding is a single
    all_to_all on entry and exit.
    """
    b, s, d = x.shape
    e = router_w.shape[1]
    capacity = default_capacity(s, e, top_k, capacity_factor)

    logits = jnp.einsum("bsd,de->bse", x, router_w,
                        preferred_element_type=jnp.float32)
    dispatch, combine, metrics = router_dispatch(
        logits, e, top_k=top_k, capacity=capacity)

    dispatch = dispatch.astype(x.dtype)
    combine = combine.astype(x.dtype)
    # [B,S,E,C] × [B,S,D] → [E,B,C,D]: the all_to_all boundary (ep enters)
    expert_in = jnp.einsum("bsec,bsd->ebcd", dispatch, x)
    expert_in = constrain(expert_in, ("expert", "batch", None, "embed"))
    h = activation(jnp.einsum("ebcd,edh->ebch", expert_in, w_in))
    h = constrain(h, ("expert", "batch", None, "mlp"))
    expert_out = jnp.einsum("ebch,ehd->ebcd", h, w_out)
    # [B,S,E,C] × [E,B,C,D] → [B,S,D]: ep exits (second all_to_all)
    out = jnp.einsum("bsec,ebcd->bsd", combine, expert_out)
    out = constrain(out, ("batch", "seq", "embed"))
    return out, metrics


def moe_ffn_manual(x: jax.Array, router_w: jax.Array, w_in_local: jax.Array,
                   w_out_local: jax.Array, *, axis_name: str,
                   num_experts: int, top_k: int = 2,
                   capacity_factor: float = 1.25,
                   activation=jax.nn.gelu) -> tuple[jax.Array, MoEMetrics]:
    """Expert-parallel MoE with EXPLICIT collectives — the arm for Manual
    (``shard_map``) contexts, where :func:`moe_ffn`'s sharding constraints
    can't reach the ``ep`` axis. This is what lets MoE compose with
    pipeline parallelism: the GPipe stage body runs under shard_map, so
    the dispatch must speak the bound axis name directly.

    Layout: activations are REPLICATED along ``axis_name`` (the pipeline
    shards its microbatch over dp only); each rank holds
    ``num_experts / ep`` experts' weights (``w_in_local`` leads with the
    local expert count). Routing is computed identically on every rank
    from the replicated activations, each rank slices its experts'
    dispatch/combine columns, runs its experts, and the partial combines
    ``psum`` into the full output — one collective per block. Gradients
    flow through slice + psum by plain AD (the transposed collective is
    the identity broadcast).
    """
    b, s, d = x.shape
    e = num_experts
    e_loc = w_in_local.shape[0]
    capacity = default_capacity(s, e, top_k, capacity_factor)

    logits = jnp.einsum("bsd,de->bse", x, router_w,
                        preferred_element_type=jnp.float32)
    dispatch, combine, metrics = router_dispatch(
        logits, e, top_k=top_k, capacity=capacity)
    dispatch = dispatch.astype(x.dtype)
    combine = combine.astype(x.dtype)
    rank = lax.axis_index(axis_name)
    d_loc = lax.dynamic_slice_in_dim(dispatch, rank * e_loc, e_loc, axis=2)
    c_loc = lax.dynamic_slice_in_dim(combine, rank * e_loc, e_loc, axis=2)
    expert_in = jnp.einsum("bsec,bsd->ebcd", d_loc, x)
    h = activation(jnp.einsum("ebcd,edh->ebch", expert_in, w_in_local))
    expert_out = jnp.einsum("ebch,ehd->ebcd", h, w_out_local)
    out = lax.psum(jnp.einsum("bsec,ebcd->bsd", c_loc, expert_out),
                   axis_name)
    return out, metrics


# ---------------------------------------------------------------------------
# Dropless routing over one chip's share of the experts
# ---------------------------------------------------------------------------

class HeldExperts(NamedTuple):
    """Which of a layer's routed experts live here: ``[first, first +
    held)`` of ``total``. The router keeps its ``total`` outputs, and
    ``n_zero`` more for experts that compute nothing: picks in ``[total,
    total + n_zero)``."""
    first: int
    held: int
    total: int
    n_zero: int = 0


def sigmoid_route(h: jax.Array, router_w: jax.Array, bias: jax.Array,
                  top_k: int, scale: float):
    """Sigmoid routing with a selection bias, over ALL experts.
    h: [T, D]; router_w: [D, E] and bias: [E], both float32. Returns
    (picks [T, k] int32, weights [T, k] float32): the ``top_k`` largest of
    ``z + bias`` with ``z = sigmoid(h @ router_w)`` — the bias steers the
    pick and nothing else — weighted by ``z`` normalised over the picked
    and scaled. The product and the scores are float32: a pick is a
    comparison of near-equal scores, and bf16 would flip it."""
    z = jax.nn.sigmoid(jnp.einsum(
        "td,de->te", h.astype(jnp.float32), router_w.astype(jnp.float32),
        precision=lax.Precision.HIGHEST))
    _, picks = lax.top_k(z + bias.astype(jnp.float32), top_k)
    w = jnp.take_along_axis(z, picks, axis=-1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * scale
    return picks.astype(jnp.int32), w


def softmax_route(h: jax.Array, router_w: jax.Array, bias: jax.Array,
                  top_k: int, scale: float):
    """Softmax routing with a selection bias, over ALL the router's
    outputs (the routed experts, then the zero ones). h: [T, D];
    router_w: [D, E] and bias: [E], both float32. Returns (picks [T, k]
    int32, weights [T, k] float32): the ``top_k`` largest of ``z + bias``
    with ``z = softmax(h @ router_w)`` over all E — the bias steers the
    pick and nothing else — weighted by ``z x scale``, NOT renormalised
    over the pick: what the unpicked experts scored stays out of the
    sum. Float32 for :func:`sigmoid_route`'s reason."""
    z = jax.nn.softmax(jnp.einsum(
        "td,de->te", h.astype(jnp.float32), router_w.astype(jnp.float32),
        precision=lax.Precision.HIGHEST), axis=-1)
    _, picks = lax.top_k(z + bias.astype(jnp.float32), top_k)
    return (picks.astype(jnp.int32),
            jnp.take_along_axis(z, picks, axis=-1) * scale)


def zero_experts_term(h: jax.Array, picks: jax.Array, weights: jax.Array,
                      total: int, live=None):
    """What a token's ZERO experts give: each is the identity, so ``(sum
    of the weights of its picks >= total) x h`` — float32, no gather, no
    product with a weight. h: [T, D]; picks, weights: [T, k]; ``live``
    [T] bool as :func:`held_experts_ffn`'s. Returns (out [T, D] float32,
    the count of (token, pick) pairs that are zero experts)."""
    with jax.named_scope("moe_zero"):
        zero = picks >= total
        if live is not None:
            zero = zero & live[:, None]
        w = jnp.sum(jnp.where(zero, weights, 0.0), axis=-1)
        return (w[:, None] * h.astype(jnp.float32),
                zero.sum(dtype=jnp.int32))


def shared_experts_ffn(h: jax.Array, gate, up, down, mean: bool = False,
                       matmul=None) -> jax.Array:
    """The shared SwiGLU experts, whole, for every token of h [B, S, D]:
    ONE expert as [D, F] / [F, D] leaves, SEVERAL as a stack [N, D, F] /
    [N, F, D], run as one product N x F wide and summed over the stack —
    divided by N where ``mean`` (averaged shared experts). ``matmul``:
    the caller's einsum (a quantized leaf's dispatch); plain
    ``jnp.einsum`` otherwise. Returns float32 [B, S, D]."""
    mm = matmul or (lambda spec, x, w, pet=None: jnp.einsum(
        spec, x, w, preferred_element_type=pet))
    n = getattr(gate, "q", gate).shape[:-2]
    if not n:
        inner = jax.nn.silu(mm("bsd,df->bsf", h, gate)) \
            * mm("bsd,df->bsf", h, up)
        return mm("bsf,fd->bsd", inner, down, jnp.float32)
    inner = jax.nn.silu(mm("bsd,ndf->bsnf", h, gate)) \
        * mm("bsd,ndf->bsnf", h, up)
    out = mm("bsnf,nfd->bsd", inner, down, jnp.float32)
    return out / n[0] if mean else out


#: rows of the sorted layout one trip of the expert loop takes: bounds
#: the gathered activations ([rows, D]) whatever the prefill's size
_EXPERT_CHUNK_ROWS = 2048
#: tokens up to which gather and combine are one-hot matmuls (a decode
#: step); above it (a prefill) a row gather and a scatter-add
_ONE_HOT_MAX_TOKENS = 256


def held_experts_ffn(h: jax.Array, picks: jax.Array, weights: jax.Array,
                     w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array,
                     layer: int, share: HeldExperts, live=None):
    """The routed part of a sparse SwiGLU layer that THIS chip gives. A
    pick is of one of three classes: a HELD expert, ``e in [first, first
    + held)`` — ``w_e * SwiGLU_e(h)``, computed here; an ABSENT routed
    expert (any other ``e < total``) — left out, here as on the rank
    that holds it nothing stands in; a ZERO expert, ``e in [total, total
    + n_zero)`` — the identity, ``w_e * h``, computed whole for this
    chip's own tokens (:func:`zero_experts_term`: it needs no weights,
    so it never leaves the token's rank). Dropless: every assignment
    that lands on a held expert is computed, none is padded to a
    capacity and none is dropped.

    h: [T, D]; picks, weights: [T, k] (:func:`sigmoid_route`);
    w_gate, w_up: [layers, held, D, F]; w_down: [layers, held, F, D] —
    the STACKED leaves with ``layer`` (static) naming the one to use: the
    kernel indexes the layer itself, because a sliced layer handed to a
    Mosaic call would first be copied out whole. ``live`` [T] bool: the
    tokens to route at all (None: every one) — a prompt's padding lands
    nowhere. Returns (out [T, D]
    float32, assignments, touched, zeros): the count of (token, pick)
    pairs that landed on a held expert, of held experts with at least
    one, and of pairs that are zero experts (the int 0 for a layer
    without any: it traces nothing).

    The assignments are sorted by expert and each expert's rows padded to
    whole tiles (:mod:`tony_tpu.ops.grouped_matmul`), so the products cost
    what landed: a decode step reads the weights of the experts it
    touched and no others. The sorted rows go through in chunks of
    ``_EXPERT_CHUNK_ROWS`` under a loop whose trip count follows the rows
    that exist, so a 16k-token prefill gathers 2,048 rows at a time, not
    tokens x k."""
    from tony_tpu.ops import mosaic
    from tony_tpu.ops.grouped_matmul import grouped_matmul

    t, d = h.shape
    k = picks.shape[1]
    n = t * k
    held = share.held
    # row tile: a decode step's few rows pad to 16 (bf16 sublanes), a
    # prefill's hundreds per expert to 256 (MXU-sized)
    tm = 16 if n <= 1024 else 256
    local = picks.reshape(n) - share.first
    on = (local >= 0) & (local < held)
    if live is not None:
        on = on & jnp.repeat(live, k)
    key = jnp.where(on, local, held)
    order = jnp.argsort(key, stable=True)                       # [N]
    counts = jnp.sum(key[:, None] == jnp.arange(held)[None, :],
                     axis=0, dtype=jnp.int32)                   # [held]
    padded = (counts + tm - 1) // tm * tm
    pad_end = jnp.cumsum(padded)
    pad_start = pad_end - padded
    start = jnp.cumsum(counts) - counts
    m_pad = -(-n // tm) * tm + held * tm        # bound of pad_end[-1]
    chunk = min(m_pad, _EXPERT_CHUNK_ROWS)
    m_pad = -(-m_pad // chunk) * chunk
    tiles = chunk // tm
    flat_w = weights.reshape(n)
    one_hot = t <= _ONE_HOT_MAX_TOKENS

    def rows_of(c):
        """Chunk ``c`` of the padded layout: (token of each row, its
        weight — 0 for padding —, the group of each tile)."""
        r = c * chunk + jnp.arange(chunk)
        g = jnp.sum(r[:, None] >= pad_end[None, :], axis=1)     # [chunk]
        gc = jnp.minimum(g, held - 1)
        j = r - pad_start[gc]
        valid = (g < held) & (j < counts[gc])
        src = order[jnp.where(valid, start[gc] + j, 0)]
        return (src // k, jnp.where(valid, flat_w[src], 0.0), valid,
                gc[::tm])

    def products(xb, tile_group, live_tiles):
        if mosaic.interpret():
            sizes = jnp.zeros((held,), jnp.int32).at[tile_group].add(
                jnp.where(jnp.arange(tiles) < live_tiles, tm, 0))
            mm = lambda a, w, dt: lax.ragged_dot(                 # noqa: E731
                a, w[layer], sizes, preferred_element_type=dt)
        else:
            # [layers, held, ..] -> [layers x held, ..]: merging leading
            # axes moves nothing
            mm = lambda a, w, dt: grouped_matmul(                 # noqa: E731
                a, w.reshape((-1,) + w.shape[2:]),
                tile_group + layer * held, live_tiles, tm=tm,
                out_dtype=dt)
        inner = (jax.nn.silu(mm(xb, w_gate, h.dtype))
                 * mm(xb, w_up, h.dtype))
        return mm(inner, w_down, jnp.float32)

    def body(c, out):
        tok, w, valid, tile_group = rows_of(c)
        live_tiles = jnp.clip(pad_end[-1] // tm - c * tiles, 0, tiles)
        if one_hot:
            sel = (tok[:, None] == jnp.arange(t)[None, :]) & valid[:, None]
            xb = jnp.einsum("rt,td->rd", sel.astype(h.dtype), h)
        else:
            xb = h[tok]
        y = products(xb, tile_group, live_tiles)
        y = jnp.where(valid[:, None], y * w[:, None], 0.0)
        if one_hot:
            return out + jnp.einsum(
                "rt,rd->td", sel.astype(jnp.float32), y,
                precision=lax.Precision.HIGHEST)
        return out.at[tok].add(y)

    with jax.named_scope("moe_experts"):
        n_chunks = (pad_end[-1] + chunk - 1) // chunk
        out = lax.fori_loop(0, n_chunks, body,
                            jnp.zeros((t, d), jnp.float32))
    zeros = 0
    if share.n_zero:
        term, zeros = zero_experts_term(h, picks, weights, share.total, live)
        out = out + term
    return (out, on.sum(dtype=jnp.int32), (counts > 0).sum(dtype=jnp.int32),
            zeros)
