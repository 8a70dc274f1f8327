"""Autoregressive decoding with a KV cache for the flagship transformer.

The inference half of the model stack (the reference delegates all compute,
so this — like training — is green-field per SURVEY.md §2.3). TPU-first
choices:

- **Static shapes everywhere**: the cache is a fixed [L, B, max_len, KV·hd]
  buffer (KV = cfg.kv_heads — n_heads/n_kv_heads× smaller under
  grouped-query attention; heads and head_dim stored MERGED, see
  :func:`init_kv_cache`) updated with ``lax.dynamic_update_slice``; the
  decode loop is a ``lax.scan`` over step index — one compiled program
  regardless of prompt or generation length.
- **Prefill/decode split**: the prompt is processed in one batched forward
  (MXU-friendly big matmuls, flash attention) that also fills the cache;
  each generated token then runs the cheap single-position path attending
  over the cache.
- **Masked cache attention**: positions beyond the current length are
  masked with -inf rather than sliced (dynamic slices of data-dependent
  length would break XLA's static shapes).

Sharding: tensor-parallel decode works by XLA sharding propagation — pass
params sharded by the model's logical axes (shard_pytree + logical_axes)
and call under ``jax.set_mesh``; outputs are token-identical to unsharded
decode (test-verified on a tp×dp mesh). The module adds no explicit
sharding constraints of its own; the cache layout follows the q/k/v
projections' propagated shardings. One island: on the chip a step's
cached read is a Mosaic kernel, which XLA does not partition, so under a
mesh it runs a device inside ``shard_cached_attention``'s ``shard_map``
— slots over the batch axes, K/V heads over ``tp``, which is where
propagation leaves the cache (:func:`_kernel_cached_attention`; a latent
cache's one head does not split, so its launch takes slots alone and a
live ``tp`` axis keeps the ``jnp`` walk: :func:`_kernel_latent_attention`).

Usage::

    out = generate(params, prompt_tokens, cfg, max_new_tokens=64,
                   rng=jax.random.PRNGKey(0), temperature=0.8)
    out.tokens      # [B, prompt_len + max_new_tokens]
"""

from __future__ import annotations

import functools
import warnings
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from tony_tpu.models import transformer as T
from tony_tpu.models.quantize import QuantizedWeight
from tony_tpu.ops import mosaic
from tony_tpu.ops import ssm as ssm_ops
from tony_tpu.ops.attention import (cached_attention, cached_attn_block,
                                    cached_attn_work, live_blocks)
from tony_tpu.ops.norms import layer_norm_reference, rms_norm_reference
from tony_tpu.parallel.moe import (HeldExperts, held_experts_ffn, moe_ffn,
                                   shared_experts_ffn, sigmoid_route,
                                   softmax_route)
from tony_tpu.parallel.sharding import (_island_mesh, cache_heads_split,
                                        shard_cached_attention)


#: TOKEN POSITIONS (the sequence axis, NOT batch x seq) STRICTLY ABOVE
#: which a quantized matmul is "prefill-shaped": compute-bound, not
#: weight-read-bound, so the int8 weight converts to the bf16 compute
#: dtype ONCE per call (the materialized copy amortizes over the many
#: activation rows) and the dot runs at bf16 MXU throughput instead of
#: f32. Gating on the sequence axis alone keeps every decode-shaped
#: call — single steps (S=1) and speculative verify chunks (S=k+1) — on
#: the fused-f32 kernel AT ANY BATCH SIZE: a batch-widened decode step
#: must never flip kernels (re-paying the materialized-copy cost per
#: step), and verify vs single-step logits must come from the SAME
#: kernel or the chunked-verify == single-step token-identity contract
#: quietly erodes on TPU.
#:
#: The threshold sits ON a power-of-two admission-ladder rung and the
#: comparison is STRICT (> not >=) on purpose: ``next_pow2(n) <= 256
#: iff n <= 256``, so a prompt and its padded power-of-two bucket always
#: land on the same side — bucketed admission (serve.py) and an
#: exact-length prefill of the same prompt pick the SAME kernel, keeping
#: quantized serving == solo generate on TPU. (A custom
#: ``admission_buckets`` ladder whose rungs straddle 256 — e.g. a
#: 300-token bucket holding 200-token prompts — reintroduces the flip;
#: keep a rung at 256 if you serve int8 weights in bf16.)
#:
#: Known carve-out: SHARED-PREFIX serving decomposes one logical prompt
#: into a template prefill (P positions) and a suffix extend (S
#: positions), each gated on its own length, while the solo baseline
#: prefills P+S in one call — when those land on different sides of the
#: rung (e.g. P, S <= 256 < P+S), the components run different kernels
#: and near-tie argmaxes can flip vs the monolithic prefill. This is
#: inherent to any shape-gated kernel choice applied to a decomposed
#: computation, and it is the SAME caveat class as chunked-vs-monolithic
#: matmul noise on TPU (see speculative_generate's caveats): quantized
#: shared-prefix exactness is CPU-pinned; on TPU it holds modulo
#: near-tie flips.
_QUANT_PREFILL_MIN_S = 256


def _weinsum(spec, x, w, pet=None):
    """Weight-matmul dispatch: plain arrays take the ordinary einsum;
    :class:`~tony_tpu.models.quantize.QuantizedWeight` operands compute
    the dot on the int8 weight cast to FLOAT32 (not the bf16 compute
    dtype: XLA fuses the int8→f32 convert into the dot's operand read,
    while int8→bf16 MATERIALIZES a full-size converted copy — measured
    3× slower on the lm_head matmul; f32 is exact for integers ≤ 127
    anyway) and apply the per-output-channel scale OUTSIDE the
    contraction. ``pet=jnp.float32`` callers (the lm_head) get f32 out
    either way.

    PREFILL-shaped quantized matmuls (more than ``_QUANT_PREFILL_MIN_S``
    token positions on the sequence axis, bf16 activations) instead cast
    the int8 weight to bf16: prefill over a long prompt is compute-bound
    and f32 MXU throughput is far below bf16, so the one-time converted
    copy is the right trade there — the scale still applies outside the
    contraction with f32 accumulation, so the numerics contract (int8
    values exact in the operand dtype, scale exact in f32) is unchanged.
    Decode-shaped calls (any batch size — the gate reads the sequence
    axis only), 2-D projections (the lm_head's last-position read), and
    f32 activations (the CPU/test path) keep the f32 route bit-for-bit;
    the strict on-a-ladder-rung threshold keeps bucket-padded and
    exact-length prefills of the same prompt on the same kernel (see the
    constant's comment)."""
    if isinstance(w, QuantizedWeight):
        s_len = x.shape[1] if x.ndim >= 3 else 1
        if s_len > _QUANT_PREFILL_MIN_S and x.dtype == jnp.bfloat16:
            y = jnp.einsum(spec, x, w.q.astype(x.dtype),
                           preferred_element_type=jnp.float32) * w.scale
            return y if pet == jnp.float32 else y.astype(x.dtype)
        y = jnp.einsum(spec, x.astype(jnp.float32),
                       w.q.astype(jnp.float32),
                       preferred_element_type=jnp.float32) * w.scale
        return y if pet == jnp.float32 else y.astype(x.dtype)
    return jnp.einsum(spec, x, w, preferred_element_type=pet)


class GenerateOutput(NamedTuple):
    tokens: jax.Array        # [B, prompt_len + max_new_tokens]
    logprobs: jax.Array      # [B, max_new_tokens] logprob of each sampled token


def _ring_capacity(cfg: T.TransformerConfig) -> int:
    """Rolling-cache rows per slot (0 = linear cache of max_len rows)."""
    return cfg.kv_cache_capacity


def _kv_spec(cfg: T.TransformerConfig) -> dict:
    """name → (per-head width, dtype) of each position buffer of the
    dense decoder: k/v hold ``head_dim`` values per (token, kv-head); an
    int8 cache adds one f32 absmax scale per (token, kv-head) beside
    each. (A model with layer_kinds: :func:`cache_layout`.)"""
    if cfg.kv_quant:
        return {"k": (cfg.head_dim, jnp.int8), "v": (cfg.head_dim, jnp.int8),
                "k_scale": (1, jnp.float32), "v_scale": (1, jnp.float32)}
    return {"k": (cfg.head_dim, cfg.dtype), "v": (cfg.head_dim, cfg.dtype)}


def ring_rows(cfg: T.TransformerConfig) -> int:
    """Rows a slot's RING of a ``window`` kind holds: ``attn_window`` —
    a query sees its window's rows, its own among them, and a decode
    step writes its row before it reads — rounded up to whole 16-row
    tiles (a bf16 buffer's [rows, lanes] tile is 16 x 128: a row count
    off the tile pads every slot's slab and turns the whole-slot landing
    of an admission into partial-tile writes). 4,096 stays 4,096."""
    return -(-cfg.attn_window // 16) * 16


def cache_layout(cfg: T.TransformerConfig, max_len: int,
                 ring: bool = True) -> dict:
    """name → (layers, rows a slot, row width, dtype) of every position
    buffer. The dense decoder: its k/v (+ int8 scales), all layers, one
    row count (``kv_cache_capacity`` or ``max_len``). A model with
    layer_kinds: each ATTENTION owns its buffers, indexed by its place
    among the attentions of that sort
    (``TransformerConfig.attention_of``; a double layer runs two and
    owns two consecutive indices, never one row-set for both halves),
    with its OWN row count —
    ``latent``: ``ckv``, one compressed row [c_kv; k_rope] a token,
    ``max_len`` rows; ``full``: ``k`` / ``v``, ``max_len`` rows;
    ``window``: ``k_ring`` / ``v_ring``, :func:`ring_rows` rows written
    modulo their count — or ``max_len`` linear rows where ``ring`` is
    False: the mini cache of a prefill, which :func:`place_rows` lands
    in a ring by each row's length. An ``ssm`` kind owns NO position
    buffer: its state has no rows (:func:`state_layout`)."""
    if not cfg.kinded:
        rows = _ring_capacity(cfg) or max_len
        return {n: (cfg.n_layers, rows, cfg.kv_heads * w, dt)
                for n, (w, dt) in _kv_spec(cfg).items()}
    kv, out = cfg.kv_heads * cfg.head_dim, {}
    for attention, n in cfg.attention_layers().items():
        if attention == "latent":
            out["ckv"] = (n, max_len, cfg.latent.stored_row, cfg.dtype)
        elif attention == "full":
            out["k"] = out["v"] = (n, max_len, kv, cfg.dtype)
        elif attention == "window":
            rows = ring_rows(cfg) if ring else max_len
            out["k_ring"] = out["v_ring"] = (n, rows, kv, cfg.dtype)
    return out


def state_layout(cfg: T.TransformerConfig) -> dict:
    """name → (mixers, what ONE slot holds, dtype) of every STATE buffer:
    state that is the same size at every length, so it has no position
    axis, no rows to mask and nothing a length could slice. The ``ssm``
    kinds own two, indexed by a mixer's place among the ``ssm`` mixers
    (``TransformerConfig.attention_of``): ``ssm``, the recurrence
    ``[d_state, n_heads · head_dim]`` (the stored layout of
    :mod:`tony_tpu.ops.ssm`) in ``ssm.state_dtype`` (None: the model's
    dtype); ``conv``, the convolution's last ``d_conv - 1`` INPUTS
    ``[d_conv - 1, conv_dim]``. Every step rewrites a slot's state whole
    and an admission lands it whole (:func:`place_rows`); nothing else
    protects a slot's next occupant. Empty for a model without them."""
    m = cfg.ssm             # set only beside layer_kinds that use it
    if m is None:
        return {}
    n = cfg.attention_layers()["ssm"]
    return {"ssm": (n, (m.d_state, m.d_inner), m.state_dtype or cfg.dtype),
            "conv": (n, (m.d_conv - 1, m.conv_dim), cfg.dtype)}


def cache_bytes_by_kind(cfg: T.TransformerConfig, batch: int,
                        max_len: int) -> dict:
    """Bytes of the buffers ``batch`` slots hold, by the KIND of state:
    ``latent`` / ``window`` / ``full`` for a model with layer_kinds (each
    attention's own position buffers) beside ``ssm`` / ``conv`` (a
    state-space mixer's state buffers, whatever ``max_len``); ``ring`` or
    ``linear`` for the dense decoder."""
    out: dict = {}
    for n, (layers, rows, width, dt) in cache_layout(cfg, max_len).items():
        kind = _buffer_kind(cfg, n)
        out[kind] = out.get(kind, 0) + (layers * batch * rows * width
                                        * jnp.dtype(dt).itemsize)
    for n, (layers, shape, dt) in state_layout(cfg).items():
        out[n] = layers * batch * int(np.prod(shape)) * jnp.dtype(dt).itemsize
    return out


def _buffer_kind(cfg: T.TransformerConfig, name: str) -> str:
    """The kind of state that owns the position buffer ``name``."""
    if not cfg.kinded:
        return "ring" if _ring_capacity(cfg) else "linear"
    return {"ckv": "latent", "k_ring": "window",
            "v_ring": "window"}.get(name, "full")


def init_kv_cache(cfg: T.TransformerConfig, batch: int,
                  max_len: int, ring: bool = True) -> dict:
    """Zeroed cache pytree: k/v of shape [L, B, max_len, KV·hd] — KV is
    cfg.kv_heads, so grouped-query configs carry an n_heads/n_kv_heads×
    smaller cache (the main GQA payoff at long max_len).

    Heads and head_dim are stored MERGED in one trailing axis, for every
    configuration, so that the layout the device gives the buffer at
    allocation is the layout the per-row write updates in place and the
    blockwise read slices. With a trailing [.., KV, hd] the TPU's default
    layout for ``bf16[24,6,1280,32,96]`` is ``{2,4,3,1,0:T(8,128)}`` —
    the ROWS minor, because a minor axis of 96 would pad to 128 lanes —
    while the write inside ``step_rows``' loop wants head_dim minor
    (padded: 1.51 GB a buffer instead of 1.13). Every decode chunk then
    began and ended with a transposing copy of the whole K and V cache
    (4 copies, 3.03 GB of temporaries; at 8 slots the cache was re-laid
    out inside every step), and an admission wrote its rows as strided
    partial tiles. Merged, the minor axis is KV·hd (3,072 = 24 × 128
    lanes: nothing padded), the default layout ``{3,2,1,0}`` is also the
    loop's, and no program that holds the cache copies it
    (described-chip compile at 24 layers × 6 and × 8 slots × 1,280
    rows: 0 cache-sized copies, temporaries 0.01 GB;
    ``tests/test_chip_compile.py`` holds it). Only :func:`_kv_flat`
    (writes) and :func:`_spread_queries` / :func:`_head_values` (reads)
    know the trailing shape; what crosses a process boundary keeps the
    5-D wire form (:func:`kv_to_wire`).

    ``cfg.kv_cache_dtype == "int8"`` stores k/v as int8 with per-token,
    per-kv-head absmax scales in parallel ``k_scale``/``v_scale`` buffers
    of shape [L, B, max_len, KV] (f32) — the SAME rank and leading
    dims as k/v, so every cache write path (contiguous slice, bounded
    window, per-row scatter) applies to the scale buffers unchanged with
    a per-head width of 1. Cache memory and read traffic halve vs bf16
    (each of k and v costs 1 + 4/hd bytes per element ≈ 1.06 at hd=64,
    vs 2 bf16); see :func:`_kv_quantize` for the numerics.

    ``cfg.kv_cache_capacity`` allocates a ROLLING cache of that many
    rows instead of ``max_len`` — writes wrap modulo the capacity
    (sliding-window models only; the ring read masks by each row's
    absolute position). Memory is O(capacity) however long the stream
    runs.

    A model with layer_kinds: the buffers of :func:`cache_layout`, each
    attention's own — a ``window`` kind's ring beside a ``full`` kind's
    ``max_len`` rows (``ring=False``: linear, a prefill's mini cache)."""
    cap = _ring_capacity(cfg)
    rows = cap or max_len
    if cap and cfg.attn_window and cap >= 4 * cfg.attn_window:
        # _ring_cached_attention reads the rows WRITTEN, not the rows in
        # the window: off the chip it is dense over all capacity rows
        # every step; on it the kernel visits the blocks that hold
        # written rows — every block once the ring has wrapped. Either
        # way a long stream's per-token cost is O(capacity), NOT
        # O(window). Capacity near the window is the intended regime; a
        # large multiple silently forfeits the sliding window's bound.
        warnings.warn(
            f"kv_cache_capacity={rows} is {rows // cfg.attn_window}x "
            f"attn_window={cfg.attn_window}: once the ring has wrapped, "
            "ring-cache attention reads every capacity row per token "
            "(O(capacity), not O(window)) — size the capacity near the "
            "window", stacklevel=2)
    cache = {n: jnp.zeros((layers, batch, n_rows, width), dt)
             for n, (layers, n_rows, width, dt)
             in cache_layout(cfg, max_len, ring).items()}
    cache.update({n: jnp.zeros((layers, batch) + shape, dt)
                  for n, (layers, shape, dt) in state_layout(cfg).items()})
    if cfg.experts is not None:
        # what the expert layers counted since the holding program began
        # (assignments landed on held experts, held experts touched, and
        # — a model with zero experts — assignments that were one):
        # state the routed layers own, riding the cache through the layer
        # loop; serve.step_rows / admit_rows hand it out with the tokens
        cache[MOE_COUNTS] = jnp.zeros((3 if cfg.experts.n_zero else 2,),
                                      jnp.int32)
    return dict(cache, length=jnp.zeros((), jnp.int32))


#: the ``window`` kinds' buffers: rows written modulo their row count
_RING_BUFS = ("k_ring", "v_ring")
#: cache keys that hold per-position buffers (and so follow every write/
#: gather/tile path together); beside them the STATE buffers
#: (``_STATE_BUFS``: no position axis, landed whole), "length" and the
#: expert layers' counters (``MOE_COUNTS``)
_KV_BUFS = ("k", "v", "k_scale", "v_scale", "ckv") + _RING_BUFS
#: the ``ssm`` kinds' buffers (:func:`state_layout`)
_STATE_BUFS = ("ssm", "conv")
MOE_COUNTS = "moe_counts"


def _kv_state(cache: dict) -> dict:
    """Everything a layer loop threads: the buffers, and the expert
    layers' counters where the model has them. For the dense decoder
    this is :func:`_kv_bufs`."""
    return {n: a for n, a in cache.items() if n != "length"}


def cache_rows(cache: dict) -> int:
    """Positions a cache (or mini cache, or template) holds per slot:
    its linear buffers' rows — a ``window`` kind's ring never limits a
    request's length (only a model that is all rings counts those)."""
    bufs = _kv_bufs(cache)
    linear = [a for n, a in bufs.items() if n not in _RING_BUFS]
    return (linear or list(bufs.values()))[0].shape[2]


def _has_ring_bufs(cache: dict) -> bool:
    """Whether a ``window`` kind's ring is among the cache's buffers."""
    return any(n in cache for n in _RING_BUFS)


def _kv_bufs(cache: dict) -> dict:
    """The cache's position-indexed buffers (k/v + scales when present),
    without the length field."""
    return {n: cache[n] for n in _KV_BUFS if n in cache}


def _kv_flat(chunk):
    """[.., KV, w] → [.., KV·w]: a chunk (or a wire buffer) as the cache
    stores it. The WRITE side's one statement of the stored trailing
    shape; :func:`_spread_queries` / :func:`_head_values` are the read
    side's."""
    return chunk.reshape(chunk.shape[:-2] + (-1,))


def _kv_rows(buf, li, start=None, n=None):
    """Layer ``li``'s rows of a stored buffer [L, B, rows, KV·w], as
    stored: [B, n, KV·w] — all rows, or the ``n`` from traced ``start``.
    The block is sliced from the STACKED buffer directly: slicing the
    layer first (``buf[li]``) reads loop-invariant in the blockwise
    ``fori_loop``, so XLA hoists and MATERIALIZES the whole padded
    per-layer cache before the loop, re-paying exactly the O(max_len)
    traffic that path exists to avoid (measured: 5x decode slowdown at
    max_len 8192)."""
    if start is None:
        return buf[li]
    return jax.lax.dynamic_slice(
        buf, (li, 0, start, 0), (1, buf.shape[1], n, buf.shape[3]))[0]


def _spread_queries(q, kv):
    """q [B, Q, H, hd] laid out block-diagonally over the ``kv`` K/V
    heads, to meet stored rows whole: ``qx[(k, d), k', g, q] =
    q[q, k, g, d]·[k == k']`` as [B, KV·hd, KV, G, Q] (G = H/KV query
    heads share each K/V head). Loop-invariant: the blockwise path
    builds it once, outside its ``fori_loop``.

    Why: the stored rows are NOT split into heads. With hd = 96 that
    split falls off the 128-lane tiles, and the compiler answers it with
    a transposition of every block read (measured on the v5e: 0.28 ms a
    layer and step against 0.18 with the padded 5-D cache, PR 26).
    Against ``qx`` ONE matmul contracts the whole stored row
    (:func:`_head_scores`): the other heads' columns meet exact zeros,
    so each score is the same bf16 products accumulated in f32 as the
    per-head einsum's. The MXU does KV times the needed work, on an
    operand it would otherwise wait on HBM for."""
    b, n_q, h, d = q.shape
    qg = q.reshape(b, n_q, kv, h // kv, d)
    qx = jnp.einsum("bqkgd,kK->bkdKgq", qg, jnp.eye(kv, dtype=q.dtype))
    return qx.reshape(b, kv * d, kv, h // kv, n_q)


def _head_scores(qx, k_rows):
    """Per-head q·k over stored rows. ``qx``: :func:`_spread_queries`;
    ``k_rows``: [B, S, KV·hd] as :func:`_kv_rows` returns them. Returns
    f32 scores [B, KV, G, Q, S]."""
    b, f = qx.shape[:2]
    s = jnp.einsum("bsf,bfn->bns", k_rows, qx.reshape(b, f, -1),
                   preferred_element_type=jnp.float32)
    return s.reshape((b,) + qx.shape[2:] + k_rows.shape[1:2])


def _head_values(p, v_rows):
    """Per-head p·v over stored rows, the counterpart of
    :func:`_head_scores`. p: [B, KV, G, Q, S] (the rows' dtype);
    ``v_rows``: [B, S, KV·hd]. Returns f32 [B, KV, G, Q, hd]: one
    matmul ``p @ v_rows`` gives every (query head, stored column) pair,
    and each KV head keeps its own hd columns (a select, no
    arithmetic)."""
    b, kv, g, n_q, n_s = p.shape
    r = jnp.einsum("bns,bsf->bnf", p.reshape(b, -1, n_s), v_rows,
                   preferred_element_type=jnp.float32)
    r = r.reshape(b, kv, g, n_q, kv, -1)
    own = jnp.eye(kv, dtype=bool)[None, :, None, None, :, None]
    return jnp.where(own, r, 0.0).sum(axis=4)


def _merge_query_heads(o, dtype):
    """Attention output [B, KV, G, Q, hd] → [B, Q, H, hd] in ``dtype``."""
    b, kv, g, n_q, d = o.shape
    return o.transpose(0, 3, 1, 2, 4).reshape(b, n_q, kv * g, d).astype(dtype)


def kv_wire_layout(cfg: T.TransformerConfig) -> dict:
    """name → ``ShapeDtypeStruct`` of ONE position of every buffer in the
    WIRE form [L, 1, 1, KV, w]: what a shipped ``KVPackage`` or prefix
    template is validated against (layers, heads, head_dim, dtype)."""
    cfg.refuse("KV shipping and prefix templates (the wire form is K and "
               "V rows per head)")
    return {n: jax.ShapeDtypeStruct(
                (cfg.n_layers, 1, 1, cfg.kv_heads, w), dt)
            for n, (w, dt) in _kv_spec(cfg).items()}


def kv_to_wire(bufs: dict, cfg: T.TransformerConfig) -> dict:
    """Stored buffers [L, B, S, KV·w] → the wire form [L, B, S, KV, w]
    that ``KVPackage`` rows and prefix-template blobs have always had, so
    replicas of either cache representation land each other's ships."""
    return {n: a.reshape(a.shape[:3] + (cfg.kv_heads, -1))
            for n, a in bufs.items()}


def kv_from_wire(bufs: dict) -> dict:
    """Wire buffers [L, B, S, KV, w] → the stored form (a host reshape:
    no copy)."""
    return {n: _kv_flat(a) for n, a in bufs.items()}


def _kv_quantize(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Symmetric int8 quantization of a K/V chunk [..., KV, hd] along its
    head dim: scale = absmax/127 per (token, kv-head), q = round(x/scale)
    in [-127, 127]. Returns (q int8, scale [..., KV, 1] f32). Integer
    values up to 127 are exact in bf16, so the dequantized dot can cast
    the int8 operand straight to the compute dtype and apply the scale
    OUTSIDE the contraction (it is constant along hd — see
    :func:`_cached_attention`), keeping HBM reads int8-wide."""
    x32 = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(x32), axis=-1, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(x32 / scale), -127, 127).astype(jnp.int8)
    return q, scale


# Length-aware decode attention: caches at or above this many positions
# take the block-wise path whose cost scales with the LIVE length
# (ceil(length/block) blocks) instead of the padded max_len. Below it the
# dense einsum is both cheaper (no while_loop overhead) and bit-exact
# against the training forward, which the CPU equivalence tests rely on.
DECODE_BLOCK = 256
_BLOCKWISE_MIN_LEN = 2 * DECODE_BLOCK


def _q_positions(q_start, b, n_q):
    """[B, Q] absolute positions for a decode chunk. ``q_start`` may be a
    scalar (all rows at the same frontier — plain generate) or a [B]
    vector (per-row frontiers — batched speculative decoding, where each
    row commits its own acceptance length)."""
    q_start = jnp.asarray(q_start)
    if q_start.ndim == 0:
        q_start = jnp.broadcast_to(q_start, (b,))
    return q_start[:, None] + jnp.arange(n_q)[None, :]


def _cached_attention_blockwise(q, bufs, li, q_start,
                                block: int = DECODE_BLOCK,
                                attn_window: int | None = None):
    """Online-softmax cached attention reading only the ACTIVE cache
    blocks. The dense path reads all max_len rows every step — cost
    scales with the padded buffer, not the tokens generated, which at
    serving max_len (2k-32k) dominates decode wall-clock. Here a
    ``fori_loop`` with a traced trip count ``ceil((q_start+K)/block)``
    walks only blocks that can hold unmasked positions, carrying the
    standard (running max, normalizer, weighted-value) flash state; the
    compiled program is static-shape (one [block]-row slice per step)
    while the executed cost follows the live length.

    Takes the STACKED caches [L, B, max_len, KV·hd] plus this layer's
    static index ``li``; each block is one :func:`_kv_rows` slice of the
    stacked buffer (never of a per-layer slice — see there), contracted
    as stored (:func:`_head_scores`, :func:`_head_values`): nothing is
    re-laid-out, block-sized or cache-sized.

    Same contract as the dense path: q [B, K, H, hd] at positions
    q_start..q_start+K-1 (GQA reads its shared K/V head unexpanded),
    query i attends positions <= q_start+i. Cache-dtype operands with f32
    accumulation. Numerics are flash-style (running max/rescale) rather
    than one global softmax, so logits agree with the dense path to
    normal flash tolerance, not bitwise.

    Trailing partial blocks: ``max_len`` need not divide by ``block`` —
    the last slice start is clamped (dynamic_slice semantics) and a
    position-range mask discards the re-read rows.

    Quantized caches (``k_scale``/``v_scale`` present in ``bufs``): the
    int8 K/V blocks cast to the compute dtype inside the dots (integer
    values <= 127 are exact in bf16) and the per-token scales apply
    OUTSIDE the hd-contractions they are constant along — the K scale on
    the [.., q, s] scores, the V scale folded into ``p`` — so HBM block
    reads stay int8-wide."""
    k_all, v_all = bufs["k"], bufs["v"]
    quant = "k_scale" in bufs
    b, n_q, h, d = q.shape
    max_len = k_all.shape[2]
    kv = k_all.shape[3] // d
    group = h // kv
    scale = d ** -0.5
    q_pos = _q_positions(q_start, b, n_q)                       # [B, Q]
    qx = _spread_queries(q, kv)
    n_active = (jnp.max(q_pos) + block) // block                # traced
    # sliding window: blocks entirely older than every row's window are
    # never read — the loop STARTS at the window's first block, so
    # per-token serving cost is O(window) regardless of history length
    lo = (jnp.maximum(jnp.min(q_pos) - attn_window + 1, 0) // block
          if attn_window is not None else 0)

    m0 = jnp.full((b, kv, group, n_q), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, kv, group, n_q), jnp.float32)
    acc0 = jnp.zeros((b, kv, group, n_q, d), jnp.float32)

    def body(i, carry):
        m, l, acc = carry
        start = jnp.minimum(i * block, max_len - block)
        kb = _kv_rows(k_all, li, start, block)              # [B, S, KV·hd]
        vb = _kv_rows(v_all, li, start, block)
        if quant:
            kb, vb = kb.astype(q.dtype), vb.astype(q.dtype)
        k_pos = start + jnp.arange(block)                       # [S]
        # >= i*block drops rows re-read by a clamped trailing slice
        mask = ((k_pos[None, None, :] >= i * block)
                & (k_pos[None, None, :] <= q_pos[:, :, None]))  # [B, Q, S]
        if attn_window is not None:
            mask = mask & (q_pos[:, :, None] - k_pos[None, None, :]
                           < attn_window)
        s = _head_scores(qx, kb) * scale
        if quant:
            ksb = _kv_rows(bufs["k_scale"], li, start, block)   # [B, S, KV]
            s = s * ksb.transpose(0, 2, 1)[:, :, None, None, :]
        s = jnp.where(mask[:, None, None], s, -jnp.inf)
        new_m = jnp.maximum(m, s.max(axis=-1))
        # all-masked (query, block) pairs keep m=-inf; subtract 0 there so
        # exp(-inf - 0) = 0 instead of exp(nan)
        safe_m = jnp.where(jnp.isneginf(new_m), 0.0, new_m)
        alpha = jnp.exp(m - safe_m)                             # -inf -> 0
        p = jnp.exp(s - safe_m[..., None])
        l = l * alpha + p.sum(axis=-1)
        if quant:
            vsb = _kv_rows(bufs["v_scale"], li, start, block)   # [B, S, KV]
            p_eff = p * vsb.transpose(0, 2, 1)[:, :, None, None, :]
        else:
            p_eff = p
        pv = _head_values(p_eff.astype(vb.dtype), vb)
        acc = acc * alpha[..., None] + pv
        return new_m, l, acc

    m, l, acc = jax.lax.fori_loop(lo, n_active, body, (m0, l0, acc0))
    # l > 0: every query attends itself
    return _merge_query_heads(acc / l[..., None], q.dtype)


def _read_arm(rows: int, kv_heads: int, quantized: bool, n_q: int = 1,
              ring: bool = False) -> str:
    """Which cached read a buffer of ``rows`` rows a slot takes — the ONE
    ladder: the reads (:func:`_cached_attention`,
    :func:`_ring_cached_attention`, :func:`_latent_cached_attention` —
    a latent row is ONE K/V head, ``kv_heads`` 1) and the host's count of
    what they visit (:func:`cache_rows_visited`) both ask here.

    ``"kernel"`` (:func:`_kernel_cached_attention`): on the chip — the
    repo's one question, ``mosaic.interpret()`` — for what is visible in
    the input that the kernel takes: ``_BLOCKWISE_MIN_LEN`` rows or more,
    a cache in its own dtype (no int8 scales), ONE query position a slot
    (``extend_step``'s chunks and speculation's verify keep the walk),
    and K/V heads the ambient mesh leaves whole on a device
    (``cache_heads_split``: a Mosaic call is not partitioned, it runs a
    device inside a ``shard_map`` island). Otherwise the ``jnp`` reads,
    which XLA partitions as it finds them: ``"dense"`` over the whole
    buffer (a short one, and every ring), or the block-wise ``"walk"``
    to the longest row."""
    if (rows >= _BLOCKWISE_MIN_LEN and not mosaic.interpret()
            and not quantized and n_q == 1
            and cache_heads_split(kv_heads)):
        return "kernel"
    return "dense" if ring or rows < _BLOCKWISE_MIN_LEN else "walk"


def _kernel_cached_attention(q, bufs, li, q_pos, window=None,
                             ring: bool = False):
    """The cached read as ``tony_cached_attn``
    (:func:`tony_tpu.ops.attention.cached_attention`): each slot's OWN
    live blocks of the stacked buffers, from its position ``q_pos`` [B] —
    where the walk reads every slot to the LONGEST row and the dense ring
    read every ring whole. The work list is built here, from ``q_pos``
    alone: every layer of one buffer kind builds the same one, and XLA
    keeps one. The block's height follows the stored row's bytes
    (``cached_attn_block``).

    Under a mesh (``jax.set_mesh``: tensor-parallel serving) the launch
    runs a device, inside ``shard_cached_attention``'s island: each
    device's slots and K/V heads, a work list of its own slots, the
    queries spread over ITS heads (``qx`` is block-diagonal a K/V head,
    so the split is exact)."""
    d = q.shape[3]

    def local(q, k_all, v_all, q_pos):
        b, _, h, _ = q.shape
        rows, f = k_all.shape[2:]
        block = cached_attn_block(rows, f * k_all.dtype.itemsize)
        work = cached_attn_work(q_pos, rows, block, window, ring)
        qx = _spread_queries(q, f // d).reshape(b, f, h).transpose(0, 2, 1)
        o = cached_attention(qx, k_all, v_all, li, q_pos, work,
                             scale=d ** -0.5, head_dim=d, block=block,
                             window=window, ring=ring)
        return o[:, None]

    return shard_cached_attention(local, q, bufs["k"], bufs["v"], q_pos)


def _cached_attention(q, bufs, li, q_start, attn_window=None):
    """q: [B, K, H, hd] holding positions q_start..q_start+K-1; ``bufs``:
    the cache's stacked [L, B, max_len, KV·hd] k/v buffers (plus
    ``k_scale``/``v_scale`` for int8 caches) with ``li`` this layer's
    static index (KV = H for MHA; KV < H for grouped-query, where each
    query group reads its shared K/V head WITHOUT materializing a
    repeated cache — the bandwidth saving is the point of GQA decode).
    Query i attends cache positions <= q_start+i (causal within the
    chunk, full history before it). Operands stay in the cache dtype
    (bf16 on TPU; int8 casting to the compute dtype in-dot for quantized
    caches) with f32 accumulation — casting the whole cache to f32 would
    double the hot loop's HBM traffic and halve MXU throughput.

    Large caches (max_len >= ``_BLOCKWISE_MIN_LEN``) take a length-aware
    path, so serving cost follows the live length rather than the padded
    buffer: on the chip ``tony_cached_attn`` over each slot's OWN live
    blocks (:func:`_kernel_cached_attention`; one query position a slot,
    a cache in its own dtype); off it, for int8 caches and for chunks of
    several positions, the block-wise walk to the LONGEST row."""
    k_all, v_all = bufs["k"], bufs["v"]
    quant = "k_scale" in bufs
    b, n_q, h, d = q.shape
    max_len = k_all.shape[2]
    arm = _read_arm(max_len, k_all.shape[3] // d, quant, n_q)
    if arm == "kernel":
        return _kernel_cached_attention(
            q, bufs, li, _q_positions(q_start, b, 1)[:, 0],
            window=attn_window)
    if arm == "walk":
        return _cached_attention_blockwise(q, bufs, li, q_start,
                                           attn_window=attn_window)
    k_cache, v_cache = _kv_rows(k_all, li), _kv_rows(v_all, li)
    if quant:
        k_cache, v_cache = (k_cache.astype(q.dtype),
                            v_cache.astype(q.dtype))
    scale = d ** -0.5
    q_pos = _q_positions(q_start, b, n_q)                       # [B, Q]
    k_pos = jnp.arange(max_len)                                 # [S]
    mask = k_pos[None, None, :] <= q_pos[:, :, None]            # [B, Q, S]
    if attn_window is not None:
        mask = mask & (q_pos[:, :, None] - k_pos[None, None, :]
                       < attn_window)
    qx = _spread_queries(q, k_cache.shape[-1] // d)
    scores = _head_scores(qx, k_cache) * scale      # [B, KV, G, Q, S]
    if quant:
        # per-token K scale is constant along the contracted hd — apply
        # it on the scores instead of dequantizing the cache
        ks = _kv_rows(bufs["k_scale"], li).transpose(0, 2, 1)   # [B, KV, S]
        scores = scores * ks[:, :, None, None, :]
    scores = jnp.where(mask[:, None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)                     # f32
    if quant:
        vs = _kv_rows(bufs["v_scale"], li).transpose(0, 2, 1)   # [B, KV, S]
        probs = probs * vs[:, :, None, None, :]
    return _merge_query_heads(
        _head_values(probs.astype(v_cache.dtype), v_cache), q.dtype)


def _ring_cached_attention(q, bufs, li, q_pos, attn_window: int):
    """Cached attention over a ROLLING cache [L, B, C, KV·hd]: writes
    wrapped modulo C, so ring row ``r`` holds the most recent absolute
    position congruent to r — ``q_pos - ((q_pos - r) mod C)``. A query
    at ``q_pos`` attends exactly the rows whose offset
    ``(q_pos - r) mod C`` is below ``min(attn_window, q_pos + 1)``:
    in-window history written by the CURRENT occupant (older residue in
    a reused slot can never satisfy the offset test — the slot-reuse
    argument of serve.py carries over row-wise). Single-position
    queries only (K = 1 — the callers enforce it; chunked verify keeps
    the linear cache).

    On the chip (:func:`_read_arm`; rings of ``_BLOCKWISE_MIN_LEN``
    rows or more) the read is ``tony_cached_attn`` under this mask: each
    slot's blocks up to its last WRITTEN row — a ring that has not
    wrapped reads ``ceil((q_pos + 1) / block)`` blocks, an idle slot
    one. What follows is the CPU arm, the int8 cache's, and the tests'
    oracle: dense over the C ring rows of every slot, live or not.

    q: [B, 1, H, hd]; q_pos: [B] absolute positions. Quantized caches
    fold their scales outside the dots exactly as the linear paths do."""
    k_all, v_all = bufs["k"], bufs["v"]
    quant = "k_scale" in bufs
    b, n_q, h, d = q.shape
    c = k_all.shape[2]
    if _read_arm(c, k_all.shape[3] // d, quant, n_q, ring=True) == "kernel":
        return _kernel_cached_attention(q, bufs, li, q_pos,
                                        window=attn_window, ring=True)
    k_cache, v_cache = _kv_rows(k_all, li), _kv_rows(v_all, li)
    if quant:
        k_cache, v_cache = (k_cache.astype(q.dtype),
                            v_cache.astype(q.dtype))
    scale = d ** -0.5
    offset = jnp.mod(q_pos[:, None] - jnp.arange(c)[None, :], c)  # [B, C]
    mask = offset < jnp.minimum(attn_window, q_pos[:, None] + 1)
    qx = _spread_queries(q, k_cache.shape[-1] // d)
    scores = _head_scores(qx, k_cache) * scale      # [B, KV, G, Q, C]
    if quant:
        ks = _kv_rows(bufs["k_scale"], li).transpose(0, 2, 1)   # [B, KV, C]
        scores = scores * ks[:, :, None, None, :]
    scores = jnp.where(mask[:, None, None, None, :], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)                     # f32
    if quant:
        vs = _kv_rows(bufs["v_scale"], li).transpose(0, 2, 1)
        probs = probs * vs[:, :, None, None, :]
    return _merge_query_heads(
        _head_values(probs.astype(v_cache.dtype), v_cache), q.dtype)


def cache_rows_visited(cfg: T.TransformerConfig, max_len: int,
                       pos) -> dict:
    """kind → (rows READ, rows LIVE) of the K/V reads of single-position
    decode steps, summed over the layers of that kind: HOST arithmetic
    (numpy) on ``pos`` [B, n], the position every slot's query holds at
    each of a chunk's ``n`` steps — what ``ContinuousBatcher`` counts a
    chunk at issue. LIVE: the rows the step's mask admits (``pos + 1``,
    inside the window where there is one). READ: the rows of the blocks
    that the arm :func:`_read_arm` names visits — the kernel each slot's
    own (:func:`live_blocks`), the walk every slot's from the oldest
    window's first block to the longest row's last, the dense reads the
    whole buffer. live / read is how far the read follows the rows. The
    kinds are :func:`cache_bytes_by_kind`'s; ``latent``
    (:func:`_latent_cached_attention`, the stored row as one K/V head)
    has the kernel or the walk and no dense read, summed over its
    attentions — two a double layer."""
    pos = np.asarray(pos, np.int64)
    quant = cfg.kv_cache_dtype == "int8"
    layout, out = cache_layout(cfg, max_len), {}
    if "ckv" in layout:
        attentions, rows, width, dt = layout["ckv"]
        if _read_arm(rows, 1, False) == "kernel":
            block = cached_attn_block(rows, width * jnp.dtype(dt).itemsize)
            _, hi = live_blocks(np, pos, rows, block, None, False)
            read = int(np.minimum((hi + 1) * block, rows).sum())
        else:
            block = min(DECODE_BLOCK, rows)
            read = int(((pos.max(axis=0, initial=0) + block)
                        // block * block).sum()) * pos.shape[0]
        out["latent"] = (attentions * read,
                         attentions * int((pos + 1).sum()))
    for name in ("k", "k_ring"):                    # a row's V is its K's
        if name not in layout:
            continue
        layers, rows, width, dt = layout[name]
        kind = _buffer_kind(cfg, name)
        ring = kind in ("ring", "window")
        window = (cfg.attn_window or None) if ring or not cfg.kinded \
            else None
        live = np.minimum(pos + 1, min(window or rows, rows))
        arm = _read_arm(rows, cfg.kv_heads, quant, ring=ring)
        if arm == "kernel":
            block = cached_attn_block(rows, width * jnp.dtype(dt).itemsize)
            lo, hi = live_blocks(np, pos, rows, block, window, ring)
            read = np.minimum((hi + 1) * block, rows) - lo * block
        elif arm == "walk":
            first = (np.maximum(pos.min(axis=0) - window + 1, 0)
                     // DECODE_BLOCK if window else 0)
            read = np.broadcast_to(
                ((pos.max(axis=0) + DECODE_BLOCK) // DECODE_BLOCK - first)
                * DECODE_BLOCK, pos.shape)
        else:
            read = np.full_like(pos, rows)
        out[kind] = (layers * int(read.sum()), layers * int(live.sum()))
    return out


def _window_write(buf_all, chunk, li, pos, window):
    """Bounded-window per-row cache write: the scatter-free alternative to
    ``.at[li, b, pos_b + j].set`` when per-row frontiers are guaranteed to
    lie within ``window`` positions of each other (max(pos) - min(pos) <=
    window - K — the caller's commit schedule enforces it).

    One contiguous ``window``-wide slice of the stacked cache is read,
    each row's K-token chunk lands at its own offset via a one-hot
    einsum (an MXU-shaped [B,W,K]x[B,K,KV·hd] contraction instead of a
    serialized gather/scatter), and the window is written back with one
    ``dynamic_update_slice``. Traffic is O(B * window) contiguous rows —
    independent of max_len and free of scatter lowering. Measured ~25%
    faster per speculative round than the global-cache scatter at the
    bench shapes (see docs/performance.md, round 5). ``chunk`` arrives
    flattened ([B, K, KV·w]) from :func:`_write_kv_chunk`."""
    b, n_k, f = chunk.shape
    max_len = buf_all.shape[2]
    # clamp base the way dynamic_slice clamps its start (start <=
    # max_len - window), so `off` stays relative to where the slice
    # ACTUALLY lands. This clamp is LOAD-BEARING: near the end of
    # generation the draft writes' base sits up to k past the slowest
    # active row and the slice would run off the cache tail — the
    # caller's sizing argument (speculative_generate_device) only
    # guarantees the clamp shifts base by <= k-1 rows, which the
    # offsets absorb because they are computed against the CLAMPED base
    base = jnp.minimum(jnp.min(pos), max_len - window)
    # clip is a safety net only: the commit schedule keeps every offset
    # in [0, window - K] (window-invariant proof in
    # speculative_generate_device); a clipped frozen-row surrogate writes
    # garbage into that DEAD row's own cache, which nothing reads
    off = jnp.clip(pos - base, 0, window - n_k)                 # [B]
    w_idx = jnp.arange(window)
    sel = (w_idx[None, :, None]
           == off[:, None, None] + jnp.arange(n_k)[None, None, :])
    win = jax.lax.dynamic_slice(
        buf_all, (li, 0, base, 0), (1, b, window, f))[0]        # [B, W, KV·w]
    upd = jnp.einsum("bwj,bjf->bwf", sel.astype(chunk.dtype), chunk)
    win = jnp.where(sel.any(-1)[..., None], upd, win)
    return jax.lax.dynamic_update_slice(buf_all, win[None],
                                        (li, 0, base, 0))


def _kv_writes(bufs: dict, k: jax.Array, v: jax.Array) -> dict:
    """The buffer→chunk map a K/V write must apply: plain k/v for float
    caches, quantized k/v plus their scale chunks for int8 caches. The
    single source of truth for the quantized write layout — shared by
    the decode blocks and prefill."""
    if "k_scale" in bufs:
        kq, ks = _kv_quantize(k)
        vq, vs = _kv_quantize(v)
        return {"k": kq, "k_scale": ks, "v": vq, "v_scale": vs}
    return {"k": k, "v": v}


def _write_kv_chunk(buf, chunk, li, pos, window):
    """Write a K-token chunk [B, K, KV, w] into the stacked cache buffer
    [L, B, max_len, KV·w] at layer ``li``, positions ``pos`` — flattened
    here, once, to the stored row (:func:`_kv_flat`). The three write
    modes (scalar contiguous slice / bounded window / per-row unique
    scatter) are dtype- and width-agnostic, so int8 caches route their
    [.., KV, 1] scale chunks through the same path as k/v."""
    chunk = _kv_flat(chunk)
    if pos.ndim == 0:                   # uniform frontier: contiguous slice
        return jax.lax.dynamic_update_slice(buf, chunk[None],
                                            (li, 0, pos, 0))
    if window is not None:              # bounded divergence: window write
        return _window_write(buf, chunk, li, pos, window)
    # per-row frontiers: unique scatter
    b_idx = jnp.arange(chunk.shape[0])[:, None]
    s_idx = pos[:, None] + jnp.arange(chunk.shape[1])[None, :]
    return buf.at[li, b_idx, s_idx].set(chunk, unique_indices=True)


def _decode_block(x, layer_params, bufs, li, pos, cfg, rope,
                  window=None):
    """Chunked decoder block. x: [B, K, D] at positions pos..pos+K-1;
    ``bufs``: the FULL stacked cache buffers [L, B, max_len, KV·hd]
    (k/v, plus scales for int8 caches); ``li``: this layer's static
    index; ``rope``: (cos, sin) tables precomputed once per chunk
    (position-only, so layer-invariant — same hoisting as the training
    forward). Writes only the K-token slice into the stacked cache (a
    layer-scan carrying the caches as xs/ys instead forced XLA to COPY
    the whole cache every decode step — the xs and ys buffers of a scan
    cannot alias — which dominated decode wall-clock). ``window``
    (static) selects the bounded-window write for vector ``pos`` whose
    rows the caller keeps within the window — see :func:`_window_write`.
    Returns (x, bufs)."""
    p = layer_params
    cos, sin = rope

    h = rms_norm_reference(x, p["attn_norm"], cfg.rms_eps)
    q = _weinsum("bsd,dhk->bshk", h, p["wq"])
    k = _weinsum("bsd,dhk->bshk", h, p["wk"])
    v = _weinsum("bsd,dhk->bshk", h, p["wv"])
    q, k = T.apply_rope(q, cos, sin), T.apply_rope(k, cos, sin)
    # v goes straight to the cache write, which flattens its heads; left
    # alone, XLA folds that reshape into the projection, needs wv[li] as
    # a 2-D matrix, and MATERIALIZES every layer's slice of the stacked
    # weight in each step to get one (453 MB a step at Phi-3-mini
    # widths, 7% of the decode chunk on the v5e — PR 26). The barrier
    # keeps the projection in the [.., KV, hd] form q and k have anyway.
    v = jax.lax.optimization_barrier(v)
    # write this chunk into the stacked cache — in place under jit, and
    # with the merged trailing axis (init_kv_cache) in place for the
    # WHOLE program: the buffer's layout at entry is the one this write
    # and the read below want, so nothing re-lays-out the cache
    # (tests/test_chip_compile.py holds that for step_rows)
    pos = jnp.asarray(pos)
    cap = _ring_capacity(cfg)
    # named sections (metadata only): a decode profile is read by them
    if cap:
        # rolling cache: the write position wraps modulo the capacity
        # (single-token chunks only — _blocks_forward enforces it), and
        # the read masks rows by their ring offset from each query
        with jax.named_scope("cache_write"):
            bufs = {n: _write_kv_chunk(bufs[n], c, li, pos % cap, None)
                    for n, c in _kv_writes(bufs, k, v).items()}
        q_pos = (jnp.broadcast_to(pos, (x.shape[0],))
                 if pos.ndim == 0 else pos)
        with jax.named_scope("cached_attention"):
            o = _ring_cached_attention(q, bufs, li, q_pos,
                                       cfg.attn_window)
    else:
        with jax.named_scope("cache_write"):
            bufs = {n: _write_kv_chunk(bufs[n], c, li, pos, window)
                    for n, c in _kv_writes(bufs, k, v).items()}
        with jax.named_scope("cached_attention"):
            o = _cached_attention(q, bufs, li, pos,
                                  attn_window=cfg.attn_window or None)
    x = x + _weinsum("bshk,hkd->bsd", o, p["wo"])

    with jax.named_scope("mlp"):
        h = rms_norm_reference(x, p["mlp_norm"], cfg.rms_eps)
        mlp_out = _mlp(h, p, cfg)
    return x + mlp_out, bufs


def _mlp(h, p, cfg):
    """Dense SwiGLU or MoE feed-forward on [B, S, D] (same params as the
    training block, transformer._block).

    MoE caveat: routing capacity scales with the LOCAL sequence length, so
    single-position decode (S=1, capacity >= top_k) never drops tokens while
    a full forward at low ``moe_capacity_factor`` may — cached generation
    can then diverge from the training forward on overflow tokens. This is
    the standard gshard trade; raise the capacity factor if you need exact
    equivalence."""
    if "router" in p:
        out, _ = moe_ffn(h, p["router"], p["w_gate"], p["w_down"],
                         top_k=cfg.moe_top_k,
                         capacity_factor=cfg.moe_capacity_factor,
                         activation=jax.nn.silu)
        return out
    gate = _weinsum("bsd,df->bsf", h, p["w_gate"])
    up = _weinsum("bsd,df->bsf", h, p["w_up"])
    return _weinsum("bsf,fd->bsd", jax.nn.silu(gate) * up, p["w_down"])


def _rope_tables(positions, cfg: T.TransformerConfig):
    """(cos, sin) for a chunk's positions, once per chunk, over the head
    dim. A model with layer_kinds: {attention: (cos, sin)} — over the
    rotary slice of a ``latent`` head, over the whole head of a
    ``window`` kind, None for a ``full`` or ``ssm`` kind (no positional
    rotation)."""
    def tables(d):
        return T.rope_tables(positions, d, cfg.rope_base, cfg.rope_scaling)
    if not cfg.kinded:
        return tables(cfg.head_dim)
    return {a: None if a in ("full", "ssm") else tables(
                cfg.latent.rope_dim if a == "latent" else cfg.head_dim)
            for a in cfg.attention_layers()}


def _norm(x, w, cfg: T.TransformerConfig):
    """The model's norm at its epsilon: RMSNorm, or (a kinded block's
    setting) the weight-only LayerNorm."""
    if cfg.norm == "layer":
        return layer_norm_reference(x, w, None, cfg.rms_eps)
    return rms_norm_reference(x, w, cfg.rms_eps)


def _head(params: dict, x, cfg: T.TransformerConfig):
    """Normed x [B, S, D] or [B, D] → logits in
    ``cfg.logits_storage_dtype`` (f32 accumulation): through the untied
    ``lm_head``, or through the embedding where the head is tied, x
    ``logit_scale``."""
    io = "bsd,{}->bsv" if x.ndim == 3 else "bd,{}->bv"
    if cfg.tie_embeddings:
        logits = _weinsum(io.format("vd"), x, params["embed"],
                          pet=jnp.float32)
    else:
        logits = _weinsum(io.format("dv"), x, params["lm_head"],
                          pet=jnp.float32)
    if cfg.logit_scale != 1.0:
        logits = logits * cfg.logit_scale
    return logits.astype(cfg.logits_storage_dtype)


#: the routed experts' leaves, [layers, held, ...] each
_ROUTED = ("w_gate", "w_up", "w_down")


def _layer_params(params: dict, cfg: T.TransformerConfig, li: int) -> dict:
    """Layer ``li``'s leaves, unstacked: from the one stacked group of
    the dense decoder, or from its KIND's group."""
    if not cfg.kinded:
        return jax.tree.map(lambda a: a[li], params["blocks"])
    kind, i = cfg.kind_index(li)
    group, ffn = params["blocks"][kind], T.LAYER_KINDS[kind][1]
    if ffn not in T._ROUTED_FFNS:
        return jax.tree.map(lambda a: a[i], group)
    # the routed experts go to their kernel STACKED, with the layer's
    # index: a Mosaic call takes whole buffers, so a sliced layer would
    # be copied out (1 GB a matrix at 12 x 7168 x 2048) in every step
    routed = dict(routed=tuple(group[n] for n in _ROUTED), routed_layer=i)
    if ffn != "scmoe":
        p = {n: jax.tree.map(lambda a: a[i], a) for n, a in group.items()
             if n not in _ROUTED}
        return dict(p, **routed)
    # a double layer: each half's leaves ([layers, 2, ...]) are cut
    # [layer, half] in ONE step, the dense SwiGLU under the names _mlp
    # reads — a layer's slice with both halves in it has two readers and
    # is copied out whole (0.3 GB a matrix at 2 x 6144 x 12288, 2.1 GB of
    # temporaries a decode chunk: described-chip compile, PR 37)
    shared = ("router", "router_bias") + _ROUTED
    halves = tuple(
        {T._HALF_MLP.get(n, n): jax.tree.map(lambda a: a[i, h], w)
         for n, w in group.items() if n not in shared} for h in range(2))
    return dict(routed, halves=halves, router=group["router"][i],
                router_bias=group["router_bias"][i])


# ---------------------------------------------------------------------------
# The layers of a model with layer_kinds: latent / window / full attention,
# each over the buffers it owns; dense or sparse feed-forward
# ---------------------------------------------------------------------------

def _latent_scale(cfg: T.TransformerConfig) -> float:
    scale = (cfg.latent.qk_dim ** -0.5 if cfg.attn_scale is None
             else cfg.attn_scale)
    if cfg.rope_scaling is not None:
        scale *= cfg.rope_scaling.softmax_scale
    return scale


def _latent_qkv(h, p, cfg: T.TransformerConfig, rope):
    """Projections of latent attention on normed h [B, S, D]. Returns
    (q_n [B, S, H, nope], q_r [B, S, H, rope] rotated, row [B, S, 1,
    stored_row]): ``row`` is what the cache stores, the normed
    compressed c_kv beside the ONE rotated key head every query head
    shares, then zeros up to whole lane tiles
    (``LatentAttention.stored_row``). Where the model scales its normed
    bottlenecks (``q_scale``, ``kv_scale``) the constants go on here,
    so the stored row carries ``kv_scale`` and both reads take it as it
    is; the rotary key head is not scaled."""
    la = cfg.latent
    cos, sin = rope
    cq = rms_norm_reference(_weinsum("bsd,dr->bsr", h, p["wq_a"]),
                            p["q_norm"], cfg.rms_eps)
    if la.q_scale != 1.0:
        cq = (cq * la.q_scale).astype(cq.dtype)
    q = _weinsum("bsr,rhk->bshk", cq, p["wq_b"])
    q_n = q[..., :la.nope_dim]
    q_r = T.apply_rope(q[..., la.nope_dim:], cos, sin)
    kv = _weinsum("bsd,dr->bsr", h, p["wkv_a"])
    c = rms_norm_reference(kv[..., :la.kv_rank], p["kv_norm"], cfg.rms_eps)
    if la.kv_scale != 1.0:
        c = (c * la.kv_scale).astype(c.dtype)
    k_r = T.apply_rope(kv[:, :, None, la.kv_rank:], cos, sin)
    tail = jnp.zeros(k_r.shape[:-1] + (la.stored_row - la.row,), k_r.dtype)
    return q_n, q_r, jnp.concatenate([c[:, :, None, :], k_r, tail], axis=-1)


def _latent_prompt_attention(q_n, q_r, row, p, cfg: T.TransformerConfig):
    """The EXPANDED form, for a prompt: per-head keys ``[k_n; k_r]`` and
    values from ``c_kv @ wkv_b``, causal flash attention. The kernels
    take one head width for q, k and v, so v (``v_dim``) is zero-padded
    to the q·k width and the output cut back: at 192 against 128 that
    is a fifth more attention FLOPs, on a prompt whose matmuls are a
    hundred times its attention."""
    la = cfg.latent
    b, s, heads, _ = q_n.shape
    kvb = _weinsum("bsc,chk->bshk", row[:, :, 0, :la.kv_rank], p["wkv_b"])
    k = jnp.concatenate(
        [kvb[..., :la.nope_dim],
         jnp.broadcast_to(row[..., la.kv_rank:la.row],
                          (b, s, heads, la.rope_dim))],
        axis=-1)
    v = kvb[..., la.nope_dim:]
    if la.v_dim < la.qk_dim:
        v = jnp.pad(v, ((0, 0),) * 3 + ((0, la.qk_dim - la.v_dim),))
    q = jnp.concatenate([q_n, q_r], axis=-1)
    if mosaic.interpret():
        o = T.reference_attention(q, k, v, causal=True,
                                  scale=_latent_scale(cfg))
    else:
        # narrower blocks than the kernels' defaults: those were swept at
        # head_dim 64-128, and a 192-wide head (256 lanes) at 16 heads x
        # 512 keys a step is 23 MB of the 16 MB of VMEM (described-chip
        # compile, PR 28). A prompt of at most 256 that is no multiple
        # of 128 (_flash_safe_len leaves it unpadded) keeps the default
        # q block, which clamps to the prompt and takes the dense arm.
        o = T.flash_attention(q, k, v, causal=True,
                              scale=_latent_scale(cfg),
                              block_q=128 if s % 128 == 0 else 256,
                              block_k=256)
    return o[..., :la.v_dim]


def _kernel_latent_attention(qx, buf, li, q_pos, cfg: T.TransformerConfig):
    """The absorbed read as ``tony_cached_attn``'s latent arm
    (:func:`tony_tpu.ops.attention.cached_attention` with no V operand):
    the stored row ``[c_kv; k_r; tail]`` is the ONE K/V head the query
    heads share and its own value, so each slot's own live blocks are
    read once and contracted twice — scores against ``qx`` [B, 1, H,
    row], a head's ``[q~; q_r; 0]`` at positions ``q_pos`` [B], the value
    product over the row's first ``kv_rank`` columns. Returns the
    softmax-weighted c_kv [B, 1, H, kv_rank], which the caller takes
    through ``W_uv``. The work list is built from ``q_pos`` alone: every
    latent read of a step builds the same one, and XLA keeps one. Under
    a mesh the launch runs a device inside ``shard_cached_attention``'s
    island, on its own slots."""
    def local(qx, rows_all, q_pos):
        rows, f = rows_all.shape[2:]
        block = cached_attn_block(rows, f * rows_all.dtype.itemsize)
        work = cached_attn_work(q_pos, rows, block)
        o = cached_attention(qx[:, 0], rows_all, None, li, q_pos, work,
                             scale=_latent_scale(cfg),
                             head_dim=cfg.latent.kv_rank, block=block)
        return o[:, None]

    return shard_cached_attention(local, qx, buf, None, q_pos)


def _latent_cached_attention(q_n, q_r, buf, li, q_start, p,
                             cfg: T.TransformerConfig,
                             block: int = DECODE_BLOCK):
    """The ABSORBED form, for decode: the key up-projection moves onto the
    query (``q~ = q_n @ W_uk^T``, kv_rank wide) and the value
    up-projection behind the softmax, so every head attends the STORED
    rows ``[c_kv; k_r]`` as they are — multi-query attention whose
    value is the row's first kv_rank columns:

        scores = (q~ . c_kv + q_r . k_r) * scale
        o      = (softmax(scores) @ c_kv) @ W_uv

    The same mathematics as :func:`_latent_prompt_attention`, and the
    cache holds kv_rank + rope values a token in place of heads x
    (qk_dim + v_dim). Online softmax over the live blocks only, as
    :func:`_cached_attention_blockwise` (which this sits beside): cost
    follows the live length. q_n, q_r: [B, Q, H, .] at positions
    q_start..q_start+Q-1; ``buf``: the stacked [L, B, max_len, row]
    buffer. Returns [B, Q, H, v_dim].

    On the chip (:func:`_read_arm`, the stored row as ONE K/V head: one
    query position a slot, ``_BLOCKWISE_MIN_LEN`` rows or more, no
    "heads" axis live in an ambient mesh) the read is
    ``tony_cached_attn`` over each slot's OWN live blocks
    (:func:`_kernel_latent_attention`). What follows it here is the walk
    of every slot to the LONGEST row: the CPU arm and the oracle, and
    the read of ``extend_step``'s chunks, speculation's verify and a
    tensor-parallel mesh."""
    la = cfg.latent
    b, n_q, heads, _ = q_n.shape
    max_len = buf.shape[2]
    block = min(block, max_len)
    w_uk = p["wkv_b"][..., :la.nope_dim]              # [c, H, nope]
    w_uv = p["wkv_b"][..., la.nope_dim:]              # [c, H, v]
    qc = jnp.einsum("bqhk,chk->bqhc", q_n, w_uk)
    tail = jnp.zeros(q_r.shape[:-1] + (la.stored_row - la.row,), q_r.dtype)
    qx = jnp.concatenate([qc, q_r, tail], axis=-1)    # [B, Q, H, row]
    if _read_arm(max_len, 1, False, n_q) == "kernel":
        ctx = _kernel_latent_attention(
            qx, buf, li, _q_positions(q_start, b, 1)[:, 0], cfg)
        return jnp.einsum("bqhc,chk->bqhk", ctx, w_uv)
    # [B, row, Q·H], built once outside the loop: the stored rows then
    # meet it as the LEFT operand, contracted over their minor axis as
    # stored (the same orientation as _head_scores) — as a right operand
    # the compiler re-lays-out the whole cache to put rows minor
    qx = qx.reshape(b, n_q * heads, la.stored_row).transpose(0, 2, 1)
    q_pos = _q_positions(q_start, b, n_q)             # [B, Q]
    row_pos = jnp.repeat(q_pos, heads, axis=1)        # [B, Q·H]
    n_active = (jnp.max(q_pos) + block) // block
    scale = _latent_scale(cfg)

    m0 = jnp.full((b, n_q * heads), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, n_q * heads), jnp.float32)
    acc0 = jnp.zeros((b, n_q * heads, la.stored_row), jnp.float32)

    def body(i, carry):
        m, l, acc = carry
        start = jnp.minimum(i * block, max_len - block)
        rows = _kv_rows(buf, li, start, block)                  # [B, S, row]
        k_pos = start + jnp.arange(block)
        mask = ((k_pos[None, None, :] >= i * block)
                & (k_pos[None, None, :] <= row_pos[:, :, None]))
        sc = jnp.einsum("bsf,bfn->bns", rows, qx,
                        preferred_element_type=jnp.float32) * scale
        sc = jnp.where(mask, sc, -jnp.inf)
        new_m = jnp.maximum(m, sc.max(axis=-1))
        safe_m = jnp.where(jnp.isneginf(new_m), 0.0, new_m)
        alpha = jnp.exp(m - safe_m)
        pr = jnp.exp(sc - safe_m[..., None])
        l = l * alpha + pr.sum(axis=-1)
        # p @ the whole stored row: the rotary columns and the tail ride
        # along and are cut after the loop (no slice per block)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bns,bsf->bnf", pr.astype(rows.dtype), rows,
            preferred_element_type=jnp.float32)
        return new_m, l, acc

    m, l, acc = jax.lax.fori_loop(0, n_active, body, (m0, l0, acc0))
    ctx = (acc[..., :la.kv_rank] / l[..., None]).astype(q_n.dtype)
    return jnp.einsum("bqhc,chk->bqhk",
                      ctx.reshape(b, n_q, heads, la.kv_rank), w_uv)


def _sparse_mlp(h, p, cfg: T.TransformerConfig, live=None):
    """The routed block (``cfg.experts``) on [B, S, D]: the held experts'
    part of the routed sum and the zero experts' term (dropless,
    :func:`tony_tpu.parallel.moe.held_experts_ffn`) plus the whole shared
    experts where the model has any
    (:func:`tony_tpu.parallel.moe.shared_experts_ffn`). ``live``
    [B, S] bool: positions that hold a real token — a
    prompt's padding is not routed (its output is never read, and every
    padded position carries the same token, so on a seed whose token 0
    picks held experts a 32 x 512 prefill would land 16k rows on them).
    Returns (out, counts int32: assignments landed here, held experts
    touched and — a model with zero experts — assignments that were
    one)."""
    e = cfg.experts
    b, s, d = h.shape
    flat = h.reshape(b * s, d)
    route = softmax_route if e.route == "softmax" else sigmoid_route
    with jax.named_scope("moe_route"):
        picks, w = route(flat, p["router"], p["router_bias"], e.top_k,
                         e.scale)
    routed, landed, touched, zeros = held_experts_ffn(
        flat, picks, w, *p["routed"], p["routed_layer"],
        HeldExperts(e.first, e.n_held, e.total, e.n_zero),
        live=None if live is None else live.reshape(b * s))
    if e.n_shared:
        with jax.named_scope("moe_shared"):
            shared = shared_experts_ffn(
                h, p["shared_gate"], p["shared_up"], p["shared_down"],
                e.shared_mean, matmul=_weinsum)
        out = routed.reshape(b, s, d) + shared
    else:
        out = routed.reshape(b, s, d)
    counts = [landed, touched] + ([zeros] if e.n_zero else [])
    return out.astype(h.dtype), jnp.stack(counts)


def _kinded_ffn(x, h, a, p, cfg: T.TransformerConfig, bufs: dict,
                live=None):
    """The rest of a layer with layer_kinds after its attention output
    ``a``: the dense SwiGLU, or the sparse experts, whose counts join
    ``bufs``. Sequential block: the stream takes ``a``, the
    feed-forward reads its own norm of that, ``x + a + ffn(norm(x +
    a))``. ``parallel_block``: attention and feed-forward read the SAME
    normed ``h`` and both add to the stream, ``x + a + ffn(h)``. Each
    branch joins the stream x ``residual_scale``."""
    x = x + _branch(a, cfg)
    with jax.named_scope("mlp"):
        if not cfg.parallel_block:
            h = _norm(x, p["mlp_norm"], cfg)
        if "router" not in p:
            return x + _branch(_mlp(h, p, cfg), cfg), bufs
        out, counts = _sparse_mlp(h, p, cfg, live)
        return (x + _branch(out, cfg),
                dict(bufs, **{MOE_COUNTS: bufs[MOE_COUNTS] + counts}))


def _branch(y, cfg: T.TransformerConfig):
    """A branch's output as it joins the stream: x ``residual_scale``
    (1.0: the output itself, nothing traced)."""
    if cfg.residual_scale == 1.0:
        return y
    return (y * cfg.residual_scale).astype(y.dtype)


def _gqa_qkv(h, p, rope, cfg: T.TransformerConfig, barrier: bool = False):
    """q [B, S, H, hd], k and v [B, S, KV, hd] of a ``window`` or
    ``full`` kind; q and k rotated where the kind has positions
    (``rope`` None: it has none). ``barrier``: a decode step's v, as
    :func:`_decode_block` keeps it from folding into the cache write.

    ``cfg.attn_scale``: every read of these kinds — the flash kernel,
    ``tony_cached_attn``, the ``jnp`` reads — scales its scores by
    ``head_dim ** -0.5``, so a model with a softmax scale of its own has
    ``attn_scale x head_dim ** 0.5`` FOLDED INTO q here, once, in q's
    dtype (a power of two — 1/64 at heads of 64 gives 1/8 — is exact in
    bfloat16; any other factor costs q one more rounding). None: nothing
    traced."""
    q = _weinsum("bsd,dhk->bshk", h, p["wq"])
    k = _weinsum("bsd,dhk->bshk", h, p["wk"])
    v = _weinsum("bsd,dhk->bshk", h, p["wv"])
    if cfg.attn_scale is not None:
        q = (q * (cfg.attn_scale * q.shape[-1] ** 0.5)).astype(q.dtype)
    if rope is not None:
        q, k = T.apply_rope(q, *rope), T.apply_rope(k, *rope)
    return q, k, (jax.lax.optimization_barrier(v) if barrier else v)


#: the buffers a ``window`` / ``full`` kind's K and V go to
_KIND_KV = {"window": ("k_ring", "v_ring"), "full": ("k", "v")}


# --- the state-space mixer (``ssm`` kinds): cfg.ssm, ops/ssm.py ---------

def _ssm_project(h, p, cfg: T.TransformerConfig):
    """The mixer's ONE input projection of normed h [B, S, D], split
    ``[z | c | dt]``: the gate [.., d_inner], the convolution's input
    [.., conv_dim] and each head's step before its bias [.., H]."""
    m = cfg.ssm
    zcd = _weinsum("bsd,df->bsf", h, p["w_in"])
    cut = m.d_inner + m.conv_dim
    return zcd[..., :m.d_inner], zcd[..., m.d_inner:cut], zcd[..., cut:]


def _ssm_conv(window, p):
    """silu(bias + sum_j w[j] * window[.., j, :]) over the LAST axis but
    one of ``window`` [.., d_conv, conv_dim] (the inputs at t - d_conv +
    1 .. t), in float32."""
    w = p["conv_w"].astype(jnp.float32)
    return jax.nn.silu(p["conv_b"].astype(jnp.float32)
                       + jnp.sum(window.astype(jnp.float32) * w, axis=-2))


def _ssm_inputs(conv, dt, p, cfg: T.TransformerConfig):
    """The recurrence's operands from the convolution's output ``conv``
    [B, S, conv_dim] float32 and the raw steps ``dt`` [B, S, H]: x
    [B, S, H, P] in the model's dtype, b and c [B, S, G, N], dt =
    softplus(dt + dt_bias) float32, a = -exp(A_log) [H]."""
    m = cfg.ssm
    lead = conv.shape[:-1]
    x = conv[..., :m.d_inner].reshape(lead + (m.n_heads, m.head_dim))
    gn = m.n_groups * m.d_state
    b = conv[..., m.d_inner:m.d_inner + gn].reshape(
        lead + (m.n_groups, m.d_state))
    c = conv[..., m.d_inner + gn:].reshape(lead + (m.n_groups, m.d_state))
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    return x.astype(cfg.dtype), b, c, dt, -jnp.exp(p["A_log"])


def _ssm_output(y, x, z, p, cfg: T.TransformerConfig):
    """y [B, S, H, P] float32 from the recurrence → the mixer's output:
    the skip ``D x``, the gate FIRST and then the norm over all d_inner
    (one group), the output projection."""
    y = y + p["D"][:, None] * x.astype(jnp.float32)
    g = y.reshape(z.shape) * jax.nn.silu(z.astype(jnp.float32))
    g = rms_norm_reference(g.astype(cfg.dtype), p["gate_norm"], cfg.rms_eps)
    return _weinsum("bsf,fd->bsd", g, p["w_out"])


def _ssm_state_step(state_all, ai, decay, inp, b, c):
    """One step of every slot's recurrence against the stacked state: on
    the chip ``tony_ssm_step`` (:func:`tony_tpu.ops.ssm.ssm_step`); off it
    — the repo's one question, ``mosaic.interpret()`` — and under a mesh
    (a Mosaic call is not partitioned) the ``jnp`` arm, the oracle."""
    if mosaic.interpret() or _island_mesh(None) is not None:
        return ssm_ops.ssm_step_reference(state_all, ai, decay, inp, b, c)
    return ssm_ops.ssm_step(state_all, ai, decay, inp, b, c)


def _ssm_decode_mixer(h, p, bufs, ai, cfg: T.TransformerConfig):
    """The mixer of a decode step on normed h [B, 1, D]: the conv window
    (``conv`` at index ``ai`` beside this position's input) shifts by
    one, and the recurrence reads the slot's whole state, updates it and
    writes it back (``ssm`` at ``ai``). No position enters: the state IS
    the history. Returns (the mixer's output, bufs)."""
    if h.shape[1] != 1:
        raise ValueError(
            "a state-space mixer decodes single-position steps only: its "
            "state is one recurrence a slot, not rows a chunk could "
            "rewind (speculation's verify chunks need a linear cache)")
    m = cfg.ssm
    with jax.named_scope("ssm_mixer"):
        z, c_in, dt = _ssm_project(h, p, cfg)
        with jax.named_scope("ssm_conv"):
            window = jnp.concatenate([bufs["conv"][ai], c_in], axis=1)
            conv = _ssm_conv(window, p)[:, None]
            bufs = dict(bufs, conv=bufs["conv"].at[ai].set(window[:, 1:]))
        x, b, c, dt, a = _ssm_inputs(conv, dt, p, cfg)
        with jax.named_scope("ssm_state"):
            dt0 = dt[:, 0]                                      # [B, H]
            inp = dt0[..., None] * x[:, 0].astype(jnp.float32)
            y, state = _ssm_state_step(
                bufs["ssm"], ai,
                jnp.repeat(jnp.exp(dt0 * a), m.head_dim, axis=1),
                inp.reshape(inp.shape[0], -1), b[:, 0], c[:, 0])
            bufs = dict(bufs, ssm=state)
        y = y.reshape(x.shape)
        return _ssm_output(y, x, z, p, cfg), bufs


def _ssm_prompt_mixer(h, p, bufs, ai, cfg: T.TransformerConfig, live):
    """The mixer over a (padded) prompt on normed h [B, S, D], and the
    state it hands to decode. A recurrence runs THROUGH a padded tail
    unless told not to — causality hides nothing here — so ``live``
    [B, S] stops each row at its own length: the step ``dt`` is 0 past it
    (decay 1, input 0: the carried state is the state AT the length), and
    the conv state is the conv's INPUT at the last ``d_conv - 1`` live
    positions (zeros before position 0), gathered by the row's length.
    Both land whole in the mini cache at index ``ai``. Returns (the
    mixer's output — garbage past a row's length, never read —, bufs)."""
    m = cfg.ssm
    taps = m.d_conv - 1
    with jax.named_scope("ssm_mixer"):
        z, c_in, dt = _ssm_project(h, p, cfg)
        s = h.shape[1]
        with jax.named_scope("ssm_conv"):
            padded = jnp.pad(c_in, ((0, 0), (taps, 0), (0, 0)))
            window = jnp.stack([padded[:, j:j + s] for j in range(m.d_conv)],
                               axis=2)
            conv = _ssm_conv(window, p)
            # the input at position q is padded[:, q + taps]
            last = jnp.sum(live, axis=1, dtype=jnp.int32)[:, None] \
                + jnp.arange(taps)
            conv_state = jnp.take_along_axis(padded, last[:, :, None], axis=1)
        x, b, c, dt, a = _ssm_inputs(conv, dt, p, cfg)
        with jax.named_scope("ssm_state"):
            y, state = ssm_ops.ssm_chunked(
                x, jnp.where(live[..., None], dt, 0.0), a,
                b.astype(cfg.dtype), c.astype(cfg.dtype), m.chunk)
        bufs = dict(bufs,
                    ssm=bufs["ssm"].at[ai].set(
                        state.astype(bufs["ssm"].dtype)),
                    conv=bufs["conv"].at[ai].set(conv_state))
        return _ssm_output(y, x, z, p, cfg), bufs


def _latent_decode_attention(h, p, bufs, ai, pos, cfg, rope, window,
                             scope: str = "mla_attention"):
    """Latent attention of a decode chunk on normed ``h``: the rows
    written to ``ckv`` at index ``ai`` through the caller's write mode,
    then read in the absorbed form. Returns (attention output after
    ``wo``, bufs)."""
    with jax.named_scope(scope):
        q_n, q_r, row = _latent_qkv(h, p, cfg, rope["latent"])
        with jax.named_scope("cache_write"):
            bufs = dict(bufs, ckv=_write_kv_chunk(bufs["ckv"], row, ai,
                                                  pos, window))
        with jax.named_scope("cached_attention"):
            o = _latent_cached_attention(q_n, q_r, bufs["ckv"], ai, pos,
                                         p, cfg)
        return _weinsum("bshk,hkd->bsd", o, p["wo"]), bufs


def _latent_prompt(h, p, cfg, rope, scope: str = "mla_attention"):
    """Latent attention of a (padded) prompt on normed ``h``, expanded.
    Returns (attention output after ``wo``, the rows the cache stores)."""
    with jax.named_scope(scope):
        q_n, q_r, row = _latent_qkv(h, p, cfg, rope["latent"])
        o = _latent_prompt_attention(q_n, q_r, row, p, cfg)
        return _weinsum("bshk,hkd->bsd", o, p["wo"]), row


def _scmoe_block(x, p, bufs, cfg, attend, live=None):
    """The double layer with its shortcut-connected routed block, on the
    stream x [B, S, D] (``p``: :func:`_layer_params`' — the halves'
    leaves under ``halves``); ``attend(i, h, half, bufs) -> (a, bufs)`` is
    half ``i``'s attention on its normed input (a decode chunk's or a
    prompt's). With ``A_i`` / ``F_i`` the halves' attention and dense
    SwiGLU and ``M`` the routed block:

        x1 = x  + A_0(norm(x))
        h1 = norm(x1)
        m  = M(h1)              # reads the FIRST half's normed stream
        x2 = x1 + F_0(h1)
        x3 = x2 + A_1(norm(x2))
        y  = x3 + F_1(norm(x3)) + m     # lands after the SECOND half

    ``M`` is issued where ``h1`` exists: in an expert-parallel deployment
    its exchange runs under ``F_0``, ``A_1`` and ``F_1``; on one chip the
    compiler orders it, and nothing stands in for the exchange."""
    m = None
    for i, half in enumerate(p["halves"]):
        a, bufs = attend(i, _norm(x, half["attn_norm"], cfg), half, bufs)
        x = x + _branch(a, cfg)
        h = _norm(x, half["mlp_norm"], cfg)
        if i == 0:
            m, counts = _sparse_mlp(h, p, cfg, live)
            bufs = dict(bufs, **{MOE_COUNTS: bufs[MOE_COUNTS] + counts})
        with jax.named_scope(f"mlp_{i}"):
            x = x + _branch(_mlp(h, half, cfg), cfg)
    return x + _branch(m, cfg), bufs


def _scmoe_decode_block(x, p, bufs, ai, pos, cfg, rope, window):
    """A decode chunk through a double layer: each half writes its rows
    to ``ckv`` at its own index (``ai``, ``ai + 1``) and reads them in
    the absorbed form, through the paths every latent kind uses."""
    pos = jnp.asarray(pos)

    def attend(i, h, half, bufs):
        return _latent_decode_attention(h, half, bufs, ai + i, pos, cfg,
                                        rope, window, f"mla_attention_{i}")
    return _scmoe_block(x, p, bufs, cfg, attend)


def _scmoe_prompt_block(x, p, bufs, ai, cfg, rope, s, live):
    """A (padded) prompt through a double layer: expanded latent
    attention in each half, rows [0, s) of each written linearly at its
    own index; only the ``live`` positions are routed."""
    def attend(i, h, half, bufs):
        a, row = _latent_prompt(h, half, cfg, rope, f"mla_attention_{i}")
        with jax.named_scope("cache_write"):
            return a, dict(bufs, ckv=_write_kv_chunk(
                bufs["ckv"], row[:, :s], ai + i, jnp.asarray(0, jnp.int32),
                None))
    return _scmoe_block(x, p, bufs, cfg, attend, live)


def _kinded_decode_block(x, p, bufs, li, pos, cfg, rope, window=None):
    """:func:`_decode_block` for a layer of a model with layer_kinds:
    the chunk's rows go through the SAME cache write paths into the
    buffers the layer's attention owns, at its index among them.
    ``latent``: the compressed row, read in the absorbed form;
    ``window``: K and V at ``pos`` modulo the ring's rows, read as
    :func:`_ring_cached_attention` (masked by offset); ``full``: K and V
    at ``pos``, read as :func:`_cached_attention` — on the chip both
    through ``tony_cached_attn``, each slot's own live blocks. Returns
    (x, bufs). ``ssm``: no row is written and none read — the mixer
    steps the state it owns (:func:`_ssm_decode_mixer`)."""
    attention, ai = cfg.attention_of(li)
    if T.LAYER_KINDS[cfg.layer_kinds[li]][1] == "scmoe":
        return _scmoe_decode_block(x, p, bufs, ai, pos, cfg, rope, window)
    h = _norm(x, p["attn_norm"], cfg)
    pos = jnp.asarray(pos)
    if attention == "ssm":
        a, bufs = _ssm_decode_mixer(h, p, bufs, ai, cfg)
        return _kinded_ffn(x, h, a, p, cfg, bufs)
    if attention == "latent":
        a, bufs = _latent_decode_attention(h, p, bufs, ai, pos, cfg, rope,
                                           window)
        return _kinded_ffn(x, h, a, p, cfg, bufs)
    nk, nv = _KIND_KV[attention]
    with jax.named_scope("attn_" + attention):
        q, k, v = _gqa_qkv(h, p, rope[attention], cfg, barrier=True)
        ring = attention == "window"
        with jax.named_scope("cache_write"):
            # a ring takes the row at pos modulo its rows, by the per-row
            # scatter; a linear buffer at pos, by the caller's write mode
            at = pos % bufs[nk].shape[2] if ring else pos
            bufs = dict(bufs, **{
                n: _write_kv_chunk(bufs[n], t, ai, at,
                                   None if ring else window)
                for n, t in ((nk, k), (nv, v))})
        with jax.named_scope("cached_attention"):
            kv = {"k": bufs[nk], "v": bufs[nv]}
            if ring:
                q_pos = (jnp.broadcast_to(pos, (x.shape[0],))
                         if pos.ndim == 0 else pos)
                o = _ring_cached_attention(q, kv, ai, q_pos,
                                           cfg.attn_window)
            else:
                o = _cached_attention(q, kv, ai, pos)
        a = _weinsum("bshk,hkd->bsd", o, p["wo"])
    return _kinded_ffn(x, h, a, p, cfg, bufs)


def _kinded_prompt_block(x, p, bufs, li, cfg, rope, s, live):
    """One layer of :func:`_prompt_forward` for a model with
    layer_kinds: attention over the (padded) prompt — expanded latent
    attention, or the flash kernel with the window on a ``window`` kind
    and plain causal on a ``full`` kind — and rows [0, s) written
    LINEARLY to the attention's buffers (a prefill's mini cache:
    :func:`place_rows` lands a ``window`` kind's in its ring); only the
    ``live`` positions are routed. ``ssm``: the chunked scan, stopped at
    each row's length, and the state landed whole
    (:func:`_ssm_prompt_mixer`)."""
    attention, ai = cfg.attention_of(li)
    if T.LAYER_KINDS[cfg.layer_kinds[li]][1] == "scmoe":
        return _scmoe_prompt_block(x, p, bufs, ai, cfg, rope, s, live)
    h = _norm(x, p["attn_norm"], cfg)
    if attention == "ssm":
        a, bufs = _ssm_prompt_mixer(h, p, bufs, ai, cfg, live)
        return _kinded_ffn(x, h, a, p, cfg, bufs, live)
    if attention == "latent":
        a, row = _latent_prompt(h, p, cfg, rope)
        writes = {"ckv": row}
    else:
        with jax.named_scope("attn_" + attention):
            q, k, v = _gqa_qkv(h, p, rope[attention], cfg)
            o = T._attention(q, k, v, None, window=(
                cfg.attn_window if attention == "window" else None))
            a = _weinsum("bshk,hkd->bsd", o, p["wo"])
        writes = dict(zip(_KIND_KV[attention], (k, v)))
    x, bufs = _kinded_ffn(x, h, a, p, cfg, bufs, live)
    with jax.named_scope("cache_write"):
        return x, dict(bufs, **{
            n: _write_kv_chunk(bufs[n], t[:, :s], ai,
                               jnp.asarray(0, jnp.int32), None)
            for n, t in writes.items()})


def _embed(params: dict, tokens, cfg: T.TransformerConfig):
    """The tokens' rows as they enter the stream, x ``embed_scale`` in
    float32 where the model has one (1.0: the rows themselves)."""
    x = params["embed"][tokens]
    if cfg.embed_scale != 1.0:
        x = x.astype(jnp.float32) * cfg.embed_scale
    return x.astype(cfg.dtype)


def _blocks_forward(params: dict, tokens: jax.Array, cache: dict, pos,
                    cfg: T.TransformerConfig,
                    window: int | None = None) -> tuple[jax.Array, dict]:
    """Run the decoder blocks over a K-token chunk, writing its K/V into
    the cache. Returns (block output x [B, K, D], updated cache) — the
    shared body of :func:`extend_step` and the head-free K/V write the
    device speculative loop uses (its eager last draft step discards the
    logits, so paying the lm_head vocab projection there is pure waste)."""
    x = _embed(params, tokens, cfg)                            # [B, K, D]
    b, n_q = tokens.shape
    if (_ring_capacity(cfg) or _has_ring_bufs(cache)) and n_q > 1:
        raise ValueError(
            "rolling KV cache (kv_cache_capacity) supports single-token "
            "decode steps only — chunked verify (speculative decoding) "
            "needs the linear cache")
    positions = _q_positions(pos, b, n_q)           # scalar or per-row pos
    rope = _rope_tables(positions, cfg)             # once, not per layer

    # Unrolled layer loop with static per-layer indices — NOT a lax.scan
    # with the caches as xs/ys (see _decode_block: scan forces whole-cache
    # copies every step)
    bufs = _kv_state(cache)
    block = _kinded_decode_block if cfg.kinded else _decode_block
    for li in range(cfg.n_layers):
        x, bufs = block(x, _layer_params(params, cfg, li), bufs, li, pos,
                        cfg, rope, window)
    return x, dict(bufs, length=pos + tokens.shape[1])


def extend_step(params: dict, tokens: jax.Array, cache: dict, pos,
                cfg: T.TransformerConfig,
                window: int | None = None) -> tuple[jax.Array, dict]:
    """Extend the cache with a K-token chunk at positions pos..pos+K-1.
    tokens: [B, K] int32; returns (logits [B, K, V] in
    cfg.logits_storage_dtype — logits[:, i] is the next-token distribution
    AFTER tokens[:, :i+1] — and the updated cache), rounded EXACTLY like
    the training forward so greedy decode agrees with it token for token.
    The chunked verify primitive for speculative decoding; K=1 is the
    plain decode step. ``window`` (static; vector ``pos`` only) routes
    the K/V writes through the bounded-window path —
    :func:`_window_write`."""
    x, new_cache = _blocks_forward(params, tokens, cache, pos, cfg, window)
    with jax.named_scope("lm_head"):
        logits = _head(params, _norm(x, params["final_norm"], cfg), cfg)
    return logits, new_cache


def decode_step(params: dict, token: jax.Array, cache: dict, pos,
                cfg: T.TransformerConfig,
                window: int | None = None) -> tuple[jax.Array, dict]:
    """One decode step. token: [B] int32; returns (logits [B, V] in
    cfg.logits_storage_dtype, updated cache). ``pos`` is the position
    being written (traced ok)."""
    logits, new_cache = extend_step(params, token[:, None], cache, pos, cfg,
                                    window)
    return logits[:, 0], new_cache


def _pad_prompts() -> bool:
    """Whether prefill right-pads prompts to flash-block-aligned lengths
    (needed on TPU; a seam so the CPU tests can force the padding path
    and pin its slicing/last-position logic)."""
    return not mosaic.interpret()


def _flash_safe_len(s: int) -> int:
    """Smallest sequence length >= s the TPU flash kernels accept: any
    length up to 256 tiles (block_q clamps to s; sub-128-lane cases fall
    back to dense attention inside flash_attention), lengths up to 1024
    must tile the 256-wide q blocks, and longer ones must tile the
    1024-wide kv blocks."""
    if s <= 256:
        return s
    if s <= 1024:
        return -(-s // 256) * 256
    return -(-s // 1024) * 1024


def prefill(params: dict, tokens: jax.Array, cfg: T.TransformerConfig,
            max_len: int) -> tuple[jax.Array, dict]:
    """Process the whole prompt in one forward, filling the cache.
    tokens: [B, S]; returns (last-position logits [B, V] in
    cfg.logits_storage_dtype, cache).

    Arbitrary prompt lengths: the TPU flash kernels need block-aligned
    sequences, so the forward runs at :func:`_flash_safe_len` with the
    prompt right-padded by zeros — causal masking keeps every REAL
    position's output independent of the padding tail, and only the real
    S rows of K/V are written to the cache (the returned logits read
    position S-1, not the padded end). Serving prompts are whatever
    length users send; without this, any prompt past 256 tokens that
    didn't tile the blocks raised at trace time. Caveat: with MoE
    layers, padded tokens still occupy router capacity (capacity scales
    with the PADDED length), so extreme padding can shift routing-drop
    behavior at low capacity factors."""
    b, s = tokens.shape
    cache = init_kv_cache(cfg, b, max_len)
    if _has_ring_bufs(cache):
        # a window kind's ring takes a prompt through the mini cache of
        # the bucketed prefill, landed row by row (place_rows)
        logits, mini = prefill_rows(params, tokens,
                                    jnp.full((b,), s, jnp.int32), cfg)
        cache = place_rows(dict(cache, length=jnp.zeros((b,), jnp.int32)),
                           mini, jnp.arange(b), mini["length"])
        return logits, dict(cache, length=jnp.asarray(s, jnp.int32))
    x, bufs = _prompt_forward(params, tokens, cfg, _kv_state(cache), s)
    return (_head(params, x[:, s - 1], cfg),
            dict(bufs, length=jnp.asarray(s, jnp.int32)))


def _prompt_forward(params, tokens, cfg, bufs, s, lengths=None):
    """The prompt forward shared by :func:`prefill` and
    :func:`prefill_rows`: right-pads ``tokens`` [B, s] to a flash-safe
    length when the kernels need it, runs the unrolled layer loop writing
    positions [0, s) of K/V into ``bufs``, and returns the final-norm'd
    activations [B, s_padded, D] plus the filled buffers — each caller
    does its own lm_head projection (last position for prefill, per-row
    true last positions for the bucketed variant). ``lengths`` [B]: the
    rows' true lengths where they differ (the bucketed variant); a model
    with experts routes only the positions below them."""
    b = tokens.shape[0]
    sp = _flash_safe_len(s) if _pad_prompts() else s
    if sp != s:
        tokens = jnp.pad(tokens, ((0, 0), (0, sp - s)))
    x = _embed(params, tokens, cfg)
    positions = jnp.broadcast_to(jnp.arange(sp), (b, sp))
    rope = _rope_tables(positions, cfg)                 # once, not per layer
    live = (positions < (s if lengths is None else lengths[:, None])
            if cfg.kinded else None)

    # Unrolled layers, prompt K/V written straight into the stacked cache
    # (same no-scan rationale as extend_step; int8 caches quantize at the
    # write — the prefill forward itself runs full-precision)
    for li in range(cfg.n_layers):
        p = _layer_params(params, cfg, li)
        if cfg.kinded:
            x, bufs = _kinded_prompt_block(x, p, bufs, li, cfg, rope, s,
                                           live)
            continue
        cos, sin = rope
        h = rms_norm_reference(x, p["attn_norm"], cfg.rms_eps)
        q = _weinsum("bsd,dhk->bshk", h, p["wq"])
        k = _weinsum("bsd,dhk->bshk", h, p["wk"])
        v = _weinsum("bsd,dhk->bshk", h, p["wv"])
        q, k = T.apply_rope(q, cos, sin), T.apply_rope(k, cos, sin)
        # GQA K/V go to the kernels unexpanded (flash/reference consume
        # kv_heads-wide K/V natively; no-op distinction for MHA)
        o = T._attention(q, k, v, None, window=cfg.attn_window or None)
        x = x + _weinsum("bshk,hkd->bsd", o, p["wo"])
        h = rms_norm_reference(x, p["mlp_norm"], cfg.rms_eps)
        x = x + _mlp(h, p, cfg)
        cap = _ring_capacity(cfg)
        if cap:
            # rolling cache: only the last min(s, cap) prompt positions
            # survive (everything older is outside every future query's
            # window anyway — cap >= attn_window); each lands at its
            # ring slot position % cap
            s0 = max(s - cap, 0)
            idx = jnp.arange(s0, s) % cap
            for n, c in _kv_writes(bufs, k[:, s0:s], v[:, s0:s]).items():
                layer = bufs[n][li].at[:, idx].set(_kv_flat(c),
                                                   unique_indices=True)
                bufs[n] = bufs[n].at[li].set(layer)
        else:
            for n, c in _kv_writes(bufs, k[:, :s], v[:, :s]).items():
                bufs[n] = _write_kv_chunk(bufs[n], c, li,
                                          jnp.asarray(0, jnp.int32), None)
    return _norm(x, params["final_norm"], cfg), bufs


def prefill_rows(params: dict, tokens: jax.Array, lengths: jax.Array,
                 cfg: T.TransformerConfig) -> tuple[jax.Array, dict]:
    """BUCKETED multi-prompt prefill: process K prompts right-padded to
    one shared bucket length in a single forward, compiling once per
    bucket instead of once per distinct prompt length. tokens: [K, S_b]
    int32 with each row's real prompt in its first ``lengths[k]``
    positions (``lengths`` is TRACED — any mix of real lengths reuses
    the bucket's compiled program); returns (per-row last-REAL-position
    logits [K, V], mini cache of S_b rows with per-row frontiers at the
    true lengths).

    Correctness of the padding tail: causal masking keeps every real
    position's output independent of the positions after it (the same
    argument :func:`prefill` makes for flash-block padding), and the
    padding rows' K/V beyond each row's frontier are unreachable by any
    future query — decode writes position ``lengths[k]`` before reading
    it, overwriting the first padding row, and queries attend positions
    <= their own only (the serve.py slot-reuse argument). MoE caveat as
    in :func:`prefill`: padded tokens still occupy router capacity.

    Rolling caches are rejected: ring writes wrap padded positions onto
    live rows (padding at position p lands on ring row p % C, clobbering
    real history), so ring configs keep the per-length admission path."""
    _check_no_ring(cfg, "bucketed prefill")
    k_rows, s = tokens.shape
    # a window kind's buffers are LINEAR here: the mini cache holds the
    # padded prompt's rows by position, and place_rows lands each row's
    # last ones in the ring by its own length
    cache = init_kv_cache(cfg, k_rows, s, ring=False)
    x, bufs = _prompt_forward(params, tokens, cfg, _kv_state(cache), s,
                              lengths)
    xl = x[jnp.arange(k_rows), lengths - 1]                   # [K, D]
    return (_head(params, xl, cfg),
            dict(bufs, length=lengths.astype(jnp.int32)))


def _ring_rows_of(mini_buf, lengths, c: int):
    """What a ring of ``c`` rows holds of prompts prefilled LINEARLY:
    ``mini_buf`` [L, K, S, F] by position (S > c), row k real up to
    ``lengths[k]``. Ring row r takes the LAST position p < length with
    p = r (mod c) — ``r + c * floor((length - 1 - r) / c)`` — so a
    prompt's last ``min(length, c)`` rows land where decode's ``pos %
    c`` writes would have put them, and the padding tail lands nowhere
    (the reason a flat ring model admits at exact lengths). Ring rows no
    position reaches (r >= length) take position 0's row: never read —
    a query at q masks every ring row whose offset ``(q - r) mod c``
    is beyond ``q``, and decode writes row r before a query reaches it.
    Returns [L, K, c, F]."""
    r = jnp.arange(c)[None, :]
    src = r + c * ((lengths[:, None] - 1 - r) // c)             # [K, c]
    src = jnp.clip(src, 0, mini_buf.shape[2] - 1)
    return jax.vmap(lambda m, i: jnp.take(m, i, axis=1),
                    in_axes=(1, 0), out_axes=1)(mini_buf, src)


def place_rows(cache: dict, mini: dict, rows: jax.Array,
               lengths: jax.Array) -> dict:
    """Land a K-row mini cache's K/V into cache slots ``rows`` — the
    multi-row counterpart of serve.py's single-slot placement: one
    scatter on the batch axis per buffer (k/v plus int8 scales) covering
    positions [0, S_b), and the slots' frontiers set to their true
    ``lengths``. Out-of-range row indices are DROPPED (standard jit
    scatter semantics) — the batched admission path pads its row vector
    with distinct out-of-range sentinels, so a partial admission batch
    writes exactly its real rows.

    A ``window`` kind's ring (``_RING_BUFS``) shorter than the bucket
    takes, per row, the last ``min(length, C)`` rows by position modulo
    its row count (:func:`_ring_rows_of`), the whole slot in one
    scatter; a bucket that fits the ring lands as any linear buffer
    (no position has wrapped yet). An ``ssm`` kind's state buffers
    (``_STATE_BUFS``) land WHOLE, each slot's over whatever the slot held
    — the one thing that stands between a reused slot's residue, or an
    idle slot's garbage, and its next occupant."""
    s_b = cache_rows(mini)
    placed = {}
    for n, m in _kv_bufs(mini).items():
        c = cache[n].shape[2]
        if n in _RING_BUFS and s_b > c:
            placed[n] = cache[n].at[:, rows].set(
                _ring_rows_of(m, lengths, c), mode="drop",
                unique_indices=True)
        else:
            placed[n] = cache[n].at[:, rows, :s_b].set(
                m, mode="drop", unique_indices=True)
    for n in _STATE_BUFS:
        if n in mini:       # no positions: a slot's state lands WHOLE
            placed[n] = cache[n].at[:, rows].set(
                mini[n], mode="drop", unique_indices=True)
    return dict(cache, **placed, length=cache["length"].at[rows].set(
        lengths.astype(jnp.int32), mode="drop", unique_indices=True))


def extract_kv_rows(mini: dict, widths,
                    cfg: T.TransformerConfig) -> list[dict]:
    """Per-row HOST copies of a K-row mini cache's buffers — the
    extraction half of disaggregated serving's KV shipment
    (:func:`place_rows` is the landing half). Row ``i`` ships its first
    ``widths[i]`` positions of every buffer (k/v plus int8 scales when
    the cache is quantized — quantized caches ship their int8 payload
    as-is, never dequantized): for linear caches that is the true
    prompt length — the bucket-padding tail past the frontier is
    unreachable garbage, so shipping it would double the bytes of a
    short prompt for nothing — and for rolling (ring) caches the full
    capacity, whose positional wrap only a whole-slot landing
    preserves. Returns one ``{name: np [L, 1, w, KV, hd]}`` dict per
    row — the wire form (:func:`kv_to_wire`, a free host reshape) —
    every layer's slice in one device fetch per row."""
    bufs = _kv_bufs(mini)
    out = []
    for i, w in enumerate(widths):
        w = int(w)
        out.append(kv_to_wire(jax.device_get(
            {n: b[:, i:i + 1, :w] for n, b in bufs.items()}), cfg))
    return out


def _filter_logits(logits, temperature: float, top_k: int, top_p: float):
    """The sampling filter stack on [..., V] f32 logits: top-k mask →
    temperature → top-p nucleus mask (keep the smallest prefix of the
    temperature-scaled distribution whose cumulative probability reaches
    ``top_p``; the crossing token stays; ties at the cutoff logit are
    all kept — the usual trade for a sort-free vocab-order mask; 0
    disables). Returns unnormalized log-space logits whose softmax IS
    the sampling distribution — shared by ad-hoc sampling
    (:func:`_sample`) and speculative SAMPLING, where the accept ratio
    must be computed against exactly the filtered distributions both
    models sample from. ``temperature`` must be > 0 here (the greedy
    case never needs a distribution)."""
    if top_k > 0:
        vals, _ = jax.lax.top_k(logits, top_k)
        cutoff = vals[..., -1][..., None]
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    scaled = logits / temperature
    if 0.0 < top_p < 1.0:
        desc = -jnp.sort(-scaled, axis=-1)                   # descending
        probs = jax.nn.softmax(desc, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep positions whose PRECEDING mass is < top_p (the crossing
        # token stays; position 0 always kept)
        kept = (cum - probs) < top_p
        last = kept.sum(axis=-1) - 1
        cut = jnp.take_along_axis(desc, last[..., None], axis=-1)
        scaled = jnp.where(scaled < cut, -jnp.inf, scaled)
    return scaled


@jax.named_scope("sample")
def _sample(logits, rng, temperature: float, top_k: int,
            top_p: float = 0.0):
    """logits [B, V] → (token [B], logprob [B]). Math in f32 whatever the
    storage dtype. Filters compose per :func:`_filter_logits`.

    The returned logprob is the MODEL's log p(token) — computed from the
    raw logits, before any masking or temperature — so it is usable for
    perplexity / importance weights regardless of sampling settings."""
    logits = logits.astype(jnp.float32)
    model_logp = jax.nn.log_softmax(logits, axis=-1)
    if temperature == 0.0:
        # top-k cannot change an argmax (the argmax is in every top-k)
        token = jnp.argmax(logits, axis=-1)
    else:
        token = jax.random.categorical(
            rng, _filter_logits(logits, temperature, top_k, top_p),
            axis=-1)
    return token, jnp.take_along_axis(model_logp, token[:, None],
                                      axis=-1)[:, 0]


def _check_no_ring(cfg, what: str):
    """Entry points whose cache discipline needs the LINEAR cache
    (chunked verifies, beam gathers, prefix templates) reject rolling
    caches up front — a silent wrong-output would be far worse."""
    if _ring_capacity(cfg):
        raise ValueError(f"{what} requires a linear KV cache; unset "
                         f"kv_cache_capacity (rolling cache) for it")


def _check_draft_vocab(cfg, draft_cfg):
    """Speculation compares TOKEN IDS between draft and target, so the
    two models must share a vocabulary. A mismatch is silent corruption
    in greedy mode (target ids past the draft's vocab clamp in its
    embedding gather, producing garbage proposals) and a shape error in
    sampled mode — reject it up front. (Every speculative entry point
    passes here, so this is also where a model with layer_kinds is
    refused.)"""
    for c in (cfg, draft_cfg):
        c.refuse("speculative decoding")
    if draft_cfg.vocab_size != cfg.vocab_size:
        raise ValueError(
            f"draft vocab_size {draft_cfg.vocab_size} != target "
            f"vocab_size {cfg.vocab_size}: speculative decoding requires "
            f"a shared vocabulary (same tokenizer)")


def _propose_chunk(params, draft_params, t_cache, d_cache, pending,
                   pos_arg, cfg, draft_cfg, k, win, token_dtype,
                   propose, extra_xs):
    """The draft-propose + target-verify scaffold shared by the greedy
    and sampled speculative rounds: the draft runs ``k`` single steps
    following ``pending`` (a ``lax.scan``; the LAST proposal's K/V is
    written eagerly through the head-free block body), then the target
    verifies the k+1-wide chunk in one :func:`extend_step`.

    ``propose(logits, x)`` picks each draft step's next token from the
    draft's [B, V] logits (``x`` is that step's element of
    ``extra_xs`` — rng keys for sampling, unused for greedy) and
    returns ``(token [B], aux)``; the per-step ``aux`` pytrees come
    back stacked (the sampled round collects the draft's sampling
    distributions this way).

    ``pos_arg`` is the position handed to the decode stack — a scalar
    (uniform frontier fast path) or a [B] vector (per-row frontiers);
    ``win`` routes vector-position K/V writes through the bounded-window
    path. Returns ``(chunk [B, k+1], auxes, logits [B, k+1, V],
    t_cache, d_cache)`` with ``chunk[:, 0] == pending``."""
    b = pending.shape[0]

    def d_step(carry, xs):
        i, x = xs
        tok, cache = carry
        logits, cache = decode_step(draft_params, tok, cache,
                                    pos_arg + i, draft_cfg, win)
        # keep the carried length [B]-shaped: the scalar-pos fast path
        # (b==1) returns a scalar length, which would flip the scan
        # carry's type
        cache = dict(cache, length=jnp.broadcast_to(
            cache["length"], (b,)).astype(jnp.int32))
        nxt, aux = propose(logits, x)
        return (nxt.astype(token_dtype), cache), (tok, aux)

    (last, d_cache), (fed, auxes) = jax.lax.scan(
        d_step, (pending, d_cache), (jnp.arange(k), extra_xs))
    _, d_cache = _blocks_forward(draft_params, last[:, None],
                                 d_cache, pos_arg + k, draft_cfg, win)
    # proposed[0] == pending; drafts are proposed[1:]
    proposed = jnp.concatenate([fed, last[None]])           # [k+1, B]
    chunk = proposed.T                                      # [B, k+1]
    logits, t_cache = extend_step(params, chunk, t_cache, pos_arg, cfg,
                                  win)
    return chunk, auxes, logits, t_cache, d_cache


def _propose_and_verify(params, draft_params, t_cache, d_cache, pending,
                        pos_arg, cfg, draft_cfg, k, win, token_dtype):
    """One GREEDY speculative round, shared by
    :func:`speculative_generate_device` and the serving path
    (:class:`tony_tpu.models.serve`'s speculative batcher), built on
    :func:`_propose_chunk`. Returns ``(chunk [B, k+1],
    argmaxes [B, k+1], acc [B], t_cache, d_cache)`` where ``argmaxes``
    are the target's greedy continuations after each chunk prefix and
    ``acc`` is the per-row length of the longest draft prefix the
    target agreed with. The COMMIT decision (how much of the chunk each
    row keeps) is the caller's — generation clamps to budgets/windows,
    serving clamps to nothing."""
    chunk, _, logits, t_cache, d_cache = _propose_chunk(
        params, draft_params, t_cache, d_cache, pending, pos_arg, cfg,
        draft_cfg, k, win, token_dtype,
        propose=lambda lg, _: (jnp.argmax(lg, axis=-1), ()),
        extra_xs=jnp.zeros((k,), jnp.int32))
    argmaxes = jnp.argmax(logits, axis=-1).astype(token_dtype)
    # per-row accepted = longest prefix where draft matched target
    matches = (chunk[:, 1:] == argmaxes[:, :k]).astype(jnp.int32)
    acc = jnp.cumprod(matches, axis=1).sum(axis=1)          # [B], 0..k
    return chunk, argmaxes, acc, t_cache, d_cache


def _propose_and_verify_sampled(params, draft_params, t_cache, d_cache,
                                pending, pos_arg, cfg, draft_cfg, k, win,
                                token_dtype, rng, temperature, top_k,
                                top_p):
    """One SPECULATIVE-SAMPLING round (the rejection-sampling
    counterpart of :func:`_propose_and_verify`): the draft SAMPLES k
    tokens from its filtered distribution q, the target verifies the
    chunk once, and each proposal x_i is accepted with probability
    ``min(1, p_i(x_i)/q_i(x_i))`` — the classic scheme whose committed
    tokens are distributed EXACTLY as target-only sampling from the
    filtered p, for any draft. On the first rejection the round's extra
    token is drawn from the residual ``normalize(max(p - q, 0))``; on
    full acceptance, from the bonus position's p (equivalently: residual
    against q = 0). Both models' distributions run through the SAME
    filter stack (:func:`_filter_logits`) — filtering only p or only q
    would break the guarantee.

    Returns ``(chunk [B, k+1], extra [B], acc [B], t_cache, d_cache)``:
    ``chunk[:, :acc+1]`` are committable tokens and ``extra`` is the
    round's residual/bonus sample — the next ``pending`` when the caller
    commits the full ``acc + 1``. A caller clamping its commit BELOW
    ``acc + 1`` (budget/window) must take ``chunk[:, count]`` as pending
    instead: an accepted draft token is itself a faithful sample of
    p( · | chunk[:count]) — that is precisely what acceptance certifies
    — while ``extra`` belongs to the deeper position only.

    The accept test is ``u * q(x) < p(x)`` (never divides; q(x) > 0
    because x was sampled from q). All probability math in f32.

    ``rng`` may be one PRNGKey (a shared per-round stream — the
    generate-path callers) or a [B, 2] array of PER-ROW keys (the
    serving path: each slot's draws come from its own request-derived
    stream, so a request's sampled tokens are a function of (request,
    round index) alone — independent of which other requests share the
    batch or when admission happened, which is what lets the pipelined
    serve loop shift admission timing without changing outputs)."""
    b = pending.shape[0]
    per_row = rng.ndim == 2
    if per_row:
        trip = jax.vmap(lambda kk: jax.random.split(kk, 3))(rng)
        d_rng, u_rng, r_rng = trip[:, 0], trip[:, 1], trip[:, 2]
        # [k, B, 2]: scan xs of per-row draft-step keys
        d_xs = jax.vmap(lambda kk: jax.random.split(kk, k))(
            d_rng).transpose(1, 0, 2)
    else:
        d_rng, u_rng, r_rng = jax.random.split(rng, 3)
        d_xs = jax.random.split(d_rng, k)
    vocab = cfg.vocab_size

    def propose(logits, key):
        f = _filter_logits(logits.astype(jnp.float32), temperature,
                           top_k, top_p)
        tok = (jax.vmap(jax.random.categorical)(key, f) if per_row
               else jax.random.categorical(key, f, axis=-1))
        return tok, jax.nn.softmax(f, axis=-1)

    chunk, qs, logits, t_cache, d_cache = _propose_chunk(
        params, draft_params, t_cache, d_cache, pending, pos_arg, cfg,
        draft_cfg, k, win, token_dtype,
        propose=propose, extra_xs=d_xs)
    p = jax.nn.softmax(_filter_logits(logits.astype(jnp.float32),
                                      temperature, top_k, top_p),
                       axis=-1)                             # [B, k+1, V]
    x = chunk[:, 1:].astype(jnp.int32)[..., None]           # [B, k, 1]
    q_bkv = qs.transpose(1, 0, 2)                           # [B, k, V]
    qx = jnp.take_along_axis(q_bkv, x, axis=2)[..., 0]
    px = jnp.take_along_axis(p[:, :k], x, axis=2)[..., 0]   # [B, k]
    u = (jax.vmap(lambda kk: jax.random.uniform(kk, (k,)))(u_rng)
         if per_row else jax.random.uniform(u_rng, (b, k)))
    accept = (u * qx < px).astype(jnp.int32)
    acc = jnp.cumprod(accept, axis=1).sum(axis=1)           # [B], 0..k

    # residual/bonus at the decision position: q rows padded with a zero
    # slab so acc == k selects residual against 0, i.e. the bonus p
    sel = acc[:, None, None]                                # [B, 1, 1]
    p_sel = jnp.take_along_axis(p, sel, axis=1)[:, 0]       # [B, V]
    q_pad = jnp.concatenate(
        [q_bkv, jnp.zeros((b, 1, vocab), jnp.float32)], axis=1)
    q_sel = jnp.take_along_axis(q_pad, sel, axis=1)[:, 0]
    res = jnp.maximum(p_sel - q_sel, 0.0)
    # numeric guard: mathematically res sums to > 0 whenever a rejection
    # happened, but f32 cancellation can zero it — fall back to p
    res = jnp.where(res.sum(-1, keepdims=True) > 0, res, p_sel)
    extra = (jax.vmap(jax.random.categorical)(r_rng, jnp.log(res))
             if per_row
             else jax.random.categorical(r_rng, jnp.log(res), axis=-1)
             ).astype(token_dtype)
    return chunk, extra, acc, t_cache, d_cache


def speculative_generate(params: dict, draft_params: dict, prompt: jax.Array,
                         cfg: T.TransformerConfig,
                         draft_cfg: T.TransformerConfig,
                         max_new_tokens: int,
                         num_speculative: int = 4) -> jax.Array:
    """Greedy speculative decoding: a cheap draft model proposes
    ``num_speculative`` tokens per round; the target model verifies the
    whole chunk in ONE chunked :func:`extend_step` and commits the longest
    prefix matching its own argmax chain plus one corrected token — output
    is token-identical to the target model's greedy :func:`generate`, in
    (accepted+1) tokens per target call instead of 1.

    Batch size must be 1: acceptance length is data-dependent per row and
    the cache keeps a single scalar length. Returns tokens
    [1, prompt_len + max_new_tokens].

    Caveats (measured, not theoretical):
    - Exactness holds when chunked and single-step logits agree exactly.
      That is true where matmul accumulation is shape-independent (the
      CPU test path — bit-identical). On TPU, XLA's default matmul
      precision runs even f32 models through bf16 passes, so chunk vs
      single-step logits differ by ~1e-2 and a near-tie argmax can flip
      regardless of dtype: occasional tokens (and everything after the
      first flip) may differ from plain greedy — both are valid greedy
      decodes of the model within matmul noise.
    - This is a host-driven reference implementation: each round syncs with
      the device for the acceptance decision, so wall-clock wins require
      low host-device latency (it is NOT faster when the driving host
      is remote from the device and every sync costs a network round
      trip).
    """
    b, s = prompt.shape
    if b != 1:
        raise ValueError("speculative_generate supports batch size 1")
    if num_speculative < 1:
        raise ValueError("num_speculative must be >= 1 (use generate() for "
                         "plain greedy decoding)")
    _check_draft_vocab(cfg, draft_cfg)
    _check_no_ring(cfg, "speculative decoding")
    _check_no_ring(draft_cfg, "speculative decoding (draft)")
    k = num_speculative
    max_len = s + max_new_tokens + k + 1
    t_logits, t_cache = prefill(params, prompt, cfg, max_len)
    _, d_cache = prefill(draft_params, prompt, draft_cfg, max_len)

    # Donate the cache: a non-donated jit input cannot alias its output, so
    # without this every call would copy the full stacked K/V buffers —
    # exactly the whole-cache-copy cost the unrolled layer loop removed
    # inside generate()'s single jit. Each round threads the returned cache
    # forward and never touches the donated input again.
    extend_t = jax.jit(extend_step, static_argnames=("cfg",),
                       donate_argnames=("cache",))
    step_d = jax.jit(decode_step, static_argnames=("cfg",),
                     donate_argnames=("cache",))

    out: list[int] = []
    # pending = committed token whose K/V is not yet in the target cache
    pending = int(jnp.argmax(t_logits, axis=-1)[0])
    pos = s
    while len(out) < max_new_tokens:
        # draft proposes k tokens following `pending` from its own cache
        drafts = []
        tok = jnp.array([pending])
        d_pos = pos
        for _ in range(k):
            d_logits, d_cache = step_d(draft_params, tok, d_cache, d_pos,
                                       draft_cfg)
            tok = jnp.argmax(d_logits, axis=-1)
            drafts.append(int(tok[0]))
            d_pos += 1
        # target verifies [pending, d1..dk] in one chunk
        chunk = jnp.array([[pending] + drafts])
        logits, t_cache = extend_t(params, chunk, t_cache, pos, cfg)
        argmaxes = jnp.argmax(logits[0], axis=-1)      # [k+1]
        out.append(pending)
        accepted = 0
        for i in range(k):
            if len(out) >= max_new_tokens:
                break
            if drafts[i] != int(argmaxes[i]):
                break
            out.append(drafts[i])
            accepted += 1
        new_pending = int(argmaxes[accepted])
        if accepted == k:
            # full acceptance: the draft cache never wrote d_k's K/V (it
            # produced d_k as output only); backfill so the next round's
            # history is complete.
            _, d_cache = step_d(draft_params, jnp.array([drafts[-1]]),
                                d_cache, pos + k, draft_cfg)
        # Roll both caches back to the committed frontier. Stale entries
        # from rejected drafts are NOT masked (attention reads positions
        # <= each query's own position, not the length field) — they are
        # safe only because the next round's k+1-wide chunk rewrites the
        # whole stale region (<= k entries) before any query can reach it.
        pos += 1 + accepted
        t_cache = dict(t_cache, length=jnp.asarray(pos, jnp.int32))
        d_cache = dict(d_cache, length=jnp.asarray(pos, jnp.int32))
        pending = new_pending
    tokens = jnp.array([out[:max_new_tokens]], dtype=prompt.dtype)
    return jnp.concatenate([prompt, tokens], axis=1)


@functools.partial(jax.jit, static_argnames=(
    "cfg", "draft_cfg", "max_new_tokens", "num_speculative", "commit",
    "window", "temperature", "top_k", "top_p", "return_rounds"))
def speculative_generate_device(params: dict, draft_params: dict,
                                prompt: jax.Array,
                                cfg: T.TransformerConfig,
                                draft_cfg: T.TransformerConfig,
                                max_new_tokens: int,
                                num_speculative: int = 4,
                                commit: str = "window",
                                window: int = 0,
                                temperature: float = 0.0,
                                top_k: int = 0,
                                top_p: float = 0.0,
                                rng: jax.Array | None = None,
                                return_rounds: bool = False) -> jax.Array:
    """Speculative decoding as ONE compiled device program — greedy
    (token-exact) by default, rejection-SAMPLED (distribution-exact)
    at ``temperature > 0``.

    The host-driven :func:`speculative_generate` syncs with the device
    every round for the acceptance decision — a host round trip per
    round, a network one when the driver is remote. This version runs
    the whole draft→verify→accept loop inside a ``lax.while_loop``: the draft
    proposes ``k`` tokens (a ``lax.scan`` of single steps), the target
    verifies the k+1 chunk in one :func:`extend_step`, and the accepted
    prefix length is a cumulative-product reduction — no host in the
    loop. Output is token-identical to the target model's greedy
    :func:`generate` wherever chunked and single-step logits agree
    (bit-exact on CPU; on TPU the default matmul precision can flip
    near-tie argmaxes in any dtype — see :func:`speculative_generate`'s
    caveats). Any batch size — see the min-commit paragraph below.

    Not measured on a chip attached to its host; the host-driven
    version pays one device sync per round, which is exactly what the
    while_loop removes. Wall-clock wins over plain :func:`generate`
    additionally require a draft that actually predicts the target
    (tokens/round ≈ 1 + acceptance·k); with a random draft this is a
    correctness demonstration, not a speedup.

    Batch > 1 uses PER-ROW CACHE FRONTIERS: acceptance length is
    data-dependent per row, so the cache ``length`` and every position
    argument generalize to [B] vectors — RoPE positions, causal masks,
    and the K/V writes all take per-row frontiers. Each row commits its
    OWN ``acc_r + 1`` tokens per round; no row waits for the batch
    minimum, so tokens/round does not decay as per-row acceptances
    diverge (the min-commit design this replaced decayed toward 1 with
    batch). Rows that reach ``max_new_tokens`` freeze (commit clamped
    to 0) while the rest finish. ``commit="per_row"`` writes each row's
    K/V at its own frontier with a unique-index scatter; the default
    ``commit="window"`` (below) keeps per-row commits but replaces the
    scatter with a bounded-window write — measured faster at every
    acceptance level (interleaved medians, one v5e, b8 k=10: +7% at
    near-perfect acceptance, +4.5% at a mediocre draft, +36% over
    min-commit).

    ``commit="min"`` restores the decayed min-commit schedule (every row
    commits the batch-minimum acceptance) — kept as the baseline the
    tests compare the per-row schedules against, not for production use.
    ``return_rounds=True`` additionally returns the number of
    draft→verify rounds executed (tokens/round = the speculation
    efficiency the sweep records).

    ``commit="window"`` (the default) is per-row commit with the cache
    writes routed
    through the scatter-free bounded-window path (:func:`_window_write`):
    rows commit their own acceptance like ``per_row``, EXCEPT a row more
    than ``window - (k+1)`` positions ahead of the slowest active row is
    clamped to that bound, so every round's writes land inside one
    ``window``-wide contiguous slice of the cache (one dynamic slice +
    an MXU one-hot merge instead of a global-cache scatter). ``window``
    defaults to ``4*(k+1)`` — wide enough that clamping only bites when
    per-row acceptances diverge persistently, at which point the
    schedule degrades gracefully toward min-commit rather than paying
    the scatter. Rows that finish FREEZE their true frontier and are
    excluded from the window base (no drag-along writes are needed: a
    frozen row's surrogate position is clipped into the active window
    and its writes are garbage into its own dead cache rows, which
    nothing reads — its committed tokens already live in the output
    buffer). Window-invariant (enforced each round, relied on by
    ``_window_write``): for every active row,
    ``pos_r - min(active pos) <= window - (k+1)``; the clamp preserves
    it because the slowest active row is never clamped and every other
    row is cut to exactly the bound. Token-identical to greedy, same as
    the other schedules (test-verified, including forced-clamp windows).

    Cache discipline (static shapes throughout): the target's stale
    entries from rejected drafts are overwritten by the next round's
    k+1-wide per-row chunk before any query can reach them (same argument
    as the host version, applied row-wise); the draft runs k+1 steps per
    round — the last proposal's K/V is written eagerly — so full
    acceptance needs no backfill branch. The token buffer is written with
    full k+1-wide rows at each row's own offset: positions past the
    committed count are garbage that the next round's write (which starts
    exactly there) or the final slice removes.

    ``temperature > 0`` switches every round to SPECULATIVE SAMPLING
    (:func:`_propose_and_verify_sampled`, rng required): the draft
    samples its proposals, the target accept/rejects each with the
    classic ``min(1, p/q)`` test, and the committed stream is
    distributed exactly as target-only sampling through the same
    ``top_k``/``top_p`` filter stack as :func:`generate` — for ANY
    draft (a bad draft costs rounds, never correctness;
    distribution-verified against direct sampling in the tests). All
    commit schedules compose: a row clamped below its acceptance takes
    the accepted draft token at the cut as its next pending (itself a
    faithful sample at that position), the unclamped row takes the
    round's residual/bonus sample.
    """
    b, s = prompt.shape
    k = num_speculative
    if k < 1:
        raise ValueError("num_speculative must be >= 1")
    if commit not in ("per_row", "min", "window"):
        raise ValueError(f"unknown commit policy {commit!r}")
    if temperature > 0.0 and rng is None:
        raise ValueError("speculative sampling (temperature > 0) "
                         "requires an rng key")
    _check_draft_vocab(cfg, draft_cfg)
    _check_no_ring(cfg, "speculative decoding")
    _check_no_ring(draft_cfg, "speculative decoding (draft)")
    if commit == "window":
        # default + validate at ANY batch size (a window accepted at b=1
        # must not start raising when the batch widens), though the
        # window write itself only engages at b > 1
        window = window or 4 * (k + 1)
        if window < k + 2:
            raise ValueError(f"window must be >= num_speculative + 2 "
                             f"(chunk width k+1 plus >= 1 slack), got "
                             f"{window}")
    if commit == "window" and b > 1:
        # `window` rows of tail padding suffice: the target-chunk write's
        # base (= the slowest active row, < s+max_new_tokens) never
        # clamps, and the draft writes' base (+i <= +k) clamps by at most
        # c = amin + k - (s+max_new_tokens) <= k-1 rows, which keeps
        # every K=1 offset at slack + c <= window - 2 — inside the
        # window (_window_write computes offsets AGAINST the clamped
        # base, so a clamp shifts the slice, not the write positions).
        # Oversizing further would inflate the padded cache the dense
        # attention path reads every step.
        max_len = s + max_new_tokens + window
    else:
        # includes commit="window" at b==1: the window path is a no-op
        # there (win=None routes to the scalar contiguous-slice writes),
        # so take per_row's k+2 padding rather than inflating the padded
        # cache the dense attention reads every step
        max_len = s + max_new_tokens + k + 2
    #: per-row divergence bound in window mode (chunk is k+1 wide)
    slack = window - (k + 1)
    t_logits, t_cache = prefill(params, prompt, cfg, max_len)
    _, d_cache = prefill(draft_params, prompt, draft_cfg, max_len)
    # per-row frontiers: vectorize the scalar length prefill produced so
    # the while_loop state pytree is shape-stable across rounds
    t_cache = dict(t_cache, length=jnp.full((b,), s, jnp.int32))
    d_cache = dict(d_cache, length=jnp.full((b,), s, jnp.int32))

    # new tokens land here; k+1 slack for the final round's overshoot
    # (commits clamp so no row's write can start past max_new_tokens)
    buf0 = jnp.zeros((b, max_new_tokens + k + 1), prompt.dtype)
    if temperature > 0.0:
        rng, p0_rng = jax.random.split(rng)
        pending0 = jax.random.categorical(
            p0_rng, _filter_logits(t_logits.astype(jnp.float32),
                                   temperature, top_k, top_p),
            axis=-1).astype(prompt.dtype)
    else:
        pending0 = jnp.argmax(t_logits, axis=-1).astype(prompt.dtype)

    def _pos_arg(pos):
        """Position argument for the decode stack: at batch 1 per-row and
        uniform frontiers coincide, so hand the cache writers the SCALAR
        form — the contiguous dynamic_update_slice path instead of the
        scatter, which measured ~17% slower end-to-end at b1."""
        return pos[0] if b == 1 else pos

    # static per-call write mode: bounded-window writes only make sense
    # for genuinely per-row (vector) positions
    win = window if (commit == "window" and b > 1) else None

    def round_body(state):
        if temperature > 0.0:
            (t_cache, d_cache, buf, n_gen, pending, pos, rounds,
             cur_rng) = state
            cur_rng, round_rng = jax.random.split(cur_rng)
        else:
            t_cache, d_cache, buf, n_gen, pending, pos, rounds = state

        if win is not None:
            # frozen rows (n_gen == max_new_tokens) are excluded from the
            # window base — otherwise their pinned frontier would stall
            # the window and deadlock the still-active rows — and fed a
            # surrogate position clipped into the active window (their
            # writes/reads are garbage in dead rows; see docstring)
            active = n_gen < max_new_tokens
            amin = jnp.min(jnp.where(active, pos, jnp.iinfo(jnp.int32).max))
            pos_fed = jnp.where(active, pos,
                                jnp.clip(pos, amin, amin + slack))
        else:
            pos_fed = pos

        if temperature > 0.0:
            chunk, extra, acc, t_cache, d_cache = (
                _propose_and_verify_sampled(
                    params, draft_params, t_cache, d_cache, pending,
                    _pos_arg(pos_fed), cfg, draft_cfg, k, win,
                    prompt.dtype, round_rng, temperature, top_k, top_p))
        else:
            chunk, argmaxes, acc, t_cache, d_cache = _propose_and_verify(
                params, draft_params, t_cache, d_cache, pending,
                _pos_arg(pos_fed), cfg, draft_cfg, k, win, prompt.dtype)
        # per-row commit, clamped so finished rows freeze and no write
        # can overrun the buffer slack
        committed = jnp.min(acc) if commit == "min" else acc
        count = jnp.minimum(committed + 1, max_new_tokens - n_gen)  # [B]
        if win is not None:
            # window clamp: no row may end the round more than `slack`
            # past the slowest still-active row. The min-achieving row is
            # never clamped (count_min + slack >= count_min), so the
            # post-clamp active minimum EQUALS amin_next and the window
            # invariant holds next round; every active row still
            # advances >= 1 (amin_next >= amin + 1 and pos <= amin +
            # slack give the bound >= 1), so the loop terminates.
            amin_next = jnp.min(jnp.where(active, pos + count,
                                          jnp.iinfo(jnp.int32).max))
            # min-then-max (not clip): a frozen row ahead of the window
            # has a NEGATIVE bound, and clip(x, 0, neg) is neg under
            # numpy semantics — the max(..., 0) keeps its count frozen
            count = jnp.maximum(
                jnp.minimum(count, amin_next + slack - pos), 0)
        b_idx = jnp.arange(b)[:, None]
        buf = buf.at[b_idx, n_gen[:, None] + jnp.arange(k + 1)[None]].set(
            chunk, unique_indices=True)
        if temperature > 0.0:
            # committed in full: the residual/bonus sample continues the
            # stream; clamped below acc+1: the accepted draft token AT
            # the cut is itself a faithful sample there (see
            # _propose_and_verify_sampled)
            cont = jnp.take_along_axis(
                chunk, jnp.clip(count, 0, k)[:, None], axis=1)[:, 0]
            new_pending = jnp.where(
                count > 0, jnp.where(count == acc + 1, extra, cont),
                pending)
        else:
            sel = jnp.clip(count - 1, 0, k)
            corr = jnp.take_along_axis(argmaxes, sel[:, None],
                                       axis=1)[:, 0]
            new_pending = jnp.where(count > 0, corr, pending)
        n_gen = n_gen + count
        pos = pos + count
        # rollback: stale cache entries past each row's pos are rewritten
        # by the next round's chunk before any query reaches them
        t_cache = dict(t_cache, length=pos.astype(jnp.int32))
        d_cache = dict(d_cache, length=pos.astype(jnp.int32))
        out = (t_cache, d_cache, buf, n_gen, new_pending, pos, rounds + 1)
        return out + (cur_rng,) if temperature > 0.0 else out

    def cond(state):
        return jnp.min(state[3]) < max_new_tokens

    state0 = (t_cache, d_cache, buf0,
              jnp.zeros((b,), jnp.int32), pending0,
              jnp.full((b,), s, jnp.int32), jnp.asarray(0, jnp.int32))
    if temperature > 0.0:
        state0 = state0 + (rng,)
    final = jax.lax.while_loop(cond, round_body, state0)
    buf, rounds = final[2], final[6]
    tokens = jnp.concatenate([prompt, buf[:, :max_new_tokens]], axis=1)
    return (tokens, rounds) if return_rounds else tokens


class BeamSearchOutput(NamedTuple):
    tokens: jax.Array    # [B, W, prompt_len + max_new_tokens], best first
    scores: jax.Array    # [B, W] sum of token logprobs (length-penalized
    #                      ordering; raw sums reported)
    lengths: jax.Array   # [B, W] generated tokens incl. eos (= max_new
    #                      when no eos was hit)


@functools.partial(jax.jit, static_argnames=(
    "cfg", "max_new_tokens", "beam_width", "length_penalty", "eos_id"))
def beam_search(params: dict, prompt: jax.Array, cfg: T.TransformerConfig,
                max_new_tokens: int, beam_width: int = 4,
                length_penalty: float = 0.0,
                eos_id: int | None = None) -> BeamSearchOutput:
    """KV-cache beam search: keep the ``beam_width`` highest-logprob
    continuations per prompt, expanding all beams in one batched decode
    step per token. One compiled program (prefill + ``lax.scan``), same
    static-shape discipline as :func:`generate`.

    Mechanics: the prompt prefills once per row and its cache tiles
    across beams ([L, B, S, KV·hd] → [L, B·W, S, KV·hd] — beams share
    history until they diverge); each step feeds every beam's last token
    (writing its K/V), forms the [B, W·V] successor scores, takes the
    top W, and GATHERS the cache along the beam axis by parent index —
    the standard reorder, O(cache) per step, which is why beam search
    costs ~W× greedy plus the reorder traffic. Finished beams (``eos``)
    reproduce themselves with frozen scores via a one-hot candidate
    mask, so static shapes hold while they stop growing.

    Ranking uses ``score / length**length_penalty`` (0 = pure logprob;
    higher favors longer continuations); returned beams are sorted by
    that key, best first, with the RAW logprob sums in ``scores`` and
    per-beam generated lengths (including the eos token) in
    ``lengths``. Tokens after a beam's eos are padding (the eos token
    repeated) — slice with ``lengths`` if you need exact sequences.

    Reference: green-field (SURVEY.md §2.3 — the reference delegates
    all decoding); verified against exhaustive-enumeration search and a
    cache-free reimplementation in ``tests/test_decode.py``."""
    b, s = prompt.shape
    w = beam_width
    if w < 1:
        raise ValueError("beam_width must be >= 1")
    if max_new_tokens < 1:
        raise ValueError("max_new_tokens must be >= 1")
    _check_no_ring(cfg, "beam search")
    cfg.refuse("beam search")
    v = cfg.vocab_size
    max_len = s + max_new_tokens
    logits, cache = prefill(params, prompt, cfg, max_len)

    # tile the prefilled cache across beams: [L, B, ...] -> [L, B*W, ...]
    # (all position buffers — k/v plus int8 scales when present)
    cache = dict({n: jnp.repeat(x, w, axis=1)
                  for n, x in _kv_bufs(cache).items()},
                 length=cache["length"])

    logp0 = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    scores, first = jax.lax.top_k(logp0, w)                  # [B, W]
    first = first.astype(prompt.dtype)
    alive0 = (jnp.ones((b, w), bool) if eos_id is None
              else first != eos_id)
    tok_buf0 = jnp.zeros((b, w, max_new_tokens), prompt.dtype)
    tok_buf0 = tok_buf0.at[:, :, 0].set(first)
    len0 = jnp.ones((b, w), jnp.int32)

    def step(carry, i):
        cache, scores, last, alive, tok_buf, lens = carry
        flat_last = last.reshape(b * w)
        logits, cache = decode_step(params, flat_last, cache,
                                    cache["length"], cfg)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32),
                                  axis=-1).reshape(b, w, v)
        if eos_id is not None:
            # finished beams emit ONLY eos (a self-reproducing padding
            # token) at no score cost; everything else is -inf
            eos_only = jnp.full((v,), -jnp.inf).at[eos_id].set(0.0)
            logp = jnp.where(alive[..., None], logp, eos_only)
        cand = scores[..., None] + logp                      # [B, W, V]
        new_scores, idx = jax.lax.top_k(cand.reshape(b, w * v), w)
        parent = idx // v                                    # [B, W]
        token = (idx % v).astype(prompt.dtype)

        # reorder every per-beam tensor by parent; the cache gathers
        # along its flattened B*W axis
        gidx = (jnp.arange(b)[:, None] * w + parent).reshape(-1)
        cache = dict(cache, **{n: x[:, gidx]
                               for n, x in _kv_bufs(cache).items()})
        take = functools.partial(jnp.take_along_axis, indices=parent,
                                 axis=1)
        alive = take(alive)
        lens = take(lens)
        tok_buf = jnp.take_along_axis(
            tok_buf, parent[..., None], axis=1)
        tok_buf = tok_buf.at[:, :, i].set(token)
        lens = jnp.where(alive, lens + 1, lens)
        if eos_id is not None:
            alive = alive & (token != eos_id)
        return (cache, new_scores, token, alive, tok_buf, lens), None

    carry = (cache, scores, first, alive0, tok_buf0, len0)
    if max_new_tokens > 1:
        carry, _ = jax.lax.scan(step, carry,
                                jnp.arange(1, max_new_tokens))
    _, scores, _, _, tok_buf, lens = carry

    # order by the length-penalized key, report raw scores
    key = scores if length_penalty == 0.0 else (
        scores / (lens.astype(jnp.float32) ** length_penalty))
    order = jnp.argsort(-key, axis=1)
    scores = jnp.take_along_axis(scores, order, axis=1)
    lens = jnp.take_along_axis(lens, order, axis=1)
    tok_buf = jnp.take_along_axis(tok_buf, order[..., None], axis=1)
    prompts = jnp.broadcast_to(prompt[:, None], (b, w, s))
    return BeamSearchOutput(
        tokens=jnp.concatenate([prompts, tok_buf], axis=2),
        scores=scores, lengths=lens)


@functools.partial(jax.jit, static_argnames=("cfg", "max_new_tokens",
                                             "temperature", "top_k",
                                             "top_p"))
def generate(params: dict, prompt: jax.Array, cfg: T.TransformerConfig,
             max_new_tokens: int, rng: jax.Array,
             temperature: float = 0.0, top_k: int = 0,
             top_p: float = 0.0) -> GenerateOutput:
    """Prefill + scan-decode. prompt: [B, S] int32. Greedy when
    temperature=0; ``top_k``/``top_p`` (nucleus) filters compose as in
    :func:`_sample`. One compiled program; re-traces only on new static
    shapes/config."""
    b, s = prompt.shape
    max_len = s + max_new_tokens
    logits, cache = prefill(params, prompt, cfg, max_len)

    def step(carry, step_rng):
        logits, cache = carry
        token, logp = _sample(logits, step_rng, temperature, top_k, top_p)
        new_logits, cache = decode_step(params, token, cache,
                                        cache["length"], cfg)
        return (new_logits, cache), (token, logp)

    rngs = jax.random.split(rng, max_new_tokens)
    _, (tokens, logprobs) = jax.lax.scan(step, (logits, cache), rngs)
    return GenerateOutput(
        tokens=jnp.concatenate([prompt, tokens.T], axis=1),
        logprobs=logprobs.T)
