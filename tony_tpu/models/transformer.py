"""Flagship decoder-only transformer LM, TPU-first.

The model family the framework's parallelism layer is designed around (the
reference ships only MNIST example scripts — tony-examples/ — because it
delegates all compute; SURVEY.md §2.3 flags TP/SP/CP/EP as green-field
obligations for this build). Design choices, each mapped to the hardware:

- **bfloat16 everywhere, f32 where it matters**: params/activations bf16 for
  MXU throughput; logits, softmax and loss in f32.
- **Stacked layers + lax.scan**: one compiled block body instead of L copies
  (compile time, icache); pairs with ``jax.checkpoint`` for remat and with
  the pipeline layer (same [L, ...] leading-stage layout).
- **Logical-axis annotations**: every param carries logical axes resolved by
  tony_tpu.parallel.sharding rules, so DP→FSDP→TP+SP→EP is a mesh/rule
  change, not a model change.
- **Flash attention** (tony_tpu.ops) on TPU; ring attention over the ``cp``
  mesh axis for long context; dense reference elsewhere.
- **RoPE** positions (no position-embedding table to shard), RMSNorm,
  SwiGLU MLP — the standard modern decoder block.
- Optional **MoE** MLP (gshard dispatch over ``ep``) per
  tony_tpu.parallel.moe.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh

from tony_tpu.models import remat
from tony_tpu.ops import mosaic
from tony_tpu.ops.attention import flash_attention, reference_attention
from tony_tpu.ops.norms import rms_norm_reference
from tony_tpu.parallel.moe import moe_ffn
from tony_tpu.parallel.ring_attention import ring_attention
from tony_tpu.parallel.sharding import (DEFAULT_RULES, _is_axes_leaf,
                                        constrain, shard_attention)
from tony_tpu.models.train import masked_cross_entropy


@dataclasses.dataclass(frozen=True)
class RopeYarn:
    """YaRN scaling of the rotary frequencies (Peng et al. 2023, as the
    DeepSeek-V3 family configures it): each frequency blends the
    unscaled ``theta_i`` and ``theta_i / factor`` by a linear ramp between
    the dimensions at which ``beta_fast`` and ``beta_slow`` rotations fit
    in ``original_max`` positions. ``mscale`` / ``mscale_all_dim``: the
    cos/sin tables carry ``m(mscale) / m(mscale_all_dim)`` and the
    attention scale ``m(mscale_all_dim)**2``, ``m(x) = 0.1 x ln(factor)
    + 1``."""
    factor: float
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    original_max: int = 4096
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    def m(self, x: float) -> float:
        if self.factor <= 1.0 or not x:
            return 1.0
        return 0.1 * x * math.log(self.factor) + 1.0

    @property
    def table_scale(self) -> float:
        return self.m(self.mscale) / self.m(self.mscale_all_dim)

    @property
    def softmax_scale(self) -> float:
        return self.m(self.mscale_all_dim) ** 2


@dataclasses.dataclass(frozen=True)
class LatentAttention:
    """Latent attention (DeepSeek-V2's MLA): queries through a normed
    low-rank bottleneck (``q_rank``), keys and values from ONE compressed
    row a token — ``[c_kv (kv_rank, normed); k_rope (rope_dim)]``, the
    only thing the cache stores. A head's query is ``[nope_dim;
    rope_dim]`` wide and its value ``v_dim``; the rotary part of the key
    is one head shared by all."""
    q_rank: int
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    # constants on the normed bottlenecks (``c_q * q_scale``, ``c_kv *
    # kv_scale``; the cache stores the scaled c_kv): 1.0 = none
    q_scale: float = 1.0
    kv_scale: float = 1.0

    @property
    def row(self) -> int:
        """Values a token stores per layer."""
        return self.kv_rank + self.rope_dim

    @property
    def stored_row(self) -> int:
        """Width of the cache buffer's row: ``row`` rounded up to whole
        128-lane tiles, the tail zeros. With 576 minor the device pads to
        640 anyway, and — offered a layout with padding — the compiler
        chose to keep the ROWS minor inside the read loop and re-laid-out
        the whole cache around every layer's write (8 cache-sized copies
        a decode step; described-chip compile, PR 28)."""
        return -(-self.row // 128) * 128

    @property
    def qk_dim(self) -> int:
        return self.nope_dim + self.rope_dim


@dataclasses.dataclass(frozen=True)
class SparseExperts:
    """Routed SwiGLU experts, beside shared ones where there are any:
    every token scores all ``total`` experts — and ``n_zero`` more that
    have no weights —, picks ``top_k`` by score + selection bias, and
    weighs them by ``route``: ``"sigmoid"`` scores normalised over the
    pick x ``scale`` (:func:`tony_tpu.parallel.moe.sigmoid_route`), or
    ``"softmax"`` over all ``total + n_zero`` scores x ``scale``, NOT
    renormalised (:func:`tony_tpu.parallel.moe.softmax_route`). A pick
    is of one of three classes: a routed expert HELD here — THIS
    program holds ``[first, first + held)``, one rank's share of an
    expert-parallel layer, and computes their part
    (:func:`tony_tpu.parallel.moe.held_experts_ffn`; ``held == total``
    is the whole layer) —, a routed expert held elsewhere, whose part
    is left out, or a ZERO expert in ``[total, total + n_zero)``: the
    identity, ``w x h``, no weight read and no product. ``d_expert``:
    width of one routed expert and of each shared one. ``n_shared``
    shared experts (0: none) run whole for every token, their outputs
    summed, or averaged where ``shared_mean``
    (:func:`tony_tpu.parallel.moe.shared_experts_ffn`); one is a [d, f]
    leaf, several a [n_shared, d, f] stack."""
    total: int
    top_k: int
    d_expert: int
    scale: float = 1.0
    first: int = 0
    held: int | None = None
    n_shared: int = 1
    shared_mean: bool = False
    route: str = "sigmoid"
    n_zero: int = 0

    @property
    def n_held(self) -> int:
        return self.total if self.held is None else self.held

    @property
    def n_scored(self) -> int:
        """The router's outputs: the routed experts, then the zero ones."""
        return self.total + self.n_zero


@dataclasses.dataclass(frozen=True)
class StateSpace:
    """A state-space mixer (Mamba-2's selective scan, SSD): the normed
    stream goes through ONE projection to ``[z | c | dt]`` — a gate
    (``d_inner`` wide), the conv channels (``conv_dim``) and a step a
    head —; ``c`` through a depthwise causal convolution of ``d_conv``
    taps and a silu, then split into the heads' inputs ``x`` (``n_heads``
    of ``head_dim``) and ``B``, ``C`` (``d_state`` a group, shared by a
    group's heads); the recurrence of :mod:`tony_tpu.ops.ssm` per head;
    ``RMSNorm(y * silu(z))`` over all ``d_inner`` and the output
    projection. Its slot state is a FIXED-SIZE recurrence — ``[d_state,
    d_inner]`` and the conv's last ``d_conv - 1`` inputs — not rows by
    position (models/decode.py, ``state_layout``). ``chunk``: positions a
    chunk of the prompt scan. ``state_dtype``: what the recurrence is
    STORED in between steps (None: the model's ``dtype``); every step's
    arithmetic is float32."""
    n_heads: int
    head_dim: int
    d_state: int
    n_groups: int = 1
    d_conv: int = 4
    chunk: int = 256
    state_dtype: Any = None

    @property
    def d_inner(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        """Channels the convolution runs over: x, then B and C a group."""
        return self.d_inner + 2 * self.n_groups * self.d_state

    @property
    def in_dim(self) -> int:
        """Outputs of the input projection: ``[z | c | dt]``."""
        return self.d_inner + self.conv_dim + self.n_heads


#: layer kinds of a model with a ``layer_kinds`` list: name -> (the
#: mixer it runs, its feed-forward). Attention: ``latent`` (one
#: compressed row a token, :class:`LatentAttention`); ``window`` (GQA
#: K/V of ``head_dim``, RoPE over the whole head, query i sees keys j
#: with ``0 <= i - j < attn_window``: its state is a RING of about
#: ``attn_window`` rows a slot); ``full`` (the same K/V, plain causal
#: over the whole context and NO positional rotation — the interleaved
#: local/global convention, where the window layers carry the
#: positions: its state is ``max_len`` rows a slot). ``ssm`` is no
#: attention: a state-space mixer (:class:`StateSpace`) whose state is
#: the same size at every length. Feed-forward:
#: ``dense`` (a SwiGLU of ``d_ff``), ``moe`` (:class:`SparseExperts`), or
#: ``scmoe``: the DOUBLE layer with a shortcut-connected expert block —
#: two (attention, dense SwiGLU) halves in sequence, each attention
#: with its own rows of the cache, and ONE routed block that reads the
#: first half's normed stream and joins the stream after the second
#: half's SwiGLU (models/decode.py, ``_scmoe_decode_block``). Each
#: attention owns the cache buffers it writes, with its OWN row count
#: (models/decode.py, ``cache_layout``).
LAYER_KINDS = {
    "dense": ("latent", "dense"), "moe": ("latent", "moe"),
    "window_dense": ("window", "dense"), "window_moe": ("window", "moe"),
    "full_dense": ("full", "dense"), "full_moe": ("full", "moe"),
    "latent2_scmoe": ("latent", "scmoe"),
    "ssm_dense": ("ssm", "dense"),
}
#: feed-forwards that route through :class:`SparseExperts`
_ROUTED_FFNS = ("moe", "scmoe")


def kind_attentions(kind: str) -> int:
    """Attentions a layer of ``kind`` runs: the sets of cache rows it
    owns (2 for the double layer, 1 otherwise)."""
    return 2 if LAYER_KINDS[kind][1] == "scmoe" else 1


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32_000
    d_model: int = 512
    n_layers: int = 8
    n_heads: int = 8
    # Grouped-query attention: number of K/V heads (None = n_heads, plain
    # MHA). Shrinks K/V projections and — the real win — the decode cache
    # by n_heads/n_kv_heads; query heads attend their group's shared K/V.
    n_kv_heads: int | None = None
    # Width of one attention head. 0 = d_model // n_heads (resolved in
    # __post_init__); a model whose heads are wider than that (128 heads
    # of 128 over a hidden size of 4,096: q is 16,384 wide) sets it.
    head_dim: int = 0
    d_ff: int = 2048
    max_seq: int = 2048
    dtype: Any = jnp.bfloat16
    # Storage dtype of the [B, S, V] logits — the largest activation in the
    # step. Matmul accumulation and all loss math stay f32 regardless; only
    # the HBM round trip between them is rounded. bf16 halves that traffic
    # (+3-4% step throughput at 16×1024×32k on one v5e) and measured loss /
    # grad-norm agree with f32 storage to ~1e-5. None = follow ``dtype``
    # (bf16 models store bf16 logits, f32 models keep f32); set explicitly
    # to pin it.
    logits_dtype: Any = None
    remat: bool = True
    # What a block keeps for its backward when remat=True. "fit" (the
    # default): whatever of models/remat.LADDER the device's memory
    # holds, decided when the step is traced — nothing to set. "full":
    # the pin for minimum memory — only block inputs are kept and the
    # backward replays each block's forward (also the tests' reference).
    remat_policy: str = "fit"
    # lax.scan unroll factor over layers: 1 = rolled while-loop (fast
    # compile, the default); n_layers = fully unrolled (removes the scan's
    # activation-stacking dynamic-update-slices, ~6% faster per step on one
    # chip, slower compile). Any divisor of n_layers is valid.
    scan_unroll: int = 1
    # Context-parallel strategy when the mesh has a cp axis: "ring"
    # (ppermute K/V rotation, O(S/cp) memory, any head count) or "ulysses"
    # (two all-to-alls, full-seq attention on H/cp local heads).
    cp_strategy: str = "ring"
    # Sliding-window attention (Mistral-style local attention): each
    # query attends its `attn_window` most recent positions. 0 = full
    # causal. The flash kernels triage out-of-window blocks exactly like
    # above-diagonal ones (skipped compute + elided DMA), so fwd+bwd
    # attention cost scales with seq×window instead of seq²; the decode
    # blockwise path starts its cache walk at the window's first block,
    # making per-token serving cost O(window) regardless of history.
    # Not composable with context parallelism (cp > 1) yet.
    attn_window: int = 0
    # Rolling (ring-buffer) KV cache for WINDOWED decode: > 0 allocates
    # that many cache rows per slot instead of max_len, with writes
    # wrapping modulo the capacity — serving/generation memory is
    # O(capacity) however long the stream runs. Requires attn_window > 0
    # (a full-causal query needs the whole history) and capacity >=
    # attn_window. Per-token read COST is O(capacity), not O(window)
    # (the ring read is dense over all capacity rows) — size capacity
    # near the window; init_kv_cache warns at >= 4x. Greedy/sampled
    # generate + continuous batching; speculative decoding, beam
    # search, and shared-prefix templates keep the linear cache
    # (models/decode.py rejects the combos).
    kv_cache_capacity: int = 0
    # GPipe microbatch count when the mesh has a pp axis > 1 (forward routes
    # through parallel/pipeline.py automatically). 0 = auto: 2·pp if it
    # divides the batch (bubble (pp-1)/(pp+1)), else pp. Must divide the
    # global batch; the per-microbatch batch must divide the dp axis.
    pp_microbatches: int = 0
    # Pipeline schedule: "gpipe" (differentiable through lm_loss — the
    # default) or "1f1b" (O(pp) instead of O(M) live microbatch
    # activations; gradients come from lm_value_and_grad, not jax.grad —
    # make_train_step's value_and_grad_fn hook).
    pp_schedule: str = "gpipe"
    # MoE: 0 experts = dense MLP
    num_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01
    # KV-cache storage for the decode/serving path: "model" stores K/V in
    # ``dtype`` (exact); "int8" quantizes each written token per kv-head
    # (absmax/127 scale carried in a parallel [L, B, rows, KV] f32
    # buffer) — the cache's HBM footprint and read traffic halve vs bf16, so a
    # serving host fits ~2x the slots (or 2x max_len) in the same memory.
    # Decode logits shift by the ~0.4% relative rounding of K/V; training
    # and prefill math are untouched (quantization happens only at the
    # cache write). See models/decode.py.
    kv_cache_dtype: str = "model"
    # RMSNorm epsilon and rotary base as the model publishes them
    rms_eps: float = 1e-6
    rope_base: float = 10000.0
    rope_scaling: RopeYarn | None = None
    # A model with MORE THAN ONE KIND of layer: the kind of each layer in
    # order (see LAYER_KINDS), parameters stacked per kind under
    # params["blocks"][kind], and each kind's attention owning the cache
    # buffers it writes, with its own row count. None = the dense
    # decoder: one kind, today's layout. Its ``latent`` kinds attend
    # through ``latent``, its ``window`` kinds inside ``attn_window``,
    # its ``moe`` kinds route through ``experts``; it is SERVED
    # (models/decode.py, models/serve.py) — the train step and the
    # serving features that :meth:`refuse` names refuse it.
    layer_kinds: tuple[str, ...] | None = None
    latent: LatentAttention | None = None
    experts: SparseExperts | None = None
    # Settings of the kinded block (a model with layer_kinds). "rms", or
    # "layer": a weight-only LayerNorm (mean subtracted, no bias), both
    # at ``rms_eps``.
    norm: str = "rms"
    # One norm a layer; attention and feed-forward both read it and both
    # add to the stream: x + attention(h) + ffn(h), h = norm(x).
    parallel_block: bool = False
    # The head is the embedding, transposed (no ``lm_head`` leaf);
    # logits x ``logit_scale``.
    tie_embeddings: bool = False
    logit_scale: float = 1.0
    # its ``ssm`` kinds mix through ``ssm``
    ssm: StateSpace | None = None
    # Three scalars of the kinded block: the embedding's rows x
    # ``embed_scale`` as they enter the stream; every branch (mixer,
    # feed-forward) x ``residual_scale`` as it joins it; the softmax of
    # q.k x ``attn_scale`` (None: the usual ``head_dim ** -0.5``).
    embed_scale: float = 1.0
    residual_scale: float = 1.0
    attn_scale: float | None = None

    def __post_init__(self):
        # fail where the config was written, not at first trace
        kv = self.n_kv_heads if self.n_kv_heads is not None else self.n_heads
        if kv <= 0 or self.n_heads % kv:
            raise ValueError(f"n_kv_heads={kv} must be a positive divisor "
                             f"of n_heads={self.n_heads}")
        if self.remat_policy not in ("fit", "full"):
            raise ValueError(f"unknown remat_policy "
                             f"{self.remat_policy!r}; expected 'fit' "
                             f"(keep what the device's memory holds) or "
                             f"'full' (keep block inputs only)")
        if self.pp_schedule not in ("gpipe", "1f1b"):
            raise ValueError(f"unknown pp_schedule {self.pp_schedule!r}; "
                             f"expected 'gpipe' or '1f1b'")
        if self.kv_cache_dtype not in ("model", "int8"):
            raise ValueError(f"unknown kv_cache_dtype "
                             f"{self.kv_cache_dtype!r}; expected 'model' "
                             f"or 'int8'")
        if self.attn_window < 0:
            raise ValueError(f"attn_window must be >= 0 (0 = full causal "
                             f"attention), got {self.attn_window}")
        if self.kv_cache_capacity:
            if not self.attn_window:
                raise ValueError(
                    "kv_cache_capacity (rolling KV cache) requires "
                    "attn_window > 0: a full-causal query attends the "
                    "whole history, which a ring buffer has overwritten")
            if self.kv_cache_capacity < self.attn_window:
                raise ValueError(
                    f"kv_cache_capacity ({self.kv_cache_capacity}) must "
                    f"be >= attn_window ({self.attn_window}): a decode "
                    f"step reads its window's rows from the ring")
        if not self.head_dim:
            object.__setattr__(self, "head_dim",
                               self.d_model // self.n_heads)
        if self.head_dim <= 0:
            raise ValueError(f"head_dim must be positive, got "
                             f"{self.head_dim}")
        if self.layer_kinds is not None:
            if len(self.layer_kinds) != self.n_layers or any(
                    k not in LAYER_KINDS for k in self.layer_kinds):
                raise ValueError(
                    f"layer_kinds must name one of {tuple(LAYER_KINDS)} "
                    f"for each of the {self.n_layers} layers, got "
                    f"{self.layer_kinds}")
            attns = set(self.attentions)
            ffns = {LAYER_KINDS[k][1] for k in self.layer_kinds}
            routed = bool(ffns & set(_ROUTED_FFNS))
            if ("latent" in attns and self.latent is None) or (
                    routed and self.experts is None):
                raise ValueError(
                    "a model with layer_kinds attends through `latent` "
                    "(LatentAttention) in its latent kinds and its 'moe' "
                    "and 'scmoe' kinds route through `experts` "
                    "(SparseExperts): set both where both are listed")
            if "window" in attns and not self.attn_window:
                raise ValueError("a 'window' kind attends inside "
                                 "`attn_window`: set it")
            if "ssm" in attns and self.ssm is None:
                raise ValueError("an 'ssm' kind mixes through `ssm` "
                                 "(StateSpace): set it")
            for what, on, need in (
                    ("latent", self.latent is not None, "latent" in attns),
                    ("ssm", self.ssm is not None, "ssm" in attns),
                    ("experts", self.experts is not None, routed),
                    ("attn_window", self.attn_window, "window" in attns)):
                if on and not need:
                    raise ValueError(
                        f"`{what}` is set, and no kind of layer_kinds "
                        f"{sorted(set(self.layer_kinds))} uses it")
            if self.latent is not None and self.latent.rope_dim % 2:
                raise ValueError("latent.rope_dim must be even")
            if attns - {"latent", "ssm"} and self.head_dim % 2:
                raise ValueError("head_dim must be even (rotary halves)")
            m = self.ssm
            if m is not None and not (
                    min(m.n_heads, m.head_dim, m.d_state, m.chunk) > 0
                    and m.d_conv > 1 and m.n_groups > 0
                    and m.n_heads % m.n_groups == 0):
                raise ValueError(
                    f"ssm needs positive n_heads, head_dim, d_state and "
                    f"chunk, a convolution of d_conv > 1 taps, and "
                    f"n_groups dividing n_heads; got {m}")
            if not attns - {"ssm"}:
                raise ValueError(
                    "a model of 'ssm' kinds alone holds no rows by "
                    "position, and the serving path sizes a slot by "
                    "them: keep an attention kind among layer_kinds")
            e = self.experts
            if e is not None and not (0 <= e.first and 0 < e.n_held
                                      and e.first + e.n_held <= e.total
                                      and 0 <= e.n_zero
                                      and 0 < e.top_k <= e.n_scored
                                      and 0 <= e.n_shared):
                raise ValueError(
                    f"experts [{e.first}, {e.first + e.n_held}) must lie "
                    f"inside the {e.total} routed experts the router "
                    f"scores (beside {e.n_zero} >= 0 zero experts), top_k "
                    f"{e.top_k} among them, beside {e.n_shared} >= 0 "
                    f"shared (0: none)")
            if e is not None and e.route not in ("sigmoid", "softmax"):
                raise ValueError(f"unknown experts.route {e.route!r}; "
                                 f"expected 'sigmoid' or 'softmax'")
            if "scmoe" in ffns and self.parallel_block:
                raise ValueError(
                    "parallel_block has no meaning in a 'scmoe' layer: "
                    "its routed block already reads the first half's "
                    "norm and lands after the second half")
            if self.norm not in ("rms", "layer"):
                raise ValueError(f"unknown norm {self.norm!r}; expected "
                                 f"'rms' or 'layer'")
            for what, on in (("kv_cache_dtype='int8'", self.kv_quant),
                             ("kv_cache_capacity (the whole-model ring "
                              "cache)", self.kv_cache_capacity),
                             ("num_experts (the gshard block)",
                              self.num_experts)):
                if on:
                    raise ValueError(
                        f"{what} is not supported with layer_kinds: each "
                        f"kind's attention owns its buffers in the "
                        f"model's dtype — a latent row, a ring of "
                        f"attn_window rows, or max_len rows a slot")
        elif (self.latent is not None or self.experts is not None
              or self.ssm is not None
              or self.norm != "rms" or self.parallel_block
              or self.tie_embeddings or self.logit_scale != 1.0
              or self.embed_scale != 1.0 or self.residual_scale != 1.0
              or self.attn_scale is not None):
            raise ValueError("`latent`, `experts`, `ssm`, `norm`, "
                             "`parallel_block`, `tie_embeddings`, "
                             "`logit_scale`, `embed_scale`, "
                             "`residual_scale` and `attn_scale` describe "
                             "a model with `layer_kinds`: set it")

    @property
    def kinded(self) -> bool:
        """More than one kind of layer (``layer_kinds`` set)."""
        return self.layer_kinds is not None

    def kind_index(self, li: int) -> tuple[str, int]:
        """(kind of layer ``li``, its index within that kind's stack)."""
        kind = self.layer_kinds[li]
        return kind, self.layer_kinds[:li].count(kind)

    @property
    def attentions(self) -> tuple[str, ...]:
        """The mixer of each layer, in order: ``latent`` / ``window`` /
        ``full`` attention, or ``ssm`` (see LAYER_KINDS)."""
        return tuple(LAYER_KINDS[k][0] for k in self.layer_kinds)

    def attention_of(self, li: int) -> tuple[str, int]:
        """(attention of layer ``li``, the index of its FIRST attention
        among the attentions of that sort: its index in the cache
        buffers that attention owns). A double layer's second half
        follows at the next index (:func:`kind_attentions`)."""
        attns = self.attentions
        return attns[li], sum(
            kind_attentions(k) for k, a in zip(self.layer_kinds[:li], attns)
            if a == attns[li])

    def attention_layers(self) -> dict[str, int]:
        """attention -> how many ATTENTIONS attend so (the leading axis
        of the buffers it owns: a double layer counts two), in first-use
        order."""
        out: dict[str, int] = {}
        for kind, a in zip(self.layer_kinds, self.attentions):
            out[a] = out.get(a, 0) + kind_attentions(kind)
        return out

    def refuse(self, what: str) -> None:
        """Raise where ``what`` (a serving or training feature) cannot
        take a model with ``layer_kinds`` yet — at construction, with the
        reason, never a wrong answer later."""
        if self.kinded:
            raise NotImplementedError(
                f"{what} is not supported for a model with layer_kinds "
                f"(kinds {sorted(set(self.layer_kinds))}) yet: it is "
                f"written against K and V rows of one head width in one "
                f"linear buffer of one row count, and one stacked block "
                f"group (ROADMAP.md, Reach A)")

    def scaled(self, **overrides) -> "TransformerConfig":
        if ("head_dim" not in overrides
                and overrides.keys() & {"d_model", "n_heads"}
                and self.head_dim == self.d_model // self.n_heads):
            overrides["head_dim"] = 0       # derived: derive it again
        return dataclasses.replace(self, **overrides)

    @property
    def kv_heads(self) -> int:
        return (self.n_kv_heads if self.n_kv_heads is not None
                else self.n_heads)

    @property
    def kv_quant(self) -> bool:
        return self.kv_cache_dtype == "int8"

    @property
    def logits_storage_dtype(self):
        if self.logits_dtype is not None:
            return self.logits_dtype
        return jnp.bfloat16 if self.dtype == jnp.bfloat16 else jnp.float32



# Preset sizes (BASELINE.json progression: ... → BERT-base scale → beyond)
PRESETS = {
    "tiny": TransformerConfig(d_model=128, n_layers=2, n_heads=4, d_ff=512,
                              vocab_size=1024, max_seq=256),
    "small": TransformerConfig(d_model=512, n_layers=8, n_heads=8, d_ff=2048),
    "base": TransformerConfig(d_model=768, n_layers=12, n_heads=12,
                              d_ff=3072),
    "large": TransformerConfig(d_model=1536, n_layers=24, n_heads=16,
                               d_ff=6144),
}


def init_params(rng: jax.Array, cfg: TransformerConfig) -> dict:
    """Initialize the parameter pytree. Layer params are stacked [L, ...]
    (a model with ``layer_kinds``: one stacked group per kind, see
    :func:`_init_kinded_blocks`)."""
    k_emb, k_blocks, k_out = jax.random.split(rng, 3)
    d, h, hd, f, L = (cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.d_ff,
                      cfg.n_layers)
    dt = cfg.dtype

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                * (fan_in ** -0.5)).astype(dt)

    if cfg.kinded:
        params = {
            "embed": dense(k_emb, (cfg.vocab_size, d), d),
            "blocks": _init_kinded_blocks(k_blocks, cfg, dense),
            "final_norm": jnp.ones((d,), dt),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = dense(k_out, (d, cfg.vocab_size), d)
        return params

    ks = jax.random.split(k_blocks, 8)
    kv = cfg.kv_heads
    block = {
        "attn_norm": jnp.ones((L, d), dt),
        "wq": dense(ks[0], (L, d, h, hd), d),
        "wk": dense(ks[1], (L, d, kv, hd), d),
        "wv": dense(ks[2], (L, d, kv, hd), d),
        "wo": dense(ks[3], (L, h, hd, d), d),
        "mlp_norm": jnp.ones((L, d), dt),
    }
    if cfg.num_experts:
        e = cfg.num_experts
        # experts are 2-matrix MLPs (silu): dispatch/combine already cost
        # two extra einsums, so the gshard path skips the gated "up" branch
        block.update({
            "router": dense(ks[4], (L, d, e), d).astype(jnp.float32),
            "w_gate": dense(ks[5], (L, e, d, f), d),
            "w_down": dense(ks[7], (L, e, f, d), f),
        })
    else:
        block.update({
            "w_gate": dense(ks[5], (L, d, f), d),
            "w_up": dense(ks[6], (L, d, f), d),
            "w_down": dense(ks[7], (L, f, d), f),
        })
    return {
        "embed": (jax.random.normal(k_emb, (cfg.vocab_size, d), jnp.float32)
                  * (d ** -0.5)).astype(dt),
        "blocks": block,
        "final_norm": jnp.ones((d,), dt),
        "lm_head": dense(k_out, (d, cfg.vocab_size), d),
    }


def kinded_block_shapes(cfg: TransformerConfig, kind: str) -> dict:
    """leaf -> (shape of ONE layer, fan-in or None for a norm / the
    bias, logical axes) of a ``kind`` layer of a model with
    ``layer_kinds``. The one statement of that layout: ``init_params``
    and ``logical_axes`` stack it per kind."""
    d, h = cfg.d_model, cfg.n_heads
    attention, ffn = LAYER_KINDS[kind]
    if ffn == "scmoe":
        return _scmoe_block_shapes(cfg)
    leaves = {"attn_norm": ((d,), None, ("norm",))}
    if attention == "latent":
        la = cfg.latent
        leaves.update({
            "wq_a": ((d, la.q_rank), d, ("embed", None)),
            "q_norm": ((la.q_rank,), None, ("norm",)),
            "wq_b": ((la.q_rank, h, la.qk_dim), la.q_rank,
                     (None, "heads", "kv")),
            "wkv_a": ((d, la.row), d, ("embed", None)),
            "kv_norm": ((la.kv_rank,), None, ("norm",)),
            "wkv_b": ((la.kv_rank, h, la.nope_dim + la.v_dim), la.kv_rank,
                      (None, "heads", "kv")),
            "wo": ((h, la.v_dim, d), h * la.v_dim,
                   ("heads", "kv", "embed")),
        })
    elif attention == "ssm":
        m = cfg.ssm
        leaves.update({
            "w_in": ((d, m.in_dim), d, ("embed", "mlp")),
            # taps x channels, tap j on the input d_conv - 1 - j back
            "conv_w": ((m.d_conv, m.conv_dim), m.d_conv, (None, "mlp")),
            "conv_b": ((m.conv_dim,), None, ("mlp",)),
            # float32, as the router is: a step's size and decay
            "dt_bias": ((m.n_heads,), None, (None,)),
            "A_log": ((m.n_heads,), None, (None,)),
            "D": ((m.n_heads,), None, (None,)),
            "gate_norm": ((m.d_inner,), None, ("mlp",)),
            "w_out": ((m.d_inner, d), m.d_inner, ("mlp", "embed")),
        })
    else:
        # K/V heads replicate under tp when there are fewer than query
        # heads, as the dense decoder's (logical_axes)
        hd, kv = cfg.head_dim, cfg.kv_heads
        kv_axis = "heads" if kv == h else None
        leaves.update({
            "wq": ((d, h, hd), d, ("embed", "heads", "kv")),
            "wk": ((d, kv, hd), d, ("embed", kv_axis, "kv")),
            "wv": ((d, kv, hd), d, ("embed", kv_axis, "kv")),
            "wo": ((h, hd, d), h * hd, ("heads", "kv", "embed")),
        })
    if not cfg.parallel_block:
        leaves["mlp_norm"] = ((d,), None, ("norm",))
    if ffn == "dense":
        f = cfg.d_ff
        leaves.update({"w_gate": ((d, f), d, ("embed", "mlp")),
                       "w_up": ((d, f), d, ("embed", "mlp")),
                       "w_down": ((f, d), f, ("mlp", "embed"))})
        return leaves
    leaves.update(_routed_block_shapes(cfg))
    return leaves


def _routed_block_shapes(cfg: TransformerConfig) -> dict:
    """The leaves of a routed block (``cfg.experts``): router, selection
    bias, the held experts, and the shared ones where there are any."""
    d, e, f = cfg.d_model, cfg.experts, cfg.experts.d_expert
    leaves = {
        # router and selection bias stay float32: a pick compares scores
        "router": ((d, e.n_scored), d, ("embed", None)),
        "router_bias": ((e.n_scored,), None, (None,)),
        "w_gate": ((e.n_held, d, f), d, ("expert", "embed", "mlp")),
        "w_up": ((e.n_held, d, f), d, ("expert", "embed", "mlp")),
        "w_down": ((e.n_held, f, d), f, ("expert", "mlp", "embed")),
    }
    if e.n_shared:
        # one shared expert is a [d, f] leaf, several a [n_shared, d, f]
        # stack
        ns = () if e.n_shared == 1 else (e.n_shared,)
        nax = () if e.n_shared == 1 else (None,)
        leaves.update({
            "shared_gate": (ns + (d, f), d, nax + ("embed", "mlp")),
            "shared_up": (ns + (d, f), d, nax + ("embed", "mlp")),
            "shared_down": (ns + (f, d), f, nax + ("mlp", "embed")),
        })
    return leaves


#: the dense SwiGLU of a double layer's half, under names of its own: the
#: routed experts of the same layer are ``w_gate`` / ``w_up`` / ``w_down``
_HALF_MLP = {"mlp_gate": "w_gate", "mlp_up": "w_up", "mlp_down": "w_down"}


def _scmoe_block_shapes(cfg: TransformerConfig) -> dict:
    """The double layer (kind ``latent2_scmoe``): the leaves of a
    ``dense`` layer — latent attention, its norms, a SwiGLU of ``d_ff``
    — for each of the two halves, stacked on a leading axis of 2 (the
    SwiGLU as ``mlp_gate`` / ``mlp_up`` / ``mlp_down``), beside the ONE
    routed block's."""
    half = kinded_block_shapes(cfg, "dense")
    back = {v: k for k, v in _HALF_MLP.items()}
    leaves = {back.get(n, n): ((2,) + shape, fan_in, (None,) + axes)
              for n, (shape, fan_in, axes) in half.items()}
    leaves.update(_routed_block_shapes(cfg))
    return leaves


#: float32 leaves of a state-space mixer, and how each starts (Mamba-2's
#: own initializer): steps log-uniform in [0.001, 0.1] through
#: ``dt_bias = softplus^-1(dt)``, ``-a`` uniform in [1, 16], ``D`` ones
SSM_F32_LEAVES = ("dt_bias", "A_log", "D")


def _init_ssm_leaf(key, leaf: str, shape):
    if leaf == "D":
        return jnp.ones(shape, jnp.float32)
    u = jax.random.uniform(key, shape, jnp.float32)
    if leaf == "A_log":
        return jnp.log(1.0 + 15.0 * u)
    dt = jnp.exp(math.log(1e-3) + u * math.log(1e2))
    return dt + jnp.log(-jnp.expm1(-dt))


def _init_kinded_blocks(rng, cfg: TransformerConfig, dense) -> dict:
    """{kind: {leaf: [layers of that kind, ...]}}: norms ones, the
    selection bias and the convolution's zeros, router float32, a
    state-space mixer's step and decay leaves by
    :func:`_init_ssm_leaf`."""
    blocks = {}
    for ki, kind in enumerate(dict.fromkeys(cfg.layer_kinds)):
        n = cfg.layer_kinds.count(kind)
        shapes = kinded_block_shapes(cfg, kind)
        keys = jax.random.split(jax.random.fold_in(rng, ki), len(shapes))
        group = {}
        for key, (leaf, (shape, fan_in, _)) in zip(keys, shapes.items()):
            if leaf == "router_bias":
                group[leaf] = jnp.zeros((n,) + shape, jnp.float32)
            elif leaf in SSM_F32_LEAVES:
                group[leaf] = _init_ssm_leaf(key, leaf, (n,) + shape)
            elif leaf == "conv_b":
                group[leaf] = jnp.zeros((n,) + shape, cfg.dtype)
            elif fan_in is None:
                group[leaf] = jnp.ones((n,) + shape, cfg.dtype)
            else:
                w = dense(key, (n,) + shape, fan_in)
                group[leaf] = (w.astype(jnp.float32) if leaf == "router"
                               else w)
        blocks[kind] = group
    return blocks


def logical_axes(cfg: TransformerConfig) -> dict:
    """Logical-axis pytree matching init_params (leading axis = "stage" so
    the same layout drives FSDP sharding and pipeline stage assignment)."""
    if cfg.kinded:
        axes = {
            "embed": ("vocab", "embed"),
            "blocks": {kind: {leaf: ("stage",) + axes for leaf, (_, _, axes)
                              in kinded_block_shapes(cfg, kind).items()}
                       for kind in dict.fromkeys(cfg.layer_kinds)},
            "final_norm": ("norm",),
        }
        if not cfg.tie_embeddings:
            axes["lm_head"] = ("embed", "vocab")
        return axes
    # Under GQA the K/V head count can be smaller than any tp axis, so
    # those params replicate instead of claiming the "heads" rule (they are
    # n_heads/n_kv_heads× smaller than MHA's to begin with; Llama-style TP
    # replicates KV heads the same way).
    kv_head_axis = "heads" if cfg.kv_heads == cfg.n_heads else None
    block = {
        "attn_norm": ("stage", "norm"),
        "wq": ("stage", "embed", "heads", "kv"),
        "wk": ("stage", "embed", kv_head_axis, "kv"),
        "wv": ("stage", "embed", kv_head_axis, "kv"),
        "wo": ("stage", "heads", "kv", "embed"),
        "mlp_norm": ("stage", "norm"),
    }
    if cfg.num_experts:
        block.update({
            "router": ("stage", "embed", None),
            "w_gate": ("stage", "expert", "embed", "mlp"),
            "w_down": ("stage", "expert", "mlp", "embed"),
        })
    else:
        block.update({
            "w_gate": ("stage", "embed", "mlp"),
            "w_up": ("stage", "embed", "mlp"),
            "w_down": ("stage", "mlp", "embed"),
        })
    return {
        "embed": ("vocab", "embed"),
        "blocks": block,
        "final_norm": ("norm",),
        "lm_head": ("embed", "vocab"),
    }


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def rope_tables(positions: jax.Array, d: int, base: float = 10000.0,
                scaling: RopeYarn | None = None
                ) -> tuple[jax.Array, jax.Array]:
    """(cos, sin) tables [B, S, 1, d/2] for head dim ``d``. Position-only,
    so callers hoist them OUT of the layer scan — recomputing the trig per
    layer cost ~2.3 ms/step at 8 layers × 16×1024 on one v5e. ``base``
    and ``scaling`` (YaRN, :class:`RopeYarn`) as the model publishes
    them."""
    half = d // 2
    freqs = jnp.exp(-jnp.arange(0, half, dtype=jnp.float32)
                    * (jnp.log(base) / half))
    if scaling is not None:
        freqs = _yarn_frequencies(freqs, d, base, scaling)
    angles = positions[..., None].astype(jnp.float32) * freqs   # [B, S, half]
    cos, sin = jnp.cos(angles)[:, :, None, :], jnp.sin(angles)[:, :, None, :]
    if scaling is not None and scaling.table_scale != 1.0:
        cos, sin = cos * scaling.table_scale, sin * scaling.table_scale
    return cos, sin


def _yarn_frequencies(freqs, d: int, base: float, y: RopeYarn):
    """Blend each rotary frequency with its ``1/factor`` interpolation:
    dimensions that turn more than ``beta_fast`` times in the original
    context keep their frequency, those that turn fewer than
    ``beta_slow`` times are interpolated, a linear ramp between."""
    def dim_of(rotations: float) -> float:
        return (d * math.log(y.original_max / (rotations * 2 * math.pi))
                / (2 * math.log(base)))
    low = max(math.floor(dim_of(y.beta_fast)), 0)
    high = min(math.ceil(dim_of(y.beta_slow)), d - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return freqs / y.factor * ramp + freqs * (1.0 - ramp)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Rotate [B, S, H, D] by precomputed tables (f32 math, x-dtype out)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1).astype(x.dtype)


def _rope(x: jax.Array, positions: jax.Array) -> jax.Array:
    """Rotary embeddings on [B, S, H, D] (one-shot convenience; the train
    path precomputes the tables once via :func:`rope_tables`)."""
    cos, sin = rope_tables(positions, x.shape[-1])
    return apply_rope(x, cos, sin)


def expand_kv(q: jax.Array, k: jax.Array,
              v: jax.Array) -> tuple[jax.Array, jax.Array]:
    """GQA → full heads: broadcast each K/V head across its query group
    (blocked layout: query head h reads kv head h // (H/KV) — the same
    layout the flash kernels and the grouped cache read use natively).
    Only the context-parallel wrappers need the expansion; the
    flash/reference arms consume GQA K/V unexpanded."""
    h, hk = q.shape[2], k.shape[2]
    if h == hk:
        return k, v
    if hk <= 0 or h % hk:
        raise ValueError(f"kv heads ({hk}) must divide heads ({h})")
    rep = h // hk
    return jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)


def _attention(q, k, v, mesh: Mesh | None, cp_strategy: str = "ring",
               window: int | None = None):
    if cp_strategy not in ("ring", "ulysses"):
        # Silent fallback would make a typo'd strategy benchmark the wrong
        # collective pattern.
        raise ValueError(f"unknown cp_strategy {cp_strategy!r}; "
                         f"expected 'ring' or 'ulysses'")
    if mesh is not None and "cp" in mesh.shape and mesh.shape["cp"] > 1:
        if window is not None:
            # a window crossing chunk boundaries needs window-aware hop
            # masking in the ring (and head-split-aware masking in
            # ulysses) — silently ignoring it would train a different
            # model than the config says
            raise NotImplementedError(
                "attn_window is not supported with context parallelism "
                "(cp > 1) yet; shard long sequences with cp OR bound "
                "attention with a window, not both")
        if cp_strategy == "ulysses":
            # GQA K/V stay unexpanded when kv heads divide tp·cp — the
            # wrapper expands only when the head split cannot be satisfied
            from tony_tpu.parallel.ulysses import ulysses_attention
            return ulysses_attention(q, k, v, mesh, causal=True)
        # ring rides GQA K/V unexpanded: the rotation payload (the ring's
        # whole inter-chip cost) shrinks by n_heads/n_kv_heads
        return ring_attention(q, k, v, mesh, causal=True)
    # flash and reference both consume GQA K/V natively (fewer kv heads).
    # On a multi-device mesh the arm runs per device inside shard_map
    # (batch over dp/fsdp, heads over tp): a Mosaic kernel cannot be
    # partitioned by the compiler, and the dense arm rides the same
    # island so the CPU mesh tests pin the wrapper the chip runs.
    arm = reference_attention if mosaic.interpret() else flash_attention
    return shard_attention(
        functools.partial(arm, causal=True, window=window), q, k, v, mesh)


def _checkpointed(block_fn, cfg: TransformerConfig, params: dict,
                  b: int, s: int, mesh, rules):
    """``block_fn`` under ``jax.checkpoint``, keeping the rung of
    :data:`tony_tpu.models.remat.LADDER` that fits this device
    (:func:`tony_tpu.models.remat.decide`, at trace time)."""
    grads = sum(jax.tree.leaves(jax.tree.map(
        lambda ax, p: remat.sharded_bytes(p.shape, ax, p.dtype.itemsize,
                                          mesh, rules),
        logical_axes(cfg), params, is_leaf=_is_axes_leaf)))
    rung = remat.decide(cfg, b, s, grads, mesh, rules)
    return jax.checkpoint(block_fn, policy=remat.policy(rung))


def _block(x, p, cfg: TransformerConfig, mesh, rules, rope=None,
           ep_axis: str | None = None):
    """One decoder block. x: [B, S, D]; p: this layer's params (unstacked);
    ``rope``: precomputed (cos, sin) tables (derived from positions here
    when absent). ``ep_axis``: set inside shard_map bodies (the pipeline
    stage) to run the MoE arm with explicit ep collectives —
    ``p["w_gate"]/p["w_down"]`` then hold only this rank's experts."""
    b, s, d = x.shape
    if rope is None:
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        rope = rope_tables(positions, cfg.head_dim, cfg.rope_base,
                           cfg.rope_scaling)
    cos, sin = rope
    # what models/remat.LADDER may keep for the backward is marked by name
    # (the flash kernel's operands and outputs in ops/attention.py);
    # without remat there is nothing to choose, and the program's text
    # stays the one it was (a name is an equation of the trace)
    keep = checkpoint_name if cfg.remat else (lambda x, name: x)

    # named sections (metadata only): a profile is read by these scopes
    with jax.named_scope("attn"):
        h = rms_norm_reference(x, p["attn_norm"], cfg.rms_eps)
        h = constrain(h, ("batch", "seq", "embed"), mesh, rules)
        q = jnp.einsum("bsd,dhk->bshk", h, p["wq"])
        k = jnp.einsum("bsd,dhk->bshk", h, p["wk"])
        v = jnp.einsum("bsd,dhk->bshk", h, p["wv"])
        q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
        # GQA K/V stay at kv_heads width — the flash kernels read each
        # shared head once per query group in-kernel (ops/attention.py
        # "GQA-native"); only the cp wrappers expand (inside _attention).
        # KV heads replicate under TP when h_kv < h (Llama-style),
        # mirroring logical_axes.
        kv_head_axis = "heads" if cfg.kv_heads == cfg.n_heads else None
        q = constrain(q, ("batch", "seq", "heads", "kv"), mesh, rules)
        k = constrain(k, ("batch", "seq", kv_head_axis, "kv"), mesh, rules)
        v = constrain(v, ("batch", "seq", kv_head_axis, "kv"), mesh, rules)
        o = _attention(q, k, v, mesh, cfg.cp_strategy,
                       cfg.attn_window or None)
        attn_out = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
        x = x + keep(constrain(attn_out, ("batch", "seq", "embed"), mesh,
                               rules), "attn_proj")

    with jax.named_scope("mlp"):
        h = rms_norm_reference(x, p["mlp_norm"], cfg.rms_eps)
        h = constrain(h, ("batch", "seq", "embed"), mesh, rules)
        if "router" in p:
            if ep_axis is not None:
                from tony_tpu.parallel.moe import moe_ffn_manual
                moe_out, metrics = moe_ffn_manual(
                    h, p["router"], p["w_gate"], p["w_down"],
                    axis_name=ep_axis, num_experts=cfg.num_experts,
                    top_k=cfg.moe_top_k,
                    capacity_factor=cfg.moe_capacity_factor,
                    activation=jax.nn.silu)
            else:
                moe_out, metrics = moe_ffn(
                    h, p["router"], p["w_gate"], p["w_down"],
                    top_k=cfg.moe_top_k,
                    capacity_factor=cfg.moe_capacity_factor,
                    activation=jax.nn.silu)
            aux = metrics.aux_loss
            mlp_out = moe_out
        else:
            gate = keep(jnp.einsum("bsd,df->bsf", h, p["w_gate"]),
                        "mlp_gate")
            up = keep(jnp.einsum("bsd,df->bsf", h, p["w_up"]), "mlp_up")
            inner = jax.nn.silu(gate) * up
            inner = constrain(inner, ("batch", "seq", "mlp"), mesh, rules)
            mlp_out = jnp.einsum("bsf,fd->bsd", inner, p["w_down"])
            aux = jnp.zeros((), jnp.float32)
    x = x + constrain(mlp_out, ("batch", "seq", "embed"), mesh, rules)
    return x, aux


def _lm_head(params: dict, x: jax.Array, cfg: TransformerConfig,
             mesh, rules) -> jax.Array:
    """final_norm + lm_head on block output x [B, S, D] → logits."""
    with jax.named_scope("lm_head"):
        x = rms_norm_reference(x, params["final_norm"], cfg.rms_eps)
        logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"],
                            preferred_element_type=jnp.float32)
        # The cast fuses into the matmul epilogue, so with bf16
        # logits_dtype the f32 array never reaches HBM (see
        # TransformerConfig.logits_dtype).
        logits = logits.astype(cfg.logits_storage_dtype)
        return constrain(logits, ("batch", "seq", "vocab"), mesh, rules)


def _pp_layout(cfg: TransformerConfig, mesh: Mesh, batch: int):
    """(pp, microbatches) for a pipelined forward — ONE definition of the
    stage-divisibility check and the auto-microbatch rule, shared by the
    GPipe (:func:`_forward_pp`) and 1F1B (:func:`lm_value_and_grad`)
    arms so the two schedules can never drift apart."""
    pp = mesh.shape.get("pp", 1)
    if cfg.n_layers % max(pp, 1):
        raise ValueError(f"n_layers={cfg.n_layers} not divisible into "
                         f"{pp} pipeline stages")
    m = cfg.pp_microbatches
    if not m:
        # auto: the microbatch dim stays sharded over dp/fsdp inside the
        # pipeline's shard_map, so M must divide b AND leave b/M divisible
        # by the live batch axes — i.e. M | b/dp. Aim for 2·pp (bubble
        # (pp-1)/(3·pp-1)), settle for the largest divisor below it.
        dp_total = 1
        for a in ("dp", "fsdp"):
            dp_total *= mesh.shape.get(a, 1)
        per = max(batch // max(dp_total, 1), 1)
        m = next(k for k in range(min(2 * pp, per), 0, -1) if per % k == 0)
    return pp, m


def _forward_pp(params: dict, tokens: jax.Array, cfg: TransformerConfig,
                mesh: Mesh, rules) -> tuple:
    """Pipeline-parallel forward: blocks run as GPipe stages over the mesh's
    ``pp`` axis (parallel/pipeline.py), embed and lm_head replicated over pp.

    The stacked [L, ...] block layout reshapes to [pp, L/pp, ...] — under the
    "stage"→"pp" sharding rule the leading dim is already split into
    contiguous layer groups per pp rank, so the reshape is shard-local.
    Activations hop stage→stage via ppermute (point-to-point), which is why
    pp is the axis that tolerates DCN (mesh.py AXIS_ORDER).
    """
    from tony_tpu.parallel.pipeline import pipeline_apply

    b, s = tokens.shape
    pp, m = _pp_layout(cfg, mesh, b)
    ep = mesh.shape.get("ep", 1)
    ep_axis = "ep" if (cfg.num_experts and ep > 1) else None
    if ep_axis and cfg.num_experts % ep:
        raise ValueError(f"num_experts={cfg.num_experts} not divisible "
                         f"over ep={ep}")
    x = params["embed"][tokens].astype(cfg.dtype)
    x = constrain(x, ("batch", "seq", "embed"), mesh, rules)
    blocks = jax.tree.map(
        lambda a: a.reshape((pp, a.shape[0] // pp) + a.shape[1:]),
        params["blocks"])

    def stage_fn(stage_params, h):
        # runs under shard_map: constrain() inside _block resolves Manual
        # axes to replication (sharding._auto_axes), so the block body is
        # reused verbatim; the MoE arm switches to explicit ep collectives
        # (moe_ffn_manual) because sharding constraints can't reach Manual
        # axes — expert weights arrive pre-sliced via param_specs below
        hb, hs = h.shape[0], h.shape[1]
        positions = jnp.broadcast_to(jnp.arange(hs), (hb, hs))
        rope = rope_tables(positions, cfg.head_dim, cfg.rope_base,
                           cfg.rope_scaling)
        block_fn = functools.partial(_block, cfg=cfg, mesh=None, rules=rules,
                                     ep_axis=ep_axis)
        if cfg.remat:
            block_fn = _checkpointed(block_fn, cfg, params, b, s, mesh,
                                     rules)

        def body(carry, p):
            h, acc = carry
            h, aux = block_fn(h, p, rope=rope)
            return (h, acc + aux), None

        (h, aux), _ = jax.lax.scan(body, (h, jnp.zeros((), jnp.float32)),
                                   stage_params, unroll=cfg.scan_unroll)
        return h, aux

    param_specs = None
    if ep_axis:
        # expert-leading leaves ([pp, L/pp, E, ...]) additionally shard E
        # over ep; everything else stays stage-sharded only. The router
        # stays replicated — every rank routes identically.
        from jax.sharding import PartitionSpec as _P
        param_specs = {
            k: (_P("pp", None, "ep") if k in ("w_gate", "w_down")
                else _P("pp"))
            for k in blocks
        }
    x, aux = pipeline_apply(stage_fn, blocks, x, mesh, num_microbatches=m,
                            with_aux=True, param_specs=param_specs)
    logits = _lm_head(params, x, cfg, mesh, rules)
    return logits, aux


def forward(params: dict, tokens: jax.Array, cfg: TransformerConfig,
            mesh: Mesh | None = None, rules=DEFAULT_RULES) -> tuple:
    """tokens [B, S] int32 → (logits [B, S, V] in
    cfg.logits_storage_dtype — f32 accumulation, storage-rounded once;
    see TransformerConfig.logits_dtype — and the aux_loss scalar).

    With a mesh whose ``pp`` axis is >1 the blocks run as a GPipe pipeline
    (:func:`_forward_pp`) — pipelining is a mesh change, not a model change.
    """
    cfg.refuse("the training forward (transformer.forward, lm_loss, the "
               "train step)")
    if mesh is not None and mesh.shape.get("pp", 1) > 1:
        return _forward_pp(params, tokens, cfg, mesh, rules)
    x = params["embed"][tokens].astype(cfg.dtype)
    x = constrain(x, ("batch", "seq", "embed"), mesh, rules)
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    rope = rope_tables(positions, cfg.head_dim, cfg.rope_base,
                       cfg.rope_scaling)      # hoisted out of the scan

    block_fn = functools.partial(_block, cfg=cfg, mesh=mesh, rules=rules)
    if cfg.remat:
        # rope tables ride the non-differentiated argument slot; marking
        # them static would re-run the trig in every layer's rematerialized
        # forward, which is exactly what hoisting avoids
        block_fn = _checkpointed(block_fn, cfg, params, b, s, mesh, rules)

    def scan_body(x, layer_params):
        x, aux = block_fn(x, layer_params, rope=rope)
        return x, aux

    x, auxes = jax.lax.scan(scan_body, x, params["blocks"],
                            unroll=cfg.scan_unroll)
    return _lm_head(params, x, cfg, mesh, rules), auxes.sum()


def lm_loss(params: dict, batch: dict, cfg: TransformerConfig,
            mesh: Mesh | None = None, rules=DEFAULT_RULES) -> jax.Array:
    """Next-token cross-entropy. batch: {"tokens": [B, S]} (shift inside) or
    {"inputs", "targets"}; ignores targets == -1."""
    if "inputs" in batch:
        inputs, targets = batch["inputs"], batch["targets"]
    else:
        inputs, targets = batch["tokens"][:, :-1], batch["tokens"][:, 1:]
    logits, aux = forward(params, inputs, cfg, mesh, rules)
    with jax.named_scope("loss"):
        return (masked_cross_entropy(logits, targets)
                + cfg.moe_aux_weight * aux)


def lm_value_and_grad(params: dict, batch: dict, cfg: TransformerConfig,
                      mesh: Mesh, rules=DEFAULT_RULES):
    """Next-token loss AND parameter gradients via the 1F1B pipeline
    schedule (``cfg.pp_schedule == "1f1b"``) — the memory-scalable arm of
    pipeline parallelism (parallel/pipeline.py: O(pp) live microbatch
    activations instead of GPipe's O(M)).

    Not a ``jax.grad`` target: 1F1B starts each microbatch's backward at
    the last stage as soon as its forward lands, which requires the loss
    head inside the pipeline — so this function IS the differentiation.
    Plug into ``make_train_step(..., value_and_grad_fn=...)``.

    The loss normalizes per (microbatch, data shard) — identical to
    :func:`lm_loss` whenever mask counts are uniform (always true for
    dense LM batches without -1 padding).
    """
    from tony_tpu.models.train import masked_cross_entropy as _mxe
    from tony_tpu.parallel.pipeline import pipeline_value_and_grad

    cfg.refuse("the 1F1B pipeline train step")
    if "inputs" in batch:
        inputs, targets = batch["inputs"], batch["targets"]
    else:
        inputs, targets = batch["tokens"][:, :-1], batch["tokens"][:, 1:]
    if cfg.num_experts and mesh.shape.get("ep", 1) > 1:
        raise NotImplementedError(
            "pp_schedule='1f1b' supports MoE with REPLICATED experts "
            "only (no ep axis): the explicit-collective dispatch's psum "
            "transposes are not exact under the schedule's per-rank "
            "vjps — shard experts over ep with pp_schedule='gpipe'")
    b, s = inputs.shape
    pp, m = _pp_layout(cfg, mesh, b)

    def embed_fn(e):
        x = e[inputs].astype(cfg.dtype)
        return constrain(x, ("batch", "seq", "embed"), mesh, rules)

    x, embed_vjp = jax.vjp(embed_fn, params["embed"])

    with_aux = bool(cfg.num_experts)

    def stage_fn(stage_params, h):
        hb, hs = h.shape[0], h.shape[1]
        positions = jnp.broadcast_to(jnp.arange(hs), (hb, hs))
        rope = rope_tables(positions, cfg.head_dim, cfg.rope_base,
                           cfg.rope_scaling)
        block_fn = functools.partial(_block, cfg=cfg, mesh=None,
                                     rules=rules)
        if cfg.remat:
            block_fn = _checkpointed(block_fn, cfg, params, b, s, mesh,
                                     rules)

        def body(carry, p):
            h, acc = carry
            h, aux = block_fn(h, p, rope=rope)
            return (h, acc + aux), None

        (h, aux), _ = jax.lax.scan(
            body, (h, jnp.zeros((), jnp.float32)), stage_params,
            unroll=cfg.scan_unroll)
        return (h, aux) if with_aux else h

    # Loss head: vocab-sharded over tp when the mesh can (matching the
    # GPipe arm, where the lm_head stays tp-sharded by propagation) — the
    # head runs inside the pipeline's Manual context, so the softmax
    # combines across vocab shards with explicit collectives (distributed
    # logsumexp + psum-picked logit); the pipeline psums the activation
    # cotangent across tp (pipeline_value_and_grad head_reduce_axes).
    tp = mesh.shape.get("tp", 1)
    vocab_sharded = (tp > 1 and cfg.vocab_size % tp == 0
                     and mesh.shape.get("pp", 1) > 1)
    if vocab_sharded:
        from jax.sharding import PartitionSpec as _P

        def loss_head(hp, out_mb, tgt_mb):
            h = rms_norm_reference(out_mb, hp["final_norm"], cfg.rms_eps)
            logits = jnp.einsum("bsd,dv->bsv", h, hp["lm_head"],
                                preferred_element_type=jnp.float32)
            # same storage rounding as _lm_head, math back in f32
            logits = logits.astype(cfg.logits_storage_dtype).astype(
                jnp.float32)
            v_loc = logits.shape[-1]
            shard = jax.lax.axis_index("tp")
            # the max is a numerical stabilizer only — lse is shift-
            # invariant and the shift's gradient cancels exactly, so stop
            # gradients rather than differentiate pmax (which has no rule)
            gmax = jax.lax.pmax(
                jax.lax.stop_gradient(jnp.max(logits, axis=-1)), "tp")
            # psum_rep: identity transpose so per-rank vjps yield TRUE
            # partials (the stock psum transpose re-psums a replicated
            # cotangent, scaling every upstream gradient by tp); the
            # pipeline sums the partials across tp exactly once
            # (head_reduce_axes)
            from tony_tpu.parallel.sharding import psum_rep
            lse = gmax + jnp.log(psum_rep(
                jnp.sum(jnp.exp(logits - gmax[..., None]), axis=-1),
                "tp"))
            ids = shard * v_loc + jnp.arange(v_loc)
            onehot = tgt_mb[..., None] == ids
            picked = psum_rep(
                jnp.sum(jnp.where(onehot, logits, 0.0), axis=-1), "tp")
            mask = (tgt_mb >= 0).astype(jnp.float32)
            return ((lse - picked) * mask).sum() / jnp.maximum(
                mask.sum(), 1.0)

        head_specs = {"final_norm": _P(), "lm_head": _P(None, "tp")}
        reduce_axes = ("tp",)
    else:
        def loss_head(hp, out_mb, tgt_mb):
            logits = _lm_head(hp, out_mb, cfg, None, rules)
            return _mxe(logits, tgt_mb)

        head_specs, reduce_axes = None, ()

    head_params = {"final_norm": params["final_norm"],
                   "lm_head": params["lm_head"]}
    blocks = jax.tree.map(
        lambda a: a.reshape((pp, a.shape[0] // pp) + a.shape[1:]),
        params["blocks"])
    loss, g_blocks, g_head, dx = pipeline_value_and_grad(
        stage_fn, blocks, x, head_params, targets, mesh,
        loss_head=loss_head, num_microbatches=m,
        head_specs=head_specs, head_reduce_axes=reduce_axes,
        with_aux=with_aux, aux_weight=cfg.moe_aux_weight)
    (g_embed,) = embed_vjp(dx)
    grads = {
        "embed": g_embed,
        "blocks": jax.tree.map(
            lambda g: g.reshape((g.shape[0] * g.shape[1],) + g.shape[2:]),
            g_blocks),
        "final_norm": g_head["final_norm"],
        "lm_head": g_head["lm_head"],
    }
    return loss, grads
