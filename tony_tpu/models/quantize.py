"""Weight-only int8 quantization for the serving path.

Small-batch decode is PARAMETER-READ-bound: every generated token streams
the full weight set from HBM (the lm_head read alone is ~40 µs/step at
the small preset — docs/performance.md "Decode"), so halving the bytes
per weight is a direct decode-latency lever, independent of the int8
KV-cache work (`decode.init_kv_cache`) that halves the *cache* traffic.
Green-field for the TPU build (SURVEY.md §2.3 — the reference delegates
all compute and has no serving path).

Scheme: symmetric per-OUTPUT-CHANNEL absmax int8. For every served
matmul ``y = x @ W`` the scale is constant along the contracted axes, so
it factors OUT of the dot: the kernels compute
``(x @ W_int8.astype(f32)) * scale`` — f32, NOT bf16: XLA fuses the
int8→f32 convert into the dot's operand read while int8→bf16
MATERIALIZES a full-size converted copy (measured 3× slower on the
lm_head matmul; integers ≤ 127 are exact either way). The HBM weight
read stays int8-wide; `decode._weinsum` is the single dispatch point.
Same fold as the int8 KV cache's score/value scales.

PREFILL-shaped matmuls invert the trade: over a long prompt the dot is
COMPUTE-bound, f32 MXU throughput sits far below bf16, and the
materialized bf16 weight copy amortizes across the many activation
rows — so `_weinsum` converts the int8 weight to the bf16 compute
dtype once per call when the activation's SEQUENCE axis exceeds 256
positions (decode-shaped calls at any batch size and f32 activations
keep the fused-f32 path bit-for-bit; the strict on-a-power-of-two
threshold keeps bucket-padded admission and exact-length prefills of
the same prompt on the same kernel — see `_QUANT_PREFILL_MIN_S`). The
scale still applies outside the contraction in f32. Without this,
serving int8 weights paid a time-to-first-token tax exactly where
admission cost matters (ADVICE.md round 5).

Scope: the decode/serving entry points (`decode.prefill`,
``extend_step``/``decode_step`` and everything built on them — generate,
beam search, speculative decoding, continuous batching) consume
quantized params transparently; the TRAINING forward does not (training
needs weight gradients — quantize a snapshot for serving, keep training
params full-precision). MoE expert weights and the embedding gather stay
full-precision (the router's capacity math and the gather don't go
through the matmul dispatcher); norms are vectors, not worth rounding.

Numerics: per-channel absmax keeps the relative rounding ≤ 1/254 per
weight; decoded logits shift ~0.5-1% relative vs the float weights, so
greedy decode is no longer bit-identical to the float model (near-tie
argmaxes can flip) — but all quant-to-quant equivalences (serving ==
generate, beam W=1 == greedy, speculative == greedy) hold and are
test-enforced, the same contract as the int8 KV cache.

Usage::

    sparams = quantize_weights_int8(params)     # serving snapshot
    out = decode.generate(sparams, prompt, cfg, n, rng)

Tensor-parallel serving: quantize AFTER sharding, not before —
``shard_pytree`` resolves logical axes against plain array leaves, so
quantize the already-placed float params and the int8 weights/scales
inherit the weights' shardings through the elementwise/reduction ops
(q keeps the weight's spec; the per-output-channel scale keeps the
output axes' spec). TP-sharded quantized decode is token-identical to
unsharded quantized decode (test-verified on the virtual mesh)::

    sharded = shard_pytree(params, T.logical_axes(cfg), mesh)
    sparams = quantize_weights_int8(sharded)
    with jax.set_mesh(mesh):
        out = decode.generate(sparams, prompt, cfg, n, rng)
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class QuantizedWeight(NamedTuple):
    """int8 weight + per-output-channel f32 scale. A pytree (NamedTuple),
    so it flows through jit/tree.map like the array it replaces: layer
    indexing ``tree.map(lambda a: a[li], blocks)`` slices ``q`` and
    ``scale`` together (both keep the stacked [L, ...] leading dim)."""
    q: jax.Array        # int8, the original weight's shape
    scale: jax.Array    # f32, the output-channel dims (contracted axes
    #                     squeezed out)


def _quantize(w: jax.Array, contract_axes: tuple[int, ...]
              ) -> QuantizedWeight:
    w32 = w.astype(jnp.float32)
    scale = jnp.max(jnp.abs(w32), axis=contract_axes, keepdims=True)
    scale = jnp.maximum(scale / 127.0, 1e-8)
    q = jnp.clip(jnp.round(w32 / scale), -127, 127).astype(jnp.int8)
    return QuantizedWeight(q, jnp.squeeze(scale, contract_axes))


#: blocks-pytree weights served through matmuls, with the axes each
#: contracts over (leading dim 0 is the stacked layer axis L)
_BLOCK_AXES = {
    "wq": (1,), "wk": (1,), "wv": (1,),    # [L, d, H, hd] contract d
    "wo": (1, 2),                          # [L, H, hd, d] contract (H, hd)
    "w_gate": (1,), "w_up": (1,),          # [L, d, f]     contract d
    "w_down": (1,),                        # [L, f, d]     contract f
}


#: the same for a layer of a model with ``layer_kinds`` (leading dim 0:
#: the kind's stacked layers): the projections that go through
#: ``decode._weinsum``. ``wkv_b`` stays full precision (the absorbed
#: decode form slices it per use), as do the router and the routed
#: experts (the grouped product reads plain arrays).
_KINDED_AXES = {
    "wq_a": (1,), "wq_b": (1,), "wkv_a": (1,), "wo": (1, 2),
    "wq": (1,), "wk": (1,), "wv": (1,),     # the window / full kinds'
    "w_in": (1,), "w_out": (1,),            # a state-space mixer's
    "shared_gate": (1,), "shared_up": (1,), "shared_down": (1,),
}
#: the same for SEVERAL shared experts, stacked [L, N, d, f] / [L, N, f,
#: d]: gate and up contract d; down contracts the stack and f together
#: (one scale an output channel — the product sums over both)
_SHARED_STACK_AXES = {"shared_gate": (2,), "shared_up": (2,),
                      "shared_down": (1, 2)}


def quantize_weights_int8(params: dict) -> dict:
    """Serving snapshot: the dense matmul weights → :class:`QuantizedWeight`
    (per-output-channel int8); everything else (embed, norms, MoE
    experts/router) passes through unchanged. The returned pytree is a
    drop-in ``params`` for every ``tony_tpu.models.decode`` entry point."""
    if all(isinstance(g, dict) for g in params["blocks"].values()):
        blocks = {}
        for kind, group in params["blocks"].items():
            axes = dict(_KINDED_AXES)
            if "mlp_gate" in group:
                # a double layer: each half's leaves behind an axis of 2
                axes = {n: tuple(a + 1 for a in ax)
                        for n, ax in axes.items()}
                axes.update(mlp_gate=(2,), mlp_up=(2,), mlp_down=(2,))
            elif "router" not in group:     # the dense SwiGLU
                axes.update(w_gate=(1,), w_up=(1,), w_down=(1,))
            if "shared_gate" in group and group["shared_gate"].ndim == 4:
                axes.update(_SHARED_STACK_AXES)
            blocks[kind] = {n: _quantize(w, axes[n]) if n in axes else w
                            for n, w in group.items()}
        out = dict(params, blocks=blocks)
        if "lm_head" in params:     # a tied head is the embedding: kept
            out["lm_head"] = _quantize(params["lm_head"], (0,))
        return out
    blocks = dict(params["blocks"])
    moe = "router" in blocks
    for name, axes in _BLOCK_AXES.items():
        if name not in blocks:
            continue
        if moe and name in ("w_gate", "w_up", "w_down"):
            continue        # expert weights ride moe_ffn's own dispatch
        blocks[name] = _quantize(blocks[name], axes)
    out = dict(params, blocks=blocks)
    out["lm_head"] = _quantize(params["lm_head"], (0,))   # [d, v]
    return out
