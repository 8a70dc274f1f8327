"""BERT-base encoder + MLM head, for the 16-worker multi-host config.

BASELINE.json's final progression step is "BERT-base pretraining (16
workers, jax.distributed multi-host)". Reuses the framework's TPU-first
blocks — flash/dense attention (bidirectional), fused-norm math, logical-
axis sharding — with the classic BERT shape: learned position embeddings,
post-LN transformer encoder, GELU MLP, weight-tied MLM head.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from tony_tpu.ops import mosaic
from tony_tpu.ops.attention import flash_attention, reference_attention
from tony_tpu.ops.norms import layer_norm_reference
from tony_tpu.parallel.sharding import (DEFAULT_RULES, constrain,
                                        shard_attention)
from tony_tpu.models.train import masked_cross_entropy


@dataclasses.dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30_522
    d_model: int = 768
    n_layers: int = 12
    n_heads: int = 12
    d_ff: int = 3072
    max_seq: int = 512
    type_vocab: int = 2
    dtype: Any = jnp.bfloat16

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads


BERT_BASE = BertConfig()
BERT_TINY = BertConfig(vocab_size=1024, d_model=128, n_layers=2, n_heads=4,
                       d_ff=512, max_seq=128)


def init_params(rng: jax.Array, cfg: BertConfig) -> dict:
    d, h, hd, f, L = (cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.d_ff,
                      cfg.n_layers)
    dt = cfg.dtype
    ks = iter(jax.random.split(rng, 16))

    def dense(shape, fan_in):
        return (jax.random.normal(next(ks), shape, jnp.float32)
                * (fan_in ** -0.5)).astype(dt)

    return {
        "tok_embed": dense((cfg.vocab_size, d), d),
        "pos_embed": dense((cfg.max_seq, d), d),
        "type_embed": dense((cfg.type_vocab, d), d),
        "embed_ln": {"scale": jnp.ones((d,), dt), "bias": jnp.zeros((d,), dt)},
        "blocks": {
            "wq": dense((L, d, h, hd), d),
            "wk": dense((L, d, h, hd), d),
            "wv": dense((L, d, h, hd), d),
            "wo": dense((L, h, hd, d), d),
            "attn_ln": {"scale": jnp.ones((L, d), dt),
                        "bias": jnp.zeros((L, d), dt)},
            "w_in": dense((L, d, f), d),
            "b_in": jnp.zeros((L, f), dt),
            "w_out": dense((L, f, d), f),
            "b_out": jnp.zeros((L, d), dt),
            "mlp_ln": {"scale": jnp.ones((L, d), dt),
                       "bias": jnp.zeros((L, d), dt)},
        },
        "mlm_dense": dense((d, d), d),
        "mlm_ln": {"scale": jnp.ones((d,), dt), "bias": jnp.zeros((d,), dt)},
        "mlm_bias": jnp.zeros((cfg.vocab_size,), jnp.float32),
    }


def logical_axes(cfg: BertConfig) -> dict:
    ln = lambda lead: {"scale": lead + ("norm",), "bias": lead + ("norm",)}
    return {
        "tok_embed": ("vocab", "embed"),
        "pos_embed": (None, "embed"),
        "type_embed": (None, "embed"),
        "embed_ln": ln(()),
        "blocks": {
            "wq": ("stage", "embed", "heads", "kv"),
            "wk": ("stage", "embed", "heads", "kv"),
            "wv": ("stage", "embed", "heads", "kv"),
            "wo": ("stage", "heads", "kv", "embed"),
            "attn_ln": ln(("stage",)),
            "w_in": ("stage", "embed", "mlp"),
            "b_in": ("stage", "mlp"),
            "w_out": ("stage", "mlp", "embed"),
            "b_out": ("stage", "embed"),
            "mlp_ln": ln(("stage",)),
        },
        "mlm_dense": ("embed", "embed"),
        "mlm_ln": ln(()),
        "mlm_bias": ("vocab",),
    }


def _attention(q, k, v, mesh):
    # per device inside shard_map on a multi-device mesh — see
    # transformer._attention
    arm = reference_attention if mosaic.interpret() else flash_attention
    return shard_attention(functools.partial(arm, causal=False),
                           q, k, v, mesh)


def _block(x, p, cfg: BertConfig, mesh, rules):
    h = constrain(x, ("batch", "seq", "embed"), mesh, rules)
    q = jnp.einsum("bsd,dhk->bshk", h, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", h, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", h, p["wv"])
    o = _attention(q, k, v, mesh)
    attn = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    x = layer_norm_reference(x + attn, p["attn_ln"]["scale"],
                             p["attn_ln"]["bias"])   # post-LN (original BERT)
    inner = jax.nn.gelu(jnp.einsum("bsd,df->bsf", x, p["w_in"]) + p["b_in"])
    inner = constrain(inner, ("batch", "seq", "mlp"), mesh, rules)
    mlp = jnp.einsum("bsf,fd->bsd", inner, p["w_out"]) + p["b_out"]
    return layer_norm_reference(x + mlp, p["mlp_ln"]["scale"],
                                p["mlp_ln"]["bias"])


def forward(params: dict, tokens: jax.Array, cfg: BertConfig,
            type_ids: jax.Array | None = None,
            mesh: Mesh | None = None, rules=DEFAULT_RULES) -> jax.Array:
    """tokens [B, S] → MLM logits [B, S, V] (f32)."""
    b, s = tokens.shape
    x = params["tok_embed"][tokens]
    x = x + params["pos_embed"][None, :s]
    if type_ids is not None:
        x = x + params["type_embed"][type_ids]
    x = layer_norm_reference(x, params["embed_ln"]["scale"],
                             params["embed_ln"]["bias"]).astype(cfg.dtype)
    x = constrain(x, ("batch", "seq", "embed"), mesh, rules)

    def body(x, layer_params):
        return _block(x, layer_params, cfg, mesh, rules), None

    x, _ = jax.lax.scan(body, x, params["blocks"])
    h = jax.nn.gelu(jnp.einsum("bsd,de->bse", x, params["mlm_dense"]))
    h = layer_norm_reference(h, params["mlm_ln"]["scale"],
                             params["mlm_ln"]["bias"])
    # weight-tied output projection
    logits = jnp.einsum("bsd,vd->bsv", h, params["tok_embed"],
                        preferred_element_type=jnp.float32)
    return logits + params["mlm_bias"]


def mlm_loss(params: dict, batch: dict, cfg: BertConfig,
             mesh: Mesh | None = None, rules=DEFAULT_RULES) -> jax.Array:
    """batch: {"tokens" [B,S], "targets" [B,S] (-1 = unmasked/ignore)}."""
    logits = forward(params, batch["tokens"], cfg,
                     batch.get("type_ids"), mesh, rules)
    return masked_cross_entropy(logits, batch["targets"])
