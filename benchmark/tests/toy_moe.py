"""A second family, as files under ``benchmark/tests/`` only: a TEST
FIXTURE, never a cell. It shows that the seam of
``benchmark/families/dense_decoder.py`` is wide enough — that a family with
other leaves, other program keywords, another reference layer and another
count of bytes a step arrives without an edit to any file the harness has.

The program block is ``tony_tpu.models.transformer`` with ``num_experts >
0``: the dense family's attention, then a softmax top-k router over
two-matrix silu experts (``parallel/moe.py``), with ``moe_capacity_factor``
set so that no token is ever dropped (every expert can hold every token of
a row) and ``moe_aux_weight`` 0, so the loss is the cross entropy alone.
The reference computes every expert for every token and weights the k the
router picked: the same mathematics with no dispatch.

What it borrows from the dense family is what the two share: RoPE, the
attention, the outer leaves and the head, and the stacked-``blocks`` names.
"""

from __future__ import annotations

import functools

from benchmark.families import dense_decoder as dense
from benchmark.lib import weights
from benchmark.lib.flops import attended
from benchmark.lib.lazyjax import jax, jnp
from benchmark.lib.reference import rms_norm

_LAYER_LEAVES = ("wq", "wk", "wv", "wo", "router", "w_gate", "w_down")
CONTRACT = {"wq": (0,), "wk": (0,), "wv": (0,), "wo": (0, 1),
            "router": (0,), "w_gate": (1,), "w_down": (1,), "lm_head": (0,)}
HEAD_LEAVES = dense.HEAD_LEAVES
outer_weights, head = dense.outer_weights, dense.head
leaf_name, leaf_norms = dense.leaf_name, dense.leaf_norms


def _dims(c: dict):
    d, h = c["hidden_size"], c["num_attention_heads"]
    return (d, h, c["num_key_value_heads"], d // h, c["intermediate_size"],
            c["num_local_experts"], c["num_experts_per_tok"])


def check(c: dict, name: str) -> None:
    d, h, kv, _, _, e, top = _dims(c)
    if d % h or h % kv or not 0 < top <= e:
        raise ValueError(f"{name}: heads must divide hidden_size, KV heads "
                         f"the heads, and 0 < experts per token <= experts")
    if c["rope_theta"] != 10000.0 or c["hidden_act"] != "silu" \
            or c["tie_word_embeddings"] or c.get("sliding_window"):
        raise ValueError(f"{name}: the program's expert block is RoPE base "
                         f"10000, silu experts, untied head, full causal")


def program_config(c: dict, **job):
    from tony_tpu.models.transformer import TransformerConfig
    d, h, kv, _, f, e, top = _dims(c)
    return TransformerConfig(
        vocab_size=c["vocab_size"], d_model=d,
        n_layers=c["num_hidden_layers"], n_heads=h, n_kv_heads=kv, d_ff=f,
        max_seq=c["max_position_embeddings"], num_experts=e, moe_top_k=top,
        # capacity = the row's length: no token is ever dropped
        moe_capacity_factor=e / top, moe_aux_weight=0.0, **job)


def layer_kinds(c: dict) -> list[str]:
    return ["moe"] * c["num_hidden_layers"]


def _attn_params(c: dict) -> int:
    d, _, kv, k, _, _, _ = _dims(c)
    return 2 * d * d + 2 * d * kv * k


def param_count(c: dict) -> int:
    d, _, _, _, f, e, _ = _dims(c)
    layer = _attn_params(c) + d * e + 2 * e * d * f + 2 * d
    return c["num_hidden_layers"] * layer + 2 * c["vocab_size"] * d + d


def forward_flops_per_token(c: dict, seq: int) -> float:
    d, _, _, _, f, e, top = _dims(c)
    layer = (2 * _attn_params(c) + 4 * attended(seq, 0) * d
             + 2 * d * e + top * 2 * 2 * d * f)     # router, k experts
    return c["num_hidden_layers"] * layer + 2 * d * c["vocab_size"]


def decode_step_bytes(c: dict, live_rows: float, ctx: dict | None = None,
                      dtype_bytes: int = 2) -> float:
    """Attention, router and head once, the live cache rows, and only the
    experts a step can touch: k for each slot of the run (``ctx``), all
    of them where no run is given."""
    d, _, kv, k, f, e, top = _dims(c)
    touched = e if ctx is None else min(e, top * ctx["mix"]["slots"])
    layer = _attn_params(c) + d * e + touched * 2 * d * f + 2 * d
    return (c["num_hidden_layers"] * (layer + live_rows * 2 * kv * k)
            + c["vocab_size"] * d + d) * dtype_bytes


def layer_weights(seed, li, c: dict, dtype, kind: str = "moe") -> dict:
    d, h, kv, k, f, e, _ = _dims(c)
    ks = jax.random.split(weights.layer_key(seed, li), len(_LAYER_LEAVES))
    shapes = {"wq": ((d, h, k), d), "wk": ((d, kv, k), d),
              "wv": ((d, kv, k), d), "wo": ((h, k, d), d),
              "router": ((d, e), d), "w_gate": ((e, d, f), d),
              "w_down": ((e, f, d), f)}
    out = {n: weights.normal(ks[i], *shapes[n], dtype)
           for i, n in enumerate(_LAYER_LEAVES)}
    out["router"] = out["router"].astype(jnp.float32)   # the program's type
    out["attn_norm"] = jnp.ones((d,), dtype)
    out["mlp_norm"] = jnp.ones((d,), dtype)
    return out


def make_params(seed: int, c: dict, dtype, shardings=None):
    @functools.partial(jax.jit, out_shardings=shardings)
    def build(seed):
        blocks = jax.vmap(lambda li: layer_weights(seed, li, c, dtype))(
            jnp.arange(c["num_hidden_layers"]))
        return dict(outer_weights(seed, c, dtype), blocks=blocks)

    return build(weights.as_seed(seed))


def layer_forward(x, p, c: dict, kind: str = "moe"):
    b, s, _ = x.shape
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    hdn = rms_norm(x, p["attn_norm"])
    q = dense.rope(jnp.einsum("bsd,dhk->bshk", hdn, p["wq"]), pos)
    k = dense.rope(jnp.einsum("bsd,dhk->bshk", hdn, p["wk"]), pos)
    v = jnp.einsum("bsd,dhk->bshk", hdn, p["wv"])
    x = x + jnp.einsum("bshk,hkd->bsd", dense.attention(q, k, v, 0),
                       p["wo"])
    hdn = rms_norm(x, p["mlp_norm"])
    probs = jax.nn.softmax(jnp.einsum("bsd,de->bse", hdn, p["router"]), -1)
    vals, idx = jax.lax.top_k(probs, c["num_experts_per_tok"])
    vals = vals / jnp.sum(vals, -1, keepdims=True)
    gate = jnp.sum(jax.nn.one_hot(idx, probs.shape[-1]) * vals[..., None],
                   axis=-2)                                    # [b, s, e]
    inner = jax.nn.silu(jnp.einsum("bsd,edf->bsef", hdn, p["w_gate"]))
    every = jnp.einsum("bsef,efd->bsed", inner, p["w_down"])
    return x + jnp.einsum("bse,bsed->bsd", gate, every)
