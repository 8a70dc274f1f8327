"""Weights from ``--seed``, made on the device in one jitted call, in the
program's parameter layout (stacked ``[L, ...]`` block leaves) and in the
type they are trained or served in. The float32 reference regenerates the
SAME values layer by layer from the same keys (:func:`layer`,
:func:`outer`) — it never sees an array the program has held."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_LAYER_LEAVES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def _dims(c: dict):
    d, h = c["hidden_size"], c["num_attention_heads"]
    return d, h, c["num_key_value_heads"], d // h, c["intermediate_size"]


def _normal(key, shape, fan_in, dtype):
    return (jax.random.normal(key, shape, jnp.float32)
            * (fan_in ** -0.5)).astype(dtype)


def _seed_key(seed):
    # --seed is any whole number up to a little over 2**31: callers pass
    # it as uint32, which holds it
    return jax.random.fold_in(jax.random.PRNGKey(0),
                              jnp.asarray(seed, jnp.uint32))


def layer(seed, li, c: dict, dtype) -> dict:
    """Block ``li``'s leaves (unstacked). Traced or concrete ``li``."""
    d, h, kv, k, f = _dims(c)
    base = jax.random.fold_in(jax.random.fold_in(_seed_key(seed), 1), li)
    ks = jax.random.split(base, len(_LAYER_LEAVES))
    shapes = {"wq": ((d, h, k), d), "wk": ((d, kv, k), d),
              "wv": ((d, kv, k), d), "wo": ((h, k, d), d),
              "w_gate": ((d, f), d), "w_up": ((d, f), d),
              "w_down": ((f, d), f)}
    out = {n: _normal(ks[i], *shapes[n], dtype)
           for i, n in enumerate(_LAYER_LEAVES)}
    out["attn_norm"] = jnp.ones((d,), dtype)
    out["mlp_norm"] = jnp.ones((d,), dtype)
    return out


def outer(seed, c: dict, dtype) -> dict:
    """Embedding, final norm and the untied head."""
    d, v = c["hidden_size"], c["vocab_size"]
    base = jax.random.fold_in(_seed_key(seed), 2)
    k_emb, k_out = jax.random.split(base)
    return {"embed": _normal(k_emb, (v, d), d, dtype),
            "final_norm": jnp.ones((d,), dtype),
            "lm_head": _normal(k_out, (d, v), d, dtype)}


def make_params(seed: int, c: dict, dtype=jnp.bfloat16, shardings=None):
    """The whole pytree in ONE jitted call. ``shardings``: an optional
    pytree of shardings (the program's, from its logical axes) so a
    sharded state is born sharded instead of gathered on one chip."""
    @functools.partial(jax.jit, out_shardings=shardings)
    def build(seed):
        blocks = jax.vmap(lambda li: layer(seed, li, c, dtype))(
            jnp.arange(c["num_hidden_layers"]))
        return dict(outer(seed, c, dtype), blocks=blocks)

    return build(jnp.uint32(seed % (1 << 32)))
