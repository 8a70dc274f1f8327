"""Weights from ``--seed``: the draws and the key-folding scheme every
family shares. A family (``benchmark/families/<name>.py``) makes its whole
pytree on the device in ONE jitted call, in the program's parameter layout
and in the type it is trained or served in; the float32 reference
regenerates the SAME values one layer at a time from the same keys — it
never sees an array the program has held.

The scheme: ``layer_key(seed, li)`` is split over layer ``li``'s leaves in
the family's fixed order; ``outer_key(seed)`` over the embedding and the
head. ``li`` counts through all layers of all kinds, and may be traced.
"""

from __future__ import annotations

from .lazyjax import jax, jnp


def normal(key, shape, fan_in, dtype):
    return (jax.random.normal(key, shape, jnp.float32)
            * (fan_in ** -0.5)).astype(dtype)


def seed_key(seed):
    # --seed is any whole number up to a little over 2**31: callers pass
    # it as uint32, which holds it
    return jax.random.fold_in(jax.random.PRNGKey(0),
                              jnp.asarray(seed, jnp.uint32))


def layer_key(seed, li):
    return jax.random.fold_in(jax.random.fold_in(seed_key(seed), 1), li)


def outer_key(seed):
    return jax.random.fold_in(seed_key(seed), 2)


def as_seed(seed: int):
    """``--seed`` as the uint32 a jitted builder takes."""
    return jnp.uint32(seed % (1 << 32))
