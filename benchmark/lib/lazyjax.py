"""``jax`` and ``jax.numpy`` imported at first use, not at import.

A family module (``benchmark/families/<name>.py``) is ONE file whose check
and counts the JAX-free parent of a run reads, and whose weights and
reference layers the children run. It takes its ``jax`` and ``jnp`` from
here, so that importing it starts no backend and claims no chip; nothing
at a family's top level may touch an attribute of either (no decorator, no
default argument) — ``tests/test_families.py`` holds every family to that.
"""

from __future__ import annotations

import importlib


class _Lazy:
    def __init__(self, name: str) -> None:
        self._name = name

    def __getattr__(self, attr: str):
        return getattr(importlib.import_module(self._name), attr)


jax = _Lazy("jax")
jnp = _Lazy("jax.numpy")
