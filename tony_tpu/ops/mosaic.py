"""Mosaic or the Pallas interpreter: the one place that choice is made.

Every ``pallas_call`` in :mod:`tony_tpu.ops` takes its ``interpret=`` from
:func:`interpret`, the block shapes that only Mosaic's tiling rules force
key on it, and the model code that picks the kernel arm over the dense
``jnp`` arm (``transformer._attention``, ``bert._attention``, the ring
chunks, prefill padding) asks the same question — so a process either runs
the chip's program everywhere or the CPU test program everywhere, never a
mix. Callers read it through the module (``mosaic.interpret()``) so one
monkeypatch steers all of them: ``tests/test_chip_compile.py`` sets it to
``False`` to compile the chip's program for a described v5e.
"""

from __future__ import annotations

import jax


def interpret() -> bool:
    """True off-TPU: kernels run in Pallas interpret mode (the CPU tests)
    and callers on a hot path take their dense ``jnp`` arm instead."""
    return jax.default_backend() != "tpu"
