"""One persistent XLA compile cache per checkout, placed from outside.

Cold compiles are a large share of a short job on the chip (the ``large``
train step alone is tens of seconds), and user scripts run in a job
directory named per application — a cache placed relative to the working
directory would never hit. So every entry point that compiles
(``rt.initialize()``, ``serve_lm.py``, ``generate.py``, ``bench.py``,
``chip_smoke.py``'s children) calls :func:`enable`, and this is the ONLY
place the tree sets ``jax_compilation_cache_dir``:

- ``JAX_COMPILATION_CACHE_DIR`` set → nothing is set in code: JAX reads
  the variable itself, and whoever launched the process owns the place;
- otherwise → ``<checkout>/.jax_cache``, derived from this package's own
  location: the same across runs, job directories, pids and time.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_REQUESTS = "/jax/compilation_cache/compile_requests_use_cache"
_HITS = "/jax/compilation_cache/cache_hits"
_counts = {_REQUESTS: 0, _HITS: 0}
_listening = False


def _count(event: str, **_kw) -> None:
    if event in _counts:
        _counts[event] += 1


def enable(cache_dir: str | None = None) -> str:
    """Turn the persistent compile cache on and return its directory.
    ``cache_dir`` overrides the checkout default (a shipped artifact's
    landing dir — ``weightstore.attach_compile_cache``); the environment
    variable overrides both. Idempotent."""
    global _listening
    import jax
    if not _listening:
        jax.monitoring.register_event_listener(_count)
        _listening = True
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    path = cache_dir or DEFAULT_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def stats() -> str:
    """``hits/requests`` of this process since :func:`enable` — the line
    entry points print so a warm run is told from a cold one by its log,
    not by its wall-clock."""
    return (f"compile cache: {_counts[_HITS]} hits of "
            f"{_counts[_REQUESTS]} requests")
