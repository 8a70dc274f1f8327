"""One persistent XLA compile cache per checkout, placed from outside.

Cold compiles are a large share of a short job on the chip (the ``large``
train step alone is tens of seconds), and user scripts run in a job
directory named per application — a cache placed relative to the working
directory would never hit. So every entry point that compiles
(``rt.initialize()``, ``serve_lm.py``, ``generate.py``,
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
#: JAX's duration events on the way from a Python function to a loaded
#: executable, under the names :func:`seconds` reports them. The backend
#: event spans ``compile_or_get_cached``: on a cache hit it is mostly
#: the retrieval, so ``backend_compile - cache_retrieval`` is XLA's own
#: compile time.
_DURATIONS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_retrieval",
}
_seconds = {name: 0.0 for name in _DURATIONS.values()}
_listening = False


def _count(event: str, **_kw) -> None:
    if event in _counts:
        _counts[event] += 1


def _add_seconds(event: str, duration_secs: float, **_kw) -> None:
    name = _DURATIONS.get(event)
    if name is not None:
        _seconds[name] += duration_secs


def enable(cache_dir: str | None = None) -> str:
    """Turn the persistent compile cache on and return its directory.
    ``cache_dir`` overrides the checkout default (a shipped artifact's
    landing dir — ``weightstore.attach_compile_cache``); the environment
    variable overrides both. Idempotent."""
    global _listening
    import jax
    if not _listening:
        jax.monitoring.register_event_listener(_count)
        jax.monitoring.register_event_duration_secs_listener(_add_seconds)
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


def seconds() -> dict:
    """Seconds this process has spent, since :func:`enable`, tracing
    (``trace``), lowering to MLIR (``lower``), in the backend's
    compile-or-load (``backend_compile``, cache retrieval included) and
    reading the persistent cache (``cache_retrieval``) — where a
    start-up's wall goes when the hit count alone cannot say."""
    return dict(_seconds)


def seconds_line() -> str:
    """:func:`seconds` as the log line entry points print under
    :func:`stats`."""
    return "compile seconds: " + ", ".join(
        f"{name} {value:.2f}" for name, value in _seconds.items())
