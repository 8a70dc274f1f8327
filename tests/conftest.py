"""Test harness config: run everything on a virtual 8-device CPU mesh.

Multi-chip TPU hardware is not available in CI; per the framework's test
strategy (SURVEY.md §4: local fake-cluster backend + chaos env hooks,
mirroring the reference's MiniCluster in tony-mini), all sharding and
collective paths are exercised on ``--xla_force_host_platform_device_count=8``
CPU devices.

The environment is set HERE, at import, before anything imports jax —
pytest loads this file before it collects a test module, in the xdist
controller and in every worker alike, and child processes the tests start
inherit it. No re-exec.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["TONY_TEST_MODE"] = "1"
# entry points under test turn the persistent compile cache on
# (runtime/compile_cache.py); the suite neither wants <checkout>/.jax_cache
# filled with CPU programs nor a test that passes only on a warm cache
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8").strip()


def _raise_stack_limit() -> None:
    """A full-suite process compiles 500+ XLA programs; deep LLVM
    recursion on the default 8 MB stack can segfault intermittently —
    raise the soft stack limit toward 256 MB (clamped to the hard cap)."""
    try:
        import resource
        soft, hard = resource.getrlimit(resource.RLIMIT_STACK)
        want = 256 * 1024 * 1024
        if hard != resource.RLIM_INFINITY:
            want = min(want, hard)
        if soft != resource.RLIM_INFINITY and soft < want:
            resource.setrlimit(resource.RLIMIT_STACK, (want, hard))
    except (ImportError, ValueError, OSError):
        pass


def pytest_configure(config):
    _raise_stack_limit()


import pytest


@pytest.fixture
def retrace_guard():
    """Retrace-count regression guard for serving AND training programs.

    `tony_tpu.models.serve.TRACE_COUNTS` increments once per TRACE of a
    serving program, keyed by (program name, static shape) — a Python
    side effect inside the jitted bodies, so it counts compiles, not
    calls. `tony_tpu.models.train.TRACE_COUNTS` does the same for
    ``train_step``/``eval_step`` (keyed by batch leaf shapes). The
    fixture snapshots both counters and yields a guard whose
    ``new_traces(name)`` returns the per-shape trace deltas for one
    program and ``assert_max(name, n)`` pins an upper bound — the
    bucketed-admission invariant ("at most one program per length
    bucket") and the train-loop invariant ("one compiled step per batch
    shape across a full run_training run") are asserted through this,
    and any change that reintroduces retraces fails loudly here rather
    than as a silent latency regression."""

    def _trace_counts() -> dict:
        from tony_tpu.models import serve, train
        counts = dict(serve.TRACE_COUNTS)
        counts.update(train.TRACE_COUNTS)   # names disjoint by convention
        return counts

    before = _trace_counts()

    class Guard:
        def new_traces(self, name: str) -> dict:
            """{static shape: new traces} for program ``name`` since the
            fixture snapshot."""
            return {key[1]: count - before.get(key, 0)
                    for key, count in _trace_counts().items()
                    if key[0] == name and count > before.get(key, 0)}

        def total_new(self, name: str) -> int:
            return sum(self.new_traces(name).values())

        def assert_max(self, name: str, n: int) -> None:
            traces = self.new_traces(name)
            assert sum(traces.values()) <= n, (
                f"{name}: {sum(traces.values())} new traces (cap {n}) — "
                f"per-shape: {traces}")

    yield Guard()


@pytest.fixture(autouse=True)
def _forbid_codecs_in_exact_tests(request):
    """Bit-exactness tripwire: tests marked ``exact`` pin bit-identical
    numerics, where a stray quantized tensor channel would surface as an
    unexplainable flake. Arm the channel layer's guard for their
    duration — constructing any non-"none" codec sender/receiver then
    raises RuntimeError at the construction site instead."""
    if request.node.get_closest_marker("exact") is None:
        yield
        return
    from tony_tpu.channels import channel
    channel.forbid_codecs(True)
    try:
        yield
    finally:
        channel.forbid_codecs(False)


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_between_modules():
    """Reset XLA's in-process compilation caches after each test module.

    A full-suite process compiles 500+ XLA programs; with everything
    accumulated in one process the CPU compiler segfaults intermittently
    on a late compile (observed deterministically at the same test once
    the suite grew past ~520 programs, while the same tests pass in a
    fresh process). Dropping the caches at module boundaries keeps the
    compiler's working state bounded; modules re-jit their own programs
    anyway (shared cross-module jit hits are rare), so the runtime cost
    is small."""
    yield
    if "jax" in sys.modules:     # don't force a jax import on jax-free
        import jax               # modules just to clear empty caches
        jax.clear_caches()
