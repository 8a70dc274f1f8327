"""First-class profiling hooks for tony tasks.

The reference's only observability into training is TensorBoard plumbing
(SURVEY.md §5 "Tracing / profiling: ABSENT"; reference: TaskExecutor.java:
73-74,124-127 reserves a TB port and registers worker:0's URL as the YARN
tracking URL). The TPU build keeps that pattern and adds what SURVEY.md §5
calls for — per-host ``jax.profiler`` / xprof capture as a framework feature:

- ``maybe_start()``: driven by executor-exported env. When profiling is on
  (``tony.task.profile.enabled``) each host starts the jax profiler server
  on its reserved TensorBoard port, so xprof / `tensorboard --logdir` can
  capture live from the registered tracking URL. Programmatic trace files
  additionally require instrumenting the loop with :class:`StepTracer` or
  :func:`trace` (both no-ops unless ``tony.task.profile.dir`` is set).
- ``trace(logdir)``: context manager for explicit capture windows.
- ``StepTracer``: step-bounded capture — start at step A, stop at step B —
  the standard way to profile steady-state without the compile noise.
- ``PhaseTimes``: a host-side wall-clock accumulator for the phases of a
  host-driven loop (the serving batchers record ``dispatch``/``fetch``/
  ``admit``/``retire`` per :meth:`serve` call) — xprof sees device work,
  but the serving question is usually about the HOST side: how much of
  the wall went to transport syncs vs dispatch vs admission. Given a
  name prefix (``PhaseTimes("tony.engine")``) every phase is also a row
  of a running profiler capture, ``tony.engine.<phase>``, on the same
  clock as the device ops (``tracing.profiler_annotation``).

User scripts get all of it through ``tony_tpu.runtime.initialize()``, which
calls :func:`maybe_start` after the jax.distributed bootstrap.
"""

from __future__ import annotations

import contextlib
import logging
import os
import time

from tony_tpu import constants
from tony_tpu.runtime import tracing

log = logging.getLogger(__name__)

_server_started = False


class _Phase:
    """One ``with`` block of :meth:`PhaseTimes.phase`: the row is entered
    inside the timed interval, and the interval is observed whether the
    block returns or raises. A class and not a generator: a dozen of
    these a decode chunk run on the engine thread."""

    __slots__ = ("times", "name", "row", "t0")

    def __init__(self, times: "PhaseTimes", name: str, row) -> None:
        self.times = times
        self.name = name
        self.row = row

    def __enter__(self) -> None:
        self.t0 = time.perf_counter()
        self.row.__enter__()

    def __exit__(self, *exc) -> bool:
        self.row.__exit__(*exc)
        self.times.observe(self.name, time.perf_counter() - self.t0)
        return False


class PhaseTimes:
    """Wall-clock accumulator for the phases of a host-driven loop.

    Usage::

        times = PhaseTimes("tony.engine")
        with times.phase("dispatch", seq=7):  # row tony.engine.dispatch
            handle = issue_chunk()            # seq=7 rides as metadata
        with times.phase("fetch"):
            host = np.asarray(handle)
        times.observe("queue_wait", now - t_queued)   # not a with block
        times.total("fetch")        # seconds
        times.summary()             # {"fetch": {"total_s", "count",
                                    #            "mean_ms"}, ...}

    The serving batchers (`tony_tpu.models.serve`) keep one per
    ``serve()`` call under ``.phase_times``, recording ``dispatch``
    (building + enqueueing a device chunk — async, no device sync),
    ``fetch`` (blocking on a chunk's tokens: device compute remaining +
    the transport round trip — the time the pipelined loop overlaps with
    the next chunk), ``admit`` (admission dispatches), and ``retire``;
    the open-loop engine adds ``consume``/``emit``/``admit_pick``/
    ``wait`` and the two request waits it records through
    :meth:`observe` (``ServeEngine``). Pure host timing: no jax import,
    no device sync of its own. With a ``prefix`` each phase also enters
    the profiler row ``<prefix>.<name>``; without one it only
    accumulates."""

    def __init__(self, prefix: str = "") -> None:
        self.prefix = prefix
        self._total: dict[str, float] = {}
        self._count: dict[str, int] = {}

    def phase(self, name: str, **attrs) -> "_Phase":
        """Time the enclosed block under ``name``. ``attrs`` ride the
        profiler row as its metadata (what a capture shows beside the
        event: a sequence number, a batch's size); they are not
        accumulated, and with no capture running the profiler drops
        them unread."""
        return _Phase(self, name, tracing.profiler_annotation(
            f"{self.prefix}.{name}", **attrs)
            if self.prefix else tracing.NO_ANNOTATION)

    def observe(self, name: str, seconds: float) -> None:
        """Add one interval that was not a ``with`` block (a wait that
        began on another thread, or ends where no block can enclose it):
        total and count move as for :meth:`phase`; no profiler row."""
        self._total[name] = self._total.get(name, 0.0) + seconds
        self._count[name] = self._count.get(name, 0) + 1

    def total(self, name: str) -> float:
        """Accumulated seconds in ``name`` (0.0 if never entered)."""
        return self._total.get(name, 0.0)

    def count(self, name: str) -> int:
        return self._count.get(name, 0)

    def summary(self) -> dict:
        """Per-phase {total_s, count, mean_ms}, insertion-ordered."""
        return {
            name: {"total_s": round(self._total[name], 6),
                   "count": self._count[name],
                   "mean_ms": round(
                       1e3 * self._total[name] / self._count[name], 3)}
            for name in self._total
        }


def profile_dir() -> str | None:
    """Trace output dir for this task, or None when profiling is off.
    Per-task subdir keeps multi-host captures separate."""
    base = os.environ.get(constants.TONY_PROFILE_DIR, "")
    if not base:
        return None
    job = os.environ.get(constants.JOB_NAME, "worker")
    idx = os.environ.get(constants.TASK_INDEX, "0")
    return os.path.join(base, f"{job}-{idx}")


def maybe_start() -> bool:
    """Start the per-host profiler server (idempotent) when enabled.

    Returns whether the profiler server is actually LIVE for this task —
    False when profiling is disabled, when no TB_PORT is exported (or
    it is 0), or when the server failed to start. (It used to return
    bare ``enabled``, reporting True for a task nothing could connect
    to.) Trace-file capture (:func:`trace` / :class:`StepTracer`) is
    independent of the server and keyed on ``tony.task.profile.dir``."""
    global _server_started
    enabled = os.environ.get(constants.TONY_PROFILE_ENABLED, "") == "true"
    if not enabled:
        return False
    if _server_started:
        return True
    import jax
    port = int(os.environ.get(constants.TB_PORT, "0") or "0")
    if not port:
        log.warning("profiling enabled but no TB_PORT exported — "
                    "profiler server not started")
        return False
    try:
        jax.profiler.start_server(port)
    except Exception:
        log.warning("profiler server failed to start", exc_info=True)
        return False
    _server_started = True
    log.info("jax profiler server on port %d", port)
    return True


def _reset_server_state_for_tests() -> None:
    """Forget that a profiler server was started (test isolation only —
    jax keeps its own server singleton; this resets OUR latch so
    maybe_start()'s decision logic can be exercised repeatedly)."""
    global _server_started
    _server_started = False


def _start_trace(logdir: str) -> None:
    """Start a capture with the Python call tracer OFF. JAX's default
    records every Python call: it swamps the file and slows the host
    that is being measured; the program's phases reach the capture as
    annotations (``tracing.profiler_annotation``), not as call frames."""
    import jax
    os.makedirs(logdir, exist_ok=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(logdir, profiler_options=opts)


@contextlib.contextmanager
def trace(logdir: str | None = None):
    """Capture a jax trace for the enclosed block (xprof/TensorBoard
    viewable). Defaults to the config-shipped profile dir."""
    import jax
    logdir = logdir or profile_dir()
    if logdir is None:
        yield
        return
    _start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
        log.info("trace written to %s", logdir)


class StepTracer:
    """Capture steps [start, stop) of a training loop::

        tracer = StepTracer(start=10, stop=13)   # skip compile+warmup
        for step in range(total):
            tracer.step(step)
            state, m = train_step(state, batch)
        tracer.close()
    """

    def __init__(self, start: int = 10, stop: int = 13,
                 logdir: str | None = None) -> None:
        self.start = start
        self.stop = stop
        self.logdir = logdir or profile_dir()
        self._active = False

    def step(self, step: int) -> None:
        if self.logdir is None:
            return
        import jax
        if not self._active and self.start <= step < self.stop:
            _start_trace(self.logdir)
            self._active = True
        elif self._active and step >= self.stop:
            jax.profiler.stop_trace()
            self._active = False
            log.info("step trace [%d,%d) written to %s",
                     self.start, self.stop, self.logdir)

    def close(self) -> None:
        if self._active:
            import jax
            jax.profiler.stop_trace()
            self._active = False
