"""Framework-owned training driver: the loop every example hand-rolled.

``run_training`` fuses the device-prefetched input pipeline
(``tony_tpu.io.prefetch``), the instrumented train step
(``train.make_train_step``), periodic eval, and async orbax checkpointing
(``CheckpointManager.save`` never blocks the loop; the manager's
``wait_until_finished`` runs ONCE, at exit) into one driver — so the step
dispatch cadence is gated only by device compute, never by decode, H2D
copies, or checkpoint IO.

The loop observes ``tony_data_wait_seconds`` into the default metrics
registry: the host wall each iteration spent blocked on ``next(data)``.
That histogram is the direct input-boundedness signal — near zero means
the prefetcher stays ahead and training is device-bound; a per-step value
tracking decode cost means the pipeline is input-bound (raise the
prefetch depth, add reader processes, or move decode off the host). It
ships through the PR 2 metrics plane like every ``tony_*`` series
(heartbeat → coordinator → history server `/metrics`).

KeyboardInterrupt-safe by construction: the ``finally`` closes the data
iterator (stopping its ``tony-datafeed-*`` producer thread) before
waiting out pending checkpoint saves.
"""

from __future__ import annotations

import itertools
import logging
import time
from typing import Any, Callable, Iterable

from tony_tpu import constants
from tony_tpu.runtime import goodput as goodput_mod
from tony_tpu.runtime import metrics as metrics_mod
from tony_tpu.runtime import tracing

log = logging.getLogger(__name__)


class GangLostError(RuntimeError):
    """The step loop died because its GANG did, not because of user code:
    a collective transport or the distributed runtime failed under the
    step (a peer process was preempted mid-collective). Trainers should
    exit with :attr:`exit_code` — the executor recognizes it and, under
    elastic training, holds the report and relaunches the trainer against
    the resized gang instead of failing the job."""

    exit_code = constants.EXIT_GANG_LOST


#: conservative substrings identifying collective/distributed-runtime
#: failures across the transports this framework runs on (gloo on CPU,
#: libtpu/megascale on slices, the jax coordination service everywhere).
#: Deliberately NOT "unavailable"/"connection" alone — user code talks to
#: networks too; every marker here names a collectives layer.
_GANG_LOSS_MARKERS = (
    "gloo", "coordination service", "nccl", "megascale",
    "distributed service", "all-reduce failed", "all-gather failed",
    "collective", "preempted",
)


def _looks_like_gang_loss(e: BaseException) -> bool:
    seen: set[int] = set()
    while e is not None and id(e) not in seen:
        seen.add(id(e))
        msg = str(e).lower()
        if any(m in msg for m in _GANG_LOSS_MARKERS):
            return True
        e = e.__cause__ or e.__context__
    return False


#: data-wait buckets: the healthy value is ~0 (the prefetcher stays ahead
#: of the step loop), so sub-millisecond resolution matters more than the
#: minute-scale tail of the generic time ladder
DATA_WAIT_BUCKETS_S: tuple[float, ...] = (
    0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
    0.1, 0.25, 0.5, 1.0, 5.0)


def run_training(step_fn: Callable[[Any, Any], tuple[Any, dict]],
                 state: Any, data: Iterable, steps: int, *,
                 start_step: int = 0, checkpoint=None,
                 eval_fn: Callable[[Any], Any] | None = None,
                 eval_every: int = 0, log_every: int = 20,
                 log_fn: Callable[[int, dict, Any], None] | None = None,
                 step_hook: Callable[[int], None] | None = None,
                 ) -> tuple[Any, dict]:
    """Drive ``steps - start_step`` train steps; returns (state, metrics).

    - ``step_fn(state, batch) -> (state, metrics)`` — any step with the
      ``make_train_step`` shape (donation-safe: the returned state is the
      live one).
    - ``data`` — an iterator of device-ready batches, normally a
      :class:`~tony_tpu.io.prefetch.DevicePrefetcher`; the loop closes it
      at exit if it has a ``close()``. A batch is fetched per step and
      the blocked wall observed into ``tony_data_wait_seconds``. If the
      iterator runs dry early the loop stops cleanly (finite datasets).
      ``None`` means this process consumes NO input feed — the shape of
      a cross-slice pipeline stage gang past stage 0, whose "input" is
      activations arriving on its tensor channel inside ``step_fn``;
      the loop then passes ``batch=None`` every step.
    - ``checkpoint`` — a :class:`~tony_tpu.models.checkpoint
      .CheckpointManager`; ``save(step+1, state)`` is offered every step
      (the manager's ``save_interval_steps`` decides), and the pipeline
      is never drained mid-run — only ``wait_until_finished`` at exit.
    - ``eval_fn(state)`` runs every ``eval_every`` steps; the most
      recent result rides in ``metrics["eval"]`` from then on, so log
      cadences that don't align with the eval cadence still surface it.
    - ``log_fn(step, metrics, batch)`` runs every ``log_every`` steps and
      on the final step (the batch is passed so callers can derive
      global examples/step from the assembled shape).
    - ``step_hook(step)`` runs first each iteration (profiler tracers).
    """
    if data is None:
        data = itertools.repeat(None)
    it = iter(data)
    reg = metrics_mod.get_default()
    wait_hist = reg.histogram(
        "tony_data_wait_seconds",
        help="host wall seconds the train loop spent blocked on data",
        buckets=DATA_WAIT_BUCKETS_S)
    metrics: dict = {}
    last_eval = None
    tracer = tracing.get_tracer()
    flight = tracing.get_flight()
    # Goodput attribution: each phase below ALSO lands in the process
    # ledger (data_wait/step/checkpoint/eval), which publishes to the
    # executor via TONY_GOODPUT_SPOOL and rides heartbeats from there.
    ledger = goodput_mod.get_ledger()
    try:
        for step in range(start_step, steps):
            if step_hook is not None:
                step_hook(step)
            # Per-step trace (head-sampled via tony.trace.sample-rate):
            # the step root with its phases as children — the causal
            # view behind the tony_data_wait/step-wall aggregates. Every
            # span is also a row of a running profiler capture: the step
            # root as xprof's step ``tony.train``, the phases as
            # ``tony.train.<phase>`` inside it, on the device's clock.
            with tracer.span("train.step", step=step, step_num=step):
                try:
                    with tracer.span("train.data_wait"):
                        # the clock reads sit INSIDE the span and around
                        # the ledger, as they always have: the histogram
                        # is the block on next(), not the span's own
                        # storing (a spool write when sampled)
                        t0 = time.perf_counter()
                        with ledger.enter("data_wait"):
                            batch = next(it)
                        wait = time.perf_counter() - t0
                except StopIteration:
                    log.warning("data exhausted at step %d (wanted %d); "
                                "stopping early", step, steps)
                    break
                wait_hist.observe(wait)
                try:
                    with tracer.span("train.dispatch"), \
                            ledger.enter("step"):
                        state, metrics = step_fn(state, batch)
                except Exception as e:
                    if _looks_like_gang_loss(e):
                        # the GANG failed, not the user's step: surface
                        # the distinguished error so elastic executors
                        # relaunch instead of charging a user failure
                        # (the finally below still flushes in-flight
                        # checkpoint saves — the checkpoint-sync step of
                        # a degraded resume). The flight ring dumps
                        # first: the step-level postmortem of WHAT died
                        # mid-collective survives the process.
                        log.warning(
                            "step %d failed with a collective/"
                            "distributed-runtime error — gang lost: %s",
                            step, e)
                        flight.record("gang_lost", step=step,
                                      error=str(e)[:500])
                        flight.dump("gang_lost", step=step)
                        raise GangLostError(str(e)) from e
                    raise
                if checkpoint is not None:
                    with tracer.span("train.checkpoint"), \
                            ledger.enter("checkpoint"):
                        checkpoint.save(step + 1, state)
                if (eval_fn is not None and eval_every > 0
                        and (step + 1) % eval_every == 0):
                    with tracer.span("train.eval"), ledger.enter("eval"):
                        last_eval = eval_fn(state)
                if last_eval is not None:
                    metrics = dict(metrics)
                    metrics["eval"] = last_eval
                if log_fn is not None and (step % max(1, log_every) == 0
                                           or step == steps - 1):
                    log_fn(step, metrics, batch)
    finally:
        close = getattr(data, "close", None)
        if close is not None:
            close()
        if checkpoint is not None:
            with ledger.enter("checkpoint"):
                checkpoint.wait_until_finished()
        # push the final breakdown to the executor bridge even if the
        # loop ends between throttled publishes
        ledger.publish()
    return state, metrics
