"""Sharded train-step builder: loss → pjit-compiled SPMD update.

The TPU-native replacement for what the reference leaves entirely to user
TF/PyTorch code (SURVEY.md §2.3: PS/worker and all-reduce DP live in
tony-examples, not the framework). Here the framework owns the recipe:
params live device-sharded per logical-axis rules, the batch arrives sharded
over dp/fsdp, jax.grad + optax run under jit over the global mesh, and XLA
inserts the gradient psum/reduce-scatter collectives that NCCL all-reduce
performed in the reference's PyTorch example (tony-examples/mnist-pytorch/
mnist_distributed.py:113-126).
"""

from __future__ import annotations

import collections
import contextlib
import functools
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tony_tpu.models import remat
from tony_tpu.parallel.sharding import (DEFAULT_RULES, Rules,
                                        logical_sharding, param_shardings,
                                        shard_pytree)
from tony_tpu.runtime import metrics as metrics_mod


# Train state is a plain dict pytree: {"params", "opt_state", "step"}.
TrainState = dict

#: Trace-time program counters keyed by (program name, batch leaf
#: shapes/dtypes): incremented when the train/eval step is TRACED
#: (compiled), not when it is called — the train-side twin of
#: ``serve.TRACE_COUNTS``. The conftest ``retrace_guard`` fixture reads
#: both, so tests pin "one compiled train step per batch shape across a
#: full run_training run" the same way serve pins bucketed admission.
TRACE_COUNTS: collections.Counter = collections.Counter()


def _count_trace(name: str, batch: Any) -> None:
    TRACE_COUNTS[(name, tuple(
        (tuple(getattr(l, "shape", ())), str(getattr(l, "dtype", "?")))
        for l in jax.tree.leaves(batch)))] += 1


def masked_cross_entropy(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """Mean -log p[target] over positions with targets >= 0 (-1 = ignore).

    Uses the logsumexp form so the [B, S, V] log_softmax is never
    materialized — at LM vocab sizes that array is the largest HBM tensor
    in the step. The target logit is picked with an on-the-fly one-hot
    compare-and-reduce rather than ``take_along_axis``: a gather is its own
    HLO and forces a SECOND full pass over the logits (+2.8 ms/step
    measured at 16×1024×32k on one v5e — and its backward is a scatter),
    while the compare/select/reduce fuses into the same fusion that
    computes lse, so the logits are read once. Loss math runs in f32
    whatever the logits' storage dtype (models may store them bf16 —
    TransformerConfig.logits_dtype — and the upcast here is elementwise,
    so it fuses into the reduction passes rather than materializing).
    """
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)              # [B, S]
    onehot = targets[..., None] == jnp.arange(logits.shape[-1])     # virtual
    picked = jnp.sum(jnp.where(onehot, logits, 0.0), axis=-1)       # [B, S]
    mask = (targets >= 0).astype(jnp.float32)
    return ((lse - picked) * mask).sum() / jnp.maximum(mask.sum(), 1.0)


def init_state(params: Any, optimizer: optax.GradientTransformation,
               mesh: Mesh | None = None, axes: Any = None,
               rules: Rules = DEFAULT_RULES) -> TrainState:
    """Build (and, given a mesh, device-shard) the train state."""
    if mesh is not None and axes is not None:
        params = shard_pytree(params, axes, mesh, rules)
    opt_state = optimizer.init(params)
    return {"params": params, "opt_state": opt_state,
            "step": jnp.zeros((), jnp.int32)}


def make_train_step(loss_fn: Callable[[Any, Any], jax.Array] | None,
                    optimizer: optax.GradientTransformation,
                    mesh: Mesh | None = None,
                    donate: bool = True,
                    value_and_grad_fn: Callable | None = None) -> Callable:
    """Compile ``state, batch → state, metrics``.

    ``loss_fn(params, batch) -> scalar``. Under a mesh the step runs as one
    SPMD program; gradients of replicated params are reduced by XLA
    automatically (no explicit all-reduce anywhere).

    ``value_and_grad_fn(params, batch) -> (loss, grads)`` replaces
    ``jax.value_and_grad(loss_fn)`` for schedules that produce their own
    gradients (the 1F1B pipeline, transformer.lm_value_and_grad — 1F1B
    must run the loss inside the pipeline, so it cannot be a jax.grad
    target); ``loss_fn`` may then be None.

    What the backward keeps of the forward is decided inside the trace
    from the memory the device has (``models/remat.py``); the step tells
    it what its own arguments hold. Should the compiler still refuse the
    step for memory, the step is rebuilt one rung down and says so in
    the log (``tony_train_saved_step_downs_total`` counts it).
    """

    fused = hasattr(optimizer, "fused_apply")
    if fused and mesh is not None:
        # fail where the step is built, not with an opaque SPMD lowering
        # error: a pallas_call does not partition under pjit, so sharded
        # params need the optax formulation (default_optimizer docstring)
        raise ValueError("fused optimizers are single-chip only — use "
                         "default_optimizer(fused=False) with a mesh")

    vag = value_and_grad_fn or jax.value_and_grad(loss_fn)
    saves = remat.Scope()

    def step(state: TrainState, batch: Any, ceiling: int):
        # ``ceiling`` is ``saves.ceiling``, static: part of the program's
        # key, so a step-down is a new trace and never a cached one
        _count_trace("train_step", batch)   # trace-time only: counts compiles
        with remat.scope(saves):
            loss, grads = vag(state["params"], batch)
        with jax.named_scope("optimizer"):
            if fused:
                # single-pass update (ops/optim.py): params change inside
                # the kernel, no separate apply_updates traversal
                params, opt_state, gnorm = optimizer.fused_apply(
                    grads, state["opt_state"], state["params"])
            else:
                updates, opt_state = optimizer.update(
                    grads, state["opt_state"], state["params"])
                params = optax.apply_updates(state["params"], updates)
                gnorm = optax.global_norm(grads)
        new_state = {"params": params, "opt_state": opt_state,
                     "step": state["step"] + 1}
        return new_state, {"loss": loss, "grad_norm": gnorm,
                           "step": new_state["step"]}

    jitted = jax.jit(step, static_argnums=(2,),
                     donate_argnums=(0,) if donate else ())

    # set_mesh must wrap the CALL, not the traced body: the ambient mesh is
    # what lets bare-PartitionSpec sharding constraints resolve.
    ambient = (contextlib.nullcontext if mesh is None
               else functools.partial(jax.set_mesh, mesh))

    def under_mesh(fn):
        def call(state, batch):
            if not saves.held:
                # a state that is not donated lives beside its successor
                saves.held = (remat.bytes_a_device((state, batch))
                              + (0 if donate else
                                 remat.bytes_a_device(state)))
            with ambient():
                return fn(state, batch, saves.ceiling)
        return call

    dispatch = under_mesh(jitted)

    def fitted(state, batch):
        while True:
            try:
                return dispatch(state, batch)
            except jax.errors.JaxRuntimeError as e:
                if not remat.step_down(saves, e, state):
                    raise

    run = _instrument_step(fitted)
    # AOT handle on the SAME program (arrays or ShapeDtypeStructs in): what
    # lets a caller read the step's text — is the flash kernel in it? — or
    # compile it for a described topology, without running it
    run.lower = under_mesh(jitted.lower)
    return run


def _instrument_step(step_fn: Callable) -> Callable:
    """Observe per-call wall time and example throughput into the default
    metrics registry (``tony_train_step_seconds`` histogram,
    ``tony_train_steps_total`` / ``tony_train_examples_total`` counters).

    The timing is the HOST wall of the dispatch: jitted steps run async,
    but under a saturated loop with donated state each dispatch gates on
    the previous step's completion, so steady-state wall-per-call tracks
    step time (the same caveat every async-dispatch profiler carries;
    ``PhaseTimes``/``StepTracer`` in runtime/profiler.py give the precise
    per-phase / device-side views). Cost per call is one perf_counter
    pair plus three GIL-atomic observations — noise next to any real
    step."""

    def instrumented(state, batch):
        t0 = time.perf_counter()
        out = step_fn(state, batch)
        dt = time.perf_counter() - t0
        reg = metrics_mod.get_default()
        reg.histogram("tony_train_step_seconds",
                      help="host wall seconds per train-step dispatch"
                      ).observe(dt)
        reg.counter("tony_train_steps_total", help="train steps run").inc()
        leaves = jax.tree.leaves(batch)
        if leaves and getattr(leaves[0], "shape", None):
            # leading batch dim of the first leaf = local examples/step;
            # rate(examples_total) is the examples/s the fleet view wants
            reg.counter("tony_train_examples_total",
                        help="examples consumed by train steps").inc(
                            leaves[0].shape[0])
        return out

    return instrumented


def batch_sharding(mesh: Mesh, rules: Rules = DEFAULT_RULES,
                   logical: tuple = ("batch",)) -> NamedSharding:
    """Sharding for input batches: batch dim over dp/fsdp, rest replicated
    (callers append dims, e.g. ("batch", "seq") for token arrays)."""
    return logical_sharding(logical, mesh, rules)


def data_parallel_rank(mesh: Mesh, axes: tuple[str, ...] = ("dp", "fsdp"),
                       ) -> int:
    """This process's rank along the data-parallel mesh axes — the value to
    seed per-process data generation with. Processes at the same dp/fsdp
    coordinate (e.g. pure-pp or pure-tp meshes, where the batch is
    REPLICATED across processes) get the same rank and must feed identical
    data; seeding by task index there would hand ``global_batch`` divergent
    "replicas" that silently disagree across devices.

    Memoized per (mesh, axes): the body runs an ``np.vectorize`` scan over
    every mesh device, and data sources call this from step-adjacent paths
    (the prefetcher's epoch seeding) — the device↔process assignment is
    fixed for the life of the process, so the scan pays once."""
    return _data_parallel_rank_cached(mesh, tuple(axes))


@functools.lru_cache(maxsize=64)
def _data_parallel_rank_cached(mesh: Mesh, axes: tuple[str, ...]) -> int:
    import numpy as np
    local = set(jax.local_devices())
    coords = np.argwhere(
        np.vectorize(lambda d: d in local)(mesh.devices))
    if coords.size == 0:    # process owns no mesh device (untracked types)
        return 0
    first = coords[0]
    rank = 0
    for ax in axes:
        if ax in mesh.axis_names:
            i = mesh.axis_names.index(ax)
            rank = rank * mesh.devices.shape[i] + int(first[i])
    return rank


def global_batch(sharding: NamedSharding, local_tree: Any) -> Any:
    """Assemble each process's LOCAL batch shard into global jax.Arrays —
    the multi-host feeding recipe (every process calls this with its own,
    different data; ``jax.device_put`` would instead assert the value is
    identical everywhere). Leaves may differ in rank; the sharding's spec
    applies to the leading (batch) dims and replicates the rest."""
    return jax.tree.map(
        lambda x: jax.make_array_from_process_local_data(sharding, x),
        local_tree)


def make_eval_step(loss_fn: Callable[[Any, Any], jax.Array],
                   mesh: Mesh | None = None) -> Callable:
    def eval_step(params, batch):
        _count_trace("eval_step", batch)
        return loss_fn(params, batch)

    jitted = jax.jit(eval_step)
    if mesh is None:
        return jitted

    def sharded(params, batch):
        with jax.set_mesh(mesh):
            return jitted(params, batch)
    return sharded


def default_optimizer(lr: float = 3e-4, weight_decay: float = 0.01,
                      warmup_steps: int = 100,
                      total_steps: int = 10_000,
                      fused: bool = False):
    """AdamW + linear warmup→cosine decay, the standard LM recipe.

    ``fused=True`` selects the single-pass Pallas update (ops/optim.py)
    with f32 moments — a NUMERICS upgrade for bf16 models (optax silently
    inherits bf16 moments from bf16 grads), at a measured ~1 ms/step cost
    at 66 M params on one v5e. It is not a throughput win: XLA fuses the
    optax chain into the backward epilogue (grads are consumed in
    registers, never re-read from HBM), which a custom call cannot match
    — see docs/performance.md "What didn't help". The optax chain is
    the default and the only multi-chip path (a pallas_call does not
    partition under pjit). Both match to fp tolerance (tests/test_ops.py).
    """
    sched = optax.warmup_cosine_decay_schedule(
        0.0, lr, warmup_steps, max(total_steps, warmup_steps + 1))
    if fused:
        from tony_tpu.ops.optim import FusedAdamW
        return FusedAdamW(sched, weight_decay=weight_decay, clip_norm=1.0)
    return optax.chain(
        optax.clip_by_global_norm(1.0),
        optax.adamw(sched, weight_decay=weight_decay),
    )
