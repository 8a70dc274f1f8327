"""Flagship decoder-LM training: sharded, checkpointed, profiled, retry-safe.

The full TPU-native training recipe the framework exists to orchestrate —
everything the reference left to user scripts, done the jax way:

- ``tony_tpu.runtime`` bootstraps jax.distributed from the coordinator env
  and builds the device mesh from ``tony.application.mesh``;
- params are sharded by logical-axis rules (dp/fsdp/tp/cp) and the train
  step compiles to one SPMD program per step (XLA inserts the collectives);
- orbax checkpointing with ``restore_or_init`` makes coordinator retries
  (ATTEMPT_NUMBER > 0) resume from the last step instead of restarting;
- the input pipeline is device-prefetched (``tony_tpu.io.prefetch``):
  reader decode, global-array assembly, and the H2D copy run on a
  producer thread, overlapped with device compute by the framework's
  ``run_training`` driver (``--prefetch_depth 0`` for the synchronous
  contrast);
- step-bounded profiler capture (``tony.task.profile.enabled=true``) records
  steady-state traces, skipping compile noise.

Usage:
    python -m tony_tpu.client.cli submit \
        --conf tony.worker.instances=4 \
        --conf tony.application.mesh=dp=-1 \
        --conf tony.am.retry-count=2 \
        --src_dir examples \
        --executes 'python examples/lm/train_lm.py --steps 200 \
                    --ckpt_dir /tmp/lm-ckpt --preset small'
"""

from __future__ import annotations

import argparse
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

import tony_tpu.runtime as rt
from tony_tpu.io.prefetch import (DevicePrefetcher, elastic_epochs,
                                   reader_epochs, synchronous_batches)
from tony_tpu.models import transformer as T
from tony_tpu.models.checkpoint import CheckpointManager, attempt_number
from tony_tpu.models.loop import GangLostError, run_training
from tony_tpu.models.train import (batch_sharding, data_parallel_rank,
                                   default_optimizer, init_state,
                                   make_train_step)
from tony_tpu.parallel import shard_pytree
from tony_tpu.runtime import compile_cache
from tony_tpu.runtime.profiler import StepTracer


def synthetic_source(seed: int, batch: int, seq: int, vocab: int):
    """Infinite host-side token batches (numpy: the prefetcher's producer
    thread decodes + assembles while the device computes)."""
    rs = np.random.RandomState(seed)
    while True:
        tokens = rs.randint(0, vocab, size=(batch, seq + 1)).astype(np.int32)
        yield {"inputs": tokens[:, :seq], "targets": tokens[:, 1:]}


def elastic_file_source(paths, global_batch: int, seq: int, seed: int,
                        start_step: int):
    """World-size-invariant feed for ELASTIC jobs (tony.elastic.enabled):
    the canonical single-reader stream is sliced per process, so the
    global batch at step s is identical before and after a shrink/regrow
    and the resumed loss curve continues exactly where the checkpoint
    left it (tony_tpu.io.prefetch.elastic_epochs; tradeoff: every process
    reads the whole dataset)."""
    rows, per_epoch = elastic_epochs(paths, global_batch, np.int32,
                                     (seq + 1,), shuffle=True, seed=seed,
                                     start_step=start_step)

    def batches():
        for tokens in rows:
            yield {"inputs": tokens[:, :seq], "targets": tokens[:, 1:]}

    return batches()


def file_source(paths, batch: int, seq: int, seed: int):
    """Epochal host-batch source over the sharded data-feed layer: each
    record is seq+1 int32 token ids; every process reads only its
    byte-range split (tony_tpu.io), reshuffled deterministically per epoch
    (seed + epoch). The DevicePrefetcher cycles epochs until the step loop
    stops pulling."""
    epoch_fn, per_epoch = reader_epochs(paths, batch, np.int32, (seq + 1,),
                                        shuffle=True, seed=seed)
    if per_epoch == 0:
        raise ValueError(
            f"data files hold fewer than one full batch per process "
            f"(batch_size={batch}, seq_len={seq}) — nothing to train on")

    def epochs(epoch: int):
        for tokens in epoch_fn(epoch):
            yield {"inputs": tokens[:, :seq], "targets": tokens[:, 1:]}

    return epochs


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--preset", default="tiny",
                        choices=sorted(T.PRESETS))
    parser.add_argument("--steps", type=int, default=100)
    parser.add_argument("--batch_size", type=int, default=8,
                        help="batch size PER PROCESS (global = this x hosts)")
    parser.add_argument("--seq_len", type=int, default=256)
    parser.add_argument("--lr", type=float, default=3e-4)
    parser.add_argument("--ckpt_dir", default="")
    parser.add_argument("--ckpt_every", type=int, default=50)
    parser.add_argument("--data_files", nargs="*", default=[],
                        help="binary token files (records of seq_len+1 "
                             "int32 ids) fed via the sharded data layer; "
                             "empty = synthetic data")
    parser.add_argument("--cp_strategy", default="ring",
                        choices=("ring", "ulysses"),
                        help="context-parallel attention when the mesh has "
                             "a cp axis: ring (ppermute K/V rotation) or "
                             "ulysses (all-to-all head resharding)")
    parser.add_argument("--num_experts", type=int, default=0,
                        help="mixture-of-experts FFN with this many experts "
                             "(0 = dense); experts shard over the mesh's ep "
                             "axis, composing with dp/tp/cp/pp")
    parser.add_argument("--pp_schedule", default="gpipe",
                        choices=("gpipe", "1f1b"),
                        help="pipeline schedule when the mesh has a pp "
                             "axis: gpipe (default) or 1f1b (O(pp) live "
                             "microbatch activations instead of O(M) — "
                             "for deep pipelines / many microbatches)")
    parser.add_argument("--attn_window", type=int, default=0,
                        help="sliding-window attention: each token "
                             "attends its N most recent positions "
                             "(0 = full causal); attention cost goes "
                             "O(seq*window) instead of O(seq^2)")
    parser.add_argument("--elastic_data", type=int, default=0,
                        metavar="GLOBAL_BATCH",
                        help="feed --data_files through the world-size-"
                             "invariant elastic source with this FIXED "
                             "global batch (must divide evenly over every "
                             "world size the job can shrink to; each "
                             "process feeds global/N rows) — required for "
                             "loss-curve continuity under "
                             "tony.elastic.enabled shrink/regrow. The "
                             "value is deliberately explicit: deriving it "
                             "from the live process count would change "
                             "the canonical stream across the very "
                             "transitions it exists to survive")
    parser.add_argument("--prefetch_depth", type=int, default=2,
                        help="device-prefetch queue depth (batches decoded "
                             "+ transferred ahead of the step loop); 0 = "
                             "synchronous inline feed (A/B contrast)")
    args = parser.parse_args()

    info = rt.initialize()
    dtype = rt.platform_dtype()
    print(rt.device_line(dtype), flush=True)
    mesh = rt.mesh()
    print(f"[{info.job_name}:{info.task_index}] attempt={info.attempt} "
          f"mesh={dict(zip(mesh.axis_names, mesh.devices.shape))} "
          f"devices={len(jax.devices())}", flush=True)

    cfg = T.PRESETS[args.preset].scaled(
        dtype=dtype,
        cp_strategy=args.cp_strategy,
        num_experts=args.num_experts,
        pp_schedule=args.pp_schedule,
        attn_window=args.attn_window)

    params = shard_pytree(T.init_params(jax.random.PRNGKey(0), cfg),
                          T.logical_axes(cfg), mesh)
    opt = default_optimizer(lr=args.lr, total_steps=args.steps)
    use_1f1b = cfg.pp_schedule == "1f1b" and mesh.shape.get("pp", 1) > 1
    print(f"pipeline schedule: {'1f1b' if use_1f1b else 'gpipe'}",
          flush=True)
    if use_1f1b:
        # 1F1B produces its own gradients (the loss head runs inside the
        # pipeline) — it plugs in through the value_and_grad hook
        step_fn = make_train_step(
            None, opt, mesh,
            value_and_grad_fn=lambda p, b: T.lm_value_and_grad(
                p, b, cfg, mesh))
    else:
        step_fn = make_train_step(lambda p, b: T.lm_loss(p, b, cfg, mesh),
                                  opt, mesh)

    mgr = (CheckpointManager(args.ckpt_dir,
                             save_interval_steps=args.ckpt_every)
           if args.ckpt_dir else None)
    state = (mgr.restore_or_init(lambda: init_state(params, opt))
             if mgr else init_state(params, opt))
    start_step = int(state["step"])

    b_sharding = batch_sharding(mesh, logical=("batch", "seq"))
    tracer = StepTracer(start=start_step + 5, stop=start_step + 8)

    # Host-batch source: files (epochal, per-epoch reshuffle) or synthetic.
    # Synthetic seeds by dp-rank, not task index: on meshes where the batch
    # replicates across processes (pure pp/tp) every process must feed
    # identical data. Each process contributes its LOCAL shard; the
    # prefetcher assembles global sharded arrays on its producer thread so
    # decode + H2D overlap device compute.
    if args.elastic_data:
        if not args.data_files:
            raise SystemExit("--elastic_data requires --data_files")
        source = elastic_file_source(
            args.data_files, args.elastic_data,
            args.seq_len, seed=0, start_step=start_step)
    elif args.data_files:
        source = file_source(args.data_files, args.batch_size,
                             args.seq_len, seed=attempt_number())
    else:
        source = synthetic_source(data_parallel_rank(mesh)
                                  + 1000 * attempt_number(),
                                  args.batch_size, args.seq_len,
                                  cfg.vocab_size)
    if args.prefetch_depth > 0:
        data = DevicePrefetcher(source, sharding=b_sharding,
                                depth=args.prefetch_depth)
    else:
        # synchronous contrast: decode + assembly inline on the step path
        # (same source protocol, no overlap)
        data = synchronous_batches(source, sharding=b_sharding)

    t0 = time.perf_counter()
    state_shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=x.sharding if x.committed else None),
        state)

    def log_fn(step, metrics, batch):
        if step == start_step:
            # what the step program IS, read from the program: a Mosaic
            # kernel lowers to a `tpu_custom_call`; the dense arm and the
            # interpreter do not
            text = step_fn.lower(state_shapes, batch).as_text()
            print(f"step program: {text.count('tpu_custom_call')} Mosaic "
                  f"kernel calls", flush=True)
        loss = float(metrics["loss"])
        # global tokens/step from the assembled batch itself (batch may
        # shard over processes — dp — or replicate — pure pp/tp)
        gb = batch["inputs"].shape[0]
        tok_s = (gb * args.seq_len * (step - start_step + 1)
                 / (time.perf_counter() - t0))
        print(f"step {step} loss {loss:.4f} tok/s {tok_s:,.0f}", flush=True)

    try:
        state, metrics = run_training(
            step_fn, state, data, args.steps, start_step=start_step,
            checkpoint=mgr, log_every=20, log_fn=log_fn,
            step_hook=tracer.step)
    except GangLostError as e:
        # elastic contract: the executor holds this distinguished exit and
        # relaunches us against the resized gang (checkpoints are flushed)
        print(f"gang lost: {e}", flush=True)
        return e.exit_code
    finally:
        tracer.close()
    if mgr:
        mgr.close()
    loss = float(metrics["loss"]) if metrics else float("nan")
    ok = jnp.isfinite(loss)
    print(compile_cache.stats(), flush=True)
    print(compile_cache.seconds_line(), flush=True)
    print(f"done: final loss {loss:.4f}", flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
