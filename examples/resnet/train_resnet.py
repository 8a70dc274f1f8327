"""ResNet-50 data-parallel training — the 8-worker progression config.

BASELINE.json progression step 4: "8w ResNet-50 DP". One SPMD program over
the ``dp`` mesh axis: every process contributes its local image shard to a
global batch, XLA inserts the gradient all-reduce, and batch-norm statistics
are cross-replica-synced by construction (the stats come out of the same
compiled program). Synthetic ImageNet-shaped data keeps the example
dependency-free; the data-feed layer (tony_tpu.io) plugs in for real input.

Usage:
    python -m tony_tpu.client.cli submit \
        --conf tony.worker.instances=8 \
        --conf tony.application.mesh=dp=-1 \
        --src_dir examples \
        --executes 'python examples/resnet/train_resnet.py --steps 100'
"""

from __future__ import annotations

import argparse
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

import tony_tpu.runtime as rt
from tony_tpu.io.prefetch import DevicePrefetcher
from tony_tpu.models import resnet as R
from tony_tpu.models.loop import run_training
from tony_tpu.models.train import batch_sharding


def synthetic_batches(seed, batch, image_size, num_classes):
    """Infinite host-side image batches (f32 numpy; the train step casts
    to the model dtype on device — a fused elementwise op)."""
    rs = np.random.RandomState(seed)
    while True:
        yield {
            "image": rs.randn(batch, image_size, image_size, 3)
                       .astype(np.float32),
            "label": rs.randint(0, num_classes, size=(batch,))
                       .astype(np.int32),
        }


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--depth", type=int, default=50,
                        choices=sorted(R.STAGE_SIZES))
    parser.add_argument("--steps", type=int, default=50)
    parser.add_argument("--batch_size", type=int, default=32,
                        help="batch size PER PROCESS (global = this x hosts)")
    parser.add_argument("--image_size", type=int, default=224)
    parser.add_argument("--num_classes", type=int, default=1000)
    parser.add_argument("--lr", type=float, default=0.1)
    args = parser.parse_args()

    info = rt.initialize()
    mesh = rt.mesh()
    print(f"[{info.job_name}:{info.task_index}] devices={len(jax.devices())} "
          f"mesh={dict(zip(mesh.axis_names, mesh.devices.shape))}",
          flush=True)

    dtype = rt.platform_dtype()
    print(rt.device_line(dtype), flush=True)
    params, stats = R.init_resnet(jax.random.PRNGKey(0), depth=args.depth,
                                  num_classes=args.num_classes, dtype=dtype)
    opt = optax.sgd(args.lr, momentum=0.9, nesterov=True)
    # batch-norm stats ride in the state pytree, so the step keeps the
    # (state, batch) -> (state, metrics) shape run_training drives
    state = {"params": params, "stats": stats,
             "opt_state": opt.init(params),
             "step": jnp.zeros((), jnp.int32)}

    def step_impl(state, batch):
        batch = dict(batch, image=batch["image"].astype(dtype))
        (loss, new_stats), grads = jax.value_and_grad(
            R.classification_loss, has_aux=True)(
                state["params"], state["stats"], batch, args.depth)
        updates, opt_state = opt.update(grads, state["opt_state"],
                                        state["params"])
        params = optax.apply_updates(state["params"], updates)
        return {"params": params, "stats": new_stats,
                "opt_state": opt_state,
                "step": state["step"] + 1}, {"loss": loss}

    jitted = jax.jit(step_impl, donate_argnums=(0,))

    def step_fn(state, batch):
        with jax.set_mesh(mesh):
            return jitted(state, batch)

    # Per-process shard → global array, assembled + transferred on the
    # prefetcher's producer thread (multi-host feeding pattern, off the
    # step critical path).
    data = DevicePrefetcher(
        synthetic_batches(info.task_index, args.batch_size,
                          args.image_size, args.num_classes),
        sharding=batch_sharding(mesh))
    t0 = time.perf_counter()

    def log_fn(i, metrics, batch):
        img_s = (args.batch_size * info.num_processes * (i + 1)
                 / (time.perf_counter() - t0))
        print(f"step {i} loss {float(metrics['loss']):.4f} "
              f"images/s {img_s:,.1f}", flush=True)

    state, metrics = run_training(step_fn, state, data, args.steps,
                                  log_every=10, log_fn=log_fn)
    loss = float(metrics["loss"]) if metrics else float("nan")
    ok = jnp.isfinite(loss)
    print(f"done: final loss {loss:.4f}", flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
