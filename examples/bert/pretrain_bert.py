"""BERT MLM pretraining — the 16-worker multi-host progression config.

BASELINE.json's final progression step: "16w BERT-base jax.distributed
multi-host". The framework boots ``jax.distributed`` across all hosts
(rt.initialize), every process feeds its shard of the global batch, and the
MLM loss/optimizer run as one SPMD program over the ``dp`` (or
``dp×fsdp``) mesh. Synthetic masked-token data (15% masked) keeps the
example self-contained.

Usage:
    python -m tony_tpu.client.cli submit \
        --conf tony.worker.instances=16 \
        --conf tony.application.mesh=dp=-1 \
        --src_dir examples \
        --executes 'python examples/bert/pretrain_bert.py --steps 200 --config base'
"""

from __future__ import annotations

import argparse
import dataclasses
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

import tony_tpu.runtime as rt
from tony_tpu.io.prefetch import DevicePrefetcher
from tony_tpu.models import bert as B
from tony_tpu.models.loop import run_training
from tony_tpu.models.train import (batch_sharding, default_optimizer,
                                   init_state, make_train_step)
from tony_tpu.parallel import shard_pytree

CONFIGS = {"base": B.BERT_BASE, "tiny": B.BERT_TINY}
MASK_FRACTION = 0.15


def synthetic_mlm_batches(seed, batch, seq, cfg):
    """Infinite host-side MLM batches: random token ids with 15% positions
    masked-out as targets (-1 = ignore elsewhere), the MLM shape without a
    corpus. Numpy on the prefetcher's producer thread — masking/decode
    overlaps the device step."""
    rs = np.random.RandomState(seed)
    mask_id = cfg.vocab_size - 1
    while True:
        tokens = rs.randint(0, cfg.vocab_size,
                            size=(batch, seq)).astype(np.int32)
        masked = rs.rand(batch, seq) < MASK_FRACTION
        yield {
            "tokens": np.where(masked, mask_id, tokens).astype(np.int32),
            "targets": np.where(masked, tokens, -1).astype(np.int32),
        }


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--config", default="tiny", choices=sorted(CONFIGS))
    parser.add_argument("--steps", type=int, default=100)
    parser.add_argument("--batch_size", type=int, default=16,
                        help="batch size PER PROCESS (global = this x hosts)")
    parser.add_argument("--seq_len", type=int, default=128)
    parser.add_argument("--lr", type=float, default=1e-4)
    args = parser.parse_args()

    info = rt.initialize()
    mesh = rt.mesh()
    print(f"[{info.job_name}:{info.task_index}] "
          f"{len(jax.devices())} global devices "
          f"mesh={dict(zip(mesh.axis_names, mesh.devices.shape))}",
          flush=True)

    cfg = dataclasses.replace(CONFIGS[args.config],
                              dtype=rt.platform_dtype())
    print(rt.device_line(cfg.dtype), flush=True)
    seq = min(args.seq_len, cfg.max_seq)

    params = shard_pytree(B.init_params(jax.random.PRNGKey(0), cfg),
                          B.logical_axes(cfg), mesh)
    opt = default_optimizer(lr=args.lr, total_steps=args.steps)
    state = init_state(params, opt)
    step = make_train_step(lambda p, b: B.mlm_loss(p, b, cfg, mesh), opt,
                           mesh)

    # Each process contributes its local shard; assembly + H2D run on the
    # prefetcher's producer thread, overlapped with the device step.
    data = DevicePrefetcher(
        synthetic_mlm_batches(1000 + info.task_index, args.batch_size,
                              seq, cfg),
        sharding=batch_sharding(mesh, logical=("batch", "seq")))
    t0 = time.perf_counter()

    def log_fn(i, metrics, batch):
        tok_s = (args.batch_size * info.num_processes * seq * (i + 1)
                 / (time.perf_counter() - t0))
        print(f"step {i} mlm loss {float(metrics['loss']):.4f} "
              f"tok/s {tok_s:,.0f}", flush=True)

    state, metrics = run_training(step, state, data, args.steps,
                                  log_every=20, log_fn=log_fn)
    loss = float(metrics["loss"]) if metrics else float("nan")
    ok = jnp.isfinite(loss)
    print(f"done: final loss {loss:.4f}", flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
