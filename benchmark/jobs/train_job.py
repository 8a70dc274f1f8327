"""The training job the benchmark submits through ``tony_tpu.client.cli
local``: a published-width decoder trained by the program's own step and
loop, fed by the program's prefetcher from a token file.

One object — the compiled step with its state — is built once, driven from
the seed through its first ``warm_steps`` steps (set-up; the first two are
the ones the float32 reference follows), and handed to the measured window
through the same ``run_training`` call and the same feed. The window lasts
``--seconds`` of host clock and ends in ``block_until_ready`` on the last
step's state. After the window the state is freed and the reference runs.

Writes ``<out>/result.json``; with ``--trace 1`` also the reduced device
trace ``<out>/trace.json``.
"""

from __future__ import annotations

T_SCRIPT = __import__("time").time()         # launch_s ends here

import argparse
import itertools
import json
import os
import re
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH))

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import modelcfg, reference, traffic, xplane


def _cache_counts() -> tuple[int, int]:
    from tony_tpu.runtime import compile_cache
    hits, requests = (int(x) for x in re.findall(
        r"\d+", compile_cache.stats()))
    return hits, requests


def _adam_mu(opt_state):
    (found,) = [s for s in jax.tree.leaves(
        opt_state, is_leaf=lambda s: hasattr(s, "mu")) if hasattr(s, "mu")]
    return found.mu


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--chips", type=int, required=True)
    ap.add_argument("--platform", default="tpu")
    ap.add_argument("--out", required=True)
    ap.add_argument("--fault", default="",
                    help="tests only: break the timed path underneath")
    args = ap.parse_args()

    c = modelcfg.load(args.config)
    fam = modelcfg.family(c)
    mix = traffic.load(args.traffic)
    # every program, however quick to compile, is served from the cache on
    # the next run (the program's own entry points keep JAX's 1 s default)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    import tony_tpu.runtime as rt
    from tony_tpu.io.prefetch import DevicePrefetcher, reader_epochs
    from tony_tpu.models import transformer as T
    from tony_tpu.models.loop import run_training
    from tony_tpu.models.train import (batch_sharding, default_optimizer,
                                       init_state, make_train_step)
    from tony_tpu.parallel.sharding import param_shardings
    from tony_tpu.runtime import metrics as metrics_mod

    rt.initialize()
    devices = jax.devices()
    if devices[0].platform != args.platform or len(devices) != args.chips:
        raise SystemExit(f"wanted {args.chips} {args.platform} device(s); "
                         f"JAX found {len(devices)} of platform "
                         f"{devices[0].platform!r}")
    dtype = jnp.bfloat16 if args.platform == "tpu" else jnp.float32
    print(rt.device_line(dtype), flush=True)
    mesh = rt.mesh()
    cfg = fam.program_config(c, dtype=dtype)
    batch, seq = mix["batch_per_process"], mix["seq_len"]
    warm, lag = mix["warm_steps"], mix["sync_lag_steps"]
    seed = args.seed

    def seeded_params():
        return fam.make_params(
            seed, c, dtype, param_shardings(T.logical_axes(cfg), mesh))

    params = seeded_params()
    opt = default_optimizer(**mix["optimizer"])
    frozen = args.fault == "frozen_state"
    step_fn = make_train_step(lambda p, b: T.lm_loss(p, b, cfg, mesh), opt,
                              mesh, donate=not frozen)
    state = init_state(params, opt)
    del params

    epoch_fn, _ = reader_epochs(
        [os.path.join(args.out, "tokens.bin")], batch, np.int32, (seq + 1,),
        shuffle=True, seed=seed % (1 << 31))
    clock = {"deadline": None, "t0": None, "t_trace": None}

    fed = []                  # the first two batches as the benchmark made them

    def source():
        for epoch in itertools.count():
            for tokens in epoch_fn(epoch):
                if clock["deadline"] and time.perf_counter() > \
                        clock["deadline"]:
                    return
                if len(fed) < 2:
                    fed.append((tokens[:, :seq].copy(),
                                tokens[:, 1:].copy()))
                if args.fault == "half_batch":
                    tokens = tokens.copy()
                    tokens[batch // 2:, 1:] = -1   # targets the loss skips
                yield {"inputs": np.maximum(tokens[:, :seq], 0),
                       "targets": tokens[:, 1:]}

    data = DevicePrefetcher(
        source(), sharding=batch_sharding(mesh, logical=("batch", "seq")),
        depth=mix["prefetch_depth"])

    seen = {"state": state, "losses": [], "checks": {}}
    marks = {}

    def wait_hist():
        return metrics_mod.get_default().histogram("tony_data_wait_seconds")

    def step(state, batch):
        with jax.profiler.TraceAnnotation("bench.train_step"):
            new, m = step_fn(state, batch)
        seen["state"] = state if frozen else new
        seen["losses"].append(m["loss"])
        return seen["state"], m

    def hook(i: int) -> None:
        """Runs first in every iteration: ``seen`` holds step i-1's output."""
        if i == 1:
            seen["checks"]["mu"] = fam.leaf_norms(_adam_mu(
                seen["state"]["opt_state"]))
        if i == 2:
            p0 = seeded_params()
            seen["checks"]["delta"] = fam.leaf_norms(
                seen["state"]["params"], p0)
            del p0
        if i == warm:
            jax.block_until_ready(seen["state"])
            marks["cache0"] = _cache_counts()
            marks["wait0"] = (wait_hist().sum, wait_hist().count)
            if args.trace:
                xplane.start(os.path.join(args.out, "trace"))
            clock["t0"] = time.perf_counter()
            marks["t_window_wall"] = time.time()
            clock["deadline"] = clock["t0"] + args.seconds
        if args.trace and i == warm + mix["trace_steps"]:
            jax.block_until_ready(seen["state"])
            clock["t_trace"] = time.perf_counter() - clock["t0"]
            jax.profiler.stop_trace()
        if i >= warm + lag:
            with jax.profiler.TraceAnnotation("bench.sync_lag"):
                jax.block_until_ready(seen["losses"][i - lag])

    state, _ = run_training(step, state, data, 1 << 30, log_every=1 << 30,
                            step_hook=hook)
    jax.block_until_ready(state)
    t_end = time.perf_counter()
    steps = len(seen["losses"]) - warm
    if clock["t0"] is None or steps < 1:
        raise SystemExit("the window never opened: too few steps")
    window = t_end - clock["t0"]
    losses = [float(x) for x in jax.device_get(seen["losses"])]
    hits1, req1 = _cache_counts()
    stats = [d.memory_stats() or {} for d in devices]
    result = {
        "t_script": T_SCRIPT, "t_window_wall": marks["t_window_wall"],
        "window_s": window, "steps": steps,
        "tokens_per_step": batch * seq * jax.process_count(),
        "losses": losses,
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind, "count": len(devices),
                   "memory_peak_bytes": max(
                       s.get("peak_bytes_in_use", 0) for s in stats)},
        "counters": {
            "compile_requests": req1, "compile_hits": hits1,
            "compile_requests_in_window": req1 - marks["cache0"][1],
            "data_wait_s": wait_hist().sum - marks["wait0"][0],
            "data_wait_n": wait_hist().count - marks["wait0"][1]},
        "trace_window_s": clock["t_trace"],
    }
    # free the program's state, then follow its first two steps in float32
    mu_norms, delta_norms = seen["checks"]["mu"], seen["checks"]["delta"]
    del state, data
    seen.clear()
    t_ref = time.perf_counter()
    ref = reference.train_two_steps(
        c, seed, fed, mix["optimizer"], weight_dtype=dtype)
    result["reference_s"] = time.perf_counter() - t_ref
    grad_prog = {n: v / (1 - 0.9) for n, v in mu_norms.items()}
    g_gap, g_at = reference.worst_leaf_gap(grad_prog, ref["grad_norm"])
    d_gap, d_at = reference.worst_leaf_gap(delta_norms, ref["delta_norm"])
    rows = np.concatenate([inputs for inputs, _ in fed])
    result["compared"] = {
        "loss_step0_gap": abs(losses[0] - ref["loss"][0]),
        "loss_step1_gap": abs(losses[1] - ref["loss"][1]),
        "grad_norm_worst_leaf_gap": g_gap,
        "param_change_worst_leaf_gap": d_gap,
        "nonfinite_losses": sum(not np.isfinite(x) for x in losses),
        "repeated_rows": len(rows) - len({r.tobytes() for r in rows}),
    }
    result["compared_at"] = {"grad_norm_worst_leaf_gap": g_at,
                             "param_change_worst_leaf_gap": d_at,
                             "reference_loss": ref["loss"],
                             "program_loss": losses[:2],
                             "reference_global_grad_norm":
                                 ref["global_grad_norm"]}
    if args.trace:
        xplane.write_reduced(os.path.join(args.out, "trace"),
                             os.path.join(args.out, "trace.json"))
    with open(os.path.join(args.out, "result.json"), "w") as f:
        json.dump(result, f)
    print(f"bench train_job: {steps} steps in {window:.3f} s, reference "
          f"{result['reference_s']:.1f} s", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
