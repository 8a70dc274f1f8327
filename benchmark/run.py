"""Run one cell of the benchmark once.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell's entry in ``BENCHMARK.json`` names a configuration
(``benchmark/configs/<config>.json``) and a traffic mix
(``benchmark/traffic/<traffic>.json``); the mix's ``kind`` names the driver
(``benchmark/drivers/<kind>.py``) that starts the system under test and
offers it the load; ``benchmark/limits/<cell>.json`` holds the limit of
each number compared for ``correct``; each per-layer metric is read by
``benchmark/metrics/<metric>.py``. This process never imports JAX: the
children hold the chips. The last line of stdout is the result object.
"""

from __future__ import annotations

T0 = __import__("time").time()               # set-up starts here

import argparse
import importlib
import importlib.util
import json
import os
import shutil
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from benchmark.lib import modelcfg, traffic, xplane      # noqa: E402
from benchmark.lib.peaks import peaks                     # noqa: E402


def child_env(root: str, platform: str) -> dict:
    """Environment of every child: the platform forced (no chip -> the
    backend fails to start, never a CPU run), the program importable, and
    ONE compile cache at a fixed place inside the checkout."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = platform
    env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(root, ".bench_cache",
                                                    "jax")
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (ROOT, env.get("PYTHONPATH", "")) if p)
    env["PYTHONUNBUFFERED"] = "1"
    env.pop("BENCH_RUN", None)
    return env


def read_metric(name: str, ctx: dict):
    path = os.path.join(BENCH, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def load_cell(workload: str, bench: dict | None = None):
    """(cell entry, configuration, traffic mix) of ``workload``, from
    ``BENCHMARK.json`` unless the tests hand in a toy ``bench``."""
    if bench is None:
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == workload)
    return (bench, cell, modelcfg.load(cell["config"]),
            traffic.load(cell["traffic"]))


def run_cell(bench: dict | None, workload: str, seed: int, seconds: float,
             trace: int, platform: str = "tpu", root: str = ROOT,
             fault: str = "", limits: dict | None = None) -> dict:
    """One run of one cell; returns the result object. ``platform`` and
    ``fault`` are for the tests, which have no chip and break the timed
    path on purpose."""
    bench, cell, c, mix = load_cell(workload, bench)
    if limits is None:
        with open(os.path.join(BENCH, "limits", f"{workload}.json")) as f:
            limits = json.load(f)["limits"]
    out = os.path.join(root, ".bench_runs", f"{workload}-{seed}-{trace}")
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)
    driver = importlib.import_module(f"benchmark.drivers.{mix['kind']}")
    got = driver.run(cell=cell, c=c, mix=mix, seed=seed, seconds=seconds,
                     trace=trace, out=out, env=child_env(root, platform),
                     platform=platform, fault=fault, t0=T0)

    correct = True
    for name, value in got["compared"].items():
        ok = value <= limits[name]
        correct &= ok
        print(f"compared {name} = {value!r} limit {limits[name]!r} "
              f"{'ok' if ok else 'NOT CORRECT'}", flush=True)
    for line in got.get("notes", []):
        print(line, flush=True)

    device = dict(got["device"])
    metrics = {}
    if not trace:
        for m in bench["end_to_end"]:
            if workload in m.get("workloads", [workload]):
                metrics[m["name"]] = {"value": got["e2e"][m["name"]],
                                      "unit": m["unit"]}
    else:
        tr = got["trace"]
        device["busy_s"], device["window_s"] = xplane.busy_and_window_s(tr)
        ctx = dict(got["ctx"], c=c, mix=mix, cell=cell, trace=tr,
                   e2e=got["e2e"], peaks=peaks(device["kind"])
                   if platform == "tpu" else None)
        for m in bench["per_layer"]:
            if workload in m.get("workloads", [workload]):
                value = read_metric(m["name"], ctx)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {"correct": bool(correct), "attempted": got["attempted"],
              "failed": got["failed"], "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = {"device_ops": xplane.top_ops(tr),
                               "idle_gaps": xplane.idle_gaps(tr)}
    for big in ("tokens.bin", "trace", "staging"):   # keep logs and JSON
        path = os.path.join(out, big)
        if os.path.isdir(path):
            shutil.rmtree(path)
        elif os.path.exists(path):
            os.remove(path)
    return result


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    result = run_cell(None, args.workload, args.seed, args.seconds,
                      args.trace)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
