"""Driver of the training mixes: the job goes in as a user submits it —
``python -m tony_tpu.client.cli local --executes "<train_job.py ...>"`` —
client, coordinator, executor, user script, one worker process driving the
cell's chips. This process only writes the token file, waits, and reads
what the job wrote."""

from __future__ import annotations

import glob
import json
import os
import subprocess
import sys

from benchmark.lib import traffic
from benchmark.lib.procs import Children, fail

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run(*, cell, c, mix, seed, seconds, trace, out, env, platform, fault,
        t0) -> dict:
    traffic.write_token_file(os.path.join(out, "tokens.bin"), seed,
                             mix["records"], mix["seq_len"], c["vocab_size"])
    job = (f"{sys.executable} benchmark/jobs/train_job.py "
           f"--config {cell['config']} --traffic {cell['traffic']} "
           f"--seed {seed} --seconds {seconds} --trace {trace} "
           f"--chips {cell['chips']} --platform {platform} --out {out}"
           + (f" --fault {fault}" if fault else ""))
    staging = os.path.join(out, "staging")
    log = os.path.join(out, "submit.log")
    with Children() as children:
        proc = children.spawn(
            [sys.executable, "-m", "tony_tpu.client.cli", "local",
             "--src_dir", BENCH, "--executes", job,
             "--conf", "tony.worker.instances=1",
             "--conf", f"tony.application.mesh={mix['mesh']}",
             "--conf", f"tony.staging.dir={staging}"],
            env=env, cwd=out, log_path=log, stdout=subprocess.DEVNULL)
        try:
            rc = proc.wait(timeout=1150)
        except subprocess.TimeoutExpired:
            rc = "no exit within 1150 s"
    task_logs = sorted(glob.glob(os.path.join(staging, "*", "logs", "*")))
    result_path = os.path.join(out, "result.json")
    if rc != 0 or not os.path.exists(result_path):
        fail(f"training job: exit {rc}", log, *task_logs)
    with open(result_path) as f:
        r = json.load(f)
    tr = None
    if trace:
        with open(os.path.join(out, "trace.json")) as f:
            tr = json.load(f)
    tokens = r["steps"] * r["tokens_per_step"]
    c0 = r["compared_at"]
    return {
        "device": r["device"], "attempted": r["steps"],
        "failed": r["compared"]["nonfinite_losses"],
        "e2e": {"train_tokens_per_s": tokens / r["window_s"],
                "setup_s": r["t_window_wall"] - t0},
        "compared": r["compared"],
        "notes": [f"reference loss {c0['reference_loss']} program loss "
                  f"{c0['program_loss']}; worst leaves: grad "
                  f"{c0['grad_norm_worst_leaf_gap']}, change "
                  f"{c0['param_change_worst_leaf_gap']}; reference took "
                  f"{r['reference_s']:.1f} s; {r['steps']} steps in "
                  f"{r['window_s']:.3f} s"],
        "trace": tr,
        "ctx": {"counters": dict(r["counters"],
                                 launch_s=r["t_script"] - t0),
                "steps": r["steps"], "window_s": r["window_s"],
                "tokens_per_step": r["tokens_per_step"],
                "trace_window_s": r["trace_window_s"]},
    }
