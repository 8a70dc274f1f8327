"""Read, on the chip, the numbers a serving cell's ``correct`` compares —
for the program as the cells run it and for its own lower-precision paths
in its place (the control, which has to come out as not correct):

    python3 benchmark/tools/control_serve.py <path> <seconds> <workload>:<seed> [...]

``path``: ``bf16``, ``int8_weights`` (``models/quantize.py``) or ``int8_kv``
(``kv_cache_dtype="int8"``). Each ``workload:seed`` is one short window at
that cell's own load through the cell's own driver (``drivers/serve.py``:
``offer`` then ``judge``), all in ONE replica process, restarted on each
seed's weights: set-up is paid once. The workloads share a configuration,
slots and cache rows. One JSON line per reading; the served tokens' gaps go
to ``.bench_runs/control/``.
"""

from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run as harness                          # noqa: E402
from benchmark.drivers import serve                           # noqa: E402
from benchmark.lib.procs import Children                      # noqa: E402
from benchmark.lib.stats import percentile                    # noqa: E402

SCRIPT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "control_replica.py")


def read(path: str, seconds: float, items, platform: str = "tpu",
         root: str = ROOT, bench: dict | None = None,
         emit=print) -> list[dict]:
    """``platform``, ``root`` and ``bench`` are for the CPU test."""
    from tony_tpu.serving.client import StreamingClient
    cells = [(harness.load_cell(w, bench)[1:], int(s)) for w, s in items]
    (cell0, c, mix0), seed0 = cells[0]
    out = os.path.join(root, ".bench_runs", "control")
    os.makedirs(out, exist_ok=True)
    env = dict(harness.child_env(root, platform), BENCH_CONTROL_PATH=path)
    readings = []
    with Children() as children:
        t0 = time.time()
        rep = serve.Replica(children, cell=cell0, seed=seed0, trace=0,
                            out=out, env=env, platform=platform, fault="",
                            script=SCRIPT)
        hello = rep.expect("listening", timeout=1000.0)
        for n, ((cell, _, mix), seed) in enumerate(cells):
            if (cell["config"], mix["slots"], mix["cache_rows"]) != (
                    cell0["config"], mix0["slots"], mix0["cache_rows"]):
                raise SystemExit("one replica serves one configuration, "
                                 "slots and cache rows")
            if n:
                hello = rep.ask("restart", "listening", timeout=1000.0,
                                seed=seed)
            with StreamingClient("127.0.0.1", hello["port"]) as client:
                if not n:       # the later seeds find every program made
                    serve.warm_up(client, c, mix, seed)
                    emit(f"set-up {time.time() - t0:.1f} s ({hello['took']})")
                streams, before, after, _, _ = serve.offer(
                    rep, client, c=c, mix=mix, seed=seed, seconds=seconds,
                    trace=0)
            judged = serve.judge(rep, streams, mix, seed)
            gaps = judged["gaps"]
            got = {
                "path": path, "workload": cell["name"], "seed": seed,
                "compared": judged["compared"],
                "requests": len(streams),
                "finished": sum(s.ok for s in streams),
                "compiles_in_window": after["compile_requests"]
                - before["compile_requests"],
                "gap_p99": percentile(gaps, 99),
                "gap_p999": percentile(gaps, 99.9),
                "note": judged["note"]}
            with open(os.path.join(
                    out, f"{path}-{cell['name']}-{seed}.json"), "w") as f:
                json.dump({"reading": got, "gaps": gaps}, f)
            emit(json.dumps(got))
            readings.append(got)
        rep.close()
    return readings


def main() -> int:
    path, seconds, *items = sys.argv[1:]
    read(path, float(seconds), [i.split(":") for i in items])
    return 0


if __name__ == "__main__":
    sys.exit(main())
