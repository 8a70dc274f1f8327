"""Find the knee of a serving cell once, on the chip: one replica, one
warm-up, then an open-loop window at each rate in turn. A rate is
sustained when at least 90% of its requests finish and no backlog grows:
the queue is empty again at the window's end and time to first token does
not climb from the first third of the window to the last.

    python3 benchmark/tools/knee_sweep.py <workload> <seed> <seconds> <rate> [<rate> ...]
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run as harness                          # noqa: E402
from benchmark.drivers import serve                           # noqa: E402
from benchmark.lib import traffic                             # noqa: E402
from benchmark.lib.procs import Children                      # noqa: E402
from benchmark.lib.stats import percentile                    # noqa: E402


def main() -> int:
    workload, seed, seconds, *rates = sys.argv[1:]
    seed, seconds = int(seed), float(seconds)
    _, cell, c, mix = harness.load_cell(workload)
    out = os.path.join(ROOT, ".bench_runs", f"sweep-{workload}")
    os.makedirs(out, exist_ok=True)
    from tony_tpu.serving.client import StreamingClient
    with Children() as children:
        rep = serve.Replica(children, cell=cell, seed=seed, trace=0, out=out,
                            env=harness.child_env(ROOT, "tpu"),
                            platform="tpu", fault="")
        hello = rep.expect("listening", timeout=1000.0)
        with StreamingClient("127.0.0.1", hello["port"]) as client:
            serve.warm_up(client, c, mix, seed)
            for i, rate in enumerate(map(float, rates)):
                at = dict(mix, rate_per_s=rate)
                due = traffic.poisson_due_times(at, seconds)
                reqs = traffic.requests(at, seed + i, len(due),
                                        c["vocab_size"])
                t0 = time.perf_counter()
                streams = serve.open_loop(client, reqs, due, t0,
                                          mix["drain_seconds"])
                # how long the backlog took to clear after the last arrival
                last_token = max((s.arrivals[-1][0] for s in streams
                                  if s.arrivals), default=t0)
                ok = [s for s in streams if s.ok]
                ttft = [s.arrivals[0][0] - s.due for s in ok]
                third = max(1, len(ok) // 3)
                lat = serve.latencies(streams, t0 + seconds)
                print(json.dumps({
                    "rate_per_s": rate, "requests": len(streams),
                    "finished_share": len(ok) / len(streams),
                    "ttft_p50_ms": 1e3 * statistics.median(ttft),
                    "ttft_p90_ms": 1e3 * percentile(ttft, 90),
                    "ttft_first_third_ms": 1e3 * statistics.median(
                        ttft[:third]),
                    "ttft_last_third_ms": 1e3 * statistics.median(
                        ttft[-third:]),
                    "itl_p95_ms": 1e3 * percentile(lat["gaps"], 95),
                    "tokens_per_s_in_window":
                        lat["tokens_in_window"] / seconds,
                    "drain_after_window_s": last_token - (t0 + seconds),
                    "gen_late_p95_ms": 1e3 * percentile(
                        [s.sent - s.due for s in streams], 95),
                    "queue_depth_at_end": rep.ask("snapshot", "snapshot")[
                        "stats"]["queue_depth"]}), flush=True)
        rep.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
