"""Read, on the chip, what a cell of ``drivers/serve_drain_rows.py``
compares for ``correct`` with one of the program's own lower-precision
paths in the program's place (the control, which has to come out as not
correct) — a whole run of the cell, its own driver, load and check:

    python3 benchmark/tools/control_cell.py <path> <workload> <seed> <seconds>

``path`` as ``tools/control_serve.py``'s: ``bf16``, ``int8_weights``,
``int8_kv``. ``control_serve.py`` itself starts ``control_replica.py``
and ``drivers/serve.py``'s loop, so it reaches neither ``check_rows`` nor
the drain: this tool names the replica child of one run instead. Prints
the run's result line. Never part of a benchmark run.
"""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run as harness                          # noqa: E402
from benchmark.drivers import serve_drain_rows                # noqa: E402

SCRIPT = os.path.abspath(__file__)


def main() -> int:
    path, workload, seed, seconds = sys.argv[1:]
    os.environ["BENCH_CONTROL_PATH"] = path
    serve_drain_rows.REPLICA = SCRIPT
    print(json.dumps(harness.run_cell(None, workload, int(seed),
                                      float(seconds), 0)), flush=True)
    return 0


def replica() -> int:
    """This file as the replica child: ``control_replica.py``'s, with the
    rows of ``serve_replica_rows.py``."""
    from benchmark.jobs import serve_replica, serve_replica_rows
    from benchmark.tools import control_replica

    class Replica(serve_replica_rows.RowsCheck,
                  control_replica.ControlReplica):
        pass

    return Replica(serve_replica.arguments(),
                   os.environ["BENCH_CONTROL_PATH"]).serve()


if __name__ == "__main__":
    sys.exit(replica() if "--config" in sys.argv else main())
