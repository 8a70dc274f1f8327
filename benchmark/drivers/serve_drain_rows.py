"""Driver of a drained closed loop (``drivers/serve_drain.py``, whole)
whose finished requests are too long for the float32 reference at the 8
rows a block that ``lib/reference.served_token_gaps`` defaults to and
``jobs/serve_replica.py`` never overrides: at 16,384 positions and 32,768
vocabulary rows the logits of ONE block of 8 are 17 GB of float32, over
the chip. Everything is that driver's but for the replica child, which is
``jobs/serve_replica_rows.py``: the same replica, handing the reference
the mix's ``check_rows`` rows at a time.

A ``benchmark`` PR should have ``jobs/serve_replica.py`` pass the mix's
``check_rows`` (default 8) and delete this file and that one; a program
PR may only add files, so the child is swapped in for the length of one
run, as ``serve_drain.py`` swaps the loop.
"""

from __future__ import annotations

import functools
import os

from benchmark.drivers import serve, serve_drain

#: the replica child of a run (``tools/control_cell.py`` names another)
REPLICA = os.path.join(serve.BENCH, "jobs", "serve_replica_rows.py")


def run(*, mix, **kw) -> dict:
    if "check_rows" not in mix:
        raise ValueError("serve_drain_rows reads the mix's check_rows; a "
                         "mix without it is drivers/serve_drain.py's")
    kept = serve.Replica
    serve.Replica = functools.partial(kept, script=REPLICA)
    try:
        return serve_drain.run(mix=mix, **kw)
    finally:
        serve.Replica = kept
