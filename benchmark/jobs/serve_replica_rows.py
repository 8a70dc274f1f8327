"""``jobs/serve_replica.py`` whose ``check`` hands the float32 reference
the mix's ``check_rows`` rows a block instead of
``lib/reference.served_token_gaps``' default of 8 (why:
``drivers/serve_drain_rows.py``). Nothing else differs: the replica, the
line protocol and the reference are the ones every serving cell runs.
"""

from __future__ import annotations

import functools
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.jobs import serve_replica                      # noqa: E402
from benchmark.lib import reference                           # noqa: E402


class RowsCheck:
    """Mixin over a ``serve_replica.Replica``: the reference's pass in
    blocks of the mix's ``check_rows``."""

    def check(self, samples) -> dict:
        kept = reference.served_token_gaps
        reference.served_token_gaps = functools.partial(
            kept, rows=self.mix["check_rows"])
        try:
            return super().check(samples)
        finally:
            reference.served_token_gaps = kept


class Replica(RowsCheck, serve_replica.Replica):
    pass


if __name__ == "__main__":
    sys.exit(Replica(serve_replica.arguments()).serve())
