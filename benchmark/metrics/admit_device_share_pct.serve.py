"""Serve engine (models/serve.py): the share of the traced window the
device spent in admissions — the summed device time of the
``jit_admit_rows`` executions (a prefill padded to the admission width
x the bucket, landed in the freed slots) over the window, first op to
last. What is not
here or idle is the decode chunk. None where the trace holds no
admission."""

from benchmark.lib import xplane


def read(ctx):
    tr = ctx["trace"]
    admits = xplane.module_events(tr, "jit_admit_rows")
    if not admits:
        return None
    _, window_s = xplane.busy_and_window_s(tr)
    return 100.0 * sum(e[2] for e in admits) / 1e9 / window_s
