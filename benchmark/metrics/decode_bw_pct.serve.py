"""Model step (models/decode.py): the bytes a decode step must read — the
weights once plus the live cache rows, counted from shapes by the
configuration's family (``families/<name>.py``) — over peak HBM bytes/s,
over the step's device time: the decode chunk program's traced device
time divided by its steps."""

from benchmark.lib import modelcfg, xplane


def read(ctx):
    tr, k = ctx["trace"], ctx["counters"]
    chunks = xplane.module_events(tr, "jit_step_rows")
    if not chunks or ctx["peaks"] is None:
        return None
    step_s = sum(e[2] for e in chunks) / 1e9 / (len(chunks) * k["chunk"])
    need = modelcfg.family(ctx["c"]).decode_step_bytes(
        ctx["c"], k["mean_live_rows"], ctx)
    return 100.0 * need / ctx["peaks"]["hbm_bytes_per_s"] / step_s
