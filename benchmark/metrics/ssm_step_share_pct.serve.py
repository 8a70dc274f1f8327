"""Model step (models/decode.py): the share of a decode chunk's device
time that is the state-space mixers' state update — the summed device
time of the ops the program names ``tony_ssm_step`` (one Mosaic launch a
mixer and step: every slot's recurrence read once, updated and written
back) that start inside a ``jit_step_rows`` execution, over those
executions' summed device time, as ``cached_attn_share_pct.serve`` reads
its kernel. None where the trace holds no decode chunk or names no such
op: a program without the kernel, a model without such layers."""

from benchmark.lib import xplane

KERNEL = "tony_ssm_step"


def read(ctx):
    tr = ctx["trace"]
    chunks = xplane.module_events(tr, "jit_step_rows")
    total = sum(e[2] for e in chunks)
    if not total:
        return None
    calls = [(s, d) for name, s, d in tr["devices"][0]["ops"]
             if xplane.is_mosaic(name) and KERNEL in name]
    inside = sum(d for s, d in calls
                 if any(c0 <= s < c0 + cd for _, c0, cd in chunks))
    return 100.0 * inside / total if inside else None
