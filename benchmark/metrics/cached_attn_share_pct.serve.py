"""Model step (models/decode.py): the share of a decode chunk's device
time that is the cached read — the summed device time of the ops the
program names ``tony_cached_attn`` (one Mosaic launch a layer and step,
over each slot's own live blocks of the K/V cache) that start inside a
``jit_step_rows`` execution, over those executions' summed device time.
None where the trace holds no decode chunk or names no such op: a
program whose cached read is not this kernel (the walk over
``dynamic_slice`` blocks before PR 36; the latent read)."""

from benchmark.lib import xplane

KERNEL = "tony_cached_attn"


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
