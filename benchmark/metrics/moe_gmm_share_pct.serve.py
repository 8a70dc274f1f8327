"""Model step (models/decode.py, parallel/moe.py): the share of a decode
chunk's device time that is the routed-expert products — the summed
device time of the ops the program names ``tony_moe_gmm`` (the gate, up
and down launches of each expert layer's touched experts) that start
inside a ``jit_step_rows`` execution, over those executions' summed
device time: the twin of ``cached_attn_share_pct.serve``. It is the one
number by which configurations that share the kernel are laid side by
side, and how the trace shows what zero experts — picks that launch
nothing — leave of the routed work. None where the trace holds no decode
chunk or names no such op (a program without the kernel)."""

from benchmark.lib import xplane

KERNEL = "tony_moe_gmm"


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
