"""Kernels (ops/attention.py) inside the serve engine's admissions: the
share of an admission's device time that is the flash forward kernel —
the summed device time of the ops the program names ``tony_flash_fwd``
that start inside a ``jit_admit_rows`` execution, over those executions'
summed device time. In a model whose layers attend through a window and
through the whole context, both kinds' prefill attention are calls of
this one kernel (windowed and plain causal), at the admission's bucket.
None where the trace holds no admission or names no such op (a program
without the kernel)."""

from benchmark.lib import xplane

KERNEL = "tony_flash_fwd"


def read(ctx):
    tr = ctx["trace"]
    admits = xplane.module_events(tr, "jit_admit_rows")
    total = sum(e[2] for e in admits)
    if not total:
        return None
    calls = [(s, d) for name, s, d in tr["devices"][0]["ops"]
             if xplane.is_mosaic(name) and KERNEL in name]
    inside = sum(d for s, d in calls
                 if any(a0 <= s < a0 + ad for _, a0, ad in admits))
    return 100.0 * inside / total if inside else None
