"""Kernels (ops/ssm.py): the state update's share of its roofline in the
decode chunk — the least time ONE chunk's launches could take (over its
mixers and steps: the larger of FLOPs over peak FLOP/s and bytes over
peak HBM bytes/s, the family's ``ssm_step_flops_bytes`` for the mix's
slots: the state read and written once at its stored width) over the
traced device time of the ops the program names ``tony_ssm_step`` inside
the ``jit_step_rows`` executions, per execution, as
``moe_experts_roofline.serve``. Bandwidth-bound: 1.2 FLOP a byte. None
where the trace names no such op (a program without the kernel), or the
family has no such shape function."""

from benchmark.lib import modelcfg, xplane

KERNEL = "tony_ssm_step"


def read(ctx):
    tr, k = ctx["trace"], ctx["counters"]
    fam = modelcfg.family(ctx["c"])
    if ctx["peaks"] is None or not hasattr(fam, "ssm_step_flops_bytes"):
        return None
    chunks = xplane.module_events(tr, "jit_step_rows")
    ops = [(s, d) for name, s, d in tr["devices"][0]["ops"]
           if xplane.is_mosaic(name) and KERNEL in name]
    inside = [sum(d for s, d in ops if c0 <= s < c0 + cd)
              for _, c0, cd in chunks]
    inside = [t for t in inside if t > 0]
    if not inside:
        return None
    fl, by = fam.ssm_step_flops_bytes(ctx["c"], ctx["mix"]["slots"])
    least = (k["chunk"] * fam.layer_kinds(ctx["c"]).count("ssm")
             * max(fl / ctx["peaks"]["flops_bf16"],
                   by / ctx["peaks"]["hbm_bytes_per_s"]))
    return 100.0 * least / (sum(inside) / len(inside) / 1e9)
