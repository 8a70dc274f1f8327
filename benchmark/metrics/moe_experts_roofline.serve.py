"""Kernels (ops/grouped_matmul.py): the routed-expert products' share of
their roofline in the decode chunk — the least time ONE chunk's products
could take (over its expert layers and steps: the larger of FLOPs over
peak FLOP/s and touched-expert bytes over peak HBM bytes/s, the family's
``moe_experts_flops_bytes`` at the load its ``expert_load`` reckons) over
the traced device time of the ops the program names ``tony_moe_gmm``
inside the ``jit_step_rows`` executions, per execution. Bandwidth-bound at
decode. None where the trace names no such op (a program without the
kernel), or the family has no such shape function."""

from benchmark.lib import modelcfg, xplane

KERNEL = "tony_moe_gmm"


def read(ctx):
    tr, k = ctx["trace"], ctx["counters"]
    fam = modelcfg.family(ctx["c"])
    if ctx["peaks"] is None or not hasattr(fam, "moe_experts_flops_bytes"):
        return None
    chunks = xplane.module_events(tr, "jit_step_rows")
    ops = [(s, d) for name, s, d in tr["devices"][0]["ops"]
           if xplane.is_mosaic(name) and KERNEL in name]
    inside = [sum(d for s, d in ops if c0 <= s < c0 + cd)
              for _, c0, cd in chunks]
    inside = [t for t in inside if t > 0]
    if not inside:
        return None
    kinds = fam.layer_kinds(ctx["c"])
    fl, by = fam.moe_experts_flops_bytes(ctx["c"],
                                         *fam.expert_load(ctx["c"], ctx))
    least = (k["chunk"] * kinds.count("moe")
             * max(fl / ctx["peaks"]["flops_bf16"],
                   by / ctx["peaks"]["hbm_bytes_per_s"]))
    return 100.0 * least / (sum(inside) / len(inside) / 1e9)
