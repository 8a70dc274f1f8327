"""Kernels (ops/attention.py): the least time the chip could take for one
step's attention — max(FLOPs / peak FLOP/s, bytes / peak bytes/s), from
the family's shape function (``families/dense_decoder.py``; None for a
family that has none) — over the summed device time of the Mosaic
attention calls per traced step (device 0; its share of the work on a
mesh). Today's trace cannot tell the forward, its remat replay
and the backward apart by name, so they are one ``attention`` group; the
replay's time counts against the kernels, its work does not."""

from benchmark.lib import modelcfg, xplane


def read(ctx):
    tr, mix, c = ctx["trace"], ctx["mix"], ctx["c"]
    steps = len(xplane.module_events(tr, "jit_step"))
    kernel_s = xplane.op_seconds(tr, xplane.is_mosaic)
    shapes = getattr(modelcfg.family(c), "flash_train_flops_bytes", None)
    if not steps or kernel_s <= 0 or ctx["peaks"] is None or not shapes:
        return None
    batch = ctx["tokens_per_step"] // mix["seq_len"]
    fl, by = shapes(c, batch, mix["seq_len"])
    # device 0's share of the work on a mesh: the kernels run per device
    least = max(fl / ctx["peaks"]["flops_bf16"],
                by / ctx["peaks"]["hbm_bytes_per_s"]) / ctx["cell"]["chips"]
    return 100.0 * least / (kernel_s / steps)
