"""The attention kernels one by one: a kernel's share of its roofline read
from the ops the program names ``tony_flash_*``, against what ONE call of
the forward and ONE layer's backward must do — the family's
``flash_layer_flops_bytes`` (the dense decoder's; a family without flash
kernels has none and reads as None).

The family's ``flash_train_flops_bytes`` counts a whole step over all
layers, forward and backward together; the per-layer function splits the
same count so that each kernel has a yardstick of its own. The shares are
per CALL: remat's replay of the forward is one more call at the same cost
and moves nothing.
"""

from __future__ import annotations

from . import modelcfg, xplane

FWD, BWD = "tony_flash_fwd", "tony_flash_bwd"
#: a layer's backward is one fused call, or a dq and a dkv call: each
#: layer has exactly one of these two
_BWD_ONE_PER_LAYER = ("tony_flash_bwd_fused", "tony_flash_bwd_dkv")


def kernel_calls(trace: dict, part: str, device: int = 0
                 ) -> tuple[float, int]:
    """(summed device seconds, number of layer executions) of the Mosaic
    calls of ``part`` (``"fwd"`` or ``"bwd"``) on ``device``. A program
    that names no kernel (the parent of the PR that named them) has
    none: (0.0, 0)."""
    if device >= len(trace["devices"]):
        return 0.0, 0
    mark = FWD if part == "fwd" else BWD
    ops = [(name, d) for name, _, d in trace["devices"][device]["ops"]
           if xplane.is_mosaic(name) and mark in name]
    if part == "fwd":
        n = len(ops)
    else:
        n = sum(any(k in name for k in _BWD_ONE_PER_LAYER)
                for name, _ in ops)
    return sum(d for _, d in ops) / 1e9, n


def roofline_pct(ctx: dict, part: str):
    """100 x the least time one call could take — max(FLOPs / peak
    FLOP/s, bytes / peak bytes/s) of one layer, over the chips that share
    it — over the mean device time of one call. None where the trace
    names no such kernel, or the family has no such shape function."""
    seconds, n = kernel_calls(ctx["trace"], part)
    shapes = getattr(modelcfg.family(ctx["c"]), "flash_layer_flops_bytes",
                     None)
    if not n or seconds <= 0 or ctx["peaks"] is None or not shapes:
        return None
    mix = ctx["mix"]
    batch = ctx["tokens_per_step"] // mix["seq_len"]
    fl, by = shapes(ctx["c"], batch, mix["seq_len"])[part]
    least = max(fl / ctx["peaks"]["flops_bf16"],
                by / ctx["peaks"]["hbm_bytes_per_s"]) / ctx["cell"]["chips"]
    return 100.0 * least / (seconds / n)
