"""Kernels (ops/attention.py): the forward flash kernel's share of its
roofline, per call — the least time ONE call could take (its FLOPs and
bytes from ``lib/flash_kernels.py``) over the mean device time of the ops
the program names ``tony_flash_fwd`` (device 0). Remat's replay is one
more call of the same kernel and distorts nothing."""

from benchmark.lib import flash_kernels


def read(ctx):
    return flash_kernels.roofline_pct(ctx, "fwd")
