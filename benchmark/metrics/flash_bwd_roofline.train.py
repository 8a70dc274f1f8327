"""Kernels (ops/attention.py): the flash backward's share of its roofline,
per layer — the least time one layer's backward could take over the
device time of the ops named ``tony_flash_bwd*`` (the fused call, or the
dq and dkv calls summed) per layer executed (device 0)."""

from benchmark.lib import flash_kernels


def read(ctx):
    return flash_kernels.roofline_pct(ctx, "bwd")
