"""The attention kernels one by one: what ONE call of the forward and ONE
layer's backward must do, from shapes, and a kernel's share of its
roofline read from the ops the program names ``tony_flash_*``.

``flops.flash_train_flops_bytes`` counts a whole step over all layers,
forward and backward together; this splits the same count (4 and 10 flops
per attended pair per head dim; the two byte counts it sums) so that each
kernel has a yardstick of its own. The shares are per CALL: remat's
replay of the forward is one more call at the same cost and moves
nothing.
"""

from __future__ import annotations

from . import flops, xplane

FWD, BWD = "tony_flash_fwd", "tony_flash_bwd"
#: a layer's backward is one fused call, or a dq and a dkv call: each
#: layer has exactly one of these two
_BWD_ONE_PER_LAYER = ("tony_flash_bwd_fused", "tony_flash_bwd_dkv")


def layer_flops_bytes(c: dict, batch: int, seq: int,
                      dtype_bytes: int = 2) -> dict:
    """``{"fwd": (flops, bytes), "bwd": (flops, bytes)}`` of ONE layer:
    forward QK^T and AV (4 flops per attended pair per head dim), reading
    q, k, v and writing o; backward dV, dP, dQ, dK and the score
    recompute (10), reading q, k, v, o, do and writing dq, dk, dv."""
    d, h = c["hidden_size"], c["num_attention_heads"]
    kvw = c["num_key_value_heads"] * (d // h)
    pairs = batch * seq * flops._attended(seq, c.get("sliding_window") or 0)
    tok = batch * seq
    return {"fwd": (4 * pairs * d, tok * (2 * d + 2 * kvw) * dtype_bytes),
            "bwd": (10 * pairs * d, tok * (4 * d + 4 * kvw) * dtype_bytes)}


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
    names no such kernel."""
    seconds, n = kernel_calls(ctx["trace"], part)
    if not n or seconds <= 0 or ctx["peaks"] is None:
        return None
    mix = ctx["mix"]
    batch = ctx["tokens_per_step"] // mix["seq_len"]
    fl, by = layer_flops_bytes(ctx["c"], batch, mix["seq_len"])[part]
    least = max(fl / ctx["peaks"]["flops_bf16"],
                by / ctx["peaks"]["hbm_bytes_per_s"]) / ctx["cell"]["chips"]
    return 100.0 * least / (seconds / n)
