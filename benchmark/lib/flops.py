"""Operations the mathematics needs, computed from shapes: what is the
same for every family. The counts of one family's layers (forward FLOPs a
token, the bytes a decode step must read, its kernels' shape functions)
are the family's (``benchmark/families/<name>.py``) — the benchmark's own
copies (the program's ``train_flops_per_token`` may be edited by a later
PR; these may not). Recomputed (remat) work never counts.
"""

from __future__ import annotations

from .modelcfg import family


def attended(seq: int, window: int) -> float:
    """Mean number of keys a query attends: causal, optionally windowed."""
    if window and window < seq:
        return (window * (window + 1) / 2 + (seq - window) * window) / seq
    return (seq + 1) / 2


def train_flops_per_token(c: dict, seq: int) -> float:
    """Forward plus backward (twice the forward) per trained token."""
    return 3.0 * family(c).forward_flops_per_token(c, seq)
