"""Operations and bytes the mathematics needs, computed from shapes.
The benchmark's own copy (the program's ``train_flops_per_token`` may be
edited by a later PR; this may not). Recomputed (remat) work never counts.
"""

from __future__ import annotations


def _attended(seq: int, window: int) -> float:
    """Mean number of keys a query attends: causal, optionally windowed."""
    if window and window < seq:
        return (window * (window + 1) / 2 + (seq - window) * window) / seq
    return (seq + 1) / 2


def forward_flops_per_token(c: dict, seq: int) -> float:
    d, f, v = c["hidden_size"], c["intermediate_size"], c["vocab_size"]
    kvw = c["num_key_value_heads"] * (d // c["num_attention_heads"])
    proj = 2 * (2 * d * d + 2 * d * kvw)             # wq, wo, wk, wv
    attn = 4 * _attended(seq, c.get("sliding_window") or 0) * d  # QK^T, AV
    mlp = 2 * 3 * d * f                              # gate, up, down
    return c["num_hidden_layers"] * (proj + attn + mlp) + 2 * d * v


def train_flops_per_token(c: dict, seq: int) -> float:
    """Forward plus backward (twice the forward) per trained token."""
    return 3.0 * forward_flops_per_token(c, seq)


def flash_train_flops_bytes(c: dict, batch: int, seq: int,
                            dtype_bytes: int = 2) -> tuple[float, float]:
    """What the attention kernels of ONE train step must do over all
    layers: forward (QK^T, AV: 4 flops per attended pair per head dim) and
    backward (dV, dP, dQ, dK and the score recompute: 10), and the bytes
    they must move: forward reads q, k, v and writes o; backward reads q,
    k, v, o, do and writes dq, dk, dv. Remat's replay of the forward is
    not counted: it is the program's choice, not the algorithm's."""
    d, h = c["hidden_size"], c["num_attention_heads"]
    kvw = c["num_key_value_heads"] * (d // h)
    pairs = batch * seq * _attended(seq, c.get("sliding_window") or 0)
    flops = (4 + 10) * pairs * d
    tok = batch * seq
    fwd_b = tok * (2 * d + 2 * kvw)
    bwd_b = tok * (4 * d + 4 * kvw)
    return (c["num_hidden_layers"] * flops,
            c["num_hidden_layers"] * (fwd_b + bwd_b) * dtype_bytes)


def decode_step_bytes(c: dict, live_rows: float, dtype_bytes: int = 2
                      ) -> float:
    """Bytes ONE decode step over the whole batch must read: every matmul
    weight once (the embedding is a gather of a few rows) plus the live
    cache rows (K and V of every layer for each token already held)."""
    from .modelcfg import layer_params
    d = c["hidden_size"]
    kvw = c["num_key_value_heads"] * (d // c["num_attention_heads"])
    weights = (c["num_hidden_layers"] * layer_params(c)
               + c["vocab_size"] * d + d)
    cache = live_rows * c["num_hidden_layers"] * 2 * kvw
    return (weights + cache) * dtype_bytes
