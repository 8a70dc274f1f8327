"""A configuration file (``benchmark/configs/<name>.json``) as the benchmark
reads it: the published ``config.json`` keys, as run. No JAX here — the
parent process reads sizes from it too."""

from __future__ import annotations

import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: the program's RMSNorm epsilon (``tony_tpu/ops/norms.py``): a constant
#: there, so the reference uses it too; both models publish 1e-5 (a
#: departure each configuration file records)
PROGRAM_RMS_EPS = 1e-6


def load(name: str) -> dict:
    """``name`` of a file in ``configs/``, or a path ending in .json."""
    path = name if name.endswith(".json") else os.path.join(
        BENCH_DIR, "configs", f"{name}.json")
    with open(path) as f:
        c = json.load(f)
    heads, d = c["num_attention_heads"], c["hidden_size"]
    if c.get("head_dim", d // heads) * heads != d:
        raise ValueError(f"{name}: head_dim x heads != hidden_size — the "
                         f"program derives head_dim = d_model / n_heads")
    if c["rope_theta"] != 10000.0 or c["hidden_act"] != "silu" \
            or c["tie_word_embeddings"]:
        raise ValueError(f"{name}: the program's block is RoPE base 10000, "
                         f"SwiGLU, untied head")
    return c


def program_kwargs(c: dict) -> dict:
    """Keyword arguments of ``tony_tpu.models.transformer.TransformerConfig``
    for this configuration (dtype and remat are the job script's)."""
    return dict(vocab_size=c["vocab_size"], d_model=c["hidden_size"],
                n_layers=c["num_hidden_layers"],
                n_heads=c["num_attention_heads"],
                n_kv_heads=c["num_key_value_heads"],
                d_ff=c["intermediate_size"],
                max_seq=c["max_position_embeddings"],
                attn_window=c.get("sliding_window") or 0)


def layer_params(c: dict) -> int:
    d, f = c["hidden_size"], c["intermediate_size"]
    kvw = c["num_key_value_heads"] * (d // c["num_attention_heads"])
    return 2 * d * d + 2 * d * kvw + 3 * d * f + 2 * d


def param_count(c: dict) -> int:
    d = c["hidden_size"]
    return (c["num_hidden_layers"] * layer_params(c)
            + 2 * c["vocab_size"] * d + d)
