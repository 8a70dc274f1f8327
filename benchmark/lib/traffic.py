"""The one general traffic generator. A traffic mix is a data file
(``benchmark/traffic/<name>.json``) of parameters; everything drawn here
comes from ``--seed`` and repeats exactly for one seed. No JAX.

Every seed gets the SAME request sizes and arrival gaps in the SAME order
(drawn from the mix's own ``shape_seed``) and other token ids (and other
weights): runs with different seeds do the same work at the same moments.
The order had to be fixed too: with ~100 requests in a window, the order
alone moved the chat cell's 90th-percentile time to first token between
716 and 1,576 ms, while two runs of one order agreed within 3% (chip
runs, PR 24).
"""

from __future__ import annotations

import json
import os

import numpy as np

from .modelcfg import BENCH_DIR


def load(name: str) -> dict:
    """``name`` of a file in ``traffic/``, or a path ending in .json."""
    path = name if name.endswith(".json") else os.path.join(
        BENCH_DIR, "traffic", f"{name}.json")
    with open(path) as f:
        return json.load(f)


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % (1 << 63), stream])


def _lognormal_int(rng, spec: dict, n: int) -> np.ndarray:
    """``n`` whole numbers, lognormal with the given median and sigma,
    clipped to [min, max]."""
    x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def request_shapes(mix: dict, n: int) -> np.ndarray:
    """[n, 2] (prompt length, answer length): the mix's fixed set."""
    rng = _rng(mix["shape_seed"], 0)
    return np.stack([_lognormal_int(rng, mix["prompt_tokens"], n),
                     _lognormal_int(rng, mix["answer_tokens"], n)], axis=1)


def requests(mix: dict, seed: int, n: int, vocab: int) -> list[dict]:
    """``n`` requests: the mix's fixed shapes, each with this seed's token
    ids."""
    rng = _rng(seed, 1)
    return [{"prompt": rng.integers(0, vocab, int(p)).tolist(),
             "max_new_tokens": int(a)} for p, a in request_shapes(mix, n)]


def poisson_due_times(mix: dict, seconds: float) -> np.ndarray:
    """Open-loop schedule: round(rate x seconds) arrivals with exponential
    gaps drawn from the mix's ``shape_seed`` and scaled so that the last
    arrival falls inside the window: a Poisson-like stream with a fixed
    amount of work."""
    n = max(1, int(round(mix["rate_per_s"] * seconds)))
    gaps = _rng(mix["shape_seed"], 2).exponential(1.0, n + 1)
    return np.cumsum((gaps * (seconds / gaps.sum()))[:n])


def token_records(seed: int, records: int, seq: int, vocab: int,
                  jump_share: float = 0.1) -> np.ndarray:
    """[records, seq + 1] int32: a learnable stream — each token is the
    last plus one (mod vocab) but for a share of random jumps — with a
    start and jumps of its own in every record, so no two rows agree."""
    rng = _rng(seed, 4)
    steps = np.ones((records, seq + 1), np.int64)
    steps[:, 0] = rng.integers(0, vocab, records)
    jumps = rng.random((records, seq + 1)) < jump_share
    jumps[:, 0] = False
    steps[jumps] = rng.integers(0, vocab, int(jumps.sum()))
    return (np.cumsum(steps, axis=1) % vocab).astype(np.int32)


def write_token_file(path: str, seed: int, records: int, seq: int,
                     vocab: int) -> None:
    token_records(seed, records, seq, vocab).tofile(path)
