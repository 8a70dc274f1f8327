"""Read whether ``correct`` holds the two terms of a shortcut-connected
routed block (``families/scmoe_mla_decoder.py``): the float32 reference
put in the program's place with ONE term of the block at fault, held
against the reference itself through the cell's own three numbers and
limits — ``tools/control_routed.py``'s pattern (its ``logits_of`` and its
reading), for the two faults this family adds or makes doubtful:

    python3 benchmark/tools/control_zero.py <config> <cell> <seed> [rows] [width]

- ``no_zero_term``: the zero experts' term ``(sum of the weights of the
  picks >= router_experts) x h`` left out — a program that treats a zero
  pick as an absent expert;
- ``zero_routed``: the held experts give nothing (``control_routed``'s
  fault). Under a softmax over 768 outputs x 6 a held expert's weight is
  ~0.06 and a token meets a quarter of one, so the routed sum is small
  beside the stream: this line says whether a run can tell it.

As there, the bf16 program's own noise is not in this reading; a run adds
it on top. Needs no window and no program: it runs wherever the
reference runs.
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time
import types

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax                                                    # noqa: E402
import jax.numpy as jnp                                       # noqa: E402
import numpy as np                                            # noqa: E402

from benchmark.lib import modelcfg                            # noqa: E402
from benchmark.tools import control_routed                    # noqa: E402


def without_zero_term(fam):
    """The family with the zero experts' term left out of its block."""
    shim = types.SimpleNamespace(**vars(fam))
    shim.layer_forward = functools.partial(
        fam.layer_forward,
        routed=functools.partial(fam.experts, zero_term=False))
    return shim


def main() -> int:
    config, cell, seed, *rest = sys.argv[1:]
    rows, width = (int(x) for x in (rest + ["4", "512"][len(rest):]))
    c = modelcfg.load(config)
    fam = modelcfg.family(c)
    with open(os.path.join(modelcfg.BENCH_DIR, "limits",
                           f"{cell}.json")) as f:
        limits = json.load(f)["limits"]
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    seed = int(seed)
    tokens = jnp.asarray(np.random.default_rng(seed).integers(
        0, c["vocab_size"], (rows, width)).astype(np.int32))
    t0 = time.perf_counter()
    sound = control_routed.logits_of(c, seed, fam, None, tokens)
    best = sound.max(-1)
    print(json.dumps({"seed": seed, "rows": rows, "width": width,
                      "reference_s": time.perf_counter() - t0}), flush=True)
    faults = {"no_zero_term": without_zero_term(fam),
              "zero_routed": control_routed._faulty(fam, "zero_routed")[0]}
    for fault, faulty in faults.items():
        t0 = time.perf_counter()
        low = control_routed.logits_of(c, seed, faulty, None, tokens)
        gaps = best - np.take_along_axis(
            sound, low.argmax(-1)[..., None], -1)[..., 0]
        got = dict(zip(control_routed.NUMBERS, (
            float((gaps > 0).mean()), float(gaps.mean()),
            float(gaps.max()))))
        over = [n for n in control_routed.NUMBERS if got[n] > limits[n]]
        print(json.dumps({"fault": fault, **got, "limits": {
            n: limits[n] for n in control_routed.NUMBERS},
            "not_correct_by": over, "told_apart": bool(over),
            "s": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
