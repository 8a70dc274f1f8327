"""Read whether ``correct`` holds the ROUTED part of a sparse family: the
float32 reference put in the program's place with only the routed experts
and the router at fault, held against the reference itself through the
cell's own three numbers and limits. Needs no window and no program (like
``control_train.py``), so it runs wherever the reference runs.

    python3 benchmark/tools/control_routed.py <config> <cell> <seed> [rows] [width]

``tools/control_serve.py``'s ``int8_weights`` cannot reach these leaves:
the program's quantizer leaves the router and the routed experts as they
are. The faults, each alone:

- ``fp8_routed``: router and routed experts rounded to fp8 over their
  contraction axes (the reference's own ``mode="fp8"``, other leaves
  untouched) — the nearest precision below, in the routed part only;
  ``fp8_experts``: the same with the router left exact, so no pick moves;
- ``zero_routed``: the held experts give nothing (``w_down`` x 0) — a
  grouped product that never lands;
- ``swapped_routed``: each held expert answers with its neighbour's down
  projection — a product that lands on the wrong group.

The faulty model's best token at every position of seeded random
sequences stands for a served token; its gap is how far the reference's
logit for it lies below the reference's best. What is NOT in this reading
is the bf16 program's own noise, which a run adds on top: a fault "told
apart" here is caught in a run, one under the limits here may still cross
them with that noise or may not — the line says which by how much room.
"""

from __future__ import annotations

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

from benchmark.lib import modelcfg, reference                # noqa: E402

ROUTED = ("router", "w_gate", "w_up", "w_down")
NUMBERS = ("served_token_mismatch_share", "served_token_mean_gap",
           "served_token_widest_gap")


def _faulty(fam, fault: str):
    """The family with one fault in its routed leaves, and the reference's
    ``mode`` that goes with it."""
    shim = types.SimpleNamespace(**vars(fam))
    if fault in ("fp8_routed", "fp8_experts"):
        shim.CONTRACT = {n: fam.CONTRACT[n] for n in ROUTED
                         if fault == "fp8_routed" or n != "router"}
        return shim, "fp8"
    shim.CONTRACT = {}

    def layer_weights(seed, li, c, dtype, kind):
        p = dict(fam.layer_weights(seed, li, c, dtype, kind))
        if "w_down" in p:
            p["w_down"] = p["w_down"] * 0 if fault == "zero_routed" \
                else jnp.roll(p["w_down"], 1, axis=0)
        return p
    shim.layer_weights = layer_weights
    return shim, None


def logits_of(c, seed, fam, mode, tokens):
    kept = reference.family
    reference.family = lambda _c: fam
    try:
        ref = reference.Reference(c, seed, mode)
    finally:
        reference.family = kept
    return np.asarray(ref.logits(tokens))


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
    sound = logits_of(c, seed, fam, None, tokens)
    best = sound.max(-1)
    print(json.dumps({"seed": seed, "rows": rows, "width": width,
                      "reference_s": time.perf_counter() - t0}), flush=True)
    for fault in ("fp8_routed", "fp8_experts", "zero_routed",
                  "swapped_routed"):
        t0 = time.perf_counter()
        low = logits_of(c, seed, *_faulty(fam, fault), tokens)
        served = low.argmax(-1)
        gaps = best - np.take_along_axis(sound, served[..., None], -1)[..., 0]
        got = dict(zip(NUMBERS, (float((gaps > 0).mean()),
                                 float(gaps.mean()), float(gaps.max()))))
        over = [n for n in NUMBERS if got[n] > limits[n]]
        print(json.dumps({"fault": fault, **got, "limits": {
            n: limits[n] for n in NUMBERS}, "not_correct_by": over,
            "told_apart": bool(over), "s": time.perf_counter() - t0}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
