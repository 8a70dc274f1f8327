"""Read the control of a training cell on the chip: the float32 reference
put in the program's place and computed with weights rounded to a lower
precision (``int8``, ``fp8``), held against the reference itself. Needs no
window and no program: only the cell's sizes and seeded batches.

    python3 benchmark/tools/control_train.py <config> <traffic> <seed> [<seed> ...]
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax                                                    # noqa: E402

from benchmark.lib import modelcfg, reference, traffic       # noqa: E402


def main() -> int:
    config, mixname, *seeds = sys.argv[1:]
    c, mix = modelcfg.load(config), traffic.load(mixname)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    b, s = mix["batch_per_process"], mix["seq_len"]
    for seed in map(int, seeds):
        rows = traffic.token_records(seed, 2 * b, s, c["vocab_size"])
        batches = [(rows[i * b:(i + 1) * b, :s], rows[i * b:(i + 1) * b, 1:])
                   for i in range(2)]
        t0 = time.perf_counter()
        ref = reference.train_two_steps(c, seed, batches, mix["optimizer"])
        out = {"seed": seed, "reference_s": time.perf_counter() - t0}
        for mode in ("int8", "fp8"):
            low = reference.train_two_steps(c, seed, batches,
                                            mix["optimizer"], mode=mode)
            out[mode] = {
                "loss_step0_gap": abs(low["loss"][0] - ref["loss"][0]),
                "loss_step1_gap": abs(low["loss"][1] - ref["loss"][1]),
                "grad_norm_worst_leaf_gap": reference.worst_leaf_gap(
                    low["grad_norm"], ref["grad_norm"]),
                "param_change_worst_leaf_gap": reference.worst_leaf_gap(
                    low["delta_norm"], ref["delta_norm"])}
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
