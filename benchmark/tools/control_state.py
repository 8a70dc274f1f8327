"""Read whether ``correct`` holds what a state-space layer kind hands from
an admission to decode (``families/ssm_hybrid_decoder.py``): the float32
reference put in the program's place with ONE fault at a time, held
against the reference itself through the cell's own three numbers and
limits — ``tools/control_zero.py``'s pattern (``control_routed``'s
``logits_of`` and its reading):

    python3 benchmark/tools/control_state.py <config> <cell> <seed> [rows] [prompt] [pad] [answer]

Every row is ``[prompt | pad | answer]`` seeded tokens — a prompt admitted
through a bucket ``pad`` positions longer than itself, then ``answer``
served tokens. The SOUND reading skips the padding as an admission must
(the family's ``live`` mask: the state takes the identity step there, the
conv's window does not shift, attention does not see it) and equals the
plain reference over ``[prompt | answer]``; each fault leaves one thing
out, and the served positions — the prompt's last and the answer's — are
judged:

- ``state_zeroed``: the recurrent state zeroed where decode takes over;
- ``tail_unmasked``: the state run THROUGH the padded tail (no step
  masked);
- ``conv_from_tail``: the conv's window taken from the padded tail;
- ``attn_scale``: the softmax of q.k x head_dim^-1/2 where the model says
  ``attention_multiplier``.

As there, the bf16 program's own noise is not in this reading; a run adds
it on top. Needs no window and no program: it runs wherever the reference
runs.
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


def skipping(fam, live, fault: str = ""):
    """The family whose layers skip the positions ``live`` [rows, width]
    marks False, with ``fault`` (one of the family's ``FAULTS``) or
    sound."""
    shim = types.SimpleNamespace(**vars(fam))
    shim.layer_forward = functools.partial(fam.layer_forward, live=live,
                                           fault=fault)
    return shim


def rows_of(seed: int, vocab: int, rows: int, prompt: int, pad: int,
            answer: int):
    """(tokens [rows, prompt + pad + answer], live mask, the served
    positions: those whose logits predict an answer token)."""
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, vocab, (rows, prompt + pad + answer))
    tokens[:, prompt:prompt + pad] = 0
    live = np.ones(tokens.shape, bool)
    live[:, prompt:prompt + pad] = False
    served = np.r_[prompt - 1, prompt + pad:prompt + pad + answer - 1]
    return jnp.asarray(tokens.astype(np.int32)), jnp.asarray(live), served


def read(c, fam, seed: int, limits: dict, rows: int, prompt: int, pad: int,
         answer: int, emit=print) -> dict:
    """fault -> its reading; ``emit`` takes each as a JSON line."""
    tokens, live, served = rows_of(seed, c["vocab_size"], rows, prompt, pad,
                                   answer)
    t0 = time.perf_counter()
    sound = control_routed.logits_of(c, seed, skipping(fam, live), None,
                                     tokens)[:, served]
    best = sound.max(-1)
    emit(json.dumps({"seed": seed, "rows": rows, "prompt": prompt,
                     "pad": pad, "answer": answer,
                     "reference_s": time.perf_counter() - t0}))
    out = {}
    for fault in fam.FAULTS:
        t0 = time.perf_counter()
        low = control_routed.logits_of(
            c, seed, skipping(fam, live, fault), None, tokens)[:, served]
        gaps = best - np.take_along_axis(
            sound, low.argmax(-1)[..., None], -1)[..., 0]
        got = dict(zip(control_routed.NUMBERS, (
            float((gaps > 0).mean()), float(gaps.mean()),
            float(gaps.max()))))
        over = [n for n in control_routed.NUMBERS if got[n] > limits[n]]
        out[fault] = dict(got, not_correct_by=over)
        emit(json.dumps({"fault": fault, **got, "limits": {
            n: limits[n] for n in control_routed.NUMBERS},
            "not_correct_by": over, "told_apart": bool(over),
            "s": time.perf_counter() - t0}))
    return out


def main() -> int:
    config, cell, seed, *rest = sys.argv[1:]
    sizes = [int(x) for x in (rest + ["2", "192", "64", "192"][len(rest):])]
    c = modelcfg.load(config)
    with open(os.path.join(modelcfg.BENCH_DIR, "limits",
                           f"{cell}.json")) as f:
        limits = json.load(f)["limits"]
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    read(c, modelcfg.family(c), int(seed), limits, *sizes,
         emit=functools.partial(print, flush=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
