"""Text generation from a trained LM checkpoint — the inference half of
examples/lm/train_lm.py.

Loads the orbax checkpoint written by train_lm.py and decodes with the
KV-cache path (prefill + scan-decode, one compiled program). Runs on TPU
(flash-attention prefill) or CPU.

Usage:
    python examples/lm/generate.py --ckpt_dir /tmp/lm-ckpt --preset tiny \
        --max_new_tokens 64 --temperature 0.8
"""

from __future__ import annotations

import argparse
import sys
import time

import jax

import tony_tpu.runtime as rt
from tony_tpu.models import transformer as T
from tony_tpu.models.checkpoint import CheckpointManager
from tony_tpu.models.decode import generate
from tony_tpu.runtime import compile_cache


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--preset", default="tiny", choices=sorted(T.PRESETS))
    parser.add_argument("--ckpt_dir", default="",
                        help="orbax checkpoint dir (empty = random params)")
    parser.add_argument("--batch_size", type=int, default=2)
    parser.add_argument("--prompt_len", type=int, default=16)
    parser.add_argument("--max_new_tokens", type=int, default=32)
    parser.add_argument("--temperature", type=float, default=0.8)
    parser.add_argument("--top_k", type=int, default=40)
    parser.add_argument("--top_p", type=float, default=0.0,
                        help="nucleus sampling mass (0 = off)")
    parser.add_argument("--beam_width", type=int, default=0,
                        help="beam search instead of sampling (> 0 "
                             "enables; returns the best beam)")
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args()

    compile_cache.enable()
    dtype = rt.platform_dtype()
    print(rt.device_line(dtype), flush=True)
    cfg = T.PRESETS[args.preset].scaled(dtype=dtype, remat=False)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    if args.ckpt_dir:
        with CheckpointManager(args.ckpt_dir) as mgr:
            from tony_tpu.models.train import default_optimizer, init_state
            state = mgr.restore(
                template=init_state(params, default_optimizer()))
        params = state["params"]
        print(f"restored step {int(state['step'])} from {args.ckpt_dir}")

    rng = jax.random.PRNGKey(args.seed)
    prompt = jax.random.randint(rng, (args.batch_size, args.prompt_len), 0,
                                cfg.vocab_size)
    t0 = time.perf_counter()
    if args.beam_width > 0:
        from tony_tpu.models.decode import beam_search
        beams = beam_search(params, prompt, cfg,
                            max_new_tokens=args.max_new_tokens,
                            beam_width=args.beam_width)
        int(beams.tokens[0, 0, -1])
        n = int(beams.tokens.shape[0] * args.max_new_tokens)
        dt = time.perf_counter() - t0
        print(f"beam search W={args.beam_width}: best-beam shape "
              f"{beams.tokens.shape[::2]} in {dt:.2f}s "
              f"({n / dt:,.0f} tok/s incl. compile)")
        print("best beam token ids:",
              beams.tokens[0, 0, args.prompt_len:].tolist()[:16])
        print("beam scores:", [round(float(x), 2) for x in beams.scores[0]])
        return 0
    out = generate(params, prompt, cfg, max_new_tokens=args.max_new_tokens,
                   rng=rng, temperature=args.temperature, top_k=args.top_k,
                   top_p=args.top_p)
    int(out.tokens[0, -1])   # host fetch: timing must include execution
    n = int(out.tokens.shape[0] * args.max_new_tokens)
    dt = time.perf_counter() - t0
    print(f"generated {out.tokens.shape} in {dt:.2f}s "
          f"({n / dt:,.0f} tok/s incl. compile)")
    print("sample token ids:", out.tokens[0, args.prompt_len:].tolist()[:16])
    print("mean logprob:", float(out.logprobs.mean()))
    return 0


if __name__ == "__main__":
    sys.exit(main())
