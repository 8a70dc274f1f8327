"""The serving replica the benchmark starts: a published-width decoder
behind the program's ``ContinuousBatcher`` -> ``ServingServer``, with the
program's defaults (decode chunk, pipelined loop, power-of-two admission
buckets). It holds the chip; the JAX-free parent is its client over the
streaming wire and steers it with one JSON object per line on stdin,
answered on stdout:

    {"cmd": "snapshot"}            counters and host phase times, now
    {"cmd": "trace_start"} / {"cmd": "trace_stop"}
    {"cmd": "check", "samples": [[prompt, tokens], ...]}
        stop the server, free the program's state, run the float32
        reference over the samples, answer the served tokens' gaps
    {"cmd": "exit"}

The faults (``--fault``, tests only) alter tokens where they are produced:
``wrong_token`` every token of every chunk, ``wrong_token_one_slot`` those
of the batch's first row alone.
"""

from __future__ import annotations

T_SCRIPT = __import__("time").time()

import argparse
import gc
import json
import os
import re
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH))

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import modelcfg, reference, traffic, xplane


def say(**obj) -> None:
    print(json.dumps(obj), flush=True)


def _annotated(name, fn):
    def call(*a, **kw):
        with jax.profiler.TraceAnnotation(name):
            return fn(*a, **kw)
    return call


def arguments(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--platform", default="tpu")
    ap.add_argument("--out", required=True)
    ap.add_argument("--fault", default="",
                    help="tests only: break the timed path underneath")
    return ap.parse_args(argv)


class Replica:
    """The program's serving stack for one configuration and mix, started
    on seeded weights and steered over stdin."""

    def __init__(self, args) -> None:
        self.args = args
        self.c = modelcfg.load(args.config)
        self.family = modelcfg.family(self.c)
        self.mix = traffic.load(args.traffic)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        import tony_tpu.runtime as rt
        from tony_tpu.runtime import compile_cache
        compile_cache.enable()
        self.compile_cache = compile_cache
        self.took = {"imports": time.time() - T_SCRIPT}
        self.devices = jax.devices()
        if self.devices[0].platform != args.platform \
                or len(self.devices) != 1:
            raise SystemExit(f"wanted 1 {args.platform} device; JAX found "
                             f"{len(self.devices)} of platform "
                             f"{self.devices[0].platform!r}")
        self.dtype = jnp.bfloat16 if args.platform == "tpu" else jnp.float32
        print(rt.device_line(self.dtype), file=sys.stderr, flush=True)
        self.took["devices"] = time.time() - T_SCRIPT
        self.batcher = self.server = None
        self.tracing = False

    # what a run serves: the published widths in the program's own types
    def config(self):
        return self.family.program_config(self.c, dtype=self.dtype,
                                          remat=False)

    def params(self, seed: int):
        return self.family.make_params(seed, self.c, self.dtype)

    def start(self, seed: int) -> int:
        """Seeded weights -> ``ContinuousBatcher`` -> ``ServingServer``;
        returns the port it listens on."""
        from tony_tpu.models.serve import ContinuousBatcher
        from tony_tpu.serving.server import ServingServer
        self.seed = seed
        params = jax.block_until_ready(self.params(seed))
        self.took["weights"] = time.time() - T_SCRIPT
        batcher = ContinuousBatcher(params, self.config(),
                                    batch=self.mix["slots"],
                                    max_len=self.mix["cache_rows"])
        del params
        if self.args.trace:
            for name in ("_issue", "_fetch", "_admit_batch", "_retire"):
                setattr(batcher, name, _annotated(f"bench.engine{name}",
                                                  getattr(batcher, name)))
        if self.args.fault in ("wrong_token", "wrong_token_one_slot"):
            fetch = batcher._fetch
            rows = slice(None) if self.args.fault == "wrong_token" \
                else slice(0, 1)

            def altered(handle):
                toks = np.array(fetch(handle))
                toks[rows] = (toks[rows] + 1) % self.c["vocab_size"]
                return toks
            batcher._fetch = altered
        self.batcher = batcher
        # the digest would pull every weight to the host to hash it
        self.server = ServingServer(batcher, port=0,
                                    weights_digest="benchmark")
        port = self.server.start()
        self.took["listening"] = time.time() - T_SCRIPT
        return port

    def hello(self, port: int) -> None:
        d = self.devices[0]
        say(event="listening", port=port,
            took=" ".join(f"{k} {v:.1f}" for k, v in self.took.items()),
            device={"platform": d.platform, "kind": d.device_kind,
                    "count": len(self.devices)})

    def snapshot(self) -> dict:
        hits, requests = (int(x) for x in re.findall(
            r"\d+", self.compile_cache.stats()))
        mem = self.devices[0].memory_stats() or {}
        b = self.batcher
        return {"t": time.time(), "compile_hits": hits,
                "compile_requests": requests,
                "phases": b.phase_times.summary(),
                "steps_executed": b.steps_executed, "chunk": b.chunk,
                "prefill_forward_tokens": b.prefill_forward_tokens,
                "stats": self.server.engine.stats(),
                "memory_peak_bytes": mem.get("peak_bytes_in_use", 0)}

    def stop(self) -> None:
        """Stop the server and free the program's state."""
        if self.tracing:
            jax.profiler.stop_trace()
            self.tracing = False
        if self.server is not None:
            self.server.stop(drain=False)
            self.server = None
        if self.batcher is not None:
            b, self.batcher = self.batcher, None
            b.params = b.cache = b.logits = None
        gc.collect()

    def check(self, samples) -> dict:
        self.stop()
        t0 = time.perf_counter()
        gaps = reference.served_token_gaps(
            self.c, self.seed, samples, self.mix["check_widths"],
            weight_dtype=self.dtype)
        if self.args.trace:
            xplane.write_reduced(os.path.join(self.args.out, "trace"),
                                 os.path.join(self.args.out, "trace.json"))
        return {"reference_s": time.perf_counter() - t0,
                "gaps": [[float(g) for g in row] for row in gaps]}

    def command(self, msg: dict) -> bool:
        """Answer one line of stdin; False ends the replica."""
        cmd = msg["cmd"]
        if cmd == "snapshot":
            say(event="snapshot", **self.snapshot())
        elif cmd == "trace_start":
            xplane.start(os.path.join(self.args.out, "trace"))
            self.tracing = True
            say(event="trace_started")
        elif cmd == "trace_stop":
            jax.profiler.stop_trace()
            self.tracing = False
            say(event="trace_stopped")
        elif cmd == "check":
            say(event="checked", **self.check(msg["samples"]))
        elif cmd == "exit":
            return False
        return True

    def serve(self) -> int:
        self.hello(self.start(self.args.seed))
        for line in sys.stdin:
            if not self.command(json.loads(line)):
                break
        self.stop()
        return 0


if __name__ == "__main__":
    sys.exit(Replica(arguments()).serve())
