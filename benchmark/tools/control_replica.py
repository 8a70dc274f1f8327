"""The replica of ``tools/control_serve.py``: ``jobs/serve_replica.py`` with
one of the program's own lower-precision paths switched on
(``BENCH_CONTROL_PATH``), and one more command so that a single process —
one set-up — reads several seeds:

    {"cmd": "restart", "seed": n}   free everything, start again on the
                                    weights of seed n, answer "listening"

``bf16`` is the program as the cells run it; ``int8_weights`` serves
``models/quantize.py``'s snapshot of the same weights; ``int8_kv`` sets
``kv_cache_dtype="int8"``. Never part of a benchmark run.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.jobs import serve_replica                      # noqa: E402

PATHS = ("bf16", "int8_weights", "int8_kv")


class ControlReplica(serve_replica.Replica):
    def __init__(self, args, path: str) -> None:
        if path not in PATHS:
            raise SystemExit(f"BENCH_CONTROL_PATH is one of {PATHS}")
        super().__init__(args)
        self.path = path

    def config(self):
        cfg = super().config()
        return cfg.scaled(kv_cache_dtype="int8") \
            if self.path == "int8_kv" else cfg

    def params(self, seed: int):
        params = super().params(seed)
        if self.path == "int8_weights":
            from tony_tpu.models.quantize import quantize_weights_int8
            params = quantize_weights_int8(params)
        return params

    def command(self, msg: dict) -> bool:
        if msg["cmd"] == "restart":
            self.stop()
            self.hello(self.start(msg["seed"]))
            return True
        return super().command(msg)


if __name__ == "__main__":
    sys.exit(ControlReplica(serve_replica.arguments(),
                            os.environ["BENCH_CONTROL_PATH"]).serve())
