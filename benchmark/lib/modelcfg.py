"""A configuration file (``benchmark/configs/<name>.json``) as the benchmark
reads it: the published ``config.json`` keys, as run, and ``family``, the
module that knows what those keys mean (``benchmark/families/<family>.py``:
its check, its counts, its seeded weights, its plain reference — the list
is in ``families/dense_decoder.py``'s docstring). Everything else in the
harness reaches a model through :func:`family`. No JAX here, and none in
the half of a family the parent process reads.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import os
import re
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: the program's RMSNorm epsilon (``tony_tpu/ops/norms.py``): a constant
#: there, so the reference uses it too; both models publish 1e-5 (a
#: departure each configuration file records)
PROGRAM_RMS_EPS = 1e-6


def load(name: str) -> dict:
    """``name`` of a file in ``configs/``, or a path ending in .json. The
    file names its family — a module of ``benchmark/families/``, or a path
    ending in .py beside the configuration file — and that family checks
    that its program block can express the configuration."""
    path = name if name.endswith(".json") else os.path.join(
        BENCH_DIR, "configs", f"{name}.json")
    with open(path) as f:
        c = json.load(f)
    if not isinstance(c.get("family"), str):
        raise ValueError(f"{name}: no \"family\" key — a configuration "
                         f"names the module under benchmark/families/ that "
                         f"reads it")
    if c["family"].endswith(".py"):
        c["family"] = os.path.join(os.path.dirname(os.path.abspath(path)),
                                   c["family"])
    family(c).check(c, name)
    return c


def family(c: dict):
    """The module of a loaded configuration's family."""
    name = c["family"]
    if not name.endswith(".py"):
        return importlib.import_module(f"benchmark.families.{name}")
    modname = "bench_family_" + re.sub(r"\W", "_", name[:-3])
    if modname not in sys.modules:
        spec = importlib.util.spec_from_file_location(modname, name)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        sys.modules[modname] = mod
    return sys.modules[modname]
