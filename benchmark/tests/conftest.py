"""The benchmark's own tests run on the CPU, in one process that never
describes a TPU: ``python -m pytest benchmark/tests -q`` from the root."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
