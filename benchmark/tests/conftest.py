"""The benchmark's own tests run on the CPU backend: the variable is set
before jax is imported (as ``tests/conftest.py`` of the repo does)."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_BENCH, os.path.dirname(_BENCH)]
