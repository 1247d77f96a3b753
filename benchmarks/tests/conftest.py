"""Tests of the benchmark's own arithmetic.  They run on the CPU and are
not part of the repo's tier-1 suite (``pytest tests/``): run them with
``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q``."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
