"""Tests of the benchmark's own arithmetic. Run by hand from the root of
the repo: `JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q`. They are
not part of the repo's tier-1 suite (that collects `tests/` only)."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "benchmarks")):
    if p not in sys.path:
        sys.path.insert(0, p)
