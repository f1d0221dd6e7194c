"""Tests of the benchmark's own arithmetic and plumbing, on the CPU.
They import the benchmark's code from `benchmarks/`; none takes the
chip or loads libtpu at import."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)
