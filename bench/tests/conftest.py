"""Tests of the benchmark itself, on the CPU at a small size:

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
