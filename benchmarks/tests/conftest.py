"""Tests of the benchmark itself.  Run with

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q

They never touch a chip: the end-to-end ones are `--rehearse` runs."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
