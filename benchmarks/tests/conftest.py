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


import pytest  # noqa: E402


@pytest.fixture
def seventh_cell(monkeypatch):
    """What an adding PR does to BENCHMARK.json, in a scratch copy the
    loader then reads: a seventh cell (the length-batch deployment under a
    second name) that joins `events_per_s` and every `.sat` list its twin is
    in.  No pin of these tests may trip on it.  Returns (table, cell)."""
    from benchmarks.harness import loader
    bench = loader.load_benchmark()          # read anew: a copy of its own
    twin, name = "lengthbatch_1000.saturated", "lengthbatch_1000.seventh"
    bench["workloads"].append(dict(
        next(w for w in bench["workloads"] if w["name"] == twin), name=name))
    for e in bench["end_to_end"] + bench["per_layer"]:
        if twin in e.get("workloads", []):
            e["workloads"].append(name)
    monkeypatch.setattr(loader, "load_benchmark", lambda: bench)
    return bench, name
