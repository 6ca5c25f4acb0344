"""Tests of the benchmark itself.  Run with

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q

They never touch a chip: the end-to-end ones are `--rehearse` runs.

THE RULE OF THESE TESTS (PR 50), stated here once and held by
`test_bench_adding_pr.py`: a PR that ADDS — new files; a configuration,
cells and `per_layer` entries APPENDED at the end of their lists; cells
appended to the lists of entries that exist — trips no test in this
directory.  Every pin holds what was accepted and nothing about what comes
after it: an entry's place is found by `names.index(...)`, never counted
from the end; a list is held as a prefix or a subset, never as a whole; what
a cell resolves is held among the entries that stood when it was accepted.

How a pin joins the rule: a table check is a module-level function
`check_<what>(bench)` of a `test_bench_*.py` here, taking the table as a dict
(and reaching files through `loader`, which the `adding_pr` fixture points at
the scratch table and the scratch files); a check of what a RUN of a cell
gives is `check_<what>(cell, done)`, `done` an `adding_pr.Rehearsal`.
`adding_pr.table_checks()` / `run_checks()` find them all by name and
signature; `test_bench_adding_pr.py` runs every table check on `adding_pr.
py`'s scratch adding PR and every run check on a traced rehearsal of each of
its new cells, and fails a `test_bench_*.py` that reads BENCHMARK.json and
brings neither.  What a test holds of what a cell READS (a value old against
new, "every send owes rows", "only this cell is served") it holds of
`adding_pr.ACCEPTED_CELLS` — nine names, a literal — and of no later cell."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


import pytest  # noqa: E402


@pytest.fixture
def adding_pr(monkeypatch):
    """The scratch adding PR (`adding_pr.scratch_adding_pr`) as the table the
    loader reads, its new files found where they lie.  No pin of these tests
    may trip on it.  Returns the table."""
    from adding_pr import find_the_scratch_files, scratch_adding_pr
    from benchmarks.harness import loader
    bench = scratch_adding_pr(loader.load_benchmark())
    monkeypatch.setattr(loader, "load_benchmark", lambda: bench)
    find_the_scratch_files(monkeypatch)
    return bench
