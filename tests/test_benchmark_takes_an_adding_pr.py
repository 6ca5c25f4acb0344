"""The benchmark takes an ADDING PR — held in tier-1 (PERF.md section 7 (a)).

`benchmarks/tests` has a rule (its `conftest.py`, PR 50): a PR that only ADDS
— new files; a configuration, cells and `per_layer` entries APPENDED; cells
appended to the lists of entries that exist — trips no pin there.  The
driver's tier-1 run does not collect that directory, and PR 46's two-sided
pin (`names[-3:]`) cost PR 49 a whole deployment before anybody ran it.  So
here, from `tests/`: every table check of every `benchmarks/tests/
test_bench_*.py` (`adding_pr.table_checks()`), one case each, on the scratch
adding PR (`adding_pr.scratch_adding_pr`) laid over the table as it stands —
and the proof that the cases are not blind: the two-sided forms, put back,
fail on the same table."""
import importlib.util
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_TESTS = os.path.join(ROOT, "benchmarks", "tests")


def _by_path(name):
    """`benchmarks/tests/<name>.py` as the module `<name>`: the files there
    import one another by those names."""
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(BENCH_TESTS, name + ".py"))
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
adding_pr = _by_path("adding_pr")
CHECKS = adding_pr.table_checks()
proofs = _by_path("test_bench_adding_pr")


@pytest.fixture
def scratch(monkeypatch):
    """`benchmarks/tests/conftest.py`'s `adding_pr` fixture: the scratch
    adding PR as the table the loader reads, its files found where they
    lie."""
    from benchmarks.harness import loader
    bench = adding_pr.scratch_adding_pr(loader.load_benchmark())
    monkeypatch.setattr(loader, "load_benchmark", lambda: bench)
    adding_pr.find_the_scratch_files(monkeypatch)
    return bench


def test_the_checks_are_every_files_and_the_new_deployments_among_them():
    files = {c.split("::")[0] for c in CHECKS}
    assert len(CHECKS) >= 18 and len(files) >= 10
    assert "test_bench_timewindow_256sym.py" in files
    assert "test_bench_sequence_within.py" in files


@pytest.mark.parametrize("check", sorted(CHECKS))
def test_the_scratch_adding_pr_trips_no_table_check(scratch, check):
    CHECKS[check](scratch)


def test_the_scratch_pr_only_appends(scratch):
    real = proofs.REAL
    assert scratch["configs"][:-1] == real["configs"]
    assert scratch["workloads"][:-4] == real["workloads"]
    assert [e["name"] for e in scratch["per_layer"]][:-3] == \
        [e["name"] for e in real["per_layer"]]
    assert len(scratch["per_layer"]) <= 128


@pytest.mark.parametrize("pin", proofs.DOCTORED, ids=lambda f: f.__name__)
def test_a_two_sided_pin_fails_on_the_scratch_pr(scratch, pin):
    """Not blind: PR 46's `names[-3:]`, a whole `workloads` list, a count
    over the whole table — each held on PR 48's tree and fails here."""
    pin(proofs.as_pr48_left_it(proofs.REAL))
    with pytest.raises(AssertionError):
        pin(scratch)
