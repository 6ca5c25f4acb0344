"""The scratch adding PR and the checks it is run through: what conftest.py's
rule is held with (`test_bench_adding_pr.py`).  A module of its own so that a
test outside this directory can import it by path without meeting another
`conftest`."""
import collections
import copy
import importlib
import inspect
import os
import sys

from benchmarks.harness import loader, runner

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:             # the test files import one another
    sys.path.insert(0, HERE)

from test_bench_doctored import load_run_module  # noqa: E402

# The cells of PR 48's tree.  A pin that holds what a cell READS (a reader's
# value old against new, "every send owes rows") is held over these names and
# over no other: a later cell is held to the general rule alone.
ACCEPTED_CELLS = (
    "pattern_1m.saturated", "pattern_1m.paced", "pattern_32m.mesh4_saturated",
    "pattern_16m_zipf.paced", "pattern_1m.served_paced",
    "lengthbatch_1000.saturated", "join_len128.saturated",
    "sequence_within.paced", "sequence_within.saturated")

# the scratch adding PR: a configuration, four cells, three entries — under
# names no real PR will take, so that the PR which adds (say)
# `plain_window_ms_per_send.paced` does not collide with its own test
NEW_CONFIG = "adding_pr"
CLOSED_TWIN, NEW_CLOSED = "lengthbatch_1000.saturated", \
    "adding_pr.scratch_closed"
OPEN_TWIN, NEW_OPEN = "sequence_within.paced", "adding_pr.scratch_open"
SERVED_TWIN, NEW_SERVED = "pattern_1m.served_paced", \
    "adding_pr.scratch_served"
TWINS = ((CLOSED_TWIN, NEW_CLOSED), (OPEN_TWIN, NEW_OPEN),
         (SERVED_TWIN, NEW_SERVED))
# the cell of the NEW configuration: files of its own (the two-stream
# fixture's directory, where a real PR's would be `configs/adding_pr/`),
# about one timed send in four owing no rows, a model with a fourth number
ZERO_ROWS_LIKE, NEW_ZERO_ROWS = "join_len128.saturated", \
    "adding_pr.scratch_zero_rows"
NEW_CELLS = (NEW_CLOSED, NEW_OPEN, NEW_SERVED, NEW_ZERO_ROWS)
FIXTURE = os.path.join(HERE, "data", "join_two_streams")
FOURTH_NUMBER = "values_over_tolerance"
# a reader nobody has written: a NEW base name, read from the device trace
# (as PR 39's `plain_*` and PR 42's `join_*` were when they were added); its
# file is an accepted section reader's, which a CPU trace gives nothing
NEW_BASE, NEW_BASE_FILE = "scratch_section_ms_per_send", \
    "plain_window_ms_per_send.py"
# (name, the end-to-end metric it moves, the one cell it lists, source):
# two existing readers under a suffix no entry has, and the new base
NEW_ENTRIES = (
    ("plain_window_ms_per_send.scratch_open", "latency_p50_ms", NEW_OPEN,
     "device_trace"),
    ("compiles_in_window.scratch_closed", "events_per_s", NEW_CLOSED,
     "program_counter"),
    (NEW_BASE + ".scratch_zero_rows", "events_per_s", NEW_ZERO_ROWS,
     "device_trace"))


def scratch_adding_pr(bench: dict) -> dict:
    """ALL an adding PR does to BENCHMARK.json, on a copy of `bench`:

    - a `configs` entry appended, with files of its own;
    - four cells appended: a closed loop (`lengthbatch_1000.saturated`'s
      twin), an open one (`sequence_within.paced`'s) and a served one
      (`pattern_1m.served_paced`'s), which keep their twins' `config` so that
      the loader finds the twins' directories, and the new configuration's
      own cell; each joined to `end_to_end` and to every `per_layer` list its
      twin (for the last: the accepted two-stream join) is in, at the END of
      those lists;
    - three `per_layer` entries appended at the END of the table, each
      listing a new cell alone (the table's rule for a single-cell name: its
      suffix is in the cell's): two existing readers under a name no entry
      has, and one under a base name no entry has, from the device trace.
    """
    out = copy.deepcopy(bench)
    out["configs"].append({
        "name": NEW_CONFIG, "source": "benchmarks/tests/adding_pr.py: the "
        "two-stream fixture as a deployment a scratch PR adds",
        "file": "benchmarks/tests/data/join_two_streams/config.json",
        "reduced": [], "why": "a send that owes no rows; a fourth number"})
    cells = {w["name"]: w for w in out["workloads"]}
    added = [(twin, dict(cells[twin], name=name)) for twin, name in TWINS]
    added.append((ZERO_ROWS_LIKE, {
        "name": NEW_ZERO_ROWS, "config": NEW_CONFIG, "traffic": "closed",
        "chips": 1, "why": "closed loop, 4 events/send alternating L, R; "
        "about one send in four owes no rows"}))
    for twin, cell in added:
        out["workloads"].append(cell)
        for e in out["end_to_end"] + out["per_layer"]:
            if twin in e.get("workloads", []):
                e["workloads"].append(cell["name"])
    names = {e["name"] for e in out["per_layer"]}
    bases = {n.split(".")[0] for n in names}
    assert NEW_BASE not in bases
    for name, moves, cell, source in NEW_ENTRIES:
        assert name not in names and cell.endswith(name.split(".")[1])
        base = name.split(".")[0]
        like = next(e for e in out["per_layer"] if e["name"].split(".")[0]
                    == (base if base in bases else NEW_BASE_FILE[:-3]))
        assert like["source"] == source
        out["per_layer"].append(dict(like, name=name, moves=moves,
                                     workloads=[cell]))
    return out


def find_the_scratch_files(monkeypatch) -> None:
    """The files the scratch PR "adds" are found where they lie: its
    configuration's directory is the two-stream fixture (its model saying a
    fourth number in `LIMITS`, as a model with a tolerance would), its new
    reader an accepted one's file."""
    real_cell, real_module = loader.load_cell, loader._load_module

    def load_cell(w, cfg_dir, traffic_path, bench, rehearse=False):
        if w["config"] != NEW_CONFIG:
            return real_cell(w, cfg_dir, traffic_path, bench, rehearse)
        cell = real_cell(w, FIXTURE, os.path.join(
            FIXTURE, w["traffic"] + ".json"), bench, rehearse)
        compare = cell.model.compare
        cell.model.LIMITS = dict(cell.model.LIMITS, **{FOURTH_NUMBER: 0})
        cell.model.compare = lambda got, want: dict(
            compare(got, want), **{FOURTH_NUMBER: 0})
        return cell

    def load_module(path, name):
        if os.path.basename(path) == NEW_BASE + ".py":
            path = os.path.join(os.path.dirname(path), NEW_BASE_FILE)
        return real_module(path, name)
    monkeypatch.setattr(loader, "load_cell", load_cell)
    monkeypatch.setattr(loader, "_load_module", load_module)


def _checks(parameters: list) -> dict:
    found = {}
    for fname in sorted(os.listdir(HERE)):
        if not (fname.startswith("test_bench_") and fname.endswith(".py")):
            continue
        mod = importlib.import_module(fname[:-3])
        for name, fn in sorted(vars(mod).items()):
            if name.startswith("check_") and inspect.isfunction(fn) and \
                    fn.__module__ == mod.__name__ and \
                    list(inspect.signature(fn).parameters) == parameters:
                found[f"{fname}::{name}"] = fn
    return found


def table_checks() -> dict:
    """{"<file>::<check>": function} — every module-level `check_*(bench)`
    of every `test_bench_*.py` in this directory: what a file holds of the
    TABLE."""
    return _checks(["bench"])


def run_checks() -> dict:
    """Every module-level `check_*(cell, done)`: what a file holds of a
    cell's REHEARSAL — `cell` its name, `done` a `Rehearsal`."""
    return _checks(["cell", "done"])


# One `run.py --rehearse` of one cell: what it printed, and — where it was
# made in this process — the dict `runner.run_cell` returned (its stamps).
Rehearsal = collections.namedtuple("Rehearsal", "out err trace run")


def rehearse_here(monkeypatch, capsys, cell: str, trace: int, seed: int = 23,
                  seconds: float = 1.0) -> Rehearsal:
    """`run.py --rehearse` in this process, through whatever table and files
    the loader is pointed at."""
    kept = []
    real = runner.run_cell

    def run_cell(*a, **kw):
        kept.append(real(*a, **kw))
        return kept[-1]
    capsys.readouterr()              # the run's own words and no others
    with monkeypatch.context() as m:
        m.setattr(runner, "run_cell", run_cell)
        rc = load_run_module().main([
            "--workload", cell, "--seed", str(seed), "--seconds",
            str(seconds), "--trace", str(trace), "--rehearse"])
    said = capsys.readouterr()
    assert rc == 0, said.out[-1500:]
    (run_,) = kept
    return Rehearsal(said.out, said.err, trace, run_)
