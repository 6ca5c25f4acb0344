"""The rule of conftest.py's docstring, held: a scratch copy of
BENCHMARK.json to which `adding_pr.scratch_adding_pr` has done ALL an adding
PR does — a configuration with files of its own, a closed-loop, an open-loop
and a served cell, a cell whose sends sometimes owe no rows and whose model
holds a fourth number, three `per_layer` entries one of them a device-trace
reader under a base name nobody has seen, each APPENDED; the cells joined at
the END of their twins' lists — trips no table check of any file here, and a
traced rehearsal of each new cell trips no run check.

And the test is not blind: PR 46's pin in the form it had (`names[-3:]`,
whole `workloads` lists), which refused PR 49, fails on the same table; so
does each two-sided form found since — a whole list, the count over the
whole table of what a CPU rehearsal must read, "every timed send owes rows"
and "None only for the one served cell" held of every cell.  A
`test_bench_*.py` that reads BENCHMARK.json and brings no check fails here
too: a pin nobody runs on the scratch PR is how PR 46's came back after
PR 41."""
import os

import pytest

import test_bench_rehearse
import test_bench_served_path
from adding_pr import (ACCEPTED_CELLS, FOURTH_NUMBER, HERE, NEW_BASE,
                       NEW_CELLS, NEW_CONFIG, NEW_ENTRIES, NEW_SERVED,
                       NEW_ZERO_ROWS, TWINS, ZERO_ROWS_LIKE, rehearse_here,
                       run_checks, table_checks)
from benchmarks.harness import loader

CHECKS = table_checks()
RUN_CHECKS = run_checks()
REAL = loader.load_benchmark()       # read before any fixture re-points it
JOINS = TWINS + ((ZERO_ROWS_LIKE, NEW_ZERO_ROWS),)


def reads_the_table(fname):
    with open(os.path.join(HERE, fname)) as fh:
        return "load_benchmark" in fh.read()


def test_the_scratch_pr_appends_and_joins_at_the_end(adding_pr):
    real = REAL
    assert loader.load_benchmark() is adding_pr
    # what was there is there, first and untouched but for the joined lists
    assert adding_pr["configs"][:-1] == real["configs"]
    assert adding_pr["workloads"][:-4] == real["workloads"]
    assert adding_pr["configs"][-1]["name"] == NEW_CONFIG
    assert [w["name"] for w in adding_pr["workloads"][-4:]] == \
        list(NEW_CELLS)
    assert len(adding_pr["per_layer"]) == len(real["per_layer"]) + 3 <= 128
    joined = 0
    for old, new in zip(real["end_to_end"] + real["per_layer"],
                        adding_pr["end_to_end"] + adding_pr["per_layer"]):
        was = old.get("workloads", [])
        tail = [name for twin, name in JOINS if twin in was]
        assert new == (dict(old, workloads=was + tail) if tail else old)
        joined += len(tail)
    assert joined >= 120
    for (name, moves, cell, source), e in zip(NEW_ENTRIES,
                                              adding_pr["per_layer"][-3:]):
        assert (e["name"], e["moves"], e["workloads"], e["source"]) == \
            (name, moves, [cell], source)
    # the base name nobody has seen, from the device trace
    bases = {e["name"].split(".")[0] for e in real["per_layer"]}
    assert NEW_BASE not in bases and NEW_ENTRIES[-1][0].startswith(NEW_BASE)
    # the loader resolves the new cells, the appended entries among theirs
    mine = {cell: name for name, _, cell, _ in NEW_ENTRIES}
    for twin, cell in JOINS:
        got = [e["name"] for e, _ in loader.resolve(cell).per_layer]
        assert got[-1:] == [mine[cell]] if cell in mine else \
            not set(got) & set(mine.values())
        assert len(got) == (cell in mine) + len(
            [e for e in real["per_layer"] if twin in e["workloads"]])
    # the new configuration's files are its own, its model's numbers four
    new = loader.resolve(NEW_ZERO_ROWS)
    assert new.config["name"] == "join_two_streams"
    assert list(new.model.LIMITS)[3:] == [FOURTH_NUMBER]
    assert set(NEW_CELLS).isdisjoint(ACCEPTED_CELLS)


@pytest.mark.parametrize("check", sorted(CHECKS))
def test_the_scratch_adding_pr_trips_no_table_check(adding_pr, check):
    CHECKS[check](adding_pr)


# -- what a run of a cell is held to: the new cells, rehearsed -------------------------

def doctored_every_cell_reads_as_an_accepted_one(cell, done):
    """`test_bench_served_path.py`'s recorded-stamps test as PR 50 first
    wrote it: what holds of the nine accepted cells, held of every cell of
    the live table."""
    owed = [st["owed"] for st in done.run["stamps"]]
    assert all(n > 0 for n in owed)
    inline = [st["subscriber_end"] is not None for st in done.run["stamps"]]
    assert all(inline) if cell != test_bench_served_path.SERVED \
        else not any(inline)


@pytest.mark.parametrize("cell", NEW_CELLS)
def test_a_traced_rehearsal_of_a_new_cell_trips_no_run_check(
        adding_pr, monkeypatch, capsys, cell):
    assert len(RUN_CHECKS) >= 2
    done = rehearse_here(monkeypatch, capsys, cell, 1)
    for name in sorted(RUN_CHECKS):
        RUN_CHECKS[name](cell, done)
    # ... and the run is one the two-sided forms could not take
    if cell == NEW_ZERO_ROWS:
        timed = done.run["stamps"]
        assert 0 < sum(st["owed"] == 0 for st in timed) < len(timed)
        assert FOURTH_NUMBER in done.out.splitlines()[-1]
        # the new device-trace reader read nothing, and that trips nothing
        withheld = next(ln for ln in done.out.splitlines()
                        if "withheld" in ln)
        assert NEW_BASE not in withheld
    if cell in (NEW_ZERO_ROWS, NEW_SERVED):
        with pytest.raises(AssertionError):
            doctored_every_cell_reads_as_an_accepted_one(cell, done)


def test_every_file_that_reads_the_table_brings_a_check():
    """No list of exceptions: a file that reads the table and holds nothing
    of it says so with a check that holds that much (`test_bench_two_
    streams.py`'s: the fixture is no cell)."""
    this = os.path.basename(__file__)
    files = sorted(f for f in os.listdir(HERE)
                   if f.startswith("test_bench_") and f.endswith(".py"))
    with_checks = {c.split("::")[0] for c in list(CHECKS) + list(RUN_CHECKS)}
    readers = [f for f in files if f != this and reads_the_table(f)]
    assert len(readers) >= 10
    for fname in readers:
        assert fname in with_checks, fname
    # every configuration's own file is among them
    for config in os.listdir(os.path.join(loader.BENCH_DIR, "configs")):
        fname = f"test_bench_{config}.py"
        if fname in files and reads_the_table(fname):
            assert fname in {c.split("::")[0] for c in CHECKS}, fname


# -- the test is not blind: the two-sided forms, put back, fail -------------------------

def doctored_pr46_pin(bench):
    """`test_the_three_entries_and_the_lists_the_cells_joined` as PR 46
    wrote it: the LAST three entries, whole lists."""
    names = [e["name"] for e in bench["per_layer"]]
    assert names[-3:] == ["step_roofline.seq", "scan_ticks_per_send.seq",
                          "layout_cells_per_event.seq"]
    by_name = {e["name"]: e for e in bench["per_layer"]}
    assert by_name["step_roofline.seq"]["workloads"] == [
        "sequence_within.paced"]


def doctored_whole_list(bench):
    by_name = {e["name"]: e for e in bench["per_layer"]}
    assert by_name["scan_ticks_per_send.seq"]["workloads"] == [
        "sequence_within.paced", "sequence_within.saturated"]


def doctored_count_of_what_the_cpu_must_read(bench):
    """`test_bench_rehearse.py`'s equality as PR 50 first wrote it: counted
    over the whole live table, so that an appended device-trace entry under
    a new base name is on neither side."""
    must = test_bench_rehearse.must_be_read_on_the_cpu
    table = bench["per_layer"]
    held = [e for e in table if must(e)]
    assert len(held) == len(table) - sum(
        e["name"].split(".")[0] in test_bench_rehearse.NOT_ON_THE_CPU
        for e in table)


DOCTORED = [doctored_pr46_pin, doctored_whole_list,
            doctored_count_of_what_the_cpu_must_read]


def as_pr48_left_it(bench):
    """The table cut back to what PR 48's tree had — 7 configurations, 9
    cells, 89 entries, later cells out of every list — which is where a
    two-sided pin was true; the table as it stands is any later PR's."""
    cells = [w["name"] for w in bench["workloads"][:9]]

    def cut(entry):
        if "workloads" not in entry:
            return dict(entry)
        return dict(entry, workloads=[c for c in entry["workloads"]
                                      if c in cells])
    return {**bench, "configs": bench["configs"][:7],
            "workloads": bench["workloads"][:9],
            "end_to_end": [cut(e) for e in bench["end_to_end"]],
            "per_layer": [cut(e) for e in bench["per_layer"][:89]]}


@pytest.mark.parametrize("pin", DOCTORED, ids=lambda f: f.__name__)
def test_a_two_sided_pin_held_once_and_fails_on_the_scratch_pr(
        adding_pr, pin):
    pin(as_pr48_left_it(REAL))
    with pytest.raises(AssertionError):
        pin(adding_pr)


def test_pr46s_pin_fails_where_pr49_stood():
    """What PR 49 did — ten entries appended behind PR 46's three — and
    nothing else: PR 46's form fails, this tree's passes."""
    bench = as_pr48_left_it(loader.load_benchmark())
    like = bench["per_layer"][-1]
    bench["per_layer"] += [dict(like, name=f"layout_cells_per_event.t{i}")
                           for i in range(10)]
    with pytest.raises(AssertionError):
        doctored_pr46_pin(bench)
    CHECKS["test_bench_sequence_within.py::"
           "check_the_three_entries_and_the_lists_the_cells_joined"](bench)
