"""`--rehearse` runs of every cell, end to end on the CPU, through the real
command: the last line is the contract's, with no metric in it."""
import json
import os
import subprocess
import sys

import pytest

from adding_pr import Rehearsal
from benchmarks.harness import loader
from test_bench_doctored import load_run_module
from test_bench_two_streams import fixture_cell

CELLS = [w["name"] for w in loader.load_benchmark()["workloads"]]
CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device",
                 "compared"}
ROWS = ("rows_missing", "rows_unexpected", "rows_differing")
HARNESS = ("stray_rows", "listener_errors", "sends_undelivered")
# what a CPU rehearsal cannot read, by design: the HBM peak and the
# roofline's peaks need a chip, the p99s a thousand sends, and the device
# step's sections (PR 35's quantities, PR 39's four) each op's `tf_op` — a
# CPU trace carries `hlo_op` and no `tf_op`, so their readers give None
NOT_ON_THE_CPU = {
    "peak_hbm_bytes", "step_roofline", "gen_late_ms_p99", "latency_p99_ms",
    "step_event_load_ms_per_send", "step_state_load_ms_per_send",
    "step_scan_ms_per_send", "step_state_store_ms_per_send",
    "step_compact_ms_per_send", "step_unscoped_ms_per_send",
    "step_mesh_reduce_ms_per_send", "hot_tier_busy_ms_per_send",
    "plain_window_ms_per_send", "plain_order_ms_per_send",
    "plain_aggregate_ms_per_send", "plain_unscoped_ms_per_send"}
# The list holds what was ACCEPTED and is closed (PR 50): every other base
# name of the 89 entries of PR 48's tree must be read on the CPU, whatever
# cell asks.  A base name that is NEW and whose entry says `device_trace`
# may read or give None here — a test cannot know a reader nobody has
# written, and a CPU trace has no `tf_op` for it.
with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                       "per_layer_pr48_names.json")) as fh:
    ACCEPTED_BASES = {name.split(".")[0] for name in json.load(fh)}


def must_be_read_on_the_cpu(entry: dict) -> bool:
    base = entry["name"].split(".")[0]
    if base in ACCEPTED_BASES:
        return base not in NOT_ON_THE_CPU
    return entry["source"] != "device_trace"


def rehearse(cell, trace, *extra):
    cmd = loader.load_benchmark()["command"] + [
        "--workload", cell, "--seed", str(2 ** 31 + 5), "--seconds", "1.5",
        "--trace", str(trace), "--rehearse", *extra]
    cmd[0] = sys.executable
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="ignored")
    return subprocess.run(cmd, cwd=loader.ROOT, env=env, text=True,
                          capture_output=True, timeout=600)


def hold_the_contract_line(stdout: str, stderr: str, limits: dict) -> dict:
    """A sound rehearsal's output, held to the contract.  The numbers
    compared are the MODEL's (`limits` = its `LIMITS`: the three `rows_*` at
    the least) and the harness's own three, each a count held to 0."""
    lines = stdout.strip().splitlines()
    assert lines[0].startswith("REHEARSAL")
    last = json.loads(lines[-1])
    assert set(last) == CONTRACT_KEYS
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    assert last["metrics"] == {}
    assert set(last["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert last["device"]["platform"] == "cpu"
    # each number compared is printed beside its limit: in the run's own
    # words, as the last lines of stderr, and last in the result line
    assert any(" limit " in ln for ln in lines)
    assert list(last)[-1] == "compared"
    assert set(ROWS) <= set(limits)
    assert list(last["compared"]) == list(limits) + list(HARNESS)
    assert all(c == {"value": 0, "limit": 0}
               for c in last["compared"].values())
    tail = stderr.strip().splitlines()[-len(last["compared"]):]
    assert [ln.split()[1] for ln in tail] == list(last["compared"])
    assert all(ln.startswith("compared: ") and ln.endswith("limit 0  ok")
               for ln in tail)
    return last


def check_a_rehearsal_says_the_contract_line_and_reads_what_the_cpu_can(
        cell, done):
    """A rehearsal of `cell` — of the real command, or in this process on
    whatever table the loader is pointed at: the contract's line with the
    MODEL's numbers, and with `--trace 1` every entry the cell is listed for
    that a CPU rehearsal must read is among the metrics computed."""
    resolved = loader.resolve(cell)
    hold_the_contract_line(done.out, done.err, resolved.model.LIMITS)
    if done.trace:
        withheld = next(ln for ln in done.out.splitlines()
                        if "withheld" in ln)
        for entry, _ in resolved.per_layer:
            if must_be_read_on_the_cpu(entry):
                assert entry["name"] in withheld, (entry["name"], withheld)


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_prints_the_contract_line_and_no_metric(cell, trace):
    done = rehearse(cell, trace)
    assert done.returncode == 0, done.stderr[-2000:]
    check_a_rehearsal_says_the_contract_line_and_reads_what_the_cpu_can(
        cell, Rehearsal(done.stdout, done.stderr, trace, None))


def check_what_a_cpu_rehearsal_must_read(bench):
    """One-sided: the sixteen names are withheld-or-skipped, every OTHER
    entry under an ACCEPTED base name must be read, whatever cell lists it
    and however many such entries a later PR appends; of an entry under a
    base name that is new, only one from the device trace may be silent.
    Nothing is counted over the whole table."""
    table = bench["per_layer"]
    bases = [e["name"].split(".")[0] for e in table]
    assert set(bases) >= ACCEPTED_BASES
    skipped = 0
    for e, base in zip(table, bases):
        if base in ACCEPTED_BASES:
            assert must_be_read_on_the_cpu(e) == \
                (base not in NOT_ON_THE_CPU), e["name"]
            skipped += base in NOT_ON_THE_CPU
        else:
            assert must_be_read_on_the_cpu(e) == \
                (e["source"] != "device_trace"), e["name"]
    assert skipped >= 24             # of the 89 entries of PR 48's tree


def test_the_sixteen_names_stand_and_a_new_device_reader_may_read_nothing():
    assert len(NOT_ON_THE_CPU) == 16 and NOT_ON_THE_CPU <= ACCEPTED_BASES
    assert len(ACCEPTED_BASES) == 57
    check_what_a_cpu_rehearsal_must_read(loader.load_benchmark())
    # an accepted base under a new name keeps its rule, either way
    assert must_be_read_on_the_cpu(
        {"name": "compiles_in_window.sat", "source": "program_counter"})
    assert not must_be_read_on_the_cpu(
        {"name": "plain_window_ms_per_send.paced", "source": "device_trace"})
    # device_trace is no excuse for a reader that WAS read on the CPU
    assert must_be_read_on_the_cpu(
        {"name": "device_busy_ms_per_send.new", "source": "device_trace"})
    # a reader nobody has written: only a device trace may be silent here
    assert not must_be_read_on_the_cpu(
        {"name": "timer_step_ms_per_send.paced", "source": "device_trace"})
    for source in ("program_span", "program_counter", "host_clock"):
        assert must_be_read_on_the_cpu(
            {"name": "timer_steps_per_send.paced", "source": source})


# -- the numbers compared are the model's ----------------------------------------------

def rehearse_the_fixture(monkeypatch, capsys, limits, compare=None):
    """`run.py --rehearse` in this process on the two-stream fixture, its
    model's `LIMITS` (and `compare`) replaced."""
    cell = fixture_cell()
    monkeypatch.setattr(cell.model, "LIMITS", limits)
    if compare is not None:
        monkeypatch.setattr(cell.model, "compare", compare(cell.model))
    monkeypatch.setattr(loader, "resolve", lambda name, rehearse=False: cell)
    rc = load_run_module().main(["--workload", "x", "--seed", "5",
                                 "--seconds", "0.5", "--trace", "0",
                                 "--rehearse"])
    assert rc == 0
    done = capsys.readouterr()
    return done.out, done.err


def test_a_model_with_a_fourth_number_rehearses_green(monkeypatch, capsys):
    """A model that counts something more than the three `rows_*` — rows
    whose computed value is over the configuration's tolerance, say — says
    so in `LIMITS` and nothing in the harness or its tests is edited."""
    def with_a_fourth(model):
        real = model.compare

        def compare(got, want):
            return dict(real(got, want), values_over_tolerance=0)
        return compare
    limits = dict.fromkeys(ROWS + ("values_over_tolerance",), 0)
    out, err = rehearse_the_fixture(monkeypatch, capsys, limits,
                                    with_a_fourth)
    last = hold_the_contract_line(out, err, limits)
    assert list(last["compared"])[3] == "values_over_tolerance"
    assert "  values_over_tolerance = 0  limit 0  ok" in out


def test_a_model_that_drops_a_rows_number_does_not(monkeypatch, capsys):
    limits = dict.fromkeys(ROWS[:2], 0)
    out, err = rehearse_the_fixture(monkeypatch, capsys, limits)
    assert "rows_differing" not in json.loads(
        out.strip().splitlines()[-1])["compared"]
    with pytest.raises(AssertionError):
        hold_the_contract_line(out, err, limits)
    # and the same output is green by the fixture's own three
    out, err = rehearse_the_fixture(monkeypatch, capsys,
                                    dict.fromkeys(ROWS, 0))
    hold_the_contract_line(out, err, dict.fromkeys(ROWS, 0))


def test_a_run_outside_rehearsal_refuses_the_cpu():
    cmd = [sys.executable, "benchmarks/run.py", "--workload", CELLS[0],
           "--seed", "1", "--seconds", "1", "--trace", "0"]
    done = subprocess.run(cmd, cwd=loader.ROOT, text=True,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"),
                          capture_output=True, timeout=600)
    assert done.returncode == 1
    assert "no TPU" in done.stderr
    assert not any(ln.startswith("{") for ln in done.stdout.splitlines())


def test_unknown_device_kind_is_an_error():
    with pytest.raises(SystemExit, match="peaks.json"):
        loader.load_peaks("TPU v9 imaginary")
    assert loader.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
