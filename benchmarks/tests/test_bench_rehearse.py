"""`--rehearse` runs of every cell, end to end on the CPU, through the real
command: the last line is the contract's, with no metric in it."""
import json
import os
import subprocess
import sys

import pytest

from benchmarks.harness import loader

CELLS = [w["name"] for w in loader.load_benchmark()["workloads"]]
CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device",
                 "compared"}
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


def rehearse(cell, trace, *extra):
    cmd = loader.load_benchmark()["command"] + [
        "--workload", cell, "--seed", str(2 ** 31 + 5), "--seconds", "1.5",
        "--trace", str(trace), "--rehearse", *extra]
    cmd[0] = sys.executable
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="ignored")
    return subprocess.run(cmd, cwd=loader.ROOT, env=env, text=True,
                          capture_output=True, timeout=600)


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_prints_the_contract_line_and_no_metric(cell, trace):
    done = rehearse(cell, trace)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = done.stdout.strip().splitlines()
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
    assert set(last["compared"]) == {
        "rows_missing", "rows_unexpected", "rows_differing", "stray_rows",
        "listener_errors", "sends_undelivered"}
    assert all(c == {"value": 0, "limit": 0}
               for c in last["compared"].values())
    tail = done.stderr.strip().splitlines()[-len(last["compared"]):]
    assert [ln.split()[1] for ln in tail] == list(last["compared"])
    assert all(ln.startswith("compared: ") and ln.endswith("limit 0  ok")
               for ln in tail)
    if trace:
        withheld = next(ln for ln in lines if "withheld" in ln)
        for entry, _ in loader.resolve(cell).per_layer:
            base = entry["name"]
            if base.split(".")[0] in NOT_ON_THE_CPU:
                continue         # everything else was read
            assert base in withheld, (base, withheld)


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
