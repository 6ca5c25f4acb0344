"""`--rehearse` runs of every cell, end to end on the CPU, through the real
command: the last line is the contract's, with no metric in it."""
import json
import os
import subprocess
import sys

import pytest

from benchmarks.harness import loader

CELLS = [w["name"] for w in loader.load_benchmark()["workloads"]]
CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


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
    # each number compared is printed beside its limit
    assert any(" limit " in ln for ln in lines)
    if trace:
        withheld = next(ln for ln in lines if "withheld" in ln)
        for entry, _ in loader.resolve(cell).per_layer:
            base = entry["name"]
            # what needs a chip (HBM peak, the roofline's peaks) or a
            # thousand sends is absent; everything else was read
            if base.split(".")[0] in ("peak_hbm_bytes", "step_roofline",
                                      "gen_late_ms_p99", "latency_p99_ms"):
                continue
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
