"""The whole of a run with the timed path broken underneath it: `correct`
comes out false and `failed` non-zero.  The faults sit under the harness —
a runtime that drops a send, alters a delivered row, or carries its payload
as bfloat16 — not in it."""
import importlib.util
import json
import os

import numpy as np
import pytest

import siddhi_tpu
from benchmarks.harness import loader, numeric


def load_run_module():
    spec = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(loader.BENCH_DIR, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class BrokenHandler:
    def __init__(self, handler, fault):
        self.handler, self.fault, self.calls = handler, fault, 0

    def send_columns(self, cols, timestamps=None):
        self.calls += 1
        if self.fault == "drop_send" and self.calls == 30:
            return                   # accepted, never processed
        self.handler.send_columns(cols, timestamps=timestamps)


class BrokenRuntime:
    """The real runtime with one fault between it and its user."""

    def __init__(self, rt, fault):
        self._rt, self._fault, self._deliveries = rt, fault, 0
        self._altered = False

    def __getattr__(self, name):
        return getattr(self._rt, name)

    def get_input_handler(self, stream):
        return BrokenHandler(self._rt.get_input_handler(stream), self._fault)

    def add_batch_callback(self, query, cb):
        def doctored(ts, b):
            self._deliveries += 1
            out = {k: b[k] for k in ("ts", "kind", "valid", "cols")}
            cols = {n: np.array(c) for n, c in out["cols"].items()}
            current = np.nonzero(out["valid"] & (out["kind"] == 0))[0]
            if self._fault == "alter_row" and self._deliveries >= 40 \
                    and current.size and not self._altered:
                self._altered = True
                name = list(cols)[1]          # p1: an f32 answer
                cols[name][current[0]] *= np.float32(1.01)
            if self._fault == "bf16_payload":
                for name, c in cols.items():
                    if c.dtype == np.float32:
                        cols[name] = numeric.to_bf16(c)
            out["cols"] = cols
            cb(ts, out)
        self._rt.add_batch_callback(query, doctored)


def run_with(monkeypatch, capsys, cell, fault):
    real = siddhi_tpu.SiddhiManager.create_siddhi_app_runtime

    def create(self, *a, **kw):
        rt = real(self, *a, **kw)
        return BrokenRuntime(rt, fault) if fault else rt
    monkeypatch.setattr(siddhi_tpu.SiddhiManager,
                        "create_siddhi_app_runtime", create)
    rc = load_run_module().main(["--workload", cell, "--seed", "11",
                                 "--seconds", "1.5", "--trace", "0",
                                 "--rehearse"])
    out = capsys.readouterr().out
    return rc, json.loads(out.strip().splitlines()[-1]), out


@pytest.mark.parametrize("cell,fault", [
    ("pattern_1m.saturated", "drop_send"),
    ("pattern_1m.saturated", "alter_row"),
    ("pattern_1m.paced", "alter_row"),
    ("pattern_1m.paced", "drop_send"),
    ("pattern_1m.saturated", "bf16_payload"),
])
def test_a_broken_timed_path_is_not_correct(monkeypatch, capsys, cell,
                                            fault):
    rc, last, out = run_with(monkeypatch, capsys, cell, fault)
    assert rc == 0
    assert last["correct"] is False, out[-1500:]
    assert last["failed"] >= 1
    assert "OVER" in out


def test_the_same_run_unbroken_is_correct(monkeypatch, capsys):
    rc, last, out = run_with(monkeypatch, capsys, "pattern_1m.paced", None)
    assert rc == 0 and last["correct"] is True and last["failed"] == 0
