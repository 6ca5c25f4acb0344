"""A send goes to the stream it names, and a send that owes no rows is done
when its call returns — proved through the whole harness (`runner.run_cell`:
deploy, warm-up, the loop, drain, check) on the CPU with a two-stream app
that is NOT a cell: `data/join_two_streams/`, the inner join of two
`window.length` streams, sends alternating L, R, the first owing no rows.

Then the same run with the timed path broken UNDER the harness, as
`test_bench_doctored.py` breaks it: a runtime that drops every R-side call,
one that delivers a row for a send that owes none; and a configuration that
does not list a stream its model names.  `sweep.py` and `bind_sweep.py`
send through the same `Deployment.issue`: one rehearsal each on the
fixture."""
import importlib.util
import json
import os
import time

import numpy as np
import pytest

import siddhi_tpu
from benchmarks.harness import loader, runner
from benchmarks.harness import trace_reduce as tr

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                       "join_two_streams")
BENCH = loader.load_benchmark()
WARM = 8                     # the fixture's `warmup_sends`


def fixture_cell(traffic="closed", **config):
    """The fixture as a cell: `loader.load_cell`, which is `resolve` without
    the look-up in BENCHMARK.json, and no per-layer entry lists it."""
    w = {"name": "join_two_streams." + traffic, "config": "join_two_streams",
         "traffic": traffic, "chips": 1}
    cell = loader.load_cell(w, FIXTURE,
                            os.path.join(FIXTURE, traffic + ".json"), BENCH)
    cell.config.update(config)
    return cell


def check_the_fixture_is_no_cell_and_no_entry_lists_it(bench):
    """What this file holds of the table: nothing but that the fixture is
    not in it under its own name (the scratch adding PR runs its directory
    under another)."""
    listed = [w["name"] for w in bench["workloads"]] + [
        c for e in bench["end_to_end"] + bench["per_layer"]
        for c in e.get("workloads", [])]
    assert not any(c.startswith("join_two_streams.") for c in listed)
    assert "join_two_streams" not in [c["name"] for c in bench["configs"]]


def test_the_fixture_is_no_cell_and_no_entry_lists_it():
    check_the_fixture_is_no_cell_and_no_entry_lists_it(BENCH)


def load_script(name):
    spec = importlib.util.spec_from_file_location(
        "bench_" + name, os.path.join(loader.BENCH_DIR, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def run(cell, trace=False, seconds=1.0):
    import jax
    said = []
    out = runner.run_cell(cell, 2 ** 31 + 41, seconds, trace, False,
                          time.perf_counter(), runner.CompileMeters(),
                          jax.devices(), said.append)
    out["correct"] = out["failed"] == 0 and out["attempted"] > 0 and \
        out["completed"] == out["attempted"]
    out["compared"] = load_script("run").compared(out)
    return out, "\n".join(said)


# -- the runtime, broken underneath ------------------------------------------------

class BrokenHandler:
    def __init__(self, owner, stream, handler):
        self.owner, self.stream, self.handler = owner, stream, handler

    def send_columns(self, cols, timestamps=None):
        o = self.owner
        o.calls += 1
        timed = o.calls > WARM
        if o.fault == "drop_r_calls" and timed and self.stream == "R":
            return                   # accepted, never processed
        before = o.deliveries
        self.handler.send_columns(cols, timestamps=timestamps)
        if o.fault == "row_for_a_zero_row_send" and timed \
                and o.deliveries == before and not o.invented:
            o.invented = True        # nothing came of this send: make a row
            o.callback(timestamps, {
                "valid": np.ones(1, bool), "kind": np.zeros(1, np.int32),
                "cols": {"s": np.array([3], np.int64),
                         "p": np.array([0.5], np.float32),
                         "v": np.array([7], np.int32)}})


class BrokenRuntime:
    """The real runtime with one fault between it and its user."""

    def __init__(self, rt, fault):
        self._rt, self.fault = rt, fault
        self.calls = self.deliveries = 0
        self.invented = False
        self.callback = None

    def __getattr__(self, name):
        return getattr(self._rt, name)

    def get_input_handler(self, stream):
        return BrokenHandler(self, stream, self._rt.get_input_handler(stream))

    def add_batch_callback(self, query, cb):
        def counted(ts, b):
            if (b["valid"] & (b["kind"] == 0)).any():
                self.deliveries += 1
            cb(ts, b)
        self.callback = cb
        self._rt.add_batch_callback(query, counted)


@pytest.fixture
def broken(monkeypatch):
    def install(fault):
        real = siddhi_tpu.SiddhiManager.create_siddhi_app_runtime
        monkeypatch.setattr(
            siddhi_tpu.SiddhiManager, "create_siddhi_app_runtime",
            lambda self, *a, **kw: BrokenRuntime(real(self, *a, **kw), fault))
    return install


# -- the sound run ------------------------------------------------------------------

def test_the_two_stream_app_runs_through_the_whole_harness():
    out, said = run(fixture_cell())
    assert out["correct"] is True and out["failed"] == 0, said
    assert all(c == {"value": 0, "limit": 0}
               for c in out["compared"].values()), out["compared"]
    assert out["attempted"] == out["completed"] >= 20
    assert "set-up" in said and f"{WARM} untimed sends" in said
    assert len(out["latency_ms"]) == out["attempted"]
    assert out["events"] == 4 * out["attempted"]


def test_its_sends_alternate_streams_and_some_owe_no_rows():
    cell = fixture_cell()
    dep = runner.Deployment(cell, 2 ** 31 + 41, annotate=False)
    try:
        sids = [dep.make(cell.traffic) for _ in range(40)]
        sends = [dep.sends[s] for s in sids]
        assert [s["stream"] for s in sends] == ["L", "R"] * 20
        owed = [cell.model.expected_rows(s) for s in sends]
        assert owed[0] == 0                      # it meets an empty window
        assert 0 in owed[WARM:] and max(owed[WARM:]) > 0
        for sid in sids:
            dep.issue(sid, runner.now())
            # every send of this blocking app is done when its call is back
            assert dep.tracker.done_t[sid] <= dep.stamps[sid]["returned"]
    finally:
        dep.close()
    checked = runner.check(dep, sids, False, lambda _msg: None)
    assert checked["failed_sends"] == []
    assert sum(owed) == sum(
        rows["s"].shape[0] for rows in
        dep.tracker.rows_by_send(sids).values() if rows is not None) > 0


def test_a_send_that_owes_no_rows_is_done_at_its_calls_return():
    cell = fixture_cell()
    dep = runner.Deployment(cell, 7, annotate=False)
    try:
        sid = dep.make(cell.traffic)
        assert cell.model.expected_rows(dep.sends[sid]) == 0
        dep.issue(sid, runner.now())
        t = runner.now()
        done = dep.tracker.wait(sid, float(cell.traffic["drain_limit_s"]))
        waited = runner.now() - t
    finally:
        dep.close()
    assert done == dep.stamps[sid]["returned"]
    assert waited < 1e-3                         # not after `drain_limit_s`


def test_a_send_that_is_owed_rows_is_not_done_by_its_calls_return():
    tracker = runner.Tracker(None, ("s",))
    tracker.expected[0], tracker.got[0] = 3, 0
    tracker.returned(0, 1.0)
    assert tracker.wait(0, 0.0) is None


# -- doctored -------------------------------------------------------------------------

def test_a_runtime_that_drops_every_r_side_call_is_not_correct(broken):
    broken("drop_r_calls")
    out, said = run(fixture_cell("paced"))
    assert out["correct"] is False and out["failed"] >= 1, said
    missing = out["compared"]["rows_missing"]
    assert missing["value"] > missing["limit"] == 0
    assert "rows_missing" in said and "OVER" in said
    assert out["compared"]["sends_undelivered"]["value"] >= 1


def test_a_row_delivered_for_a_send_that_owes_none_is_not_correct(broken):
    broken("row_for_a_zero_row_send")
    out, said = run(fixture_cell())
    assert out["correct"] is False and out["failed"] == 1, said
    assert out["compared"]["rows_unexpected"] == {"value": 1, "limit": 0}
    assert out["compared"]["rows_missing"]["value"] == 0
    # the send itself completed: only the check can find the row
    assert out["attempted"] == out["completed"]


def test_a_stream_the_configuration_does_not_list_ends_the_run():
    with pytest.raises(RuntimeError, match=r"names stream 'R'.*\['L'\]"):
        run(fixture_cell(streams=["L"]))


def test_a_send_that_names_no_stream_goes_to_the_configurations():
    cell = fixture_cell()
    dep = runner.Deployment(cell, 7, annotate=False)
    try:
        assert sorted(dep.handlers) == ["L", "R"]
        sid = dep.make(cell.traffic)
        del dep.sends[sid]["stream"]
        dep.issue(sid, runner.now())             # config.json `stream`: L
        assert dep.tracker.wait(sid, 0.0) is not None
    finally:
        dep.close()
    assert not dep.errors


# -- the span says where a send went -----------------------------------------------

def test_the_send_span_carries_the_stream_beside_the_sid():
    out, said = run(fixture_cell(), trace=True, seconds=0.3)
    assert out["correct"] is True, said
    _devices, spans = tr.read_planes(tr.newest_xplane(out["trace_dir"]))
    sends = sorted((x for evs in spans.values() for x in evs
                    if x[0] == tr.SEND_SPAN), key=lambda x: x[1])
    assert len(sends) == int(fixture_cell().traffic["trace_sends"])
    assert [str(x[3]["stream"]) for x in sends] == ["L", "R"] * 3
    assert [int(x[3]["sid"]) for x in sends] == list(range(WARM, WARM + 6))


# -- the sweeps follow `Deployment.issue` -----------------------------------------------

@pytest.fixture
def as_a_cell(monkeypatch, tmp_path):
    """`loader.resolve` hands the scripts the fixture for any name."""
    def install(traffic, **config):
        cell = fixture_cell(traffic, **config)
        monkeypatch.setattr(loader, "resolve",
                            lambda name, rehearse=False: cell)
        return cell
    return install


def test_sweep_rehearses_on_the_two_stream_app(as_a_cell, tmp_path,
                                               monkeypatch, capsys):
    as_a_cell("paced")
    sweep = load_script("sweep")
    monkeypatch.setattr(sweep, "ROOT", str(tmp_path))
    rc = sweep.main(["--workload", "x", "--rates", "200,400",
                     "--step-seconds", "0.5", "--rehearse"])
    rows = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("{")]
    assert rc == 0 and [r["rate_events_per_s"] for r in rows] == [200, 400]
    assert all(r["completed"] == r["sends"] > 0 for r in rows)


def test_bind_sweep_rehearses_on_the_two_stream_app(as_a_cell, tmp_path,
                                                    monkeypatch, capsys):
    cell = as_a_cell("closed")
    # its arithmetic is a key space's: 24 "keys", 4 a send -> six sends
    cell.sizes["n_keys"] = int(cell.traffic["ids"])
    cell.traffic["keys_per_send"] = int(cell.traffic["events_per_send"])
    bind = load_script("bind_sweep")
    monkeypatch.setattr(bind, "ROOT", str(tmp_path))
    rc = bind.main(["--workload", "x", "--again", "2", "--rehearse"])
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and last["sends"] == 6 and last["failed_sends"] == 0
    assert last["events"] == 24 and last["errors"] == 0
