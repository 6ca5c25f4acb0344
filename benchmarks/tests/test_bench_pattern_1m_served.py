"""`pattern_1m_served` and its cell `pattern_1m.served_paced`: the model's
reference against an independent per-key loop, its order-aware attribution,
whole runs with delivery broken underneath them (two sends' rows swapped; a
send's rows held back for good), the readers this configuration brought —
on handmade stamps, on a small trace made here, on the small TPU trace
`record_served.py` recorded, and where their spans are absent — and its
entries in BENCHMARK.json."""
import gzip
import importlib
import importlib.util
import json
import os
import shutil
import time

import numpy as np
import pytest

import siddhi_tpu
from benchmarks.harness import loader, served_spans
from benchmarks.harness import trace_reduce as tr

CELL = "pattern_1m.served_paced"
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
NEW = ("send_call_ms_per_send", "delivery_lag_ms_per_send",
       "ring_wait_ms_per_send", "sends_per_drain", "h2d_bytes_per_send",
       "ring_copy_ms_per_send", "ring_copy_roofline")
TRACE_READERS = NEW[2:]
KEPT = ("stage_ms_per_send", "route_keys_ms_per_send", "obs_feed_ms_per_send",
        "h2d_ms_per_send", "dispatch_ms_per_send", "fetch_ms_per_send",
        "demux_ms_per_send", "sink_ms_per_send", "send_unspanned_ms_per_send",
        "dispatches_per_send", "fetches_per_send", "fetch_bytes_per_send",
        "idle_pre_dispatch_ms_per_send", "idle_post_step_ms_per_send",
        "device_busy_ms_per_send", "device_idle_pct", "compiles_in_window",
        "gen_late_ms_p99", "latency_p99_ms", "state_bytes", "peak_hbm_bytes",
        "compile_s")


def reader(name):
    return importlib.import_module(f"benchmarks.layer_metrics.{name}").read


@pytest.fixture(scope="module")
def cell():
    return loader.resolve(CELL, rehearse=True)


# -- the model ---------------------------------------------------------------------

def per_key_loop(send):
    """The pattern over one send, event by event, a dict of partial matches
    a key — nothing shared with the model's vectorised reference."""
    alive, rows = {}, []
    for k, p, v in zip(*(c.tolist() for c in send["cols"])):
        parts = alive.setdefault(k, [])
        if v == 1:
            parts.append([p])
            continue
        for m in list(parts):
            if len(m) == v - 1 and (v not in (2, 4) or p >= m[v - 2]):
                m.append(p)
                if v == 4:
                    parts.remove(m)
                    rows.append((k, m[0], m[1], m[3]))
    return sorted(rows)


@pytest.mark.parametrize("seed", [1, 2**31 + 9])
def test_reference_against_an_independent_per_key_loop(cell, seed):
    m = cell.model
    plan = m.plan(seed, cell.traffic, cell.sizes)
    rng = np.random.default_rng(seed)
    sends = [m.make_send(rng, i, cell.traffic, plan, 1000 + 10 * i)
             for i in range(6)]
    for s in sends:
        # every key of a generated send matches: break a third of them, at
        # stage 2 or at stage 4, so the reference has something to refuse
        price = s["cols"][1].reshape(-1, m.STAGES)
        bad = rng.random(price.shape[0]) < 1 / 3
        at = np.where(rng.random(price.shape[0]) < 0.5, 1, 3)
        price[bad, at[bad]] = price[bad, at[bad] - 1] - np.float32(0.25)
    refs = m.reference(sends, plan)
    for s, ref in zip(sends, refs):
        got = sorted(zip(*(ref[n].tolist() for n in ("k", "p1", "p2", "p4"))))
        assert got == per_key_loop(s)
        assert 0 < len(got) < m.expected_rows(s)


def test_the_generator_and_limits_are_pattern_1ms(cell):
    """The same traffic file gives the same sends and is held to the same
    limits in both paced cells: the pair differs in one annotation."""
    blocking = loader.resolve("pattern_1m.paced", rehearse=True)
    a, b = cell.model, blocking.model
    assert cell.traffic == blocking.traffic and cell.sizes == blocking.sizes
    pa, pb = (m.plan(5, cell.traffic, cell.sizes) for m in (a, b))
    sa, sb = (m.make_send(np.random.default_rng([5, 3]), 3, cell.traffic, p,
                          1234) for m, p in ((a, pa), (b, pb)))
    assert all(np.array_equal(x, y) for x, y in zip(sa["cols"], sb["cols"]))
    assert np.array_equal(sa["ts"], sb["ts"])
    assert a.LIMITS == b.LIMITS == dict.fromkeys(a.LIMITS, 0)
    assert cell.app_text.replace(" @serve", "") == blocking.app_text
    assert a.least_bytes(cell.traffic, cell.sizes, cell.config) == \
        b.least_bytes(cell.traffic, cell.sizes, cell.config)


def test_attribution_holds_deliveries_to_send_order(cell):
    m = cell.model
    plan = m.plan(3, cell.traffic, cell.sizes)
    sends = [m.make_send(np.random.default_rng(i), i, cell.traffic, plan,
                         1000 + 10 * i) for i in range(4)]
    keys = [s["cols"][0][::m.STAGES] for s in sends]
    attr = m.Attribution(plan)
    for sid, s in enumerate(sends):
        attr.on_issue(sid, s)
    assert attr.attribute({"k": keys[0]}).tolist() == [0] * 16
    # a delivery may hold rows of several sends, in any order among them
    mixed = np.concatenate([keys[2][:3], keys[1][:2], np.array([-4])])
    assert attr.attribute({"k": mixed}).tolist() == [2, 2, 2, 1, 1, -1]
    # send 1's remaining rows after a row of send 2 was delivered: too late
    assert attr.attribute({"k": keys[1][2:]}).tolist() == [-1] * 14
    # the newest send's own later rows, and what follows, still count
    assert attr.attribute({"k": keys[2][3:]}).tolist() == [2] * 13
    assert attr.attribute({"k": keys[3]}).tolist() == [3] * 16
    assert attr.attribute({"k": keys[3][:0]}).tolist() == []


def test_ring_least_bytes_from_shapes():
    c = loader.resolve(CELL)
    # 2,048 keys x 2 ranks x 32 B a row slot and the 20 B header, moved
    # four times: read and written by the append, read and written by the
    # read
    assert c.model.ring_least_bytes(c.traffic, c.sizes, c.config) == \
        4 * (2048 * 2 * 32 + 20)
    assert c.model.SLOT_BYTES == 32 and c.model.HEADER_BYTES == 20


# -- whole runs with delivery broken underneath them ----------------------------------

class BrokenDelivery:
    """The real runtime; between it and its subscriber one fault at the
    `AT`-th non-empty delivery (inside the window: 16 sends come before it):
    `swap` holds that send's rows back until the next send's have been
    delivered, `withhold` never delivers them — `flush()` included."""

    AT = 40

    def __init__(self, rt, fault):
        self._rt, self._fault = rt, fault
        self._deliveries, self._held = 0, None

    def __getattr__(self, name):
        return getattr(self._rt, name)

    def add_batch_callback(self, query, cb):
        def doctored(ts, b):
            out = {k: b[k] for k in ("ts", "kind", "valid")}
            out["cols"] = {n: np.array(c) for n, c in b["cols"].items()}
            if not (out["valid"] & (out["kind"] == 0)).any():
                return cb(ts, out)
            self._deliveries += 1
            if self._deliveries == self.AT:
                if self._fault == "swap":
                    self._held = (ts, out)
                return None
            cb(ts, out)
            if self._held is not None:
                held, self._held = self._held, None
                cb(*held)
        self._rt.add_batch_callback(query, doctored)


def run_with(monkeypatch, capsys, cell_name, fault):
    real = siddhi_tpu.SiddhiManager.create_siddhi_app_runtime

    def create(self, *a, **kw):
        rt = real(self, *a, **kw)
        return BrokenDelivery(rt, fault) if fault else rt
    monkeypatch.setattr(siddhi_tpu.SiddhiManager,
                        "create_siddhi_app_runtime", create)
    spec = importlib.util.spec_from_file_location(
        "bench_run_served", os.path.join(loader.BENCH_DIR, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    rc = mod.main(["--workload", cell_name, "--seed", "11", "--seconds",
                   "1.5", "--trace", "0", "--rehearse"])
    out = capsys.readouterr().out
    return rc, json.loads(out.strip().splitlines()[-1]), out


@pytest.mark.parametrize("fault,stray", [("swap", 16), ("withhold", 0)])
def test_delivery_out_of_order_or_lost_is_not_correct(monkeypatch, capsys,
                                                      fault, stray):
    rc, last, out = run_with(monkeypatch, capsys, CELL, fault)
    assert rc == 0
    assert last["correct"] is False, out[-1500:]
    assert last["failed"] >= 1 and last["attempted"] == 150
    assert "rows_missing = 16  limit 0  OVER" in out
    assert f"stray_rows = {stray}  limit 0" in out
    assert "149 completed" in out


@pytest.mark.parametrize("cell_name,correct", [
    (CELL, True),
    # the same swap under `pattern_1m`'s attribution, which holds no order:
    # every row is accounted for, and the run passes
    ("pattern_1m.paced", True),
])
def test_the_order_is_held_by_this_configurations_model(monkeypatch, capsys,
                                                         cell_name, correct):
    fault = None if cell_name == CELL else "swap"
    rc, last, out = run_with(monkeypatch, capsys, cell_name, fault)
    assert rc == 0 and last["correct"] is correct, out[-1500:]
    assert last["failed"] == 0 and "stray_rows = 0" in out


# -- the readers: the harness's stamps ----------------------------------------------

def stamped(returned=True, inline=False):
    stamps = []
    for i in range(4):
        st = {"due": 10.0 + i, "issued": 10.001 + i, "subscriber_s": 0.0,
              "subscriber_end": 10.004 + i if inline else None}
        if returned or i < 3:
            st["returned"] = 10.006 + i + 0.001 * i
        stamps.append(st)
    # delivered 9, 10, 11, 12 ms after due
    return {"stamps": stamps, "latency_ms": [9.0, 10.0, 11.0, 12.0]}


def test_send_call_and_delivery_lag_from_the_harness_clock():
    run = stamped()
    # calls of 5, 6, 7, 8 ms; each delivered 3 ms after its call returned
    assert reader("send_call_ms_per_send")(run) == pytest.approx(6.5)
    assert reader("delivery_lag_ms_per_send")(run) == pytest.approx(3.0)
    # a send that never returned, or was never delivered: nothing is read
    assert reader("send_call_ms_per_send")(stamped(returned=False)) is None
    short = stamped()
    short["latency_ms"] = short["latency_ms"][:3]
    assert reader("delivery_lag_ms_per_send")(short) is None
    assert reader("send_call_ms_per_send")({"stamps": [],
                                            "latency_ms": []}) is None
    # blocking delivery: the call is read, the lag is not a lag
    assert reader("send_call_ms_per_send")(stamped(inline=True)) == \
        pytest.approx(6.5)
    assert reader("delivery_lag_ms_per_send")(stamped(inline=True)) is None


# -- the readers: a small trace made here, spans shaped like the runtime's -------------

@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Three served sends: two uploads that say their bytes and one that
    does not, the step's dispatch and the ring append's; then, after the
    sends, two drain cycles — one serving two sends, one serving one — and a
    ring fetch BEFORE the slice."""
    import jax
    from jax.profiler import TraceAnnotation as span
    out = str(tmp_path_factory.mktemp("served_trace"))
    jax.profiler.start_trace(out)
    try:
        with span("siddhi:fetch", q="q", what="ring", items=9,
                  ring_wait_us=999999):
            time.sleep(0.001)                      # warm-up: not in the slice
        for i in range(3):
            with span("bench:send_columns", sid=i):
                with span("siddhi:send", stream="S", batch=i + 1, events=8):
                    with span("siddhi:h2d", q="q", bytes=4096):
                        pass
                    with span("siddhi:h2d", q="q", bytes=1024 + i):
                        pass
                    with span("siddhi:h2d", q="q"):
                        pass                       # says nothing: not summed
                    with span("siddhi:dispatch", q="q", step="pattern_step"):
                        pass
                    with span("siddhi:dispatch", q="q", step="ring_append",
                              occupancy=i + 1):
                        pass
        with span("bench:subscriber"):
            for items, wait_us in ((2, 3000), (1, 500)):
                with span("siddhi:fetch", q="q", what="ring", items=items,
                          ring_wait_us=wait_us, bytes=20 * items):
                    time.sleep(0.001)
                with span("siddhi:fetch", q="q", what="rows", bytes=4000):
                    pass
    finally:
        jax.profiler.stop_trace()
    return out


def test_the_served_reader_sums_what_the_spans_say(traced, capsys):
    got = served_spans.read_served(tr.newest_xplane(traced))
    assert got == {"sends": 3, "h2d_spans": 9,
                   "h2d_bytes": 3 * (4096 + 1024) + 3, "ring_fetches": 2,
                   "items": 3, "ring_wait_us": 3500, "occupancy_max": 3}
    run = {"trace_dir": traced, "trace_reduced": {"sends_in_slice": 3}}
    assert reader("h2d_bytes_per_send")(run) == pytest.approx(5121.0)
    assert reader("ring_wait_ms_per_send")(run) == pytest.approx(3.5 / 3)
    assert reader("sends_per_drain")(run) == pytest.approx(1.5)
    printed = [ln for ln in capsys.readouterr().out.splitlines()
               if ln.startswith("served path over the slice")]
    assert len(printed) == 1                       # read once, kept on the run


# -- the readers: the recorded TPU trace ------------------------------------------------

def recorded_run(tmp_path, cell, name="tiny_served.xplane.pb.gz"):
    """A run record whose trace is a recorded file, as run.py leaves it."""
    d = tmp_path / "plugins" / "profile" / "1"
    d.mkdir(parents=True)
    path = str(d / "t.xplane.pb")
    if name.endswith(".gz"):
        with gzip.open(os.path.join(DATA, name)) as src, \
                open(path, "wb") as dst:
            shutil.copyfileobj(src, dst)
    else:
        shutil.copy(os.path.join(DATA, name), path)
    return {"trace_dir": str(tmp_path), "cell": cell,
            "trace_reduced": tr.reduce_trace(path)}


def test_recorded_served_trace_reads_as_the_recorder_printed(tmp_path, cell):
    """`record_served.py` on the chip (TPU v5 lite, PR 32): four sends of
    the cell at its rehearsal sizes."""
    run = recorded_run(tmp_path, cell)
    red = run["trace_reduced"]
    assert red["sends_in_slice"] == 4 and red["devices"] == 1
    mods = dict(red["by_module"])
    assert mods["jit_ring_append"] == pytest.approx(4.1347e-05, abs=1e-9)
    assert mods["jit_ring_read"] == pytest.approx(3.4652e-05, abs=1e-9)
    assert served_spans.served(run) == {
        "sends": 4, "h2d_spans": 8, "h2d_bytes": 24576, "ring_fetches": 4,
        "items": 4, "ring_wait_us": 7224, "occupancy_max": 1}
    # 16 keys of a send in a 64-key bucket x 4 stages: grouped columns
    # 256 x 16 B, then the delta and `sel`, 256 x 4 B each
    assert reader("h2d_bytes_per_send")(run) == 256 * 16 + 2 * 256 * 4
    assert reader("sends_per_drain")(run) == 1.0
    assert reader("ring_wait_ms_per_send")(run) == pytest.approx(7.224 / 4)
    ring_ms = reader("ring_copy_ms_per_send")(run)
    assert ring_ms == pytest.approx((4.1347e-05 + 3.4652e-05) * 1e3 / 4)
    # the rehearsal's 16-key emission, 4 x (16 x 2 x 32 + 20) B, against
    # the table's v5e: a tiny copy is all launch, far under the roofline
    assert reader("ring_copy_roofline")(run) == pytest.approx(
        100.0 * 4 * (16 * 2 * 32 + 20) / (ring_ms * 1e-3 * 819e9))
    assert 0 < reader("ring_copy_roofline")(run) < 1.0


def test_recorded_served_sends_never_fetch_on_their_own_thread(tmp_path,
                                                               cell):
    """The cell's results are delivered off the sender's thread: every
    `siddhi:fetch` of the recorded run lies on another thread than the
    `siddhi:send` spans, and the only dispatch there besides the step's is
    the ring append."""
    from benchmarks.harness import program_spans as ps
    run = recorded_run(tmp_path, cell)
    by_thread = ps.program_only(
        tr.read_planes(tr.newest_xplane(run["trace_dir"]))[1])
    senders = [t for t, evs in by_thread.items()
               if any(x[0] == "siddhi:send" for x in evs)]
    assert len(senders) == 1
    names = {t: sorted({x[0] for x in evs}) for t, evs in by_thread.items()}
    assert "siddhi:fetch" not in names[senders[0]]
    assert "siddhi:sink" not in names[senders[0]]
    drainers = [t for t in by_thread if "siddhi:fetch" in names[t]]
    assert drainers and senders[0] not in drainers
    red = ps.program_spans(run)
    assert red["spans"]["fetch"]["count"] == 3 * 4       # ring, head, cols
    assert red["spans"]["dispatch"]["count"] == 3 * 4    # step, append, read
    # two threads: the gaps are split between what each was doing, the list
    # is cut to ten with the rest as `other`, and it still adds up
    gaps = run["trace_reduced"]["idle_gaps"]
    assert len(gaps) == 10 and gaps[-1][0] == "other"
    assert sum(v for _, v in gaps) == pytest.approx(
        run["trace_reduced"]["window_s"] - run["trace_reduced"]["busy_s"],
        abs=1e-9)
    assert {"siddhi:fetch", "siddhi:h2d", "bench:wait_due"} <= \
        {n for n, _ in gaps}
    assert "bench:send_columns" not in dict(gaps[:5])


@pytest.mark.parametrize("name", TRACE_READERS)
def test_a_trace_without_the_served_spans_reads_as_nothing(name, tmp_path,
                                                           cell):
    """PR 24's recorded blocking trace: its `h2d` spans say no bytes, it has
    no ring fetch and ran no ring module.  Every reader gives None and none
    raises; so does a run that was not traced at all."""
    run = recorded_run(tmp_path, cell, "tiny_spans.xplane.pb")
    assert run["trace_reduced"]["sends_in_slice"] == 3
    assert reader(name)(run) is None
    assert reader(name)({"trace_dir": None, "trace_reduced": None,
                         "cell": cell}) is None


def test_the_parent_of_this_pr_reads_bytes_and_copies_but_no_residency(
        tmp_path, cell, monkeypatch):
    """Before PR 32 the ring fetch said neither `items` nor `ring_wait_us`:
    what is built on them is absent, the rest is read."""
    real = served_spans.read_served

    def as_the_parent(path):
        out = real(path)
        return dict(out, items=None, ring_wait_us=None, occupancy_max=None)
    monkeypatch.setattr(served_spans, "read_served", as_the_parent)
    run = recorded_run(tmp_path, cell)
    assert reader("ring_wait_ms_per_send")(run) is None
    assert reader("sends_per_drain")(run) is None
    assert reader("h2d_bytes_per_send")(run) == 6144
    assert reader("ring_copy_ms_per_send")(run) > 0


# -- BENCHMARK.json ----------------------------------------------------------------------

def test_the_cell_and_its_configuration_are_what_the_contract_asks():
    check_the_cell_and_its_configuration(loader.load_benchmark())


def check_the_cell_and_its_configuration(bench):
    (w,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (w["config"], w["traffic"], w["chips"]) == \
        ("pattern_1m_served", "paced_scattered", 1)
    (c,) = [c for c in bench["configs"] if c["name"] == "pattern_1m_served"]
    assert c["reduced"] == []
    with open(os.path.join(loader.ROOT, c["file"])) as fh:
        cfg = json.load(fh)
    assert cfg["source"] == c["source"] and cfg["reduced"] == []
    assert cfg["sizes"]["n_keys"] == 1048576
    reported = [e["name"] for e in loader.resolve(CELL).end_to_end]
    assert reported == ["latency_p50_ms", "setup_s"]
    # the table's side — every quantity of NEW and KEPT resolved once in
    # this cell, by its own reader — is test_bench_per_layer_table.py's
    got = {e["name"].split(".")[0] for e, _ in loader.resolve(CELL).per_layer}
    assert set(NEW + KEPT) <= got
    assert not got & {"subscriber_ms_per_send", "after_delivery_ms_per_send",
                      "send_to_delivery_ms_per_send"}


# -- the traced slice ends at its last delivery ------------------------------------------

def test_the_profiler_stops_only_after_the_slices_last_delivery(monkeypatch):
    """Under `@serve` the call returns before its results: the tracer waits
    for the tracker to see the slice's last send delivered, then stops —
    and only once, and not at all for the sends before the last."""
    import jax
    from benchmarks.harness import runner
    log = []

    class Tracker:
        def wait(self, sid, timeout):
            log.append(("wait", sid, timeout))
            return 1.0

    class Dep:
        cell = loader.resolve(CELL, rehearse=True)
        tracker = Tracker()
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: log.append(("stop",)))
    t = runner.TracedRun(Dep, 3)
    t.tracing = True
    for j, sid in enumerate((70, 71, 72, 73)):
        t.send_issued(j, sid)
    t.stop()
    assert log == [("wait", 72, float(Dep.cell.traffic["drain_limit_s"])),
                   ("stop",)]


def test_a_served_rehearsal_traces_every_delivery_of_its_slice(monkeypatch,
                                                               capsys):
    """The whole run, traced: every send of the slice has its three fetches
    (ring, head, cols) and three dispatches (step, append, read) in it —
    the last send's delivery too, which the call did not wait for."""
    from test_bench_doctored import load_run_module
    rc = load_run_module().main(["--workload", CELL, "--seed", "34",
                                 "--seconds", "1.5", "--trace", "1",
                                 "--rehearse"])
    out = capsys.readouterr().out
    assert rc == 0 and json.loads(out.strip().splitlines()[-1])["correct"]
    (line,) = [ln for ln in out.splitlines()
               if ln.startswith("program spans: ")]
    red = json.loads(line[len("program spans: "):])
    assert red["sends"] == 10                      # the rehearsal's slice
    assert red["spans"]["fetch"]["count"] == 3 * red["sends"]
    assert red["spans"]["dispatch"]["count"] == 3 * red["sends"]
    assert red["spans"]["sink"]["count"] == red["sends"]
