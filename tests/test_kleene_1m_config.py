"""`kleene_1m` — the partitioned count pattern `every A -> B<1:5> -> C`
(BASELINE.json `configs`[4]) — through the harness's own deployment at
`rehearse_sizes` (1,024 keys, 128-key sends, the cell's generator): the
deployed app delivers the plain reference's rows, send by send, over six
passes in which partials with a count, lingering collectors and episodes of
more than five Bs all cross sends; the sizes `config.json` states hold the
reference's peaks; the slab's own facts (`forks`, `forks_dropped`,
`live_threads`) are read after the drain and said in `/metrics`.

And THE SEMANTICS PINS, as the program behaves today (ISSUE 55: the count
atom's semantics are an open question — PARITY.md, ROADMAP B4 — and nothing
here decides it; a PR that changes one of these cases changes the cell's
reference, `benchmarks/configs/kleene_1m/model.py`, with it)."""
import json
import logging
import os
import sys

import numpy as np
import pytest

from siddhi_tpu import SiddhiManager

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import loader, runner  # noqa: E402

CELL = "kleene_1m.saturated"
SEED, N_SENDS = 2 ** 31 + 11, 48          # six passes of eight sends


@pytest.fixture(scope="module")
def run():
    """N_SENDS sends of the cell's traffic through `runner.Deployment`, each
    waited for; what was delivered, what the reference says, and the facts
    the drain read."""
    cell = loader.resolve(CELL, rehearse=True)
    dep = runner.Deployment(cell, SEED, annotate=False)
    try:
        dep.run_untimed(cell.traffic, N_SENDS, "sends")
        dep.flush()
        facts = dict(dep.rt.query_runtimes["kleene"]._nfa_facts)
        got = dep.tracker.rows_by_send(range(N_SENDS))
        stray = dep.tracker.stray_rows
    finally:
        dep.close()
    refs = cell.model.reference(dep.sends, dep.plan)
    return {"cell": cell, "sends": dep.sends, "got": got, "refs": refs,
            "plan": dep.plan, "facts": facts, "stray": stray,
            "errors": dep.errors}


def test_every_send_delivers_the_reference_rows_by_value(run):
    m = run["cell"].model
    assert not run["errors"] and run["stray"] == 0
    assert set(m.LIMITS.values()) == {0}
    total = 0
    for sid, (send, want) in enumerate(zip(run["sends"], run["refs"])):
        got = run["got"][sid]
        if got is None:
            got = {n: a[:0] for n, a in want.items()}
        nums = m.compare(m.canonical(got), m.canonical(want))
        assert nums == dict.fromkeys(m.LIMITS, 0), (sid, nums)
        assert want["k"].shape[0] == m.expected_rows(send), sid
        total += want["k"].shape[0]
    events = sum(s["events"] for s in run["sends"])
    last = sum(r["k"].shape[0] for r in run["refs"][-8:]) / (8 * 512)
    assert total > 0.5 * events and 0.7 < last < 0.95, (total, events, last)
    # the bfloat16 control differs, and by value alone
    want = m.canonical(run["refs"][-1])
    ctl = m.compare(m.canonical(m.control_rows(want)), want)
    assert ctl["rows_differing"] > 0 and ctl["rows_missing"] == 0 \
        and ctl["rows_unexpected"] == 0


def test_what_crosses_sends_occurs(run):
    """A visit hands over a key's next FOUR events wherever its episode
    stands: an episode of more than five Bs (a full collector walking on in
    place, its sixth B ignored), a C whose rows come from several As (a
    collector that outlived an earlier C), and a key owed several rows in
    one send."""
    vol = np.stack([np.stack([s["cols"][2].reshape(-1, 4)
                              for s in run["sends"][b::8]], 1).reshape(128, -1)
                    for b in range(8)]).reshape(1024, -1)   # [key, its events]
    longest = np.zeros(1024, int)
    streak = np.zeros(1024, int)
    for j in range(vol.shape[1]):
        streak = np.where(vol[:, j] == 2, streak + 1, 0)
        longest = np.maximum(longest, streak)
    assert (longest >= 6).sum() > 100 and longest.max() == 7
    several_as = rows_most = 0
    for ref in run["refs"]:
        keys, first, counts = np.unique(ref["k"], return_index=True,
                                        return_counts=True)
        rows_most = max(rows_most, int(counts.max(initial=0)))
        for lo, n in zip(first, counts):
            several_as += len(set(ref["p1"][lo:lo + n].tolist())) > 1
    assert several_as > 1000 and rows_most >= 8
    peaks = run["plan"]["peaks"]
    assert peaks["rows"] == rows_most and 8 <= peaks["threads"] <= 32


def test_the_sizes_hold_the_references_peaks(run):
    """`slots` / `emit_rows` are powers of two at or above the reference's
    peak live threads a key and peak rows a key a send — at the sizes
    `config.json` states its peaks for, and here at `rehearse_sizes` with
    the generator's valve shut."""
    cell = run["cell"]
    cfg, m = cell.config, cell.model
    sizes = cfg["sizes"]
    for n in ("slots", "emit_rows"):
        assert sizes[n] & (sizes[n] - 1) == 0
        assert cfg["rehearse_sizes"][n] == sizes[n]
    stated = cfg["reference_peaks"]
    assert stated["sends"] >= 64 + 60
    for seed in ("seed_1", "seed_2"):
        assert sizes["slots"] // 2 < stated[seed]["threads"] <= sizes["slots"]
        assert sizes["emit_rows"] // 2 < stated[seed]["rows"] \
            <= sizes["emit_rows"]
    found = m.peaks(SEED, cell.traffic, cell.sizes, N_SENDS)
    assert found["threads"] <= sizes["slots"] and \
        found["rows"] <= sizes["emit_rows"]
    assert abs(found["rows_per_event"] -
               sum(r["k"].shape[0] for r in run["refs"]) /
               sum(s["events"] for s in run["sends"])) < 1e-9
    assert cfg["state_bytes_per_key"] == \
        m.state_bytes_per_key(sizes["slots"]) == 6408
    assert m.state_bytes_per_key(16) == 3208       # ISSUE 55's probe


def test_the_drain_reads_the_slabs_facts(run):
    """Statistics OFF (as deployed): `forks` and `forks_dropped`, no
    `live_threads` (a reduce over the slab is statistics' to ask for)."""
    facts = run["facts"]
    assert facts["forks_dropped"] == 0
    rows = sum(r["k"].shape[0] for r in run["refs"])
    # a row is a fork that met its C, or a collector's fifth B walking on
    assert rows * 0.8 < facts["forks"] <= rows * 1.2
    assert "live_threads" not in facts


# -- the semantics pins -----------------------------------------------------------

APP = """
@app:playback @app:statistics('BASIC')
define stream TradeStream (key long, price float, volume int);
partition with (key of TradeStream)
begin
  @capacity(keys='8', slots='{slots}')
  @emit(rows='16')
  @info(name='kleene')
  from every e1=TradeStream[volume == 1]
       -> e2=TradeStream[volume == 2 and price >= e1.price]<1:5>
       -> e3=TradeStream[volume == 3]
  select e1.key as k, e1.price as p1, e2[0].price as b0, e2[last].price as bl, e3.price as p3
  insert into Matches;
end;
"""
TWO_STREAMS = """
@app:playback @app:statistics('BASIC')
define stream Stream1 (symbol long, price float, volume int);
define stream Stream2 (symbol long, price float, volume int);
@info(name='q')
from e1=Stream1[price > 20]<2:5> -> e2=Stream2[price > 20]
select e1[0].price as p0, e1[1].price as p1, e1[2].price as p2,
       e1[3].price as p3, e2.price as q
insert into Out;
"""
A, B, C = 1, 2, 3


def drive(text, query, sends):
    """Each of `sends` = (stream, [(key, price, volume), ...]) as one
    `send_columns`, flushed; returns (rows per send as sorted tuples, the
    slab's facts after the last drain, the warnings logged, the state
    report)."""
    m = SiddhiManager()
    warned = []
    handler = logging.Handler()
    handler.emit = lambda rec: warned.append(rec.getMessage())
    logging.getLogger("siddhi_tpu").addHandler(handler)
    try:
        rt = m.create_siddhi_app_runtime(text)
        errors, batches = [], []
        rt.set_exception_listener(errors.append)

        def on_batch(_ts, b):
            sel = b["valid"] & (b["kind"] == 0)
            cols = [np.asarray(c)[sel].tolist() for c in b["cols"].values()]
            batches.extend(zip(*cols))
        rt.add_batch_callback(query, on_batch)
        rt.start()
        out, ts = [], 1000
        for stream, events in sends:
            k, p, v = zip(*events)
            before = len(batches)
            rt.get_input_handler(stream).send_columns(
                [np.asarray(k, np.int64), np.asarray(p, np.float32),
                 np.asarray(v, np.int32)],
                timestamps=ts + np.arange(len(events), dtype=np.int64))
            ts += 100
            rt.flush()
            out.append(sorted(
                (tuple(None if x is None or x != x else round(x, 4)
                       for x in row) for row in batches[before:]), key=str))
        assert not errors, errors[:1]
        facts = rt.query_runtimes[query]._nfa_facts
        return out, facts, warned, rt.state_report()
    finally:
        logging.getLogger("siddhi_tpu").removeHandler(handler)
        m.shutdown()


def key7(*events):
    return ("TradeStream", [(7, float(p), v) for p, v in events])


PINS = {
    # upstream's CountPatternTestCase.testQuery1 shape: three passing e1
    # events of <2:5> (one failing between), then the successor
    "testQuery1_shape_three_collected_of_2_to_5_gives_two_rows": (
        TWO_STREAMS, "q",
        [("Stream1", [(1, 25.6, 100), (1, 47.6, 100), (1, 13.7, 100),
                      (1, 47.8, 100)]),
         ("Stream2", [(1, 45.7, 100)])],
        [[], [(25.6, 47.6, None, None, 45.7),
              (25.6, 47.6, 47.8, None, 45.7)]]),
    "a_row_for_every_collected_prefix_at_the_closing_c": (
        APP, "kleene",
        [key7((1, A), (2, B), (3, B), (4, B), (9, C))],
        [[(7, 1.0, 2.0, 2.0, 9.0), (7, 1.0, 2.0, 3.0, 9.0),
          (7, 1.0, 2.0, 4.0, 9.0)]]),
    "a_collector_outlives_its_c_and_collects_for_the_old_a": (
        APP, "kleene",
        [key7((1, A), (2, B), (9, C)), key7((3, B), (8, C)),
         key7((7, C))],
        [[(7, 1.0, 2.0, 2.0, 9.0)], [(7, 1.0, 2.0, 3.0, 8.0)], []]),
    "the_fifth_b_walks_on_in_place_and_the_sixth_is_ignored": (
        APP, "kleene",
        [key7((1, A), (2, B), (3, B), (4, B)),
         key7((5, B), (6, B), (7, B), (9, C)), key7((8, B), (9, C))],
        [[], [(7, 1.0, 2.0, float(bl), 9.0) for bl in (2, 3, 4, 5, 6)],
         []]),
    "a_b_below_its_a_is_collected_by_an_older_a_alone": (
        APP, "kleene",
        [key7((1, A), (5, A), (3, B), (9, C))],
        [[(7, 1.0, 3.0, 3.0, 9.0)]]),
}


@pytest.mark.parametrize("pin", sorted(PINS))
def test_the_count_atom_as_the_program_has_it(pin):
    text, query, sends, want = PINS[pin]
    got, facts, warned, _report = drive(text.format(slots=8), query, sends)
    assert got == [sorted(w, key=str) for w in want]
    assert facts["forks_dropped"] == 0 and not warned


def test_a_full_slab_is_counted_in_dropped_and_said_once():
    """Two slots: the A takes one, the first B's fork the other, the second
    B's fork finds none — lost, counted in `PatternState.dropped`, read by
    the drain and said in a warning by the drain that found it (and not
    again by the next); the rows that could still be made are."""
    got, facts, warned, report = drive(APP.format(slots=2), "kleene", [
        key7((1, A), (2, B), (3, B)), key7((9, C)), key7((0, B))])
    assert got == [[], [(7, 1.0, 2.0, 2.0, 9.0)], []]
    assert facts == {"forks_dropped": 1, "forks": 2, "live_threads": 1}
    assert len(warned) == 1 and "1 pattern fork(s)" in warned[0] and \
        "@capacity(slots='2')" in warned[0]
    assert report["nfa"] == {"kleene": facts}


def test_the_facts_have_names_in_metrics():
    from siddhi_tpu.observability.exposition import render_prometheus
    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime(
            "@app:name('K')\n" + APP.format(slots=4))
        rt.start()
        h = rt.get_input_handler("TradeStream")
        assert "siddhi_nfa_forks_total{" not in render_prometheus(
            {"K": rt})          # nothing read yet: nothing said
        h.send_columns([np.array([7, 7, 7], np.int64),
                        np.array([1, 2, 3], np.float32),
                        np.array([A, B, B], np.int32)],
                       timestamps=np.array([1000, 1001, 1002], np.int64))
        rt.flush()
        text = render_prometheus({"K": rt})
        for line in ('siddhi_nfa_live_threads{app="K",query="kleene"} 3',
                     'siddhi_nfa_forks_total{app="K",query="kleene"} 2',
                     'siddhi_nfa_forks_dropped_total{app="K",query="kleene"}'
                     ' 0'):
            assert line in text, line
    finally:
        m.shutdown()


def test_a_pattern_with_no_count_atom_keeps_its_state_as_it_was():
    """`forked` is no leaf where no atom forks: the flagship's packed state
    has the one scalar it had, and its facts name no `forks`."""
    with open(os.path.join(ROOT, "benchmarks", "configs", "pattern_1m",
                           "app.siddhi")) as fh:
        text = fh.read().format(n_keys=8, slots=4, emit_rows=2)
    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime(text)
        rt.start()
        qr = rt.query_runtimes["flagship"]
        assert len(qr.state[0][3]) == 1 and not qr.planned.exec.forks
        rt.flush()
        assert qr._nfa_facts == {"forks_dropped": 0}
    finally:
        m.shutdown()
    with open(os.path.join(ROOT, "benchmarks", "configs", "kleene_1m",
                           "config.json")) as fh:
        cfg = json.load(fh)
    assert "THE COUNT ATOM'S SEMANTICS ARE THE PROGRAM'S" in \
        " ".join(cfg["assumed"])


def test_a_count_patterns_state_persists_and_an_older_snapshot_restores(
        tmp_path):
    """The second scalar rides a snapshot like the first; a snapshot
    written before it existed (one scalar) restores with it at zero, and
    the restored collector goes on collecting."""
    from siddhi_tpu.core import runtime as rtm
    from siddhi_tpu.utils.persistence import FileSystemPersistenceStore

    def deploy():
        m = SiddhiManager()
        m.set_persistence_store(FileSystemPersistenceStore(str(tmp_path)))
        rt = m.create_siddhi_app_runtime(
            "@app:name('K')\n" + APP.format(slots=4))
        got = []
        rt.add_batch_callback("kleene", lambda _ts, b: got.extend(
            np.asarray(b["cols"]["bl"])[b["valid"]].tolist()))
        rt.start()
        return m, rt, got

    def send(rt, ts, *events):
        p, v = zip(*events)
        rt.get_input_handler("TradeStream").send_columns(
            [np.full(len(p), 7, np.int64), np.asarray(p, np.float32),
             np.asarray(v, np.int32)],
            timestamps=ts + np.arange(len(p), dtype=np.int64))
        rt.flush()

    m, rt, _got = deploy()
    send(rt, 1000, (1, A), (2, B))
    qr = rt.query_runtimes["kleene"]
    (b32, b64, scalars), sel = rtm._host_state(qr)
    assert [int(x) for x in scalars] == [0, 1]
    older = rtm._device_state(qr, ((b32, b64, scalars[:1]), sel))
    assert [int(x) for x in older[0][3]] == [0, 0]
    m.persist()
    m.wait_for_persistence()
    m.shutdown()
    m2, rt2, got2 = deploy()
    m2.restore_last_revision()
    send(rt2, 2000, (3, B), (9, C))
    assert sorted(got2) == [2.0, 3.0]
    assert rt2.query_runtimes["kleene"]._nfa_facts["forks"] == 2
    m2.shutdown()
