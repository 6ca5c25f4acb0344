"""Golden cross-check: the block-parallel single-key NFA (pattern_block.py)
must emit exactly what the sequential scan path (pattern.py tick) emits —
same rows, same order — on randomized workloads.  The scan path is the
semantic reference (itself verified against the reference's
PatternTestCase/SequenceTestCase behaviors in test_pattern*.py)."""
import numpy as np
import pytest

import siddhi_tpu.core.pattern_planner as pp
from siddhi_tpu import SiddhiManager


def _run(ql, sends, force_scan):
    prev = pp._FORCE_SCAN
    pp._FORCE_SCAN = force_scan
    try:
        m = SiddhiManager()
        rt = m.create_siddhi_app_runtime(ql)
        got = []
        rt.add_callback("q", lambda ts, cur, exp: got.extend(
            (e.timestamp, tuple(e.data)) for e in (cur or [])))
        rt.start()
        for stream, cols, ts in sends:
            rt.get_input_handler(stream).send_columns(cols, timestamps=ts)
        rt.flush()
        m.shutdown()
        return got
    finally:
        pp._FORCE_SCAN = prev


def _cross(ql, sends):
    """Both paths must emit the same (timestamp, row) multiset in
    timestamp order.  The relative order of DIFFERENT-timestamp rows is
    asserted exactly; ties (one event completing several pending states at
    once) are unordered — the scan path orders them by slab-slot index
    (allocation order) and the block path by state age, and the reference
    itself uses pending-list insertion order, so no order is canonical."""
    blk = _run(ql, sends, force_scan=False)
    ref = _run(ql, sends, force_scan=True)
    for name, rows in (("block", blk), ("scan", ref)):
        ts = [t for t, _ in rows]
        assert ts == sorted(ts), f"{name} path emitted out of ts order"
    assert sorted(blk) == sorted(ref), (
        f"block path diverges from scan path: "
        f"block={blk[:6]}... ({len(blk)} rows) vs "
        f"scan={ref[:6]}... ({len(ref)} rows)")
    return [d for _, d in blk]


def _mk_sends(n_sends, B, seed, n_vols=4, stream="S"):
    rng = np.random.default_rng(seed)
    sends = []
    t = 1000
    for i in range(n_sends):
        vols = rng.integers(1, n_vols + 1, B).astype(np.int32)
        prices = (rng.integers(0, 50, B) / 4.0).astype(np.float32)
        ts = t + np.arange(B, dtype=np.int64) * 7
        t = int(ts[-1]) + 13
        sends.append((stream, [np.zeros(B, np.int64), prices, vols], ts))
    return sends


QL2 = """
@app:playback
define stream S (k long, price float, volume int);
@capacity(slots='256')
@info(name='q')
from every e1=S[volume == 1] {sep} e2=S[volume == 2 and price >= e1.price]
select e1.price as p1, e2.price as p2 insert into M;
"""

QL3 = """
@app:playback
define stream S (k long, price float, volume int);
@capacity(slots='256')
@info(name='q')
from every e1=S[volume == 1] -> e2=S[volume == 2 and price >= e1.price]
     -> e3=S[volume == 3 and price >= e2.price]
select e1.price as p1, e2.price as p2, e3.price as p3 insert into M;
"""


@pytest.mark.parametrize("sep", ["->", ","])
def test_two_stage_random(sep):
    rows = _cross(QL2.format(sep=sep), _mk_sends(4, 200, seed=1))
    assert rows  # non-degenerate


@pytest.mark.parametrize("sep", ["->", ","])
def test_two_stage_within(sep):
    ql = QL2.format(sep=sep).replace(
        "select", "within 100 millisec\nselect" if sep == "," else
        "within 100 millisec\nselect")
    rows = _cross(ql, _mk_sends(4, 200, seed=2))
    assert rows


def test_three_stage_pattern_random():
    rows = _cross(QL3, _mk_sends(3, 150, seed=3))
    assert rows


def test_non_every_first_match_only():
    ql = """
    @app:playback
    define stream S (k long, price float, volume int);
    @info(name='q')
    from e1=S[volume == 1] -> e2=S[volume == 2]
    select e1.price as p1, e2.price as p2 insert into M;
    """
    rows = _cross(ql, _mk_sends(3, 64, seed=4))
    assert len(rows) == 1  # non-every: exactly one match ever


def test_cross_send_pending_state():
    """A pending e1 from send N must complete on an e2 in send N+1."""
    ql = QL2.format(sep="->")
    sends = [
        ("S", [np.zeros(2, np.int64),
               np.array([5.0, 4.0], np.float32),
               np.array([1, 3], np.int32)],
         np.array([1000, 1001], np.int64)),
        ("S", [np.zeros(2, np.int64),
               np.array([6.0, 9.0], np.float32),
               np.array([2, 2], np.int32)],
         np.array([2000, 2001], np.int64)),
    ]
    rows = _cross(ql, sends)
    assert (5.0, 6.0) in rows


def test_sequence_strict_continuity_across_sends():
    """SEQUENCE pending at a send boundary: the first event of the next
    send must match or the state dies."""
    ql = QL2.format(sep=",")
    sends = [
        ("S", [np.zeros(3, np.int64),
               np.array([5.0, 7.0, 1.0], np.float32),
               np.array([3, 1, 3], np.int32)],
         np.array([1000, 1001, 1002], np.int64)),
    ]
    rows = _cross(ql, sends)
    assert rows == []  # e1 at 7.0 killed by the volume-3 event right after


def test_multi_stream_chain():
    ql = """
    @app:playback
    define stream A (x int);
    define stream B (y int);
    @capacity(slots='256')
    @info(name='q')
    from every e1=A[x > 0] -> e2=B[y >= e1.x]
    select e1.x as x, e2.y as y insert into M;
    """
    rng = np.random.default_rng(7)
    sends = []
    t = 1000
    for i in range(6):
        stream = "A" if i % 2 == 0 else "B"
        B = 32
        v = rng.integers(-3, 10, B).astype(np.int32)
        ts = t + np.arange(B, dtype=np.int64)
        t = int(ts[-1]) + 5
        sends.append((stream, [v], ts))
    rows = _cross(ql, sends)
    assert rows


def test_emit_cap_respected():
    ql = """
    @app:playback
    define stream S (k long, price float, volume int);
    @emit(rows='4')
    @info(name='q')
    from every e1=S[volume == 1] -> e2=S[volume == 2]
    select e1.price as p1, e2.price as p2 insert into M;
    """
    # 8 seeds then one e2: 8 completions at once, cap keeps first 4
    B = 9
    vols = np.array([1] * 8 + [2], np.int32)
    prices = np.arange(B, dtype=np.float32)
    sends = [("S", [np.zeros(B, np.int64), prices, vols],
              1000 + np.arange(B, dtype=np.int64))]
    rows = [d for _, d in _run(ql, sends, force_scan=False)]
    assert len(rows) == 4
    assert rows == [(float(i), 8.0) for i in range(4)]


def test_single_atom_every():
    ql = """
    @app:playback
    define stream S (k long, price float, volume int);
    @info(name='q')
    from every e1=S[volume == 2]
    select e1.price as p insert into M;
    """
    rows = _cross(ql, _mk_sends(2, 100, seed=8))
    assert rows


def test_every_seed_also_completes_earlier_state():
    """An event can complete one pending state AND seed a new one."""
    ql = """
    @app:playback
    define stream S (k long, price float, volume int);
    @capacity(slots='256')
    @info(name='q')
    from every e1=S[volume <= 2] -> e2=S[volume >= 2]
    select e1.price as p1, e2.price as p2 insert into M;
    """
    rows = _cross(ql, _mk_sends(3, 80, seed=9, n_vols=3))
    assert rows


# -- SEQUENCE chains: the linear form against the scan path ------------------

SEQ3 = """
@app:playback
define stream S (k long, price float, volume int);
@capacity(slots='{slots}')
@info(name='q')
from every e1=S[volume == 1], e2=S[volume == 2 and price >= e1.price],
     e3=S[volume == 3 and price >= e2.price] {within}
select e1.price as p1, e2.price as p2, e3.price as p3 insert into M;
"""

SEQ_AB = """
@app:playback
define stream A (x int);
define stream B (y int);
@capacity(slots='8')
@info(name='q')
from every e1=A[x > 0], e2=B[y >= e1.x]
select e1.x as x, e2.y as y insert into M;
"""

SEQ_BOTH = """
@app:playback
define stream S (k long, price float, volume int);
@capacity(slots='8')
@info(name='q')
from every e1=S[volume <= 2], e2=S[volume >= 2]
select e1.price as p1, e2.price as p2 insert into M;
"""


def _send(vols, prices, t0, stream="S", step=1):
    n = len(vols)
    return (stream, [np.zeros(n, np.int64), np.array(prices, np.float32),
                     np.array(vols, np.int32)],
            t0 + np.arange(n, dtype=np.int64) * step)


def _ab_sends():
    """Sends to A and to B in turn: an e1 left pending by a send to A
    completes at the FIRST event of the next send to B or dies there, and
    dies at any further event of A."""
    rng = np.random.default_rng(21)
    sends, t = [], 1000
    for i, stream in enumerate("ABABBAAB"):
        n = (3, 5, 1, 4)[i % 4]
        v = rng.integers(-1, 6, n).astype(np.int32)
        sends.append((stream, [v], t + np.arange(n, dtype=np.int64)))
        t += n + 3
    # one pair that must match, whatever the draw
    sends.append(("A", [np.array([2, 4], np.int32)],
                  np.array([t, t + 1], np.int64)))
    sends.append(("B", [np.array([7, 9], np.int32)],
                  np.array([t + 2, t + 3], np.int64)))
    return sends


# name -> (app, sends, the rows owed — None: whatever the scan path says)
SEQ_CASES = {
    "three_atoms_within_random": (
        SEQ3.format(slots=16, within="within 40 millisec"),
        _mk_sends(4, 200, seed=31, n_vols=3), None),
    "three_atoms_no_within_two_slots": (
        SEQ3.format(slots=2, within=""),
        _mk_sends(3, 120, seed=32, n_vols=3), None),
    "non_every": (
        QL2.format(sep=",").replace("from every e1", "from e1"),
        [_send([3, 3, 1, 2, 1, 2], [0, 0, 1, 2, 3, 4], 1000)], [(1.0, 2.0)]),
    "non_every_the_seed_dies_and_nothing_follows": (
        QL2.format(sep=",").replace("from every e1", "from e1"),
        [_send([1, 3, 1, 2], [1, 2, 3, 4], 1000)], []),
    "two_streams": (SEQ_AB, _ab_sends(), None),
    "sends_of_one_event_carry_a_thread_over_two_sends": (
        SEQ3.format(slots=8, within=""),
        [_send([1], [1.0], 1000), _send([2], [2.0], 1010),
         _send([3], [3.0], 1020)], [(1.0, 2.0, 3.0)]),
    "sends_of_two_events_shorter_than_the_chain": (
        SEQ3.format(slots=8, within=""),
        [_send([3, 1], [0, 1.0], 1000), _send([2, 3], [2.0, 3.0], 1010),
         _send([1, 2], [4.0, 5.0], 1020), _send([3], [6.0], 1030),
         _send([1], [7.0], 1040), _send([2, 2], [8.0, 9.0], 1050)],
        [(1.0, 2.0, 3.0), (4.0, 5.0, 6.0)]),
    "an_event_completes_a_carried_thread_and_seeds_a_new_one": (
        SEQ_BOTH,
        [_send([3, 1], [0, 1.0], 1000), _send([2, 3, 3], [2.0, 3.0, 4.0],
                                              1010)],
        [(1.0, 2.0), (2.0, 3.0)]),
    "within_expires_a_carried_thread": (
        SEQ3.format(slots=8, within="within 10 millisec"),
        [_send([1, 2], [1.0, 2.0], 1000), _send([3], [3.0], 1011),
         _send([1, 2], [4.0, 5.0], 1020), _send([3], [6.0], 1030)],
        [(4.0, 5.0, 6.0)]),
}


@pytest.mark.parametrize("case", sorted(SEQ_CASES))
def test_sequence_cross_checks(case):
    ql, sends, owed = SEQ_CASES[case]
    rows = _cross(ql, sends)
    if owed is None:
        assert rows                     # non-degenerate
    else:
        assert rows == owed


def _run_staged(ql, batches, force_scan):
    """Hand the query runtime staged batches as they are — `valid` masks
    with HOLES, which no staging path of the app makes."""
    from siddhi_tpu.core import event as ev
    prev = pp._FORCE_SCAN
    pp._FORCE_SCAN = force_scan
    try:
        m = SiddhiManager()
        rt = m.create_siddhi_app_runtime(ql)
        got = []
        rt.add_callback("q", lambda ts, cur, exp: got.extend(
            (e.timestamp, tuple(e.data)) for e in (cur or [])))
        rt.start()
        qr = rt.query_runtimes["q"]
        for cols, ts, valid in batches:
            staged = ev.StagedBatch(ts.copy(), np.zeros(ts.shape, np.int32),
                                    valid.copy(), [c.copy() for c in cols],
                                    ts.shape[0])
            with qr._qlock:
                qr.process_staged("S", staged, int(ts[valid][-1]))
        rt.flush()
        m.shutdown()
        return got
    finally:
        pp._FORCE_SCAN = prev


@pytest.mark.parametrize("sep", ["->", ","])
def test_a_holed_selection_lists_its_valid_rows_first(sep):
    """Invalid rows BETWEEN valid ones: the host's selection closes the
    holes up (keyslots.valid_first_sel), so the event after a valid one is
    the next valid one — the linear form's premise — and both forms see the
    rows the scan path sees.  Every hole holds an event that would match."""
    from siddhi_tpu.core.keyslots import valid_first_sel
    assert valid_first_sel(np.array([0, 1, 1, 0, 1, 0], bool)).tolist() == \
        [[1, 2, 4, -1, -1, -1]]
    rng = np.random.default_rng(41)
    batches, t = [], 1000
    for _ in range(3):
        B = 64
        vols = rng.integers(1, 4, B).astype(np.int32)
        prices = (rng.integers(0, 50, B) / 4.0).astype(np.float32)
        valid = rng.random(B) < 0.6
        valid[-3:] = [True, False, False]
        vols[~valid], prices[~valid] = 2, 99.0
        ts = t + np.arange(B, dtype=np.int64) * 3
        t = int(ts[-1]) + 5
        batches.append(([np.zeros(B, np.int64), prices, vols], ts, valid))
    ql = QL2.format(sep=sep)
    blk = _run_staged(ql, batches, force_scan=False)
    ref = _run_staged(ql, batches, force_scan=True)
    assert blk and sorted(blk) == sorted(ref)
    assert [t for t, _ in blk] == sorted(t for t, _ in blk)
    assert all(p2 != 99.0 for _, (_p1, p2) in blk)
    if sep == ",":
        # the per-event loop over the VALID rows alone
        want, pend = [], None
        for cols, ts, valid in batches:
            for p, v in zip(cols[1][valid].tolist(), cols[2][valid].tolist()):
                if pend is not None and v == 2 and p >= pend:
                    want.append((pend, p))
                pend = p if v == 1 else None
        assert [d for _, d in blk] == want
