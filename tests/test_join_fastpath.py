"""Equi-join fast path (ROADMAP item 2): grid-vs-bucketed byte parity
across the join corpora, lane growth, key-slot recycling, snapshot /
mesh-resize restore, the stream-table index probe, and the ON-clause
table-op index wiring (the former `probe_eq` dead half).

The heaviest corpus runs (time-window expiry, group-by aggregation,
sharded@4, mesh-resize restore) carry @pytest.mark.slow: they compile
large grid-twin programs and would eat the tier-1 wall-clock budget;
CI's `make test` and `make join-smoke` still run the full set."""
import numpy as np
import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.core import join as joinmod


@pytest.fixture
def manager():
    m = SiddhiManager()
    yield m
    m.shutdown()


def _drive(ql, sends, name="q", mesh=None, snapshot_at=None,
           restore_onto=None):
    """Run `ql`, deliver `sends`, return the ordered emissions.  With
    snapshot_at=i, snapshots after the i-th send pair and restores onto
    a fresh runtime (mesh `restore_onto`) for the remainder."""
    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime(ql, mesh=mesh) if mesh \
            else m.create_siddhi_app_runtime(ql)
        out = []

        def attach(r):
            r.add_callback(name, lambda ts, cur, exp: out.append(
                ([tuple(e.data) for e in (cur or [])],
                 [tuple(e.data) for e in (exp or [])])))
            r.start()

        attach(rt)
        split = None
        for i, batch in enumerate(sends):
            if snapshot_at is not None and i == snapshot_at:
                rt.flush()
                blob = rt.snapshot()
                split = len(out)
                rt2 = m.create_siddhi_app_runtime(
                    ql, mesh=restore_onto) if restore_onto \
                    else m.create_siddhi_app_runtime(ql)
                attach(rt2)
                rt2.restore(blob)
                rt = rt2
            for stream, cols, ts in batch:
                rt.get_input_handler(stream).send_columns(
                    cols, timestamps=np.full(len(cols[0]), ts, np.int64))
        rt.flush()
        mode = rt.query_runtimes[name].planned.fastpath
        qr = rt.query_runtimes[name]
        if snapshot_at is not None:
            return out, mode, qr, split
        return out, mode, qr
    finally:
        m.shutdown()


def _sends(n=4, B=32, keys=16, seed=13, step=700):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        out.append([
            ("L", [rng.integers(0, keys, B).astype(np.int64),
                   rng.random(B, np.float32)], 1000 + i * step),
            ("R", [rng.integers(0, keys, B).astype(np.int64),
                   rng.integers(1, 9, B).astype(np.int32)],
             1000 + i * step),
        ])
    return out


_STREAM_QL = """
@app:playback
define stream L (symbol long, price float);
define stream R (symbol long, qty int);
@emit(rows='65536') {ann} @info(name='q')
from L#window.{wl} {jt} R#window.{wr}
  on {on}
select {sel} insert into Out;
"""


def _parity(ql, sends, mesh=None, expect="bucket"):
    joinmod.FASTPATH_ENABLED = True
    a, mode, _ = _drive(ql, sends, mesh=mesh)
    assert mode == expect, f"expected {expect}, got {mode}"
    joinmod.FASTPATH_ENABLED = False
    try:
        b, mode_b, _ = _drive(ql, sends)
        assert mode_b is None
    finally:
        joinmod.FASTPATH_ENABLED = True
    assert a == b, "fast-path emissions diverge from the grid path"
    assert any(c or e for c, e in a), "corpus produced no rows"
    return a


# ---------------------------------------------------------------------------
# grid-vs-bucketed parity across the join corpora
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("jt,sel", [
    ("join", "L.symbol as s, L.price as p, R.qty as v"),
    ("full outer join", "L.symbol as s, L.price as p, R.qty as v"),
])
def test_parity_join_types(jt, sel):
    ql = _STREAM_QL.format(ann="", wl="length(32)", wr="length(32)",
                           jt=jt, on="L.symbol == R.symbol", sel=sel)
    _parity(ql, _sends())


@pytest.mark.slow
@pytest.mark.parametrize("jt", ["left outer join", "right outer join"])
def test_parity_one_sided_outer(jt):
    # full outer (tier-1) exercises both unmatched directions; the
    # one-sided variants ride the slow lane for CI's full run
    ql = _STREAM_QL.format(ann="", wl="length(32)", wr="length(32)",
                           jt=jt, on="L.symbol == R.symbol",
                           sel="L.symbol as s, R.qty as v")
    _parity(ql, _sends())


def test_parity_residual_conjunct():
    ql = _STREAM_QL.format(
        ann="", wl="length(32)", wr="length(32)", jt="left outer join",
        on="L.symbol == R.symbol and L.price > 0.5",
        sel="L.symbol as s, R.qty as v")
    _parity(ql, _sends())


@pytest.mark.slow
def test_parity_time_window_expiry_ordering():
    # time windows expire between sends (step > window span): EXPIRED
    # trigger rows must probe with the slots they were bucketed under.
    # Tight @emit keeps the GRID twin's sort/compaction compile small —
    # this is a parity test, not a capacity test.
    ql = _STREAM_QL.format(ann="", wl="time(2 sec)", wr="time(2 sec)",
                           jt="join", on="L.symbol == R.symbol",
                           sel="L.symbol as s, R.qty as v"
                           ).replace("rows='65536'", "rows='16384'")
    _parity(ql, _sends(n=5, B=24, step=1100))


@pytest.mark.slow
def test_parity_group_by_aggregation():
    ql = _STREAM_QL.format(
        ann="", wl="length(32)", wr="length(32)", jt="join",
        on="L.symbol == R.symbol",
        sel="L.symbol as s, sum(R.qty) as tq group by L.symbol")
    _parity(ql, _sends())


def test_parity_self_join_shared_staged():
    """A self-join hands the SAME staged batch to both sides through
    the junction: the probe cache must key per (runtime, side) or the
    retention mirror would double-count."""
    ql = """
    @app:playback
    define stream P (sym long, price float);
    @emit(rows='65536') @info(name='q')
    from P#window.length(16) as e1 join P#window.length(16) as e2
      on e1.sym == e2.sym
    select e1.sym as s, e1.price as a, e2.price as b insert into Out;
    """
    rng = np.random.default_rng(17)
    sends = [[("P", [rng.integers(0, 6, 24).astype(np.int64),
                     rng.random(24, np.float32)], 1000 + i)]
             for i in range(5)]
    _parity(ql, sends)


def test_parity_fuse_composition():
    ql = _STREAM_QL.format(ann="@fuse(batches='3')", wl="length(32)",
                           wr="length(32)", jt="join",
                           on="L.symbol == R.symbol",
                           sel="L.symbol as s, R.qty as v")
    _parity(ql, _sends())


@pytest.mark.slow
def test_parity_sharded_4way():
    import jax
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()[:4]), ("shard",))
    ql = _STREAM_QL.format(ann="", wl="length(32)", wr="length(32)",
                           jt="join", on="L.symbol == R.symbol",
                           sel="L.symbol as s, R.qty as v")
    _parity(ql, _sends(), mesh=mesh)


@pytest.mark.slow
def test_snapshot_restore_mesh_resize():
    """1-device snapshot mid-stream restores onto a 4-shard mesh and
    continues byte-identically (retention mirror + key allocator are
    rebuilt from the snapshot)."""
    import jax
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()[:4]), ("shard",))
    ql = _STREAM_QL.format(ann="", wl="length(32)", wr="length(32)",
                           jt="join", on="L.symbol == R.symbol",
                           sel="L.symbol as s, R.qty as v")
    sends = _sends(n=6)
    resumed, mode, _, split = _drive(ql, sends, snapshot_at=3,
                                     restore_onto=mesh)
    assert mode == "bucket"
    uninterrupted, _, _ = _drive(ql, sends)
    # prefix before the snapshot ran on the first runtime; the
    # post-restore emissions must match the uninterrupted run's tail
    tail = resumed[split:]
    assert tail and tail == uninterrupted[-len(tail):]


# ---------------------------------------------------------------------------
# growth + recycling
# ---------------------------------------------------------------------------

def test_lane_growth_under_skew():
    """One hot key fills the window: lanes must grow to the full
    occupancy BEFORE any dispatch could drop candidates."""
    ql = _STREAM_QL.format(ann="", wl="length(32)", wr="length(32)",
                           jt="join", on="L.symbol == R.symbol",
                           sel="L.symbol as s, R.qty as v")
    sends = _sends(keys=1)      # every row the same key
    a, mode, qr = _drive(ql, sends)
    assert mode == "bucket"
    assert qr.planned.lane_k >= 32      # window fully one bucket
    joinmod.FASTPATH_ENABLED = False
    try:
        b, _, _ = _drive(ql, sends)
    finally:
        joinmod.FASTPATH_ENABLED = True
    assert a == b


def test_key_slots_recycle_under_rotation():
    """Rotating key space far larger than the allocator: slots must
    recycle as both windows forget a key (no CapacityExceededError),
    and outputs stay correct."""
    ql = _STREAM_QL.format(ann="", wl="length(16)", wr="length(16)",
                           jt="join", on="L.symbol == R.symbol",
                           sel="L.symbol as s, R.qty as v")
    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime(ql)
        seen = []
        rt.add_callback("q", lambda ts, cur, exp: seen.extend(
            tuple(e.data) for e in (cur or [])))
        rt.start()
        qr = rt.query_runtimes["q"]
        cap = qr.planned.join_key_allocator.capacity
        B = 64
        rounds = (3 * cap) // B + 2     # >3x the allocator capacity
        for i in range(rounds):
            base = i * B                # every round: fresh keys
            ts = np.full(B, 1000 + i, np.int64)
            rt.get_input_handler("L").send_columns(
                [np.arange(base, base + B, dtype=np.int64),
                 np.ones(B, np.float32)], timestamps=ts)
            rt.get_input_handler("R").send_columns(
                [np.arange(base, base + B, dtype=np.int64),
                 np.full(B, 7, np.int32)], timestamps=ts)
        rt.flush()
        assert len(qr.planned.join_key_allocator) <= cap
        assert seen, "rotation produced no matches"
        # every match must pair identical keys
        assert all(row[0] >= 0 for row in seen)
    finally:
        m.shutdown()


def test_cross_dtype_key_parity():
    """INT-vs-LONG keys hash through the promoted dtype — values equal
    under the compiled `==` must land in one bucket."""
    ql = """
    @app:playback
    define stream L (symbol int, price float);
    define stream R (symbol long, qty int);
    @emit(rows='65536') @info(name='q')
    from L#window.length(16) join R#window.length(16)
      on L.symbol == R.symbol
    select L.symbol as s, R.qty as v insert into Out;
    """
    sends = _sends(B=32, keys=6)
    # recast left column to int32 staging
    for batch in sends:
        stream, cols, ts = batch[0]
        batch[0] = (stream, [cols[0].astype(np.int32), cols[1]], ts)
    _parity(ql, sends)


# ---------------------------------------------------------------------------
# stream-table fast path + ON-clause table-op index wiring
# ---------------------------------------------------------------------------

_TABLE_QL = """
@app:playback
define stream S (sym long, price float);
{ann}
define table T (sym long, name long);
define stream Feed (sym long, name long);
@info(name='load') from Feed select sym, name insert into T;
@emit(rows='65536') @info(name='q')
from S {jt} T on S.sym == T.sym{residual}
select S.sym as s, T.name as n insert into Out;
"""


def _drive_table(ql, fast, n=4):
    joinmod.FASTPATH_ENABLED = fast
    try:
        m = SiddhiManager()
        rt = m.create_siddhi_app_runtime(ql)
        out = []
        rt.add_callback("q", lambda ts, cur, exp: out.append(
            [tuple(e.data) for e in (cur or [])]))
        rt.start()
        rng = np.random.default_rng(31)
        for i in range(n):
            rt.get_input_handler("Feed").send_columns(
                [rng.integers(0, 48, 24).astype(np.int64),
                 rng.integers(0, 100, 24).astype(np.int64)],
                timestamps=np.full(24, 1000 + i, np.int64))
            rt.get_input_handler("S").send_columns(
                [rng.integers(0, 64, 96).astype(np.int64),
                 rng.random(96, np.float32)],
                timestamps=np.full(96, 1000 + i, np.int64))
        rt.flush()
        mode = rt.query_runtimes["q"].planned.fastpath
        m.shutdown()
        return out, mode
    finally:
        joinmod.FASTPATH_ENABLED = True


@pytest.mark.parametrize("ann,jt,residual,expect", [
    ("@PrimaryKey('sym')", "join", "", "table"),
    ("@Index('sym')", "join", " and S.price > 0.3", "table"),
    ("@PrimaryKey('sym')", "left outer join", "", "table"),
    ("", "join", "", None),     # unindexed table -> grid, with reason
])
def test_table_join_index_vs_scan_parity(ann, jt, residual, expect):
    ql = _TABLE_QL.format(ann=ann, jt=jt, residual=residual)
    a, mode = _drive_table(ql, True)
    assert mode == expect
    b, mode_b = _drive_table(ql, False)
    assert mode_b is None
    assert a == b


def test_table_on_clause_ops_consult_index(manager):
    """update/delete with an ON-equality against an indexed column must
    probe the index (never the dense [B, C] broadcast), with identical
    final table contents."""
    ql = """
    @app:playback
    define stream U (sym long, val long);
    define stream D (sym long, val long);
    @PrimaryKey('sym') @Index('val')
    define table T (sym long, val long);
    define stream Feed (sym long, val long);
    @info(name='load') from Feed select sym, val insert into T;
    @info(name='upd') from U select sym, val update T on T.sym == sym;
    @info(name='del') from D delete T on T.val == val;
    """
    rt = manager.create_siddhi_app_runtime(ql)
    rt.start()
    t = rt.tables["T"]
    rt.get_input_handler("Feed").send_columns(
        [np.arange(32, dtype=np.int64),
         np.arange(32, dtype=np.int64) % 8], timestamps=np.full(32, 1000))
    base = dict(t.index_stats)
    rt.get_input_handler("U").send_columns(
        [np.asarray([3, 5], np.int64), np.asarray([100, 100], np.int64)],
        timestamps=np.full(2, 1001))
    rt.get_input_handler("D").send_columns(
        [np.asarray([0], np.int64), np.asarray([7], np.int64)],
        timestamps=np.full(1, 1002))
    rt.flush()
    assert t.index_stats["indexed"] > base["indexed"]
    assert t.index_stats["dense"] == base["dense"]
    rows = {e.data[0]: e.data[1] for e in t.snapshot_rows()}
    assert rows[3] == 100 and rows[5] == 100
    assert all(v != 7 for v in rows.values())     # val==7 rows deleted


def test_probe_rows_matches_linear_scan(manager):
    """Regression for the former dead half: the public probe must agree
    with a brute-force scan of the shadowed column, including after
    deletes and overwrites."""
    ql = """
    define stream S (sym long, v long);
    @PrimaryKey('sym') @Index('v')
    define table T (sym long, v long);
    @info(name='load') from S select sym, v insert into T;
    """
    rt = manager.create_siddhi_app_runtime(ql)
    rt.start()
    t = rt.tables["T"]
    rng = np.random.default_rng(3)
    syms = np.arange(64, dtype=np.int64)
    vals = rng.integers(0, 9, 64).astype(np.int64)
    rt.get_input_handler("S").send_columns([syms, vals],
                                           timestamps=np.full(64, 1000))
    # overwrite a few pkeys with new indexed values
    rt.get_input_handler("S").send_columns(
        [syms[:8], (vals[:8] + 1) % 9], timestamps=np.full(8, 1001))
    rt.flush()
    vpos = t.schema.position("v")
    shadow = {e.data[0]: e.data[1] for e in t.snapshot_rows()}
    for probe_v in range(9):
        cand, ok = t.probe_rows(vpos, np.asarray([probe_v], np.int64))
        got = set(int(r) for r in cand[0][ok[0]])
        cols = np.asarray(t.cols[0])
        expect = {i for i in range(t.capacity)
                  if bool(np.asarray(t.valid)[i]) and
                  int(np.asarray(t.cols[vpos])[i]) == probe_v}
        assert got == expect, (probe_v, got, expect)
    assert shadow  # table populated


def test_in_operator_still_scans_correctly(manager):
    """`contains_fn` (dead) was deleted; the `in` operator's device
    probe path must keep working."""
    ql = """
    define stream S (sym long, v int);
    define table T (sym long, v int);
    define stream Feed (sym long, v int);
    @info(name='load') from Feed select sym, v insert into T;
    @info(name='q') from S[sym in T] select sym, v insert into Out;
    """
    rt = manager.create_siddhi_app_runtime(ql)
    got = []
    rt.add_callback("q", lambda ts, cur, exp: got.extend(
        e.data[0] for e in (cur or [])))
    rt.start()
    rt.get_input_handler("Feed").send([[5, 1], [9, 2]], timestamp=1000)
    rt.get_input_handler("S").send(
        [[5, 10], [6, 11], [9, 12]], timestamp=1001)
    rt.flush()
    assert sorted(got) == [5, 9]


# ---------------------------------------------------------------------------
# plan facts / explain
# ---------------------------------------------------------------------------

def test_fastpath_facts_in_explain_and_audit(manager):
    from siddhi_tpu.analysis.audit import query_fingerprint
    from siddhi_tpu.analysis.corpus import WINDOWED_JOIN_QL
    rt = manager.create_siddhi_app_runtime(WINDOWED_JOIN_QL)
    rt.start()
    node = rt.explain("q")["plan"]["equi_fastpath"]
    assert node["active"] and node["mode"] == "bucket"
    assert node["key_attrs"] == [["symbol", "symbol"]]
    assert node["lane_k"] >= 8 and not node["residual_predicate"]
    fp = query_fingerprint(rt, "q")
    assert fp["equi_fastpath"]["active"]
    # `join_len128`'s text: a projection, so the pair rows exist at the
    # emission cap only; a grouped join keeps every candidate row
    assert rt.explain("q")["plan"]["pair_rows_materialised"] == "cap"
    assert rt.explain("q")["plan"]["expired_rows_joined"] is False
    grouped = manager.create_siddhi_app_runtime(WINDOWED_JOIN_QL.replace(
        "select L.symbol as s, L.price as p, R.qty as v",
        "select L.symbol as s, sum(R.qty) as v group by L.symbol"))
    grouped.start()
    plan = grouped.explain("q")["plan"]
    assert plan["pair_rows_materialised"] == "all"
    assert plan["expired_rows_joined"] is True
    assert plan["equi_fastpath"]["mode"] == "bucket"


def test_fastpath_reason_for_named_window_side(manager):
    ql = """
    define stream L (id long, p float);
    define window W (id long, q int) length(8);
    define stream Wfeed (id long, q int);
    @info(name='feed') from Wfeed select id, q insert into W;
    @info(name='q')
    from L#window.length(8) join W on L.id == W.id
    select L.id as i, W.q as q insert into Out;
    """
    rt = manager.create_siddhi_app_runtime(ql)
    p = rt.query_runtimes["q"].planned
    assert p.fastpath is None
    assert "named_window" in (p.fastpath_reason or "")
