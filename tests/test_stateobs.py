"""State observatory (observability/stateobs.py): hotness-sketch and
accumulator arithmetic, the never-fetch guarantee (zero device touches
added over the PR 13 baseline), sizing-ledger persistence across
snapshot/restore for pattern + join + serve shapes, healthz
near-capacity verdicts, the STATE003 lint rule, and the REST surface."""
import json
import urllib.error
import urllib.request

import jax
import numpy as np
import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.observability import stateobs as so_mod
from siddhi_tpu.observability.stateobs import (
    STRUCTURES,
    KeyHotness,
    StateObservatory,
)
from siddhi_tpu.utils.config import InMemoryConfigManager

WINDOW_QL = """
@app:name('SoApp')
@app:statistics('BASIC')
define stream S (sym long, price float, vol int);
@info(name='q')
from S#window.length(8)
select sym, sum(price) as total
group by sym
insert into Out;
"""

PATTERN_QL = """
@app:name('SoPat')
@app:playback
define stream T (key long, price float, volume int);
partition with (key of T)
begin
  @capacity(keys='16', slots='4') @info(name='q')
  from every e1=T[volume == 1] -> e2=T[volume == 2]
  select e1.key as k, e2.price as p insert into M;
end;
"""

JOIN_QL = """
@app:name('SoJoin')
@app:playback
define stream L (symbol long, price float);
define stream R (symbol long, qty int);
@emit(rows='65536') @info(name='q')
from L#window.length(16) join R#window.length(16)
  on L.symbol == R.symbol
select L.symbol as s, L.price as p, R.qty as v insert into Out;
"""

SERVE_QL = """
@app:name('SoServe')
@app:statistics('BASIC')
define stream S (k long, v float);
@serve
@info(name='q') from S[v > 0.0] select k, v insert into Out;
"""


def _send(rt, n=4, B=64, keys=5, stream="S"):
    h = rt.get_input_handler(stream)
    for i in range(n):
        h.send_columns([np.arange(B, dtype=np.int64) % keys,
                        np.full(B, 2.0, np.float32),
                        np.arange(B, dtype=np.int32)],
                       timestamps=np.full(B, 1000 + i, np.int64))
    rt.flush()


# -- KeyHotness: sketch arithmetic -------------------------------------------

def test_key_hotness_exact_small_and_one_sided_cms():
    h = KeyHotness(capacity=64)
    h.update([0, 1, 2], [10, 5, 1])
    h.update([0, 3], [10, 2])
    assert h.total == 28
    assert h.distinct == 4
    # top-K is exact while under _TOPK keys
    assert h.top(2) == [(0, 20), (1, 5)]
    # CMS never underestimates the true count
    for k, true in ((0, 20), (1, 5), (2, 1), (3, 2)):
        assert h.estimate(k) >= true
    # negative slots (padding) and zero counts are filtered out
    h.update([-1, 4], [7, 0])
    assert h.total == 28 and h.distinct == 4


def test_key_hotness_hot_share_separates_zipf_from_uniform():
    rng = np.random.default_rng(7)
    zipf, uni = KeyHotness(1024), KeyHotness(1024)
    for _ in range(32):
        zk = np.minimum(rng.zipf(1.3, 512) - 1, 1023)
        k, c = np.unique(zk, return_counts=True)
        zipf.update(k, c)
        k, c = np.unique(rng.integers(0, 1024, 512), return_counts=True)
        uni.update(k, c)
    # the hottest 1% of a Zipf trace carries a large share; a uniform
    # trace's hottest 1% carries roughly 1%
    assert zipf.hot_share(0.01) > 0.25
    assert uni.hot_share(0.01) < 0.08
    snap = zipf.snapshot()
    assert snap["total"] == 32 * 512
    assert snap["hot_share_1pct"] == pytest.approx(
        zipf.hot_share(0.01), abs=1e-4)
    assert len(snap["top"]) == 8


def test_key_hotness_space_saving_overestimates_in_place():
    h = KeyHotness(capacity=4096)
    # fill the tracked set, then push an untracked key: it must take
    # over the minimum count (overestimate, never a silent drop)
    h.update(np.arange(64), np.full(64, 3))
    h.update([4000], [1])
    tracked = dict(h.top(64))
    # tracked (never silently dropped), and the reported count is the
    # min of the space-saving floor takeover (3+1) and the CMS estimate
    assert 4000 in tracked and 1 <= tracked[4000] <= 4


# -- KeyHotness: the batch merge against a per-key reference ------------------

_SPACE = 1 << 20
# batches a case: enough that the table fills, takes over and is hit again
_BATCHES = {1: 48, 2: 48, 63: 12, 64: 12, 65: 12, 4096: 6, 131072: 2}


def _stream(kind, width, batches, rng):
    """`batches` batches of exactly `width` distinct keys with their row
    counts, as staging hands them over."""
    for i in range(batches):
        if kind == "sweep":
            keys = (np.arange(width, dtype=np.int64) + i * width) % _SPACE
            counts = np.full(width, 4, np.int64)
        elif kind == "uniform":
            keys = rng.choice(_SPACE, width, replace=False).astype(np.int64)
            counts = rng.integers(1, 5, width)
        else:   # zipf(1.3) events; the hot ranks come back in every batch
            keys, counts = np.unique(
                np.minimum(rng.zipf(1.3, 4 * width) - 1, _SPACE - 1),
                return_counts=True)
            keys, counts = keys[:width], counts[:width]
            spare = np.setdiff1d(rng.choice(_SPACE, 2 * width), keys)
            spare = spare[:width - keys.size]
            keys = np.concatenate([keys, spare]).astype(np.int64)
            counts = np.concatenate([counts, np.ones(spare.size, np.int64)])
            order = rng.permutation(width)      # first-touch order, unsorted
            keys, counts = keys[order], counts[order]
        assert keys.size == width == np.unique(keys).size
        yield keys, counts


@pytest.mark.parametrize("kind", ["uniform", "zipf", "sweep"])
@pytest.mark.parametrize("width", sorted(_BATCHES))
def test_key_hotness_batch_merge_keeps_invariants_and_exact_sums(kind, width):
    h = KeyHotness(_SPACE)
    true = {}                                   # key -> rows really fed
    rows = [[0] * so_mod._CMS_WIDTH for _ in range(so_mod._CMS_DEPTH)]
    rng = np.random.default_rng(width * 3 + len(kind))
    for keys, counts in _stream(kind, width, _BATCHES[width], rng):
        h.update(keys, counts)
        for k, c in zip(keys.tolist(), counts.tolist()):
            true[k] = true.get(k, 0) + c
            for d, mult in enumerate(so_mod._CMS_MULT):
                rows[d][(k + 1) * mult % 2 ** 31 % so_mod._CMS_WIDTH] += c
        total, ss = sum(true.values()), h._ss
        # exact counters stay exact, bit for bit
        assert h.total == total
        assert h.distinct == len(true)
        assert np.array_equal(h._cms, np.array(rows, np.int64))
        assert len(ss) == min(len(true), so_mod._TOPK)
        # (a) a tracked count never underestimates
        assert all(c >= true[k] for k, c in ss.items())
        # (b) nothing untracked is above the table's minimum
        if len(true) > so_mod._TOPK:
            floor = min(ss.values())
            assert max(c for k, c in true.items() if k not in ss) <= floor
            assert all(k in ss for k, c in true.items()
                       if c > total / so_mod._TOPK)
        # (c) the table never holds more than was fed
        assert sum(ss.values()) <= total
        # (d) exact while every key fits
        if len(true) <= so_mod._TOPK:
            assert ss == true
        assert all(h.estimate(k) >= true[k] for k in list(true)[:32])


class _CountingDict(dict):
    """A space-saving table that counts how often it is touched."""

    def __init__(self, *a):
        super().__init__(*a)
        self.steps = self.drops = self.reads = 0

    def __setitem__(self, k, v):
        self.steps += 1
        super().__setitem__(k, v)

    def __delitem__(self, k):
        self.drops += 1
        super().__delitem__(k)

    def pop(self, *a):
        self.drops += 1
        return super().pop(*a)

    def __getitem__(self, k):
        self.reads += 1
        return super().__getitem__(k)

    def get(self, *a):
        self.reads += 1
        return super().get(*a)

    def __contains__(self, k):
        self.reads += 1
        return super().__contains__(k)


def test_key_hotness_wide_batch_touches_table_a_bounded_number_of_times():
    """The cost guard, without a clock: 131,072 keys a batch, the table
    full and every tracked key in the batch.  Every per-key step ends in
    one write of the table, and there are at most two per table entry
    (one add in place, one take-over) — never one per key of the batch."""
    width, topk = 131072, so_mod._TOPK
    h = KeyHotness(_SPACE)
    keys = np.arange(width, dtype=np.int64)
    h.update(keys, np.full(width, 4, np.int64))
    assert len(h._ss) == topk
    h._ss = table = _CountingDict(h._ss)
    h.update(keys[::-1], np.arange(1, width + 1, dtype=np.int64))
    assert topk <= table.steps <= 2 * topk
    assert table.drops <= topk and table.reads <= 4 * topk
    assert h.total == 4 * width + width * (width + 1) // 2
    assert len(table) == topk
    assert all(c >= 4 + width - k for k, c in table.items())


def test_wide_pattern_sends_feed_exact_totals_and_span_carries_keys(
        manager, tmp_path):
    """Runtime level: sends wider than the space-saving table go through
    the batch merge; the report's exact counters equal what was fed, and
    each send's `siddhi:obs_feed` span says how many keys it fed."""
    width = 3 * so_mod._TOPK
    rt = manager.create_siddhi_app_runtime(
        PATTERN_QL.replace("keys='16'", "keys='1024'"))
    rt.start()
    h = rt.get_input_handler("T")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for i in range(3):      # windows of keys sliding by half a send
            keys = np.repeat(np.arange(width, dtype=np.int64)
                             + i * width // 2, 2)
            h.send_columns([keys, np.full(2 * width, 1.0 + i, np.float32),
                            np.tile(np.array([1, 2], np.int32), width)],
                           timestamps=np.full(2 * width, 1000 + i, np.int64))
        rt.flush()
    finally:
        jax.profiler.stop_trace()
    hot = rt.state_report()["hotness"]["q"]
    assert hot["total"] == 3 * 2 * width
    assert hot["distinct"] == 2 * width
    (path,) = (tmp_path / "plugins" / "profile").glob("*/*.xplane.pb")
    fed = [dict(e.stats).get("keys")
           for plane in jax.profiler.ProfileData.from_file(str(path)).planes
           for line in plane.lines for e in line.events
           if e.name == "siddhi:obs_feed"]
    # the emission-cap demand is an `obs_feed` span too, and feeds no keys
    assert [k for k in fed if k is not None] == [width] * 3


# -- StateObservatory: accumulator arithmetic --------------------------------

def test_observe_tracks_high_water_and_capacity_refresh():
    obs = StateObservatory()
    obs.observe("q", "pattern_keys", 5, 16, growable=False,
                config_key="@capacity(keys='N')")
    obs.observe("q", "pattern_keys", 3, 16, growable=False)
    rec = obs.snapshot()["structures"]["q"]["pattern_keys"]
    assert rec["occupancy"] == 3 and rec["high_water"] == 5
    assert rec["utilization"] == pytest.approx(3 / 16)
    assert rec["config_key"] == "@capacity(keys='N')"
    # occupancy=None refreshes capacity/metadata only — HWM survives
    obs.observe("q", "pattern_keys", None, 32, growable=False)
    rec = obs.snapshot()["structures"]["q"]["pattern_keys"]
    assert rec["capacity"] == 32 and rec["high_water"] == 5


def test_snapshot_lists_structures_in_canonical_order():
    obs = StateObservatory()
    obs.observe("q", "serve_ring", 1, 8)
    obs.observe("q", "window_keys", 1, 8)
    obs.observe("q", "join_lane", 1, 8)
    got = list(obs.snapshot()["structures"]["q"])
    assert got == [s for s in STRUCTURES if s in got]


def test_ledger_adopt_max_merges_high_water():
    obs = StateObservatory()
    obs.observe("q", "pattern_keys", 9, 16)
    obs.adopt_ledger({"q": {"pattern_keys": {"high_water": 30,
                                             "capacity": 16},
                            "serve_ring": {"high_water": 4,
                                           "capacity": 8}},
                      "q2": {"join_keys": {"high_water": 2,
                                           "capacity": 64}}})
    led = obs.ledger()
    assert led["q"]["pattern_keys"]["high_water"] == 30   # restored wins
    assert led["q"]["serve_ring"]["high_water"] == 4      # adopted fresh
    assert led["q2"]["join_keys"] == {"high_water": 2, "capacity": 64}
    # live traffic beats the adopted mark again
    obs.observe("q", "pattern_keys", 40, 16)
    assert obs.ledger()["q"]["pattern_keys"]["high_water"] == 40
    # a malformed blob is ignored, never raises
    obs.adopt_ledger({"q": {"pattern_keys": {"high_water": "junk"}}})
    obs.adopt_ledger("not-a-dict")
    assert obs.ledger()["q"]["pattern_keys"]["high_water"] == 40


def test_config_memoized_from_manager(manager):
    manager.set_config_manager(InMemoryConfigManager(
        {"state.obs.enabled": "false", "state.obs.sample.every": "3",
         "state.obs.near.capacity": "0.5"}))
    rt = manager.create_siddhi_app_runtime(WINDOW_QL)
    assert so_mod.obs_enabled(rt) is False
    assert so_mod.obs_sample_every(rt) == 3
    assert so_mod.near_capacity_threshold(rt) == 0.5
    # memoized: a config swap mid-flight doesn't change the hot path
    manager.set_config_manager(InMemoryConfigManager({}))
    assert so_mod.obs_enabled(rt) is False


# -- the never-fetch guarantee ------------------------------------------------

def _count_syncs(monkeypatch, ql, config=None, n=4):
    """Run n sends and count jax.device_get / block_until_ready calls
    (warm-up send + compiles land outside the counted window)."""
    m = SiddhiManager()
    if config:
        m.set_config_manager(InMemoryConfigManager(config))
    gets, blocks = [0], [0]
    real_get, real_block = jax.device_get, jax.block_until_ready

    def g(*a, **k):
        gets[0] += 1
        return real_get(*a, **k)

    def b(*a, **k):
        blocks[0] += 1
        return real_block(*a, **k)

    try:
        rt = m.create_siddhi_app_runtime(ql)
        rt.add_callback("Out", lambda ev: None)
        rt.start()
        _send(rt, n=1)
        monkeypatch.setattr(jax, "device_get", g)
        monkeypatch.setattr(jax, "block_until_ready", b)
        _send(rt, n=n)
        monkeypatch.setattr(jax, "device_get", real_get)
        monkeypatch.setattr(jax, "block_until_ready", real_block)
    finally:
        m.shutdown()
    return gets[0], blocks[0]


def test_observatory_adds_no_sync_over_baseline(monkeypatch):
    """The PR 13 baseline arm is state.obs.enabled=false; the always-on
    observatory — hotness feeds, allocator mirrors, AND the sampled
    window-fill probe on every dispatch — must take exactly the same
    number of fetches/blocks (the probe scalar rides delivery's
    existing device_get tuple)."""
    g_off, b_off = _count_syncs(
        monkeypatch, WINDOW_QL, config={"state.obs.enabled": "false"})
    g_on, b_on = _count_syncs(
        monkeypatch, WINDOW_QL, config={"state.obs.sample.every": "1"})
    assert g_on == g_off
    assert b_on == b_off


def test_state_surfaces_never_touch_device(manager, monkeypatch):
    from siddhi_tpu.observability import render_prometheus
    from siddhi_tpu.observability.explain import explain_query
    from siddhi_tpu.observability.health import app_health
    rt = manager.create_siddhi_app_runtime(WINDOW_QL)
    rt.add_callback("Out", lambda ev: None)
    rt.start()
    _send(rt)

    def bomb(*a, **k):
        raise AssertionError("state surface touched the device")

    monkeypatch.setattr(jax, "device_get", bomb)
    monkeypatch.setattr(jax, "block_until_ready", bomb)
    rep = rt.state_report()
    text = render_prometheus(manager.runtimes)
    hz = app_health(rt)
    exp = explain_query(rt, "q", deep=False)["utilization"]
    assert rep["structures"]["q"]["group_slots"]["high_water"] >= 5
    assert rep["hotness"]["q"]["total"] >= 256
    assert "siddhi_state_occupancy" in text
    assert "siddhi_state_high_water" in text
    assert "siddhi_key_hotset_share" in text
    assert hz["state"]["structures_tracked"] >= 1
    assert exp["available"] and "group_slots" in exp["structures"]


# -- sizing-ledger persistence across restore (acceptance criterion) ---------

def _roundtrip_hints(manager, ql, drive, structures):
    """Drive traffic, snapshot, restore onto a fresh runtime of the
    same app, and assert the sizing-hints ledger carries each named
    structure's high-water through the restart unchanged."""
    rt = manager.create_siddhi_app_runtime(ql)
    rt.start()
    drive(rt)
    before = rt.state_report()["sizing_hints"]["q"]
    blob = rt.snapshot()
    rt2 = manager.create_siddhi_app_runtime(ql)
    rt2.start()
    rt2.restore(blob)
    after = rt2.state_report()["sizing_hints"]["q"]
    for s in structures:
        assert before[s]["high_water"] > 0, s
        assert after[s]["high_water"] == before[s]["high_water"], s
    return before


def test_sizing_hints_survive_restore_pattern_shape(manager):
    def drive(rt):
        h = rt.get_input_handler("T")
        for k in range(6):
            h.send([[k, 1.0 + k, 1]], timestamp=1000 + k)
        h.send([[2, 9.0, 2]], timestamp=2000)
        rt.flush()

    before = _roundtrip_hints(manager, PATTERN_QL, drive,
                              ["pattern_keys"])
    assert before["pattern_keys"]["capacity"] == 16
    assert before["pattern_keys"]["high_water"] >= 6


def test_sizing_hints_survive_restore_join_shape(manager):
    rng = np.random.default_rng(13)

    def drive(rt):
        for i in range(4):
            rt.get_input_handler("L").send_columns(
                [rng.integers(0, 8, 32).astype(np.int64),
                 rng.random(32, np.float32)],
                timestamps=np.full(32, 1000 + i, np.int64))
            rt.get_input_handler("R").send_columns(
                [rng.integers(0, 8, 32).astype(np.int64),
                 rng.integers(1, 9, 32).astype(np.int32)],
                timestamps=np.full(32, 1000 + i, np.int64))
        rt.flush()

    rt = manager.create_siddhi_app_runtime(JOIN_QL)
    if rt.query_runtimes["q"].planned.fastpath != "bucket":
        pytest.skip("join fast path disabled — no host lane mirror")
    rt.start()
    drive(rt)
    before = rt.state_report()["sizing_hints"]["q"]
    assert before["join_lane"]["high_water"] >= 1
    blob = rt.snapshot()
    rt2 = manager.create_siddhi_app_runtime(JOIN_QL)
    rt2.start()
    rt2.restore(blob)
    after = rt2.state_report()["sizing_hints"]["q"]
    for s in ("join_keys", "join_lane"):
        assert after[s]["high_water"] == before[s]["high_water"], s


def test_sizing_hints_survive_restore_serve_shape(manager):
    def drive(rt):
        h = rt.get_input_handler("S")
        for i in range(6):
            h.send_columns([np.arange(16, dtype=np.int64),
                            np.full(16, 2.0, np.float32)],
                           timestamps=np.full(16, 1000 + i, np.int64))
        rt.flush()

    rt = manager.create_siddhi_app_runtime(SERVE_QL)
    rt.add_callback("q", lambda ts, cur, exp: None)
    rt.start()
    drive(rt)
    before = rt.state_report()["sizing_hints"]["q"]
    assert before["serve_ring"]["high_water"] >= 1
    blob = rt.snapshot()
    rt2 = manager.create_siddhi_app_runtime(SERVE_QL)
    rt2.add_callback("q", lambda ts, cur, exp: None)
    rt2.start()
    rt2.restore(blob)
    after = rt2.state_report()["sizing_hints"]["q"]
    assert after["serve_ring"]["high_water"] >= \
        before["serve_ring"]["high_water"]


# -- healthz near-capacity verdict -------------------------------------------

def test_healthz_near_capacity_flips_degraded(manager):
    from siddhi_tpu.observability.health import app_health
    rt = manager.create_siddhi_app_runtime(PATTERN_QL)
    rt.start()
    h = rt.get_input_handler("T")
    h.send([[0, 1.0, 1]], timestamp=1000)
    rt.flush()
    rep = app_health(rt)
    assert rep["degraded"] is False
    assert rep["state"]["near_capacity"] == []
    # 15 of 16 pattern key slots bound -> >= 90% of a non-growable cap
    for k in range(1, 15):
        h.send([[k, 1.0, 1]], timestamp=1000 + k)
    rt.flush()
    rep = app_health(rt)
    near = rep["state"]["near_capacity"]
    assert rep["degraded"] is True
    assert any(r["structure"] == "pattern_keys" and
               r["occupancy"] >= 15 and r["capacity"] == 16
               for r in near)


def test_full_steady_state_window_is_not_near_capacity(manager):
    """A sliding length window runs 100% full by design — window_fill
    never flips degraded or appears in near-capacity verdicts."""
    manager.set_config_manager(InMemoryConfigManager(
        {"state.obs.sample.every": "1"}))
    rt = manager.create_siddhi_app_runtime(WINDOW_QL)
    rt.add_callback("Out", lambda ev: None)
    rt.start()
    _send(rt, n=4)
    from siddhi_tpu.observability.health import app_health
    rep = rt.state_report()
    wf = rep["structures"]["q"].get("window_fill")
    assert wf is not None and wf["utilization"] >= 0.9
    assert not any(r["structure"] == "window_fill"
                   for r in rep["near_capacity"])
    assert app_health(rt)["degraded"] is False


# -- STATE003 lint rule -------------------------------------------------------

def test_state003_flags_oversized_capacity(manager):
    rt = manager.create_siddhi_app_runtime(WINDOW_QL)
    rt.add_callback("Out", lambda ev: None)
    rt.start()
    _send(rt, n=4, keys=12)     # hwm 12 against the 4096 group arena
    finds = [f for f in rt.analyze()["findings"]
             if f["rule"] == "STATE003"]
    assert finds, "oversized group arena not flagged"
    assert "group_slots" in finds[0]["message"]
    assert "@capacity(groups=" in finds[0]["hint"]


def test_state003_silent_without_runtime_or_traffic(manager):
    from siddhi_tpu.analysis import analyze, report
    # static analysis (no runtime): utilization is measured, not guessed
    static = report(analyze(WINDOW_QL))
    assert not [f for f in static["findings"] if f["rule"] == "STATE003"]
    # live app, no traffic: hwm 0 never trips the 4x test
    rt = manager.create_siddhi_app_runtime(WINDOW_QL)
    rt.start()
    assert not [f for f in rt.analyze()["findings"]
                if f["rule"] == "STATE003"]


# -- REST surface -------------------------------------------------------------

def test_state_endpoint():
    from siddhi_tpu.service import SiddhiRestService
    svc = SiddhiRestService()
    svc.start()
    try:
        base = f"http://127.0.0.1:{svc.port}"
        req = urllib.request.Request(
            f"{base}/siddhi-apps", data=WINDOW_QL.encode(),
            method="POST")
        assert urllib.request.urlopen(req).status == 201
        rt = svc.manager.runtimes["SoApp"]
        rt.add_callback("Out", lambda ev: None)
        _send(rt)
        rep = json.loads(urllib.request.urlopen(
            f"{base}/siddhi-apps/SoApp/state").read())
        assert rep["app"] == "SoApp" and rep["enabled"]
        assert rep["structures"]["q"]["group_slots"]["high_water"] >= 5
        assert rep["hotness"]["q"]["hot_share_1pct"] > 0
        assert "sizing_hints" in rep
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(f"{base}/siddhi-apps/nope/state")
        assert e.value.code == 404
    finally:
        svc.stop()
