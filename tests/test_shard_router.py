"""Sharded serving runtime units: the key-space router's layout
arithmetic, mesh-resize permutations, shard-labelled observability
(/metrics, /healthz, EXPLAIN), and the PART002 lint rule."""
import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from siddhi_tpu.core import keyslots as ks
from siddhi_tpu.sharding import (ShardRouter, needs_rebucket,
                                 rebucket_rows, shard_count)


@pytest.fixture()
def mesh():
    devs = np.array(jax.devices())
    if devs.size < 8:
        pytest.skip("needs 8 virtual devices")
    return Mesh(devs[:8], ("shard",))


# ---------------------------------------------------------------------------
# router arithmetic
# ---------------------------------------------------------------------------

def test_state_row_is_a_bijection():
    for n in (1, 2, 4, 8):
        r = ShardRouter(n, 64)
        slots = np.arange(64)
        rows = r.state_row(slots)
        assert sorted(rows.tolist()) == list(range(64))
        assert np.array_equal(r.slot_of_row(rows), slots)


def test_state_row_matches_shard_blocks():
    """Slot s lands in shard (s % n)'s contiguous row block — the block
    PartitionSpec('shard') physically places on that device."""
    r = ShardRouter(4, 32)
    slots = np.arange(32)
    rows = r.state_row(slots)
    for s, row in zip(slots, rows):
        d = s % 4
        assert d * 8 <= row < (d + 1) * 8
        assert r.shard_of(np.array([s]))[0] == d


def test_rebucket_index_roundtrip():
    """new[j] = old[src[j]] moves every slot's state to its new row,
    for every (n_old, n_new) pair, including to/from 1."""
    cap = 48
    base = np.arange(cap)        # state under identity (1-way) layout
    for n_old in (1, 2, 4, 8):
        for n_new in (1, 2, 4, 8):
            r_old, r_new = ShardRouter(n_old, cap), ShardRouter(n_new, cap)
            # state value of slot s is s; old layout stores it at
            # r_old.state_row(s)
            old_state = np.empty(cap, int)
            old_state[r_old.state_row(base)] = base
            src = r_new.rebucket_index(r_old)
            new_state = old_state[src]
            # after re-bucketing, slot s must sit at r_new.state_row(s)
            assert np.array_equal(new_state[r_new.state_row(base)], base)


def test_rebucket_rows_maps_dirty_indices():
    old = {"kind": "pattern", "n": 8, "capacity": 64}
    new = {"kind": "pattern", "n": 2, "capacity": 64}
    r8, r2 = ShardRouter(8, 64), ShardRouter(2, 64)
    slots = np.array([0, 5, 17, 63])
    rows8 = r8.state_row(slots)
    assert np.array_equal(rebucket_rows(rows8, old, new),
                          r2.state_row(slots))


def test_needs_rebucket_discrimination():
    a = {"kind": "pattern", "n": 8, "capacity": 64}
    assert not needs_rebucket(a, a)
    assert not needs_rebucket(None, a)
    assert not needs_rebucket(a, None)
    assert needs_rebucket(a, {"kind": "pattern", "n": 4, "capacity": 64})
    # capacity or kind mismatch: restore verbatim (fails later exactly
    # as pre-layout snapshots did)
    assert not needs_rebucket(a, {"kind": "pattern", "n": 4,
                                  "capacity": 32})
    assert not needs_rebucket(a, {"kind": "keyed", "n": 4,
                                  "capacity": 64})


def test_capacity_must_divide():
    with pytest.raises(ValueError):
        ShardRouter(8, 60)


def test_group_routes_and_counts():
    r = ShardRouter(4, 16)
    slots = np.array([0, 1, 2, 3, 4, 5, -1, 4])
    valid = np.array([True] * 7 + [False])
    key_idx, sel, counts, _keys, _key_counts = r.group(slots, valid)
    assert key_idx.shape[0] == 4 and sel.shape[0] == 4
    # slots 0,4 -> shard 0; 1,5 -> shard 1; 2 -> shard 2; 3 -> shard 3
    assert counts.tolist() == [2, 2, 1, 1]
    # shard 0 holds local rows 0 (slot 0) and 1 (slot 4)
    live0 = key_idx[0][key_idx[0] < r.block]
    assert sorted(live0.tolist()) == [0, 1]


def _four_pass_group(r, slots, valid):
    """The plain reference: `ShardRouter.group` as it stood until PR 56 —
    one mask, one count pass and one fill pass over ALL the rows for every
    shard, then a padded copy."""
    n = r.n_shards
    slots = np.asarray(slots)
    shard, local = r.shard_of(slots), r.local_of(slots)
    groups = []
    counts = np.zeros(n, np.int64)
    for d in range(n):
        mask = (shard == d) & valid & (slots >= 0)
        counts[d] = int(mask.sum())
        groups.append(ks.group_events_by_key(
            np.where(mask, local, -1), mask, pad=r.block))
    Kb = max(g[0].shape[0] for g in groups)
    E = max(g[1].shape[1] for g in groups)
    key_idx = np.full((n, Kb), r.block, np.int32)
    sel = np.full((n, Kb, E), -1, np.int32)
    for d, (ki, s, _kv) in enumerate(groups):
        key_idx[d, :ki.shape[0]] = ki
        sel[d, :s.shape[0], :s.shape[1]] = s
    return key_idx, sel, counts


def _random_batch(n, capacity, rng):
    """Slots with repeats, rows whose `valid` is False, rows whose slot
    is -1 (a key the range partition dropped: `valid` stays True)."""
    slots = rng.integers(0, capacity, 600).astype(np.int32)
    slots[rng.integers(0, 600, 40)] = -1
    return np.repeat(slots, rng.integers(1, 4, 600)), None


def _an_empty_shard(n, capacity, rng):
    """Shard n - 1 gets nothing (on one shard there is none to spare)."""
    slots = rng.integers(0, capacity // n, 300).astype(np.int32) * n
    if n > 1:
        slots += rng.integers(0, n - 1, 300).astype(np.int32)
    return slots, None


def _every_shard_empty(n, capacity, rng):
    slots = rng.integers(0, capacity, 64).astype(np.int32)
    valid = np.zeros(64, bool)
    slots[::2] = -1
    valid[::2] = True          # a valid row has no slot, a slot no valid row
    return slots, valid


def _one_hot_key(n, capacity, rng):
    """One key holds 9 events (E bucket 16), the others one or two."""
    slots = np.concatenate([rng.permutation(capacity)[:50].astype(np.int32),
                            np.full(9, 5, np.int32),
                            rng.integers(0, capacity, 30).astype(np.int32)])
    slots = rng.permutation(slots)
    return slots, (slots == 5) | (rng.random(slots.shape[0]) < 0.9)


def _kb_edge(n, capacity, rng):
    """One shard's key count crosses a `_KB_BUCKETS` edge (64 -> 65: Kb
    512), the others stay under it."""
    slots = np.concatenate([np.arange(65, dtype=np.int32) * n,
                            np.arange(1, n, dtype=np.int32)])
    slots = rng.permutation(np.repeat(slots, 2))
    return slots, np.ones(slots.shape[0], bool)


BATCHES = {"random": _random_batch, "an_empty_shard": _an_empty_shard,
           "every_shard_empty": _every_shard_empty,
           "one_hot_key": _one_hot_key, "kb_edge": _kb_edge}


@pytest.fixture(params=["native", "numpy", "numpy_pad"])
def grouping(request, monkeypatch):
    """The three ways `group_events_by_shard` runs: the C passes, the
    numpy path for want of the library, the numpy path for a capacity of
    2**30 or more."""
    if request.param == "numpy":
        monkeypatch.setattr(ks, "LIB", None)
    elif ks.LIB is None:
        pytest.skip("native staging library unavailable")
    return request.param


@pytest.mark.parametrize("n", [1, 2, 4, 8])
@pytest.mark.parametrize("batch", sorted(BATCHES))
def test_group_equals_the_four_pass_loop(grouping, batch, n, monkeypatch):
    """The one-pass `group` lays a batch out as the per-shard loop did,
    byte for byte — values, shapes, buckets, pads, dtypes — and the keys
    and counts it hands the observers are `np.unique`'s of the live
    slots."""
    capacity = 2**30 if grouping == "numpy_pad" else 4096
    r = ShardRouter(n, capacity)
    rng = np.random.default_rng([n, sorted(BATCHES).index(batch)])
    slots, valid = BATCHES[batch](n, 4096, rng)
    if valid is None:
        valid = rng.random(slots.shape[0]) < 0.9
    with monkeypatch.context() as m:
        if grouping == "numpy_pad":
            # the loop's pad is the BLOCK, under 2**30 from two shards
            # on: keep its C passes off a scratch of that size
            m.setattr(ks, "LIB", None)
        want = _four_pass_group(r, slots, valid)
    key_idx, sel, counts, keys, key_counts = r.group(slots, valid)
    for got, ref in zip((key_idx, sel, counts), want):
        assert got.dtype == ref.dtype and got.shape == ref.shape
        assert got.tobytes() == ref.tobytes()
    uniq, cnt = np.unique(slots[valid & (slots >= 0)], return_counts=True)
    assert keys.dtype == np.int32 and key_counts.dtype == np.int32
    assert np.array_equal(keys, uniq) and np.array_equal(key_counts, cnt)
    # ... and through `state_row` they are the layout's own rows
    rows = np.sort(r.state_row(keys))
    live = key_idx < r.block
    assert np.array_equal(
        rows, (key_idx + np.arange(n)[:, None] * r.block)[live])
    assert np.array_equal(np.sort(r.slot_of_row(rows)), uniq)
    assert int((sel >= 0).sum()) == int(cnt.sum()) == int(counts.sum())
    if batch == "one_hot_key":
        assert sel.shape[2] == 16
    if batch == "kb_edge":
        assert key_idx.shape[1] == 512
    if batch == "every_shard_empty":
        assert key_idx.shape == (n, 1) and sel.shape == (n, 1, 1)
    if batch == "an_empty_shard" and n > 1:
        assert counts[n - 1] == 0 and counts[:n - 1].all()


@pytest.mark.parametrize("native", [True, False], ids=["native", "numpy"])
def test_group_is_one_grouping_of_the_batch(native, monkeypatch):
    """One count pass and one fill pass over the rows (numpy: one
    `group_events_by_key`), whatever the shard count."""
    calls = []
    if native:
        if ks.LIB is None:
            pytest.skip("native staging library unavailable")
        lib = ks.LIB

        class Counting:
            def __getattr__(self, name):
                return lambda *a: calls.append(name) or getattr(lib, name)(*a)

        monkeypatch.setattr(ks, "LIB", Counting())
        want = ["sg_group_count", "sg_group_fill_shards"]
    else:
        real = ks.group_events_by_key
        monkeypatch.setattr(ks, "LIB", None)
        monkeypatch.setattr(
            ks, "group_events_by_key",
            lambda *a, **k: calls.append("group_events_by_key") or
            real(*a, **k))
        want = ["group_events_by_key"]
    slots = np.arange(64, dtype=np.int32)
    ShardRouter(8, 64).group(slots, np.ones(64, bool))
    assert calls == want


# ---------------------------------------------------------------------------
# shard-labelled observability
# ---------------------------------------------------------------------------

STATS_APP = """
@app:name('shardmetrics')
@app:playback
@app:statistics('BASIC')
define stream S (key long, price float, volume int);
partition with (key of S)
begin
  @capacity(keys='64', slots='4')
  @info(name='q1')
  from every e1=S[volume == 1] -> e2=S[volume == 2]
  select e1.key as k, e2.price as p
  insert into Out;
end;
"""


@pytest.fixture()
def stats_rt(mesh):
    from siddhi_tpu import SiddhiManager
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(STATS_APP, mesh=mesh)
    rt.add_callback("q1", lambda ts, i, o: None)
    rt.start()
    h = rt.get_input_handler("S")
    for stage in (1, 2):
        h.send([[k, float(stage), stage] for k in range(24)],
               timestamp=1000 * stage)
    rt.flush()
    yield rt
    m.shutdown()


def test_metrics_gain_shard_dimension(stats_rt):
    from siddhi_tpu.observability.exposition import render_prometheus
    text = render_prometheus({"shardmetrics": stats_rt})
    assert 'siddhi_shard_events_total{app="shardmetrics",query="q1",' \
           'shard="0"}' in text
    # all 8 shards report residency, and the routed totals sum to the
    # events sent (24 keys x 2 stages)
    for d in range(8):
        assert f'siddhi_shard_state_bytes{{app="shardmetrics",' \
               f'shard="{d}"}}' in text
    totals = [int(float(line.rsplit(" ", 1)[1]))
              for line in text.splitlines()
              if line.startswith("siddhi_shard_events_total")]
    assert sum(totals) == 48
    assert "siddhi_shard_batch_events_bucket" in text


def test_healthz_gains_shard_dimension(stats_rt):
    rep = stats_rt.health()
    shards = rep["shards"]
    assert shards["devices"] == 8
    assert set(shards["per_shard"]) == {str(d) for d in range(8)}
    assert all(s["state_bytes"] > 0 for s in shards["per_shard"].values())
    ev = sum(s["events_total"] for s in shards["per_shard"].values())
    assert ev == 48
    # 24 keys over 8 shards round-robin: every shard saw traffic
    assert shards["balanced"] is True


def test_per_shard_state_bytes_shrink_with_mesh(stats_rt):
    """Per-shard residency counts sharded leaves at 1/n: it must be well
    below the global total for a 64-key slab over 8 devices."""
    from siddhi_tpu.observability.memory import tree_nbytes
    from siddhi_tpu.sharding import shard_state_bytes
    qr = stats_rt.query_runtimes["q1"]
    total = tree_nbytes(qr.state)
    per = shard_state_bytes(stats_rt)[0]
    assert 0 < per < total


def test_explain_reports_sharding(stats_rt):
    rep = stats_rt.explain("q1")
    node = rep["sharding"]
    assert node["devices"] == 8
    assert node["key_capacity"] == 64 and node["keys_per_shard"] == 8
    assert node["snapshot_layout"] == {"kind": "pattern", "n": 8,
                                       "capacity": 64}
    # deep explain compiles: the sharded step's HLO carries collectives
    # (the psum'd emission header at minimum)
    colls = node["collectives"]
    assert any(colls.values()), colls


def test_shard_count_accessor(mesh):
    from siddhi_tpu import SiddhiManager
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(
        "define stream S (a int); from S select a insert into O;",
        mesh=mesh)
    assert shard_count(rt) == 8
    rt2 = m.create_siddhi_app_runtime(
        "@app:name('x') define stream S (a int); "
        "from S select a insert into O;")
    assert shard_count(rt2) == 1
    m.shutdown()


# ---------------------------------------------------------------------------
# PART002
# ---------------------------------------------------------------------------

UNDERSIZED = """
define stream S (key long, v int);
partition with (key of S)
begin
  @capacity(keys='4')
  from S select key, sum(v) as t insert into Out;
end;
"""


def test_part002_fires_with_configured_mesh():
    from siddhi_tpu.analysis import LintConfig, analyze
    ids = [f.rule_id for f in analyze(
        UNDERSIZED, config=LintConfig(mesh_devices=8))]
    assert "PART002" in ids


def test_part002_silent_without_mesh():
    from siddhi_tpu.analysis import analyze
    assert "PART002" not in [f.rule_id for f in analyze(UNDERSIZED)]
    # big-enough capacity: silent even with a mesh configured
    from siddhi_tpu.analysis import LintConfig
    ok = UNDERSIZED.replace("keys='4'", "keys='64'")
    assert "PART002" not in [
        f.rule_id for f in analyze(ok, config=LintConfig(mesh_devices=8))]


def test_part002_resolves_runtime_mesh(mesh):
    from siddhi_tpu import SiddhiManager
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(UNDERSIZED, mesh=mesh)
    rep = rt.analyze()
    assert any(f["rule"] == "PART002" for f in rep["findings"])
    m.shutdown()


def test_part002_cli_flag(tmp_path):
    from siddhi_tpu.tools.lint import main
    p = tmp_path / "u.siddhi"
    p.write_text(UNDERSIZED)
    assert main([str(p), "--mesh-size", "8", "--fail-on", "warn"]) == 1
    assert main([str(p), "--fail-on", "warn"]) == 0
