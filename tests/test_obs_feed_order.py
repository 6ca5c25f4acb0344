"""The observers of a partitioned pattern send — key hotness, the purger's
liveness touch, the snapshot's dirty marks, the per-shard routing counters
— are fed after the send's last dispatch, while the device runs the step
(core/runtime.py `_feed_observers` / `_shard_feed`).  The order moved; the
bookkeeping did not: one seeded multi-send run per path (dense, scattered,
tiered, sharded) reads the same hotness, dirty mask, incremental snapshot
and shard counters as the tree that fed BEFORE the dispatch (the values
below are pinned from it), and a step that raises still leaves every key
that may have advanced marked and counted."""
import jax
import numpy as np
import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.core import keyslots

QL = """
@app:name('{name}')
@app:playback
@app:statistics('BASIC')
{mesh}
define stream T (key long, price float, stage int);
partition with (key of T)
begin
  @capacity(keys='4096', slots='4') @emit(rows='64') @info(name='q')
  from every e1=T[stage == 1] -> e2=T[stage == 2 and price >= e1.price]
  select e1.key as k, e1.price as p1, e2.price as p2 insert into Matches;
end;
"""
TIER_COUNTS = np.concatenate([np.full(200, 2), np.full(10, 16), [100]])

# read on the parent tree (commit 40ec212: the feed before the dispatch)
# with this file's drive; `dirty` = (count, sum, sum of squares mod
# 1,000,003) of the dirty state rows before the incremental snapshot
PINNED = {
    "dense": {"rows": [516, 591, 600, 627, 591], "total": 10240,
              "distinct": 3072, "top": [[2496, 10], [2497, 10], [2498, 10]],
              "dirty": (2048, 4193280, 540609), "shard_events": None},
    "scattered": {"rows": [273, 435, 396, 437, 426], "total": 7000,
                  "distinct": 1900,
                  "top": [[1586, 10], [1177, 10], [1519, 8]],
                  "dirty": (1300, 1595549, 446014), "shard_events": None},
    "tiered": {"rows": [192, 212, 212, 221, 240], "total": 3300,
               "distinct": 611, "top": [[210, 104], [310, 104], [410, 104]],
               "dirty": (712, 395516, 787009), "shard_events": None},
    "sharded": {"rows": [286, 420, 423, 426, 393], "total": 7000,
                "distinct": 1900,
                "top": [[1297, 12], [1298, 12], [1521, 10]],
                "dirty": (1300, 2398430, 82771),
                "shard_events": [1720, 1758, 1742, 1780]},
}


def sends_of(shape: str, seed: int = 33, n: int = 5):
    rng = np.random.default_rng([seed, len(shape)])
    perm = rng.permutation(4096).astype(np.int64)
    out = []
    for i in range(n):
        if shape == "dense":
            keys = np.repeat(np.arange(1024, dtype=np.int64) + 512 * i, 2)
            stage = np.tile(np.array([1, 2], np.int32), 1024)
        elif shape in ("scattered", "sharded"):
            k = np.sort(perm[300 * i:300 * i + 700])
            keys = np.repeat(k, 2)
            stage = np.tile(np.array([1, 2], np.int32), k.size)
            stage[rng.random(stage.size) < 0.2] = 1
        else:
            k = perm[100 * i:100 * i + TIER_COUNTS.size]
            keys = np.repeat(k, TIER_COUNTS)
            stage = (np.arange(keys.size) % 2 + 1).astype(np.int32)
        price = rng.integers(1, 50, keys.size).astype(np.float32)
        out.append(([keys, price, stage],
                    np.full(keys.size, 1000 + 10 * i, np.int64)))
    return out


def deploy(m, shape: str, name: str = "FeedOrder"):
    if shape == "sharded" and len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    rt = m.create_siddhi_app_runtime(QL.format(
        name=name, mesh="@app:mesh(shards='4')" if shape == "sharded"
        else ""))
    rows, errors = [], []
    rt.add_batch_callback(
        "q", lambda ts, b: rows.append(int(np.sum(b["valid"]))))
    rt.set_exception_listener(errors.append)
    rt.start()
    return rt, rows, errors


def send(rt, cols, ts):
    rt.get_input_handler("T").send_columns([c.copy() for c in cols],
                                           timestamps=ts.copy())


def dirty_facts(qr):
    idx = np.nonzero(qr._dirty)[0]
    return (int(idx.size), int(idx.sum()), int((idx * idx).sum() % 1000003))


@pytest.mark.parametrize("shape", sorted(PINNED))
def test_a_seeded_run_keeps_the_books_of_the_feed_first_order(
        shape, monkeypatch):
    if shape == "tiered":
        monkeypatch.setattr(keyslots, "_TIER_MIN_CELLS", 0)
    want = PINNED[shape]
    m = SiddhiManager()
    try:
        rt, rows, errors = deploy(m, shape)
        sends = sends_of(shape)
        for cols, ts in sends[:2]:
            send(rt, cols, ts)
        base = rt.snapshot()              # resets the dirty mask
        for cols, ts in sends[2:]:
            send(rt, cols, ts)
        rt.flush()
        assert not errors and rows == want["rows"]
        qr = rt.query_runtimes["q"]
        assert dirty_facts(qr) == want["dirty"]
        hot = rt.state_report()["hotness"]["q"]
        assert (hot["total"], hot["distinct"]) == \
            (want["total"], want["distinct"])
        assert [list(t) for t in hot["top"][:3]] == want["top"]
        assert rt.stats.exposition_snapshot()["shard_events"].get("q") == \
            want["shard_events"]
        # the dirty marks are what an incremental snapshot ships: baseline
        # + delta restore the live state bit for bit
        inc = rt.snapshot_incremental()
        assert not qr._dirty.any()
        live = [np.asarray(x) for x in jax.tree.leaves(qr.state)]
        rt2, _, _ = deploy(m, shape, name="FeedOrderRestored")
        rt2.restore(base)
        rt2.restore_increment(inc)
        back = [np.asarray(x)
                for x in jax.tree.leaves(rt2.query_runtimes["q"].state)]
        assert len(live) == len(back)
        for a, b in zip(live, back):
            np.testing.assert_array_equal(a, b)
    finally:
        m.shutdown()


@pytest.mark.parametrize("shape,fail_at", [("tiered", 2), ("dense", 1),
                                           ("sharded", 1)])
def test_a_step_that_raises_leaves_its_sends_keys_dirty_and_counted(
        shape, fail_at, monkeypatch):
    """The `fail_at`-th dispatch of the third send raises (a tiered send's
    second tier: the hot tier has advanced by then).  As when the feed came
    first: every key of the send is dirty and counted, the rows of what
    did advance are delivered, the error is reported once."""
    if shape == "tiered":
        monkeypatch.setattr(keyslots, "_TIER_MIN_CELLS", 0)
    m = SiddhiManager()
    try:
        rt, rows, errors = deploy(m, shape)
        qr = rt.query_runtimes["q"]
        sends = sends_of(shape, n=3)
        for cols, ts in sends[:2]:
            send(rt, cols, ts)
        rt.snapshot()
        before = rt.state_report()["hotness"]["q"]["total"]
        del rows[:]
        calls, real = [], qr._step

        def failing(step, *args):
            calls.append(1)
            if len(calls) == fail_at:
                raise RuntimeError("refused at dispatch")
            return real(step, *args)

        monkeypatch.setattr(qr, "_step", failing)
        cols, ts = sends[2]
        send(rt, cols, ts)
        rt.flush()
        assert len(calls) == fail_at          # later tiers were not applied
        assert len(errors) == 1 and "refused at dispatch" in str(errors[0])
        # a tier dispatched before the failing one delivers what it matched
        assert len(rows) == (1 if fail_at > 1 else 0)
        keys = np.unique(cols[0])
        slots = qr.slot_allocator.slots_for([keys],
                                            np.ones(keys.size, np.bool_))
        if shape == "sharded":
            slots = qr.shard_router.state_row(slots)
        assert qr._dirty[slots].all()
        hot = rt.state_report()["hotness"]["q"]
        assert hot["total"] == before + cols[0].size
        if shape == "sharded":
            per_shard = rt.stats.exposition_snapshot()["shard_events"]["q"]
            assert sum(per_shard) == sum(c[0].size for c, _ in sends)
    finally:
        m.shutdown()
