"""The mesh path groups a send once and its observers read that grouping
(`ShardRouter.group` -> `_shard_prep` -> `_shard_feed`): the send's distinct
slots and the events of each.  What key hotness, the purger, the dirty
marks, the per-shard counters and the row-mover's counters END UP holding is
what the per-row feed left there — `np.unique` over every row's slot, the
liveness touch and `state_row` over every row — which this file computes by
that feed's own lines on mirror observers, once for the sequential sharded
path and once under `@fuse`."""
import jax
import numpy as np
import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.core import state_rows
from siddhi_tpu.observability.stateobs import KeyHotness

QL = """
@app:name('{name}')
@app:playback
@app:statistics('BASIC')
@app:mesh(shards='4')
define stream T (key long, price float, stage int);
partition with (key of T)
begin
  @capacity(keys='4096', slots='16') @emit(rows='64')
  @purge(enable='true', interval='1 hour', idle.period='1 hour')
  {fuse}@info(name='q')
  from every e1=T[stage == 1] -> e2=T[stage == 2 and price >= e1.price]
  select e1.key as k, e1.price as p1, e2.price as p2 insert into Matches;
end;
"""
N_SENDS = 7


def sends():
    """Seven sends of 600 keys in shuffled row order, 250 of them new each
    time: every key twice (the heavy-hitter table breaks ties by feed
    order) or, every other send, 1 to 9 times."""
    rng = np.random.default_rng(56)
    perm = rng.permutation(4096).astype(np.int64)
    out = []
    for i in range(N_SENDS):
        k = perm[250 * i:250 * i + 600]
        counts = np.full(k.size, 2)
        if i % 2:
            counts = rng.integers(1, 10, k.size)
        keys = rng.permutation(np.repeat(k, counts))
        stage = (2 - (rng.random(keys.size) < 0.3)).astype(np.int32)
        price = rng.integers(1, 50, keys.size).astype(np.float32)
        out.append(([keys, price, stage],
                    np.full(keys.size, 1000 + 10 * i, np.int64)))
    return out


def deploy(m, fuse: str, name: str):
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    rt = m.create_siddhi_app_runtime(QL.format(name=name, fuse=fuse))
    errors = []
    rt.add_batch_callback("q", lambda ts, b: None)
    rt.set_exception_listener(errors.append)
    rt.start()
    return rt, errors


def send(rt, cols, ts):
    rt.get_input_handler("T").send_columns([c.copy() for c in cols],
                                           timestamps=ts.copy())


class PerRowFeed:
    """`_shard_feed` as it stood until PR 56, on observers of its own."""

    def __init__(self, qr):
        self.router, cap = qr.shard_router, qr.slot_allocator.capacity
        self.hot = KeyHotness(cap)
        self.seen = np.zeros(cap, np.int64)
        self.dirty = np.zeros(qr.planned.key_capacity, np.bool_)
        self.shard_events = np.zeros(self.router.n_shards, np.int64)
        self.row_keys = self.row_blocks = 0

    def feed(self, slots, now):
        live = slots[slots >= 0]
        self.hot.update(*np.unique(live, return_counts=True))
        self.seen[live] = now
        self.dirty[self.router.state_row(live)] = True
        n = self.router.n_shards
        self.shard_events += np.bincount(live % n, minlength=n)
        for d in range(n):
            rows = np.unique(live[live % n == d] // n)
            if rows.size:
                self.row_keys += rows.size
                self.row_blocks += 1 + int(np.count_nonzero(
                    np.diff(rows // state_rows.LANES)))


def hotness_state(hot):
    return (hot.total, hot._cms.tobytes(), hot._seen.tobytes(),
            list(hot._ss.items()), hot.snapshot())


@pytest.mark.parametrize("fuse", ["", "@fuse(batches='3') "],
                         ids=["sequential", "fused"])
def test_observers_hold_what_the_per_row_feed_left(fuse):
    m = SiddhiManager()
    try:
        rt, errors = deploy(m, fuse, "ShardFeed")
        qr = rt.query_runtimes["q"]
        assert (qr._fuse is not None) == bool(fuse)
        assert qr.shard_router.n_shards == 4 and qr._touch is not None
        (purger,) = rt._partition_purgers
        batch = sends()
        for cols, ts in batch[:2]:
            send(rt, cols, ts)
        base = rt.snapshot()              # resets the dirty mask
        for cols, ts in batch[2:]:
            send(rt, cols, ts)
        rt.flush()
        assert not errors, errors[:1]
        # every key is bound by now: a lookup gives each row's slot
        want = PerRowFeed(qr)
        for i, (cols, ts) in enumerate(batch):
            slots = qr.slot_allocator.slots_for([cols[0]], lookup_only=True)
            assert (slots >= 0).all()
            if i == 2:
                want.dirty[:] = False     # the snapshot's reset
            want.feed(slots, int(ts[-1]))
        got = rt.stats.stateobs.hotness("q")
        assert hotness_state(got) == hotness_state(want.hot)
        np.testing.assert_array_equal(purger._seen_shared, want.seen)
        np.testing.assert_array_equal(qr._dirty, want.dirty)
        assert qr._dirty.sum() > 600
        assert rt.stats.exposition_snapshot()["shard_events"]["q"] == \
            want.shard_events.tolist()
        moved = rt.state_report()["state_rows"]["q"]
        assert (moved["keys"], moved["blocks"]) == \
            (want.row_keys, want.row_blocks)
        # the dirty rows are what an incremental snapshot ships: baseline
        # + delta restore the live state bit for bit
        inc = rt.snapshot_incremental()
        assert not qr._dirty.any()
        live = [np.asarray(x) for x in jax.tree.leaves(qr.state)]
        rt2, _ = deploy(m, fuse, "ShardFeedRestored")
        rt2.restore(base)
        rt2.restore_increment(inc)
        back = [np.asarray(x)
                for x in jax.tree.leaves(rt2.query_runtimes["q"].state)]
        assert len(live) == len(back)
        for a, b in zip(live, back):
            np.testing.assert_array_equal(a, b)
    finally:
        m.shutdown()
