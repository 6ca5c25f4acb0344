"""The flagship pattern delivered through `@serve`, through the deployed
app text of `benchmarks/configs/pattern_1m_served/` at 1,024 keys: seeded
random sends against a plain per-key numpy reference, rows by value and in
send order — partitioned, scattered, several sends in the ring at once, an
emission whose shape changes mid-stream, a ring that has to grow, a tiered
(skewed) send as one ring entry, and the blocking app's rows for the same
seeds.  What `tests/test_serving.py::test_serve_parity_pattern` (one key,
12 events) cannot show of PR 31's banded emission in the ring."""
import os
import threading

import jax
import numpy as np
import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.core import keyslots
from siddhi_tpu.core.pattern_planner import BandedEmission
from siddhi_tpu.utils.config import InMemoryConfigManager

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = os.path.join(ROOT, "benchmarks", "configs")
N_KEYS, KB = 1024, 16
COLUMNS = ("k", "p1", "p2", "p4")
# a drain interval no test outlives: nothing leaves the ring before flush()
# unless its occupancy crosses high water (6 of 8)
HOLD = {"serving.drain.interval.ms": "600000"}


def app_text(config: str, emit_rows: int = 2) -> str:
    with open(os.path.join(CONFIGS, config, "app.siddhi")) as fh:
        return fh.read().format(n_keys=N_KEYS, slots=4, emit_rows=emit_rows)


def make_send(rng, keys, clock_ms: int, cycles=None):
    """Each key's four stages, `cycles[i]` times over, in arrival order;
    three keys in four complete a match a cycle (p2 >= p1 and p4 >= p3),
    the fourth fails stage 2 or stage 4 and leaves a partial behind."""
    cycles = np.ones(len(keys), np.int64) if cycles is None else cycles
    k = np.repeat(np.asarray(keys, np.int64), cycles)
    r = rng.random((k.shape[0], 4), np.float32)
    fail = rng.integers(0, 8, k.shape[0])       # 0: stage 2, 1: stage 4
    fail[np.repeat(cycles > 1, cycles)] = 7     # a cycling key always passes
    p2 = np.where(fail == 0, r[:, 0] - 0.5, r[:, 0] + r[:, 1])
    p4 = np.where(fail == 1, r[:, 2] - 0.5, r[:, 2] + r[:, 3])
    price = np.stack([r[:, 0], p2, r[:, 2], p4], 1).astype(np.float32)
    n = k.shape[0]
    return {"cols": [np.repeat(k, 4), np.ascontiguousarray(price.reshape(-1)),
                     np.tile(np.arange(1, 5, dtype=np.int32), n)],
            "ts": clock_ms + np.arange(4 * n, dtype=np.int64)}


class Reference:
    """The pattern, key by key, event by event: a list of partial matches
    a key, `every` arming a new one at each stage-1 event."""

    def __init__(self):
        self.partials = {}

    def rows(self, send) -> list:
        out = []
        for k, p, v in zip(*send["cols"]):
            alive = self.partials.setdefault(int(k), [])
            if v == 1:
                alive.append([p])
                continue
            for m in list(alive):
                if len(m) != v - 1:
                    continue
                if v == 2 and not p >= m[0]:
                    continue
                if v == 4 and not p >= m[2]:
                    continue
                m.append(p)
                if v == 4:
                    alive.remove(m)
                    out.append((int(k), m[0], m[1], m[3]))
        return sorted(out)


def delivered(batch) -> list:
    sel = batch["valid"] & (batch["kind"] == 0)
    cols = [np.asarray(batch["cols"][n])[sel] for n in COLUMNS]
    return [tuple(c[i].item() for c in cols) for i in range(sel.sum())]


class Drive:
    """The app deployed with one batch subscriber; every non-empty batch
    it was handed, in delivery order, rows in delivery order."""

    def __init__(self, manager, config="pattern_1m_served", emit_rows=2):
        self.rt = manager.create_siddhi_app_runtime(
            app_text(config, emit_rows))
        self.errors, self.batches, self.threads = [], [], set()
        self.rt.set_exception_listener(self.errors.append)
        self.rt.add_batch_callback("flagship", self.on_batch)
        self.rt.start()
        self.handler = self.rt.get_input_handler("TradeStream")

    def on_batch(self, _ts, b):
        rows = delivered(b)
        if rows:
            self.batches.append(rows)
            self.threads.add(threading.current_thread().name)

    def send(self, send) -> None:
        self.handler.send_columns([c.copy() for c in send["cols"]],
                                  timestamps=send["ts"].copy())

    @property
    def ring(self):
        return self.rt.serve_rings()["flagship"]


def scattered(seed: int, n_sends: int):
    """`n_sends` sends of KB keys drawn without replacement from a seeded
    permutation of the key space, and what the reference makes of each."""
    rng = np.random.default_rng(seed)
    perm = rng.permutation(N_KEYS)
    sends = [make_send(rng, perm[i * KB:(i + 1) * KB], 1000 + 100 * i)
             for i in range(n_sends)]
    ref = Reference()
    return sends, [ref.rows(s) for s in sends]


def held_manager(**extra) -> SiddhiManager:
    m = SiddhiManager()
    m.set_config_manager(InMemoryConfigManager(
        system_configs=dict(HOLD, **extra)))
    return m


@pytest.mark.parametrize("seed", [3, 2**31 + 17])
def test_scattered_sends_wait_in_the_ring_and_leave_in_send_order(seed):
    """(a) five sends appended before the first drain: the ring holds all
    five, `flush()` delivers them, one batch a send, in send order."""
    sends, want = scattered(seed, 5)
    m = held_manager()
    try:
        d = Drive(m)
        for i, s in enumerate(sends):
            d.send(s)
            assert d.ring.occupancy() == i + 1 and not d.batches
        d.rt.flush()
        assert d.ring.occupancy() == 0
        assert [sorted(b) for b in d.batches] == want
        assert all(w for w in want) and not d.errors
        facts = d.rt.serve_staging_facts()
        assert facts["staged_total"] == facts["adopted_total"]
        assert facts["fallback_total"] == 0
    finally:
        m.shutdown()


@pytest.mark.parametrize("seed", [5, 2**31 + 29])
def test_an_emission_of_another_shape_waits_for_the_sealed_generation(seed):
    """(b) a wide contiguous send (256 keys: the dense step, a [256]-key
    emission) then narrow scattered ones (16 keys): the second shape opens
    a second ring generation, and the first drains first."""
    rng = np.random.default_rng(seed)
    wide = make_send(rng, np.arange(256), 500)
    narrow, _ = scattered(seed, 3)
    sends = [wide] + narrow + [make_send(rng, np.arange(256, 512), 5000)]
    ref = Reference()
    want = [ref.rows(s) for s in sends]
    m = held_manager()
    try:
        d = Drive(m)
        for s in sends:
            d.send(s)
        assert d.ring.occupancy() == 5 and not d.batches
        # wide, narrow x 3, wide again: three generations, two sealed
        assert d.ring.facts()["generation"] == 3
        assert len(d.ring.state_leaves()) == 3
        d.rt.flush()
        assert [sorted(b) for b in d.batches] == want and not d.errors
        assert len(d.ring.state_leaves()) == 1      # the sealed ones freed
        assert d.ring.facts()["overflow_grows"] == 0
    finally:
        m.shutdown()


@pytest.mark.parametrize("seed", [7, 2**31 + 31])
def test_a_ring_of_two_grows_under_ten_undrained_sends(seed):
    """(c) capacity 2, the drainer held off: the ring grows (2 -> 4 -> 8
    -> 16), nothing is dropped, and the order is the sends'."""
    sends, want = scattered(seed, 10)
    m = held_manager(**{"serving.ring.capacity": "2"})
    try:
        d = Drive(m)
        d.send(sends[0])          # the first append registers the ring
        with d.rt._serve_drainer._deliver_lock:     # stall every cycle
            for s in sends[1:]:
                d.send(s)
            assert d.ring.occupancy() >= 9
        d.rt.flush()
        facts = d.ring.facts()
        assert facts["overflow_grows"] >= 2 and facts["capacity"] >= 8
        assert facts["occupancy"] == 0
        assert [sorted(b) for b in d.batches] == want and not d.errors
    finally:
        m.shutdown()


@pytest.mark.parametrize("seed", [11, 2**31 + 37])
def test_a_tiered_send_is_one_ring_entry(seed, monkeypatch):
    """(d) a skewed send — one key forty cycles, fifteen keys one — is
    laid out as tiers, whose emissions enter the ring as ONE
    (`BandedEmission.joined`) and leave as one batch."""
    monkeypatch.setattr(keyslots, "_TIER_MIN_CELLS", 0)
    joined = []
    real = BandedEmission.joined
    monkeypatch.setattr(BandedEmission, "joined", staticmethod(
        lambda ems: joined.append(len(ems)) or real(ems)))
    rng = np.random.default_rng(seed)
    perm = rng.permutation(N_KEYS)
    cycles = np.ones(KB, np.int64)
    cycles[3] = 40
    sends = [make_send(rng, perm[:KB], 1000),
             make_send(rng, perm[KB:2 * KB], 2000, cycles),
             make_send(rng, perm[2 * KB:3 * KB], 9000)]
    ref = Reference()
    want = [ref.rows(s) for s in sends]
    assert sum(k == perm[KB + 3] for k, *_ in want[1]) == 40
    m = held_manager()
    try:
        d = Drive(m, emit_rows=64)
        for s in sends:
            d.send(s)
        assert joined == [2]                  # the skewed send: two tiers
        assert d.ring.facts()["appends_total"] == 3
        assert d.ring.occupancy() == 3
        d.rt.flush()
        assert [sorted(b) for b in d.batches] == want and not d.errors
    finally:
        m.shutdown()


@pytest.mark.parametrize("seed", [13, 2**31 + 41])
def test_served_rows_are_the_blocking_rows(seed, manager):
    """(e) the same seeds through `pattern_1m`'s app: the same batches,
    row for row and in the same order — the drainer's thread delivers
    them, the sender's never fetches."""
    sends, want = scattered(seed, 12)
    blocking = Drive(manager, "pattern_1m")
    for s in sends:
        blocking.send(s)
    blocking.rt.flush()
    served = Drive(manager)
    sender = threading.current_thread()
    real_get, real_block = jax.device_get, jax.block_until_ready

    def guard(real):
        def inner(x):
            assert threading.current_thread() is not sender, \
                f"{real.__name__} called in the send path"
            return real(x)
        return inner

    jax.device_get, jax.block_until_ready = guard(real_get), guard(real_block)
    try:
        for s in sends:
            served.send(s)
    finally:
        jax.device_get, jax.block_until_ready = real_get, real_block
    served.rt.flush()
    assert served.batches == blocking.batches
    assert [sorted(b) for b in served.batches] == want
    assert blocking.threads == {sender.name}
    assert not served.errors and not blocking.errors
