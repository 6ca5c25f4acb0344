"""Direct SlotAllocator / grouping tests (the native C staging path and its
numpy fallback share semantics and snapshot format — verified here by
running every case against BOTH backends)."""
import numpy as np
import pytest

import siddhi_tpu.core.keyslots as ks
from siddhi_tpu.core.keyslots import SlotAllocator, group_events_by_key
from siddhi_tpu.exceptions import CapacityExceededError


@pytest.fixture(params=["native", "numpy"])
def backend(request, monkeypatch):
    if request.param == "numpy":
        monkeypatch.setattr(ks, "LIB", None)
    elif ks.LIB is None:
        pytest.skip("native staging library unavailable")
    return request.param


def test_basic_insert_lookup(backend):
    a = SlotAllocator(16, "t")
    keys = np.arange(8, dtype=np.int64)
    s1 = a.slots_for([keys])
    assert len(set(s1.tolist())) == 8          # distinct slots
    s2 = a.slots_for([keys])
    assert (s1 == s2).all()                    # stable
    assert len(a) == 8


def test_lookup_only_does_not_allocate(backend):
    a = SlotAllocator(8, "t")
    miss = a.slots_for([np.array([42], np.int64)], lookup_only=True)
    assert miss[0] == -1
    assert len(a) == 0
    hit = a.slots_for([np.array([42], np.int64)])
    assert hit[0] >= 0
    again = a.slots_for([np.array([42], np.int64)], lookup_only=True)
    assert again[0] == hit[0]


def test_invalid_rows_get_minus_one(backend):
    a = SlotAllocator(8, "t")
    keys = np.arange(4, dtype=np.int64)
    valid = np.array([True, False, True, False])
    s = a.slots_for([keys], valid=valid)
    assert s[1] == -1 and s[3] == -1
    assert s[0] >= 0 and s[2] >= 0
    assert len(a) == 2


def test_capacity_exhaustion_raises(backend):
    a = SlotAllocator(4, "t")
    a.slots_for([np.arange(4, dtype=np.int64)])
    with pytest.raises(CapacityExceededError):
        a.slots_for([np.array([99], np.int64)])


def test_purge_recycles_slots(backend):
    a = SlotAllocator(4, "t")
    s = a.slots_for([np.arange(4, dtype=np.int64)])
    a.purge(s[:2].tolist())
    assert len(a) == 2
    s2 = a.slots_for([np.array([100, 101], np.int64)])
    assert set(s2.tolist()) == set(s[:2].tolist())   # recycled
    # purged keys re-insert at fresh slots when capacity allows
    with pytest.raises(CapacityExceededError):
        a.slots_for([np.array([0], np.int64)])


def test_purge_churn_tombstone_rebuild(backend):
    a = SlotAllocator(8, "t")
    for r in range(300):
        s = a.slots_for([np.arange(r * 8, r * 8 + 8, dtype=np.int64)])
        assert (s >= 0).all()
        a.purge(s.tolist())
    assert len(a) == 0
    # absent-key probe terminates and reports absence
    assert a.slots_for([np.array([-5], np.int64)],
                       lookup_only=True)[0] == -1


def test_multi_column_keys(backend):
    a = SlotAllocator(16, "t")
    k1 = np.array([1, 1, 2, 2], np.int64)
    k2 = np.array([1, 2, 1, 2], np.int32)
    s = a.slots_for([k1, k2])
    assert len(set(s.tolist())) == 4


def test_float_and_bool_key_columns(backend):
    a = SlotAllocator(16, "t")
    f = np.array([1.5, 2.5, 1.5], np.float32)
    b = np.array([True, True, False], np.bool_)
    s = a.slots_for([f, b])
    assert s[0] != s[1] and s[0] != s[2]
    s2 = a.slots_for([f, b])
    assert (s == s2).all()


def test_duplicate_keys_in_batch(backend):
    a = SlotAllocator(8, "t")
    keys = np.array([7, 7, 7, 8, 8], np.int64)
    s = a.slots_for([keys])
    assert s[0] == s[1] == s[2]
    assert s[3] == s[4] != s[0]
    assert len(a) == 2


def test_snapshot_restore_roundtrip(backend):
    a = SlotAllocator(8, "t")
    s = a.slots_for([np.arange(5, dtype=np.int64)])
    snap = a.snapshot()
    b = SlotAllocator(8, "t2")
    b.restore(snap)
    s2 = b.slots_for([np.arange(5, dtype=np.int64)])
    assert (s == s2).all()
    assert len(b) == 5
    # free slots rebuilt: 3 more keys fit
    extra = b.slots_for([np.array([100, 101, 102], np.int64)])
    assert (extra >= 0).all()


def test_journal_drain_and_apply(backend):
    a = SlotAllocator(8, "t")
    a.slots_for([np.arange(3, dtype=np.int64)])
    delta = a.drain_journal()
    assert len(delta) == 3
    a.slots_for([np.array([50], np.int64)])
    delta2 = a.drain_journal()
    assert len(delta2) == 1                     # only the new insert
    b = SlotAllocator(8, "t2")
    b.apply_journal(delta)
    b.apply_journal(delta2)
    sa = a.slots_for([np.arange(4, dtype=np.int64)])
    sb = b.slots_for([np.arange(4, dtype=np.int64)])
    assert (sa == sb).all()


def test_journal_overflow_falls_back_to_full(backend):
    a = SlotAllocator(4, "t")
    # journal capacity is min(2*cap, cap + 1M) = 8; overflow it via churn
    for r in range(5):
        s = a.slots_for([np.arange(r * 4, r * 4 + 4, dtype=np.int64)])
        a.purge(s.tolist())
    a.slots_for([np.array([999], np.int64)])
    delta = a.drain_journal()
    # overflow drains the FULL live mapping (superset of the delta)
    live = a.snapshot()
    assert {k for k, _ in delta} >= set(live.keys())


def test_width_widening_preserves_bindings(backend):
    a = SlotAllocator(16, "t")
    s32 = a.slots_for([np.arange(6, dtype=np.int32)])
    s64 = a.slots_for([np.arange(6, dtype=np.int64)])
    assert (s32 == s64).all()
    wide = a.slots_for([np.arange(6, dtype=np.int64),
                        np.zeros(6, np.int64)])
    # different (wider) key space may or may not alias; lookups stay stable
    assert (a.slots_for([np.arange(6, dtype=np.int32)]) == s32).all()
    assert (a.slots_for([np.arange(6, dtype=np.int64),
                         np.zeros(6, np.int64)]) == wide).all()


def test_native_numpy_equivalence_sequences(monkeypatch):
    """The two backends produce IDENTICAL slot assignments for the same
    operation sequence (shared hash + insertion order contract)."""
    if ks.LIB is None:
        pytest.skip("native staging library unavailable")
    rng = np.random.default_rng(11)
    ops = []
    for r in range(30):
        keys = rng.integers(0, 60, rng.integers(1, 40))
        ops.append(("slots", keys.astype(np.int64)))
        if r % 7 == 3:
            ops.append(("purge", keys.astype(np.int64)[: len(keys) // 2]))

    def run(native: bool):
        if not native:
            monkeypatch.setattr(ks, "LIB", None)
        a = SlotAllocator(64, "eq")
        out = []
        for op, keys in ops:
            if op == "slots":
                out.append(a.slots_for([keys]).copy())
            else:
                s = a.slots_for([keys], lookup_only=True)
                a.purge([int(x) for x in s if x >= 0])
        if not native:
            monkeypatch.undo()
        return out

    nat = run(True)
    py = run(False)
    for x, y in zip(nat, py):
        assert (x == y).all()


def test_group_events_by_key_layout(backend):
    slots = np.array([3, 1, 3, 2, 1, 3], np.int32)
    valid = np.ones(6, np.bool_)
    key_idx, sel, kvalid = group_events_by_key(slots, valid, pad=8)
    live = {int(key_idx[i]): [int(x) for x in sel[i] if x >= 0]
            for i in range(len(key_idx)) if key_idx[i] < 8}
    # per-key batch order preserved along E
    assert live == {1: [1, 4], 2: [3], 3: [0, 2, 5]}
    assert (kvalid == (sel >= 0)).all()


def test_group_events_by_key_all_invalid(backend):
    slots = np.array([1, 2], np.int32)
    valid = np.zeros(2, np.bool_)
    key_idx, sel, kvalid = group_events_by_key(slots, valid, pad=8)
    assert not kvalid.any()


def test_slots_and_group_fused_matches_two_pass(backend):
    a = SlotAllocator(32, "t")
    rng = np.random.default_rng(5)
    keys = rng.integers(0, 20, 64).astype(np.int64)
    valid = rng.random(64) > 0.2
    slots, key_idx, sel = a.slots_and_group([keys], valid, pad=32)
    # reference grouping from the returned slots
    k2, s2, _ = group_events_by_key(slots, valid, pad=32)
    def norm(ki, se):
        return {int(ki[i]): [int(x) for x in se[i] if x >= 0]
                for i in range(len(ki)) if ki[i] < 32}
    assert norm(key_idx, sel) == norm(k2, s2)


def test_restore_with_purged_holes(backend):
    a = SlotAllocator(8, "t")
    s = a.slots_for([np.arange(6, dtype=np.int64)])
    a.purge([int(s[1]), int(s[4])])
    snap = a.snapshot()
    b = SlotAllocator(8, "t2")
    b.restore(snap)
    assert len(b) == 4
    # the holes are free: two new keys allocate into them
    s2 = b.slots_for([np.array([100, 101], np.int64)])
    assert set(s2.tolist()) <= {int(s[1]), int(s[4])}


def test_empty_batch_is_noop(backend):
    a = SlotAllocator(8, "t")
    out = a.slots_for([np.zeros(0, np.int64)])
    assert out.shape == (0,)
    assert len(a) == 0


def test_apply_journal_rebind_wins(backend):
    """A later journal entry re-binding an occupied slot wins (the source
    recycled it)."""
    a = SlotAllocator(4, "t")
    a.apply_journal([(np.int64(1).tobytes(), 0)])
    a.apply_journal([(np.int64(2).tobytes(), 0)])    # rebind slot 0
    assert a.slots_for([np.array([2], np.int64)],
                       lookup_only=True)[0] == 0
    assert a.slots_for([np.array([1], np.int64)],
                       lookup_only=True)[0] == -1


# -- tiered grouping: a skewed batch as a few [Kb, E] rectangles -----------

def _zipf_slots(seed, n=8192, n_slots=1 << 16, exponent=1.2):
    """A batch whose keys follow Zipf: one slot with ~n/5 events, most
    with one; about 2 % of the rows invalid."""
    rng = np.random.default_rng(seed)
    cdf = np.cumsum(np.arange(1, n_slots + 1, dtype=np.float64) ** -exponent)
    ranks = np.searchsorted(cdf / cdf[-1], rng.random(n), side="right")
    return (rng.permutation(n_slots)[ranks].astype(np.int32),
            rng.random(n) > 0.02)


def _rows_by_key(groups, pad):
    """{slot: its batch rows in order} over (key_idx, sel) rectangles; a
    key in two rectangles, or unsorted within one, fails here."""
    out = {}
    for key_idx, sel in groups:
        live = key_idx[key_idx < pad]
        assert (np.diff(live) > 0).all()
        assert (key_idx[live.size:] == pad).all()
        assert (sel[live.size:] == -1).all()
        for i, k in enumerate(live.tolist()):
            assert k not in out
            row = sel[i]
            n = int((row >= 0).sum())
            assert (row[n:] == -1).all()
            out[k] = row[:n].tolist()
    return out


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_tiered_grouping_equals_the_one_rectangle_row_for_row(backend, seed):
    slots, valid = _zipf_slots(seed)
    pad = 1 << 16
    tiers, maxc = ks.tier_events_by_key(slots, valid, pad=pad)
    key_idx, sel, _ = group_events_by_key(slots, valid, pad=pad)
    assert len(tiers) == 3
    assert _rows_by_key(tiers, pad) == _rows_by_key([(key_idx, sel)], pad)
    counts = (sel >= 0).sum(1)
    assert maxc == counts.max() > 1024
    # layout cells of the order of the events, not keys x hottest count;
    # ticks (the sum of the E) under twice the hottest key's count
    cells = sum(s.size for _, s in tiers)
    assert cells <= 32 * slots.size < sel.size
    assert sum(s.shape[1] for _, s in tiers) <= 2 * maxc
    # classes by count: at most 4, at most 32, the rest
    for (lo, hi), (k, s) in zip(((0, 4), (4, 32), (32, maxc)), tiers):
        c = (s >= 0).sum(1)[k < pad]
        assert lo < c.min() and c.max() <= hi


# (exponent, key space): the layout's promises at skews and hot-set sizes
# other than the Zipf(1.2) cell's — `cells an event` is what the fixed
# cuts leave, the open top class's padding included (keyslots._TIER_CUTS)
_SKEWS = {(0.5, 1 << 12): 5, (0.8, 1 << 12): 8, (0.8, 1 << 17): 12,
          (1.0, 1 << 17): 12, (1.0, 1 << 24): 14, (1.5, 1 << 12): 35,
          (1.5, 1 << 24): 33, (2.0, 1 << 17): 65, (3.0, 1 << 12): 9}


@pytest.mark.parametrize("exponent,n_slots", sorted(_SKEWS))
def test_tiers_hold_their_promises_at_other_skews(backend, exponent,
                                                  n_slots):
    slots, valid = _zipf_slots(7, n_slots=n_slots, exponent=exponent)
    tiers, maxc = ks.tier_events_by_key(slots, valid, pad=n_slots)
    key_idx, sel, _ = group_events_by_key(slots, valid, pad=n_slots)
    assert _rows_by_key(tiers, n_slots) == \
        _rows_by_key([(key_idx, sel)], n_slots)
    assert maxc == (sel >= 0).sum(1).max()
    cells = sum(s.size for _, s in tiers)
    assert 2 <= len(tiers) <= 3 and 2 * cells < sel.size
    assert cells / slots.size <= _SKEWS[exponent, n_slots]
    assert sum(s.shape[1] for _, s in tiers) <= 36 + 2 * maxc
    for k, s in tiers:                  # the lower classes: E at most 32
        c = (s >= 0).sum(1)[k < n_slots]
        assert s.shape[1] < 2 * c.max()
        assert s.shape[1] <= 32 or s is tiers[-1][1]


def test_tiers_native_equals_numpy(monkeypatch):
    if ks.LIB is None:
        pytest.skip("native staging library unavailable")
    for seed in (4, 5):
        slots, valid = _zipf_slots(seed)
        nat, maxc = ks.tier_events_by_key(slots, valid, pad=1 << 16)
        with monkeypatch.context() as mp:
            mp.setattr(ks, "LIB", None)
            py, maxc_py = ks.tier_events_by_key(slots, valid, pad=1 << 16)
        assert maxc == maxc_py and len(nat) == len(py) == 3
        for (k1, s1), (k2, s2) in zip(nat, py):
            assert k1.dtype == k2.dtype == s1.dtype == s2.dtype == np.int32
            np.testing.assert_array_equal(k1, k2)
            np.testing.assert_array_equal(s1, s2)


@pytest.mark.parametrize("times", [1, 4, 16])
def test_a_uniform_batch_is_one_tier_identical_to_the_rectangle(backend,
                                                                times):
    """Keys that all have one count: one rectangle, the very [Kb, E] and
    cells `group_events_by_key` gives."""
    slots = np.repeat(np.arange(100, 100 + 2048, dtype=np.int32), times)
    valid = np.ones(slots.size, np.bool_)
    tiers, maxc = ks.tier_events_by_key(slots, valid, pad=1 << 16)
    key_idx, sel, _ = group_events_by_key(slots, valid, pad=1 << 16)
    assert len(tiers) == 1 and maxc == times
    np.testing.assert_array_equal(tiers[0][0], key_idx)
    np.testing.assert_array_equal(tiers[0][1], sel)
    assert sel.shape == (4096, times)


def test_a_small_or_mildly_skewed_batch_keeps_the_one_rectangle(backend):
    # far-apart counts, but a rectangle of 8 x 8 cells: not worth a tier
    slots = np.array([3] * 6 + [5] * 2, np.int32)
    tiers, maxc = ks.tier_events_by_key(slots, np.ones(8, np.bool_), pad=64)
    assert len(tiers) == 1 and tiers[0][1].shape == (8, 8) and maxc == 6
    # a large one whose counts are a factor of two apart: tiers would
    # have over half its cells
    slots = np.concatenate([
        np.repeat(np.arange(30000, dtype=np.int32), 4),
        np.repeat(np.arange(30000, 60000, dtype=np.int32), 8)])
    tiers, _ = ks.tier_events_by_key(slots, np.ones(slots.size, np.bool_),
                                     pad=1 << 16)
    assert len(tiers) == 1 and tiers[0][1].shape == (65536, 8)
    # nothing valid at all
    tiers, maxc = ks.tier_events_by_key(np.array([1, 2], np.int32),
                                        np.zeros(2, np.bool_), pad=8)
    assert len(tiers) == 1 and maxc == 0 and (tiers[0][1] == -1).all()


def test_slots_and_tiers_fused_matches_two_pass(backend):
    a = SlotAllocator(1 << 16, "t")
    slots, valid = _zipf_slots(6)
    keys = slots.astype(np.int64) * 7 + 3
    got, tiers, maxc = a.slots_and_tiers([keys], valid, pad=1 << 16)
    two, maxc2 = ks.tier_events_by_key(got, valid, pad=1 << 16)
    assert maxc == maxc2 and len(tiers) == len(two) == 3
    assert _rows_by_key(tiers, 1 << 16) == _rows_by_key(two, 1 << 16)
    # the scratch is left clean: a second, uniform batch groups as ever
    again, t2, _ = a.slots_and_tiers([keys[:64]], None, pad=1 << 16)
    assert len(t2) == 1 and (again == got[:64])[valid[:64]].all()
