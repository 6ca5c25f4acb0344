"""Rank bands (PR 31): a pattern step's emission leaves as rank bands on the
u32 wire (`pattern_planner.compact_emission`, `BandedEmission`), the header
says how many ranks hold a row, and delivery fetches only those
(`runtime._EmissionRows`).  By value against numpy references: the wire, the
bands against the flat emission over random grids, a deployed app's batch /
event / counting consumers, a tiered send and a failing tier."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.core import keyslots, runtime
from siddhi_tpu.core import pattern_planner as pp
from siddhi_tpu.observability import phases

TYPES = ("LONG", "FLOAT", "INT", "BOOL")


# -- the wire and the edges --------------------------------------------------

@pytest.mark.parametrize("R, edges", [
    (1, (0, 1)), (2, (0, 1, 2)), (4, (0, 1, 4)), (5, (0, 1, 4, 5)),
    (20, (0, 1, 4, 16, 20)), (160, (0, 1, 4, 16, 64, 160)),
    (512, (0, 1, 4, 16, 64, 256, 512))])
def test_band_edges_are_a_factor_four_apart_and_cut_off_at_r(R, edges):
    assert pp.band_edges(R) == edges


@pytest.mark.parametrize("shards", [1, 4])
def test_planes_round_trip_every_dtype_bit_for_bit(shards):
    rng = np.random.default_rng(7)
    n = 64
    arrays = [rng.integers(-2 ** 62, 2 ** 62, n, dtype=np.int64),
              rng.standard_normal(n).astype(np.float32),
              rng.integers(-2 ** 31, 2 ** 31 - 1, n, dtype=np.int32),
              rng.random(n) < 0.5]
    arrays[0][:3] = (-1, np.iinfo(np.int64).min, np.iinfo(np.int64).max)
    arrays[1][:2] = (np.nan, -0.0)
    # a buffer holds every shard's planes over the shard's own slots
    per = n // shards
    buf = np.concatenate([
        np.asarray(pl)[s * per:(s + 1) * per]
        for s in range(shards)
        for a in arrays for pl in pp.u32_planes(jnp.asarray(a))])
    assert buf.dtype == np.uint32 and buf.size == 5 * n
    back = pp.unpack_planes([buf, buf], [a.dtype for a in arrays], shards)
    for a, b in zip(arrays, back):
        assert b.dtype == a.dtype
        np.testing.assert_array_equal(np.tile(a, 2).view(np.uint8),
                                      b.view(np.uint8))


# -- bands against the flat emission, over random grids ----------------------

def grid(seed, EP, K, density):
    rng = np.random.default_rng(seed)
    B = EP * K
    valid = rng.random(B) < density
    if density >= 1:
        valid[:] = True
    cols = (rng.integers(-2 ** 40, 2 ** 40, B, dtype=np.int64),
            rng.standard_normal(B).astype(np.float32),
            rng.integers(0, 1000, B).astype(np.int32),
            rng.random(B) < 0.5)
    ts = rng.integers(0, 2 ** 41, B, dtype=np.int64)
    kind = rng.integers(0, 2, B).astype(np.int32)
    return ts, kind, valid, cols


def rank_major_reference(ts, kind, valid, cols, EP, K, R):
    """Per key the first R valid rows in grid order; rank r of every key
    before rank r + 1 of any."""
    v2 = valid.reshape(EP, K)
    rank = np.cumsum(v2, axis=0) - 1
    rows = []
    for r in range(R):
        e, k = np.nonzero(v2 & (rank == r))
        order = np.argsort(k, kind="stable")
        rows.extend(e[order] * K + k[order])
    idx = np.asarray(rows, np.int64)
    counts = v2.sum(axis=0)
    return ((ts[idx], kind[idx]) + tuple(c[idx] for c in cols),
            int(np.minimum(counts, R).max(initial=0)),
            int(np.minimum(counts, R).sum()),
            int((counts - np.minimum(counts, R)).sum()))


def decode(em, ranks_used):
    """What delivery does with a fetched BandedEmission, by hand."""
    take, ranks, cap = em.used(ranks_used)
    ts, kv = pp.unpack_planes([h for h, _ in take], pp.HEAD_DTYPES, em.shards)
    cols = pp.unpack_planes([c for _, c in take],
                            [np.int64, np.float32, np.int32, np.bool_],
                            em.shards)
    valid = (kv >> 31).astype(bool)
    return ts, (kv & 0x7FFFFFFF).astype(np.int32), valid, cols, ranks, cap


GRIDS = [(EP, K, cr, density, seed)
         for seed, (EP, K) in enumerate([(5, 1), (20, 3), (20, 64),
                                         (10, 4096), (160, 17), (40, 256)])
         for cr in (2, 8, EP, EP + 7)
         for density in (0.02, 0.4, 1.0)]


@pytest.mark.parametrize("EP, K, cr, density, seed", GRIDS)
def test_used_bands_hold_the_flat_emissions_rows_rank_major(
        EP, K, cr, density, seed):
    ts, kind, valid, cols = grid(seed, EP, K, density)
    out = tuple(jnp.asarray(x) for x in (ts, kind, valid)) + \
        (tuple(jnp.asarray(c) for c in cols),)
    R = min(cr, EP)
    flat = jax.device_get(pp.compact_emission(out, EP, K, cr))
    em = jax.device_get(pp.compact_emission(out, EP, K, cr, TYPES))
    want, ranks_used, n_valid, n_dropped = rank_major_reference(
        ts, kind, valid, cols, EP, K, R)
    (hdr, bands), = em.tiers
    # the header: the flat form's counts, and the ranks that hold a row
    assert (int(hdr[0]), int(hdr[1])) == (n_valid, n_dropped)
    assert (int(flat[0]), int(flat[1])) == (n_valid, n_dropped)
    assert int(hdr[2]) == ranks_used
    assert len(bands) == len(pp.band_edges(R)) - 1
    # the used bands put end to end: exactly those rows, rank-major
    dts, dkind, dvalid, dcols, ranks, cap = decode(em, [ranks_used])
    assert cap == R and dvalid.size == ranks * K
    assert ranks_used <= ranks <= max(4 * ranks_used, 1)
    got = (dts[dvalid], dkind[dvalid]) + tuple(c[dvalid] for c in dcols)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(g, w)
    # no row sits in a band that was not fetched
    assert decode(em, [R])[2].sum() == n_valid
    # and they are the flat emission's valid rows (the same multiset;
    # the same order wherever the flat form is rank-major too)
    fv = flat[4]
    flat_rows = (flat[2][fv], flat[3][fv]) + tuple(c[fv] for c in flat[5])
    if R < EP:
        for g, f in zip(got, flat_rows):
            np.testing.assert_array_equal(g, f)
    else:
        key = lambda rows: np.lexsort(rows[::-1])    # noqa: E731
        for g, f in zip(got, flat_rows):
            np.testing.assert_array_equal(g[key(got)], f[key(flat_rows)])


# -- through a deployed app --------------------------------------------------

APP = """
define stream S (k long, price float, stage int);
partition with (k of S) begin
@info(name='q') @emit(rows='{rows}')
from every e1=S[stage==1] -> e2=S[stage==2 and price >= e1.price]
select e1.k as k, e1.price as p1, e2.price as p2 insert into Out;
end;
"""


def deploy(rows=4):
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(APP.format(rows=rows))
    errors = []
    rt.set_exception_listener(errors.append)
    return rt, errors


def pairs_send(keys, reps, t0):
    """Every key `reps` times (stage 1 then stage 2, prices rising): one
    match a pair.  (columns, timestamps, the rows it must deliver)."""
    k = np.repeat(np.asarray(keys, np.int64), 2 * reps)
    stage = np.tile(np.array([1, 2], np.int32), len(keys) * reps)
    price = np.arange(k.size, dtype=np.float32)
    ts = t0 + np.arange(k.size, dtype=np.int64)
    want = [(int(ts[i + 1]), int(k[i]), float(price[i]), float(price[i + 1]))
            for i in range(0, k.size, 2)]
    return [k, price, stage], ts, want


def masked(b):
    v = b["valid"]
    c = b["cols"]
    return sorted(zip(b["ts"][v].tolist(), c["k"][v].tolist(),
                      c["p1"][v].tolist(), c["p2"][v].tolist()))


def fetch_log(monkeypatch):
    log = []
    real = phases.fetch

    def spy(stats, query, what, tree, mult=1, **meta):
        log.append((what, dict(meta)))
        return real(stats, query, what, tree, mult, **meta)
    monkeypatch.setattr(phases, "fetch", spy)
    return log


@pytest.mark.parametrize("reps, ranks", [(1, 1), (2, 4), (4, 4)])
def test_a_batch_payload_holds_the_fetched_bands_only(monkeypatch, reps,
                                                      ranks):
    rt, errors = deploy(rows=4)
    got = []
    rt.add_batch_callback("q", lambda now, b: got.append(
        (b["valid"].size, masked(b), b["n_valid"], b["n_dropped"])))
    rt.start()
    log = fetch_log(monkeypatch)
    cols, ts, want = pairs_send(range(8), reps, 1000)
    rt.get_input_handler("S").send_columns(cols, timestamps=ts)
    rt.shutdown()
    assert not errors
    (slots, rows, n_valid, n_dropped), = got
    # R = 4 ranks x 8 keys = 32 slots flat; the bands below ranks_used —
    # decoded slot for slot or, where at most half of them are rows, the
    # rows alone, `valid` all True (PR 55: `_EmissionRows.sparse`)
    fetched = ranks * 8
    assert fetched < 4 * 8 + (ranks == 4)
    assert slots == (len(want) if 2 * len(want) <= fetched else fetched)
    assert rows == sorted(want) and (n_valid, n_dropped) == (len(want), 0)
    assert [w for w, _ in log] == ["header", "rows", "rows"]
    assert all(m == {"ranks": ranks, "ranks_cap": 4} for w, m in log[1:])


def test_an_event_callback_gets_the_rows_in_timestamp_order(monkeypatch):
    rt, errors = deploy(rows=4)
    events = []
    rt.add_callback("q", lambda ts, cur, exp: events.extend(cur or []))
    rt.start()
    log = fetch_log(monkeypatch)
    cols, ts, want = pairs_send([5, 3, 9], 3, 2000)
    rt.get_input_handler("S").send_columns(cols, timestamps=ts)
    rt.shutdown()
    assert not errors
    got = [(e.timestamp, *e.data) for e in events]
    assert got == sorted(want)
    assert [w for w, _ in log] == ["header", "rows"]      # one payload fetch


def test_a_counting_consumer_fetches_no_rows(monkeypatch):
    rt, errors = deploy(rows=4)
    counts = []
    rt.add_batch_callback("q", lambda now, b: counts.append(
        (b["n_valid"], b["n_current"], b["n_expired"], b["n_dropped"])))
    rt.start()
    log = fetch_log(monkeypatch)
    cols, ts, want = pairs_send(range(8), 2, 3000)
    rt.get_input_handler("S").send_columns(cols, timestamps=ts)
    rt.shutdown()
    assert not errors and counts == [(16, 16, 0, 0)]
    assert [w for w, _ in log] == ["header"]


def test_rows_over_the_cap_are_dropped_and_counted_as_before():
    rt, errors = deploy(rows=2)
    got = []
    rt.add_batch_callback("q", lambda now, b: got.append(
        (b["valid"].size, masked(b), b["n_valid"], b["n_dropped"])))
    rt.start()
    cols, ts, want = pairs_send(range(8), 3, 4000)
    rt.get_input_handler("S").send_columns(cols, timestamps=ts)
    rt.shutdown()
    (slots, rows, n_valid, n_dropped), = got
    # each key's first two matches, the third counted as dropped
    keep = sorted(w for i, w in enumerate(want) if i % 3 < 2)
    assert slots == 2 * 8 and rows == keep
    assert (n_valid, n_dropped) == (16, 8)


def test_phase_report_sums_the_ranks_fetched_under_d2h_drain():
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(
        "@app:statistics('BASIC')\n" + APP.format(rows=4))
    rt.add_batch_callback("q", lambda now, b: b["valid"])
    rt.start()
    h = rt.get_input_handler("S")
    for i in range(3):
        cols, ts, _ = pairs_send(range(8), 1, 5000 + 100 * i)
        h.send_columns(cols, timestamps=ts)
    drain = rt.phase_report()["queries"]["q"]["phases"]["d2h_drain"]
    rt.shutdown()
    # three sends, one `rows` fetch each (the mask): 1 of 4 ranks
    assert drain["layout"] == {"ranks": 3, "ranks_cap": 12}


# -- a tiered send -----------------------------------------------------------

def zipf_like_send(t0):
    """Three classes of keys: 40 keys once (a pair), 6 keys 8 pairs, one
    key 40 pairs — tiers of E <= 4, <= 32 and more once the rectangle
    rule is out of the way."""
    k = np.concatenate([np.repeat(np.arange(100, 140), 2),
                        np.repeat(np.arange(10, 16), 16),
                        np.repeat(np.array([3]), 80)]).astype(np.int64)
    stage = np.tile(np.array([1, 2], np.int32), k.size // 2)
    rng = np.random.default_rng(11)
    perm = rng.permutation(k.size // 2)
    idx = np.stack([2 * perm, 2 * perm + 1], 1).reshape(-1)
    # pairs stay adjacent and ordered per key; keys interleave
    k, stage = k[idx], stage[idx]
    price = np.arange(k.size, dtype=np.float32)
    ts = t0 + np.arange(k.size, dtype=np.int64)
    want = [(int(ts[i + 1]), int(k[i]), float(price[i]), float(price[i + 1]))
            for i in range(0, k.size, 2)]
    return [k, price, stage], ts, want


@pytest.fixture
def tiering(monkeypatch):
    monkeypatch.setattr(keyslots, "_TIER_MIN_CELLS", 0)


def test_a_three_tier_send_is_one_delivery_of_all_tiers_bands(
        tiering, monkeypatch):
    rt, errors = deploy(rows=64)
    batches, events, calls = [], [], []
    rt.add_batch_callback("q", lambda now, b: batches.append(
        (b["valid"].size, masked(b), b["n_valid"])))
    rt.add_callback("q", lambda ts, cur, exp: (
        calls.append(len(cur or [])), events.extend(cur or [])))
    rt.start()
    log = fetch_log(monkeypatch)
    cols, ts, want = zipf_like_send(6000)
    rt.get_input_handler("S").send_columns(cols, timestamps=ts)
    rt.shutdown()
    assert not errors
    (slots, rows, n_valid), = batches            # ONE delivery
    assert rows == sorted(want) and n_valid == len(want) == 128
    assert calls == [128]
    assert [(e.timestamp, *e.data) for e in events] == sorted(want)
    # header once (three tiers' headers in one fetch), then the payload
    assert [w for w, _ in log][0] == "header"
    assert all(w == "rows" for w, _ in log[1:])
    meta = log[1][1]
    # three tiers: R = min(64, E x 9) = 18 / 64 / 64 ranks (E = 2, 16,
    # 128; 8 slots a key); fetched: the bands below 1, 8 and 40 rows a
    # key = 1 + 16 + 64 ranks
    assert meta == {"ranks": 1 + 16 + 64, "ranks_cap": 18 + 64 + 64}
    # over [64, 2], [8, 16] and [1, 128] rectangles of keys
    fetched = 1 * 64 + 16 * 8 + 64 * 1
    assert fetched < 18 * 64 + 64 * 8 + 64 * 1
    # half of the fetched slots are rows: the payload is the rows alone
    assert 2 * n_valid == fetched and slots == n_valid


def test_a_failing_later_tier_still_delivers_the_earlier_tiers_rows(
        tiering, monkeypatch):
    rt, errors = deploy(rows=64)
    batches = []
    rt.add_batch_callback("q", lambda now, b: batches.append(masked(b)))
    rt.start()
    real = runtime.PatternQueryRuntime._step
    n = {"calls": 0}

    def failing(self, step, *args):
        n["calls"] += 1
        if n["calls"] == 3:                     # the third tier's dispatch
            raise RuntimeError("tier 3 refused")
        return real(self, step, *args)
    monkeypatch.setattr(runtime.PatternQueryRuntime, "_step", failing)
    cols, ts, want = zipf_like_send(7000)
    rt.get_input_handler("S").send_columns(cols, timestamps=ts)
    rt.shutdown()
    assert any("tier 3 refused" in str(e) for e in errors)
    # the hot tier (key 3) and the mid tier (keys 10..15) went first: each
    # delivered before the error; the cold tier's keys were not applied
    got = sorted(r for b in batches for r in b)
    assert len(batches) == 2
    assert got == sorted(w for w in want if w[1] < 100)
# -- the sharded step on the virtual four-device mesh ------------------------

# -- the sharded step on the virtual four-device mesh --------------------------

def test_bands_over_four_shards_and_ranks_used_is_the_max_over_them(
        monkeypatch):
    if len(jax.devices()) < 8:
        pytest.skip("needs the 8 virtual devices of tests/conftest.py")
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(
        "@app:mesh(shards='4')\n" + APP.format(rows=4))
    errors, got = [], []
    rt.set_exception_listener(errors.append)
    rt.add_batch_callback("q", lambda now, b: got.append(
        (b["valid"].size, masked(b), b["n_valid"])))
    events = []
    rt.add_callback("q", lambda ts, cur, exp: events.append(
        [(e.timestamp, *e.data) for e in cur or []]))
    rt.start()
    qr = rt.query_runtimes["q"]
    assert qr.planned.mesh is not None and qr.planned.banded_emission
    log = fetch_log(monkeypatch)
    h = rt.get_input_handler("S")
    # every key once: one rank used on every shard
    cols, ts, want1 = pairs_send(range(16), 1, 8000)
    h.send_columns(cols, timestamps=ts)
    # then one key (one shard's) three times, the others once
    c2, t2, w2 = pairs_send(range(16), 1, 9000)
    c3, t3, w3 = pairs_send([5], 2, 9100)
    h.send_columns([np.concatenate([a, b]) for a, b in zip(c2, c3)],
                   timestamps=np.concatenate([t2, t3]))
    rt.shutdown()
    assert not errors
    (s1, rows1, n1), (s2, rows2, n2) = got
    assert rows1 == sorted(want1) and n1 == 16
    assert rows2 == sorted(w2 + w3) and n2 == 18
    assert events == [sorted(want1), sorted(w2 + w3)]
    metas = [m_ for w, m_ in log if w == "rows"]
    # the max over shards decides for all of them: 1 rank, then 3 -> the
    # bands [0,1) and [1,4); a band's buffer holds every shard's slots
    assert [m_["ranks"] for m_ in metas] == [1, 1, 1, 4, 4, 4]
    assert all(m_["ranks_cap"] == 4 for m_ in metas)
    # the second delivery's 18 rows of 4 x s1 fetched slots: the rows alone
    assert s1 % 4 == 0 and s2 == (n2 if 2 * n2 <= 4 * s1 else 4 * s1)


# -- a rectangle that is mostly filler is decoded by its rows (PR 55) -----------

@pytest.mark.parametrize("shards", [1, 4])
def test_the_valid_slots_alone_decode_to_what_the_mask_would_keep(shards):
    """`unpack_planes_at` over `valid_slots` = `unpack_planes` then the
    `valid` mask, every dtype the wire carries (an int64 as two words, a
    bool as a word), bands end to end, every shard's slots in place."""
    from siddhi_tpu.core.pattern_planner import (
        HEAD_DTYPES, unpack_planes, unpack_planes_at, valid_slots)
    rng = np.random.default_rng(shards)
    dtypes = (np.int64, np.float32, np.bool_, np.int32)
    m = 8 * shards
    heads, cols = [], []
    for ranks in (1, 3, 12):
        n = ranks * m
        valid = rng.random(n) < 0.2
        kind = rng.integers(0, 2, n).astype(np.uint32)
        planes = [rng.integers(0, 2 ** 32, n, dtype=np.uint64).astype(
            np.uint32) for _ in range(2)] + [kind | (
                valid.astype(np.uint32) << 31)]
        body = [rng.integers(0, 2 ** 32, n, dtype=np.uint64).astype(
            np.uint32) for _ in range(5)]
        body[3] = rng.integers(0, 2, n).astype(np.uint32)     # the bool
        for bufs, pl in ((heads, planes), (cols, body)):
            bufs.append(np.stack([p.reshape(shards, -1) for p in pl], 1)
                        .reshape(-1))
    keeps = valid_slots(heads, shards)
    ts, kv = unpack_planes(heads, HEAD_DTYPES, shards)
    sel = (kv >> 31) != 0
    assert np.array_equal(np.concatenate(keeps), np.concatenate([
        np.flatnonzero(sel[lo:lo + r * m]) for lo, r in
        ((0, 1), (m, 3), (4 * m, 12))]))
    for bufs, dts in ((heads, HEAD_DTYPES), (cols, dtypes)):
        want = [a[sel] for a in unpack_planes(bufs, dts, shards)]
        got = unpack_planes_at(bufs, dts, keeps, shards)
        assert [g.dtype for g in got] == [w.dtype for w in want]
        # bit for bit (random words read as float32 hold NaNs)
        assert all(g.tobytes() == w.tobytes() for g, w in zip(got, want))
    assert 0 < sel.sum() < sel.size / 2


def test_a_sparse_payload_is_the_rows_in_the_dense_payloads_order(
        monkeypatch):
    """One key with four rows among eight with one: 32 slots fetched, 11
    rows — the payload is the 11, in the order the 32 slots hold them, every
    column, whichever of head and cols is read first."""
    from siddhi_tpu.core import runtime as rtm
    payloads = {}
    for sparse in (True, False):
        if not sparse:
            real = rtm._EmissionRows.__init__

            def dense(self, qr, out, ranks_used=None, n_valid=None):
                real(self, qr, out, ranks_used, None)
            monkeypatch.setattr(rtm._EmissionRows, "__init__", dense)
        for first in ("valid", "cols"):
            rt, errors = deploy(rows=4)
            got = []

            def on_batch(_now, b, first=first):
                b[first]
                got.append((b["ts"][b["valid"]], b["kind"][b["valid"]],
                            {n: np.asarray(c)[b["valid"]]
                             for n, c in b["cols"].items()},
                            b["valid"].size))
            rt.add_batch_callback("q", on_batch)
            rt.start()
            c1, t1, _w = pairs_send(range(8), 1, 1000)
            c2, t2, _w = pairs_send([5], 3, 1100)
            rt.get_input_handler("S").send_columns(
                [np.concatenate([a, b]) for a, b in zip(c1, c2)],
                timestamps=np.concatenate([t1, t2]))
            rt.shutdown()
            assert not errors
            (payloads[sparse, first],) = got
    for first in ("valid", "cols"):
        (ts, kind, cols, size), (ts0, kind0, cols0, size0) = \
            payloads[True, first], payloads[False, first]
        assert (size, size0) == (11, 32)
        assert np.array_equal(ts, ts0) and np.array_equal(kind, kind0)
        assert all(np.array_equal(cols[n], cols0[n]) for n in cols0)
