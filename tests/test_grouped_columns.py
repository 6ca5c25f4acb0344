"""The one-chip sequential pattern programs take the event columns grouped
by the host (`runtime._group_columns`: a numpy `take` by the `sel` the host
has already computed, or the staged buffers themselves where `sel` is the
identity) and reshape them; they gather nothing.  By value: state planes,
emission header, rows and `wake` of every send equal, bit for bit, what
the programs that gather the staged `[B]` batch on the device return.  By
cost, clocklessly: an identity `sel` copies nothing and uploads as many
buffers as the parent did; the span says which way a send went."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.core import event as ev
from siddhi_tpu.core import pattern_planner
from siddhi_tpu.core import runtime as rtm

T0 = 1_760_000_000_000          # epoch milliseconds
KEYS = 2048

PART_QL = """
@app:playback %s
define stream T (key long, price float, volume int);
partition with (key of T)
begin
  @capacity(keys='2048', slots='4') @emit(rows='4') @info(name='q')
  from every e1=T[volume == 1] -> e2=T[volume == 2 and price >= e1.price]
  select e1.key as k, e1.price as p1, e2.price as p2 insert into M;
end;
"""
# a logical pair keeps the query off the block step: an unpartitioned
# pattern on the scan program, its one key's events along E
PLAIN_QL = """
@app:playback
define stream T (key long, price float, volume int);
@info(name='q')
from every (e1=T[volume == 1] and e2=T[volume == 2]) -> e3=T[volume == 3]
select e1.price as p1, e2.price as p2, e3.price as p3 insert into M;
"""


def rows(keys, vol, ts, price=10.0):
    k = np.asarray(list(keys), np.int64)
    return ([k, np.full(k.shape, price, np.float32) + (k % 7).astype(
        np.float32), np.zeros(k.shape, np.int32) + np.asarray(vol, np.int32)],
        np.asarray(ts, np.int64) + np.zeros(k.shape, np.int64))


def both_stages(keys, ts, times=1):
    """Each key's two stages, consecutive, `times` over: as many matches
    a key a send."""
    k = np.repeat(np.asarray(list(keys), np.int64), 2 * times)
    return rows(k, np.tile([1, 2], k.size // 2), ts)


def block(ts, keys=range(512)):
    """512 keys x 4 events: the [512, 4] bucket and the 2,048-row batch
    bucket, both full."""
    return both_stages(keys, ts, times=2)


def holes(batch, valid):
    """The batch as a StagedBatch whose `valid` has holes in it."""
    cols_, ts = batch
    valid = np.asarray(valid, np.bool_)
    return ev.StagedBatch(ts.copy(), np.zeros(ts.shape, np.int32), valid,
                          [c.copy() for c in cols_], int(valid.size))


PERM = np.random.default_rng(29).permutation(KEYS)
HOT = np.array([3, 700, 3, 3, 41, 3, 3, 9, 3, 3, 1500, 3, 3, 3, 8, 3, 3, 3,
                5])                      # key 3 thirteen times among six
# name: (app, warm-up sends, [first send, second send], expected `grouped`)
CASES = {
    # contiguous keys, each one's events consecutive: `sel` lists the
    # batch in order
    "contiguous_identity": (PART_QL % "", [], [block(T0), block(T0 + 10)],
                            "view"),
    # the same block again and again: the memo's entry carries the fact
    "memo_hit_identity": (PART_QL % "", [block(T0 - 20), block(T0 - 10)],
                          [block(T0), block(T0 + 10)], "view"),
    # bound slots, then keys from a permutation: `sel` points all over
    "permuted_keys": (PART_QL % "", [rows(range(KEYS), 0, T0 - 10)], [
        both_stages(PERM[:300], T0), both_stages(PERM[100:400], T0 + 10)],
        "take"),
    # E bucket 16 for one key's 13 events: padding cells and padding keys
    "hot_key": (PART_QL % "", [rows(range(KEYS), 0, T0 - 10)], [
        rows(HOT, 1 + np.arange(HOT.size) % 2, T0 + np.arange(HOT.size)),
        rows(HOT[::-1], 1 + np.arange(HOT.size) % 2,
             T0 + 50 + np.arange(HOT.size))], "take"),
    # rows the batch itself marks invalid, in the middle of it
    "invalid_rows": (PART_QL % "", [rows(range(16), 0, T0 - 10)], [
        holes(both_stages(range(8), T0), [1, 1, 0, 1, 1, 0, 0, 1] * 2),
        holes(both_stages(range(8), T0 + 10), [0, 1, 1, 1, 1, 1, 0, 1] * 2)],
        "take"),
    # no partition: `_identity_sel` over a full bucket ...
    "unpartitioned_full": (PLAIN_QL, [], [
        rows([0] * 8, [1, 2, 3, 1, 2, 2, 3, 3], T0 + np.arange(8)),
        rows([0] * 8, [2, 1, 3, 3, 1, 2, 3, 1], T0 + 10 + np.arange(8))],
        "view"),
    # ... and the where() selection over one with padding rows
    "unpartitioned_padded": (PLAIN_QL, [], [
        rows([0] * 5, [1, 2, 3, 1, 2], T0 + np.arange(5)),
        rows([0] * 6, [3, 2, 1, 3, 1, 2], T0 + 10 + np.arange(6))], "take"),
    # a batch spanning 2**31 ms or more: the int64 delta is grouped too
    "wide_delta": (PART_QL % "", [rows(range(KEYS), 0, T0 - 10)], [
        both_stages(PERM[:6], T0 + np.repeat([0, 2**33, 5, 7, 2**31, 9], 2)),
        both_stages(PERM[3:9], T0 + 2**34 + np.arange(12))], "take"),
}


def gather_on_device(fn):
    """A grouped program, fed as the parent fed its own: the staged `[B]`
    columns and delta, gathered by the clipped `sel` on the device, then
    the very function `fn` traces."""
    inner = fn.__wrapped__

    def step(packed, sel_state, raw_cols, ts_base, ts_delta, sel_idx,
             key_ref, now, in_tabs=()):
        csel = jnp.clip(sel_idx, 0, ts_delta.shape[0] - 1)
        return inner(packed, sel_state,
                     tuple(c[csel].reshape(-1) for c in raw_cols), ts_base,
                     ts_delta[csel].reshape(-1), sel_idx, key_ref, now,
                     in_tabs)
    return jax.jit(step, donate_argnums=(0, 1))


def recording(fn, log):
    def call(*args):
        res = fn(*args)
        log.append(jax.device_get(res))   # state, selector, out, wake
        return res
    call._siddhi_role = getattr(fn, "_siddhi_role", "step")
    return call


def deploy(text, gathering):
    """A runtime whose pattern programs record what they return.
    `gathering`: the plan ships the staged batch and its programs gather
    on the device — `steps` and `dense_steps` the grouped programs behind
    a device gather (PR 31: the planner's own gather wrapper, `raw_steps`,
    keeps the flat emission for @fuse, so it is no longer the twin)."""
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(text)
    errors, got, log = [], [], []
    rt.set_exception_listener(errors.append)
    rt.add_callback("q", lambda ts, i, o: got.extend(
        [(int(e.timestamp), *[float(x) for x in e.data]) for e in (i or [])]))
    rt.start()
    qr = rt.query_runtimes["q"]
    p = qr.planned
    assert p.grouped_input and p.dense_steps is not None
    steps, dense = p.steps, p.dense_steps
    if gathering:
        steps = {sid: gather_on_device(fn) for sid, fn in steps.items()}
        dense = {sid: gather_on_device(fn) for sid, fn in dense.items()}
    qr.planned = dataclasses.replace(
        p, grouped_input=not gathering,
        steps={s: recording(f, log) for s, f in steps.items()},
        dense_steps={s: recording(f, log) for s, f in dense.items()})
    # a cap growth would re-plan and drop the doctored programs
    qr._replan = None
    return m, rt, qr, got, errors, log


def drive(rt, qr, batches):
    h = rt.get_input_handler("T")
    for b in batches:
        if isinstance(b, ev.StagedBatch):
            qr.process_staged("T", b, int(b.ts.max()))
        else:
            c, ts = b
            h.send_columns([x.copy() for x in c], timestamps=ts.copy())
        rt.flush()


@pytest.fixture()
def grouped_log(monkeypatch):
    """(identity?, grouped columns, staged columns) of every host
    grouping."""
    seen = []
    real = rtm._group_columns

    def spy(sel, identity, cols, ts_delta):
        out = real(sel, identity, cols, ts_delta)
        seen.append((identity, out, (cols, ts_delta)))
        return out
    monkeypatch.setattr(rtm, "_group_columns", spy)
    return seen


@pytest.mark.parametrize("case", sorted(CASES))
def test_host_grouping_equals_the_device_gather(case, grouped_log):
    text, warm, sends, grouped = CASES[case]
    m, rt, qr, got, errors, log = deploy(text, gathering=False)
    m2, rt2, qr2, got2, errors2, log2 = deploy(text, gathering=True)
    try:
        drive(rt, qr, warm)
        drive(rt2, qr2, warm)
        del log[:], log2[:], grouped_log[:]
        drive(rt, qr, sends)
        n_grouped = len(grouped_log)
        drive(rt2, qr2, sends)
        assert not errors and not errors2, (errors, errors2)
        # the host grouped each send of the first runtime, none of the
        # second's, and the way the case says
        assert n_grouped == len(grouped_log) == 2
        assert [g[0] for g in grouped_log] == [grouped == "view"] * 2
        # two consecutive sends, one program call each: everything the
        # program returns — the packed planes, the selector's state, the
        # emission (header, rows) and the wake — bit for bit
        assert len(log) == len(log2) == 2
        for a, b in zip(log, log2):
            la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
            assert len(la) == len(lb) and len(la) > 6
            for x, y in zip(la, lb):
                assert x.dtype == y.dtype and x.shape == y.shape
                np.testing.assert_array_equal(x, y)
        assert got == got2 and got
        # the wide send's int64 delta rode the same programs
        if case == "wide_delta":
            assert [str(g[1][1].dtype) for g in grouped_log] == \
                ["int64", "int32"]
    finally:
        m.shutdown()
        m2.shutdown()


def test_hot_key_layout_is_larger_than_the_batch(grouped_log):
    """Kb * E > B: the grouped columns carry the padding cells, row 0's
    values in each, as the device gather by the clipped `sel` did."""
    text, warm, sends, _ = CASES["hot_key"]
    m, rt, qr, _got, errors, _log = deploy(text, gathering=False)
    try:
        drive(rt, qr, warm + sends[:1])
        assert not errors
        identity, (cols, delta), (staged, staged_delta) = grouped_log[-1]
        B = staged_delta.shape[0]
        assert not identity and B == 32
        assert delta.shape == (8 * 16,) and all(
            c.shape == (8 * 16,) for c in cols)
        # six keys in a bucket of 8, 19 events in 128 cells
        key = cols[0].reshape(8, 16)
        assert (key[0] == 3).all() and (key[:6, 0] != key[0, 0]).sum() == 5
        n_pad = 128 - HOT.size
        for c, s in zip(cols, staged):
            assert (c == s[0]).sum() >= n_pad
    finally:
        m.shutdown()


# -- clockless cost guard ----------------------------------------------------

@pytest.fixture()
def uploads(monkeypatch):
    """Every `jnp.asarray` call the runtime module makes."""
    calls = []
    real = jax.numpy.asarray

    def counting(x, *a, **k):
        calls.append(x)
        return real(x, *a, **k)
    monkeypatch.setattr(rtm.jax.numpy, "asarray", counting)
    return calls


def route_keys_stats(monkeypatch):
    """What each `route_keys` span was given as stats."""
    seen = []
    real = rtm._phases.phase

    class Spy:
        def __init__(self, span, meta):
            self.span, self.meta = span, meta

        def __enter__(self):
            self.span.__enter__()
            return self

        def __exit__(self, *exc):
            return self.span.__exit__(*exc)

        def set_metadata(self, **kw):
            self.meta.update(kw)
            self.span.set_metadata(**kw)

    def phase(stats, query, name, *a, **meta):
        span = real(stats, query, name, *a, **meta)
        if name != "route_keys":
            return span
        seen.append(meta)
        return Spy(span, meta)
    monkeypatch.setattr(rtm._phases, "phase", phase)
    return seen


def test_identity_block_copies_nothing_and_uploads_what_the_parent_did(
        monkeypatch, grouped_log, uploads):
    stats = route_keys_stats(monkeypatch)
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(PART_QL % "@app:statistics('BASIC')")
    errors = []
    rt.set_exception_listener(errors.append)
    rt.add_batch_callback("q", lambda ts, b: None)
    rt.start()
    h = rt.get_input_handler("T")
    try:
        qr = rt.query_runtimes["q"]
        for i in range(3):          # bind and memoise, hit, hit
            c, ts = block(T0 + 10 * i)
            del uploads[:]
            h.send_columns(c, timestamps=ts)
            rt.flush()
        assert not errors
        assert [(s["memo_hit"], s["grouped"]) for s in stats] == \
            [(0, "view"), (1, "view"), (1, "view")]
        assert next(iter(qr._block_cache.values()))[1][0][2] is True
        # the grouped buffers ARE the staged ones
        identity, (cols, delta), (staged, staged_delta) = grouped_log[-1]
        assert identity and delta is staged_delta
        for c, s in zip(cols, staged):
            assert c is s and np.shares_memory(c, s)
        # the send's upload calls: three columns, the delta, sel, the
        # dense step's key_lo, now — the parent's seven, the same buffers
        # (the columns, `[B]`; sel, `[Kb, E]`)
        assert len(uploads) == 7
        assert [np.shape(u) for u in uploads] == \
            [(2048,)] * 4 + [(512, 4), (), ()]
        assert all(u is s for u, s in zip(uploads, staged))
        # a permuted block of as many rows: a take, into buffers of its own
        c, ts = block(T0 + 100, np.random.default_rng(1).permutation(512))
        h.send_columns(c, timestamps=ts)
        rt.flush()
        assert not errors and stats[-1]["grouped"] == "take"
        identity, (cols, _d), (staged, _sd) = grouped_log[-1]
        assert not identity
        assert not any(np.shares_memory(c, s) for c, s in zip(cols, staged))
        # ... and phase_report() counts both under stage_host's part
        node = rt.phase_report()["queries"]["q"]["phases"]["stage_host"]
        assert node["parts"]["route_keys"]["grouped"] == \
            {"view": 3, "take": 1}
    finally:
        m.shutdown()


def test_is_identity_sel_rejects_near_misses():
    ident = rtm._identity_sel(64)[0].reshape(16, 4)
    assert rtm._is_identity_sel(ident, 64)
    assert rtm._is_identity_sel(rtm._identity_sel(64), 64)
    swapped = ident.copy()
    swapped[3, 1], swapped[3, 2] = ident[3, 2], ident[3, 1]
    assert not rtm._is_identity_sel(swapped, 64)
    assert not rtm._is_identity_sel(ident, 128)          # padding rows
    padded = np.full((16, 8), -1, np.int32)
    padded[:, :4] = ident
    assert not rtm._is_identity_sel(padded, 64)          # padding cells
    cols_ = [np.arange(64, dtype=np.int64), np.arange(64, dtype=np.float32)]
    delta = np.arange(64, dtype=np.int32)
    out, d = rtm._group_columns(swapped, False, cols_, delta)
    np.testing.assert_array_equal(d, swapped.reshape(-1))
    np.testing.assert_array_equal(out[0], swapped.reshape(-1))
    out, d = rtm._group_columns(padded, False, cols_, delta)
    # a padding cell carries row 0's value: the clipped gather's
    np.testing.assert_array_equal(d.reshape(16, 8)[:, 4:], 0)
    np.testing.assert_array_equal(d.reshape(16, 8)[:, :4], ident)


# -- a skewed send: tiers against the one rectangle ---------------------------

def zipf_send(seed, ts, n=1024):
    """1,024 events whose keys follow Zipf(1.2) over the 2,048 bound keys
    (one key ~230 times, hundreds once), stages alternating per key."""
    rng = np.random.default_rng(seed)
    cdf = np.cumsum(np.arange(1, KEYS + 1, dtype=np.float64) ** -1.2)
    k = PERM[np.searchsorted(cdf / cdf[-1], rng.random(n), side="right")]
    order = np.argsort(k, kind="stable")
    within = np.empty(n, np.int64)
    _, first, counts = np.unique(k[order], return_index=True,
                                 return_counts=True)
    within[order] = np.arange(n) - np.repeat(first, counts)
    return rows(k, 1 + within % 2, ts + np.arange(n) // 256,
                price=rng.random(n).astype(np.float32))


def test_a_tiered_send_equals_the_one_rectangle_send(monkeypatch):
    """The same skewed sends through two runtimes, one laying each out as
    tiers (three dispatches of one step), one as the single [Kb, E]
    rectangle: the same events delivered, the state planes equal bit for
    bit, and the tiers' spans say what they are."""
    text = PART_QL.replace("@emit(rows='4')", "@emit(rows='128')") % \
        "@app:statistics('BASIC')"
    warm = [rows(range(KEYS), 0, T0 - 10)]
    sends = [zipf_send(s, T0 + 10 * s) for s in range(3)]
    calls = {}
    for mode in ("tiers", "rectangle"):
        if mode == "rectangle":
            from siddhi_tpu.core import keyslots
            monkeypatch.setattr(keyslots, "_TIER_MIN_CELLS", 1 << 40)
        m, rt, qr, got, errors, log = deploy(text, gathering=False)
        try:
            drive(rt, qr, warm)
            del log[:], got[:]
            drive(rt, qr, sends)
            assert not errors, errors[:1]
            node = rt.phase_report()["queries"]["q"]["phases"]["stage_host"]
            calls[mode] = (len(log), sorted(got),
                           jax.device_get(qr.state),
                           node["parts"]["route_keys"]["layout"],
                           rt.statistics().get("counters", {}))
        finally:
            m.shutdown()
    n_t, got_t, state_t, lay_t, ctr_t = calls["tiers"]
    n_r, got_r, state_r, lay_r, ctr_r = calls["rectangle"]
    assert (n_t, n_r) == (9, 3)                  # three tiers a send
    assert got_t == got_r and len(got_t) > 500
    assert ctr_t.get("q.dropped", 0) == ctr_r.get("q.dropped", 0) == 0
    for x, y in zip(jax.tree.leaves(state_t), jax.tree.leaves(state_r)):
        np.testing.assert_array_equal(x, y)
    # warm-up send + three sends; the rectangle is [512, 256] a send
    assert lay_r["tiers"] == 4 and lay_t["tiers"] == 1 + 9
    assert lay_r["cells"] - lay_t["cells"] > 3 * 100000
    assert lay_t["max_e"] == lay_r["max_e"] > 3 * 128
    assert lay_t["ticks"] <= 1 + 2 * lay_t["max_e"]


def test_a_tiered_send_is_delivered_as_one_emission_in_timestamp_order(
        monkeypatch):
    """A skewed send's tiers leave as ONE emission: one header and one
    payload fetch pair, one batch callback, one event callback whose rows
    are in timestamp order over ALL of the send's keys — the sequence the
    one-rectangle send delivers, not its multiset alone."""
    text = PART_QL.replace("@emit(rows='4')", "@emit(rows='128')") % ""
    warm = [rows(range(KEYS), 0, T0 - 10)]
    sends = []
    for s in range(3):
        c, ts = zipf_send(s, T0 + 5000 * s)
        sends.append((c, T0 + 5000 * s + np.arange(ts.size)))
    seen = {}
    for mode in ("tiers", "rectangle"):
        if mode == "rectangle":
            from siddhi_tpu.core import keyslots
            monkeypatch.setattr(keyslots, "_TIER_MIN_CELLS", 1 << 40)
        m, rt, qr, got, errors, log = deploy(text, gathering=False)
        calls, batches, fetches = [], [], []
        rt.add_callback("q", lambda ts, i, o: calls.append(
            [(int(e.timestamp), *[float(x) for x in e.data])
             for e in (i or [])]))
        rt.add_batch_callback("q", lambda now, payload: batches.append(
            int(payload["n_valid"])))
        real = rtm._phases.fetch
        monkeypatch.setattr(
            rtm._phases, "fetch",
            lambda st, q, what, tree, mult=1, **meta: fetches.append(what)
            or real(st, q, what, tree, mult, **meta))
        try:
            drive(rt, qr, warm)
            del log[:], got[:], calls[:], batches[:], fetches[:]
            drive(rt, qr, sends)
            assert not errors, errors[:1]
            seen[mode] = (len(log), calls, batches, fetches)
        finally:
            monkeypatch.setattr(rtm._phases, "fetch", real)
            m.shutdown()
    steps, calls, batches, fetches = seen["tiers"]
    assert steps == 9 and seen["rectangle"][0] == 3
    # one emission a send: one event callback, one batch callback, and
    # the header + the rows of the event delivery, as a rectangle's
    assert len(calls) == len(batches) == 3
    assert fetches == seen["rectangle"][3] == ["header", "rows"] * 3
    assert batches == [len(c) for c in calls] == seen["rectangle"][2]
    for call in calls:
        stamps = [row[0] for row in call]
        assert stamps == sorted(stamps)
        # an event may release several partials: equal stamps, one key
        assert len({row[:2] for row in call}) == len(set(stamps))
        assert len({row[1] for row in call}) > 50       # over many keys
    assert calls == seen["rectangle"][1]


def test_a_tier_that_fails_loses_no_match_of_the_tiers_before_it():
    """A send is dispatched tier by tier, the hottest keys first.  Where a
    later tier's step raises, the earlier tiers have advanced their keys'
    state: what they matched is delivered before the error is reported,
    the failing tier's keys and the ones after it are untouched, and the
    next send runs as if nothing had happened to them."""
    text = PART_QL.replace("@emit(rows='4')", "@emit(rows='128')") % ""
    warm = [rows(range(KEYS), 0, T0 - 10)]
    send = zipf_send(0, T0)
    # what every key's events match by themselves (a key is in one tier)
    m, rt, qr, want, errors, log = deploy(text, gathering=False)
    try:
        drive(rt, qr, warm + [send])
        assert not errors and len(log) == 1 + 3
        # the hot tier is dispatched first; its header's n_valid
        hot_rows = int(log[1][2].headers[0][0])
    finally:
        m.shutdown()
    m, rt, qr, got, errors, log = deploy(text, gathering=False)
    try:
        drive(rt, qr, warm)
        del log[:]
        p, n = qr.planned, [0]

        def second_fails(fn):
            def call(*args):
                n[0] += 1
                if n[0] == 2:
                    raise RuntimeError("tier 2 of 3 cannot run")
                return fn(*args)
            call._siddhi_role = fn._siddhi_role
            return call
        qr.planned = dataclasses.replace(
            p, steps={s: second_fails(f) for s, f in p.steps.items()})
        drive(rt, qr, [send])
        assert len(errors) == 1 and "tier 2 of 3" in str(errors[0])
        assert len(log) == 1 and 0 < hot_rows == len(got) < len(want)
        assert set(got) <= set(want)
        hot_keys = {row[1] for row in got}
        assert not hot_keys & {row[1] for row in set(want) - set(got)}
    finally:
        m.shutdown()
