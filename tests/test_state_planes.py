"""The NFA's 64-bit state crosses every jit boundary as two u32 planes
(`StatePacker`: `(b32, lo64, hi64, scalars)`), never as an i64 `[W, K]`
array: bit-exact pack/unpack, no 64-bit key-axis argument or result on any
pattern step, the planes sharded as `_shard_specs` says, purge and
incremental persistence on timestamps past 2**32, a parent-written
snapshot restoring unchanged, and a no-chip compile guard for v5e:2x2.
The event timestamps cross it on the wire of `core.event.encode_ts`: an
i64 scalar and an i32 `[B]` on every sequential step, the same callable
taking an i64 `[B]` delta for a batch that spans 2**31 ms or more."""
import collections
import functools
import json
import os
import pickle
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from siddhi_tpu import SiddhiManager
from siddhi_tpu.core import event as ev
from siddhi_tpu.core import pattern_planner
from siddhi_tpu.core import state_rows
from siddhi_tpu.core.pattern_planner import StatePacker
from siddhi_tpu.core.window import NO_WAKEUP
from siddhi_tpu.observability.explain import compiled_steps
from siddhi_tpu.observability.recompile import RECOMPILES

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
T0 = 1_760_000_000_000          # epoch milliseconds: high word non-zero
I64_CASES = {
    "zero": 0, "minus_one": -1, "no_wakeup": int(NO_WAKEUP),
    "two_31": 2**31, "two_32": 2**32, "two_32_minus_1": 2**32 - 1,
    "int64_min": -2**63, "int64_max": 2**63 - 1, "epoch_ms": T0,
    "minus_epoch_ms": -T0,
}


@pytest.fixture()
def mesh4():
    devs = np.array(jax.devices())
    if devs.size < 4:
        pytest.skip("needs 4 virtual devices")
    return Mesh(devs[:4], ("shard",))


# -- (a) pack -> planes -> unpack ------------------------------------------

Leaves = collections.namedtuple(
    "Leaves", "start_ts cap_ts price active pos counter")
K = 6


def example(v):
    """Every leaf kind the NFA state has, the i64 ones holding `v` beside
    its neighbours (so a plane mix-up between rows or keys shows)."""
    col = np.array([v, 0, -1, v, T0, v], np.int64)
    return Leaves(
        start_ts=jnp.asarray(col),
        cap_ts=jnp.asarray(np.stack([col, col[::-1], np.full(K, v)])
                           .reshape(3, 1, K)),
        price=jnp.asarray(np.linspace(-2.5, 1e9, 2 * K, dtype=np.float32)
                          .reshape(2, K)),
        active=jnp.asarray(np.arange(K) % 2 == 0),
        pos=jnp.asarray(np.arange(K, dtype=np.int32) - 3),
        counter=jnp.asarray(v, jnp.int64))


def one_key(st):
    return jax.tree.map(lambda x: x if x.ndim == 0 else x[..., :1], st)


@pytest.mark.parametrize("name", sorted(I64_CASES))
def test_pack_unpack_is_bit_exact(name):
    st = example(I64_CASES[name])
    packer = StatePacker(one_key(st))
    b32, lo64, hi64, scalars = jax.jit(packer.pack)(st)
    assert (b32.dtype, lo64.dtype, hi64.dtype) == \
        (jnp.int32, jnp.uint32, jnp.uint32)
    assert lo64.shape == hi64.shape == (packer.w64, K) == (4, K)
    assert b32.shape == (packer.w32, K) == (4, K)
    back = jax.jit(packer.unpack)(b32, lo64, hi64, scalars)
    for got, want in zip(back, st):
        assert got.dtype == want.dtype and got.shape == want.shape
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # row order inside the planes is the i64 blob's (leaf order): the
    # host's int64 view is the leaves stacked, bit for bit
    want64 = np.concatenate([np.asarray(st.start_ts).reshape(1, K),
                             np.asarray(st.cap_ts).reshape(3, K)])
    np.testing.assert_array_equal(StatePacker.join_host(lo64, hi64), want64)
    lo2, hi2 = StatePacker.split_host(want64)
    np.testing.assert_array_equal(lo2, np.asarray(lo64))
    np.testing.assert_array_equal(hi2, np.asarray(hi64))
    assert lo2.dtype == hi2.dtype == np.uint32


def test_host_form_is_the_old_blob_pair():
    st = example(T0)
    packer = StatePacker(one_key(st))
    packed = packer.pack(st)
    b32, b64, scalars = StatePacker.to_host(packed)
    assert b64.dtype == np.int64 and b64.shape == (packer.w64, K)
    assert b32.dtype == np.int32 and len(scalars) == 1
    again = StatePacker.from_host((b32, b64, scalars))
    for a, b in zip(jax.tree.leaves(again), jax.tree.leaves(packed)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert np.asarray(a).dtype == np.asarray(b).dtype


# -- (b) no 64-bit array with the key axis crosses a jit boundary ----------

KEYS = 96       # a key capacity no batch, group or emission axis equals

PART_QL = """
@app:playback
define stream T (key long, price float, volume int);
partition with (key of T)
begin
  @capacity(keys='%d', slots='4') %s @info(name='q')
  from every e1=T[volume == 1] -> e2=T[volume == 2 and price >= e1.price]
       within 10 sec
  select e1.key as k, e1.price as p1, e2.price as p2 insert into M;
end;
"""
ABSENT_QL = """
@app:playback
define stream T (key long, price float, volume int);
partition with (key of T)
begin
  @capacity(keys='%d', slots='4') @info(name='q')
  from every e1=T[volume == 1] -> not T[volume == 2] for 1 sec
  select e1.key as k, e1.price as p1 insert into M;
end;
""" % KEYS
BLOCK_QL = """
@app:playback
define stream T (key long, price float, volume int);
%s @info(name='q')
from every e1=T[volume == 1] -> e2=T[volume == 2]
select e1.price as p1, e2.price as p2 insert into M;
"""


def cols(keys, price, vol, ts):
    k = np.asarray(list(keys), np.int64)
    return ([k, np.full(k.shape, price, np.float32) + k.astype(np.float32),
             np.full(k.shape, vol, np.int32)],
            np.asarray(ts, np.int64) + np.zeros(k.shape, np.int64))


def deploy(text, mesh=None):
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(text, **({"mesh": mesh} if mesh else {}))
    errors, got = [], []
    rt.set_exception_listener(errors.append)
    rt.add_callback("q", lambda ts, i, o: got.extend(
        [(int(e.timestamp), *[float(x) for x in e.data]) for e in (i or [])]))
    rt.start()
    return m, rt, got, errors


def send(rt, batches, flush_each=True):
    h = rt.get_input_handler("T")
    for c, ts in batches:
        h.send_columns([x.copy() for x in c], timestamps=ts.copy())
        if flush_each:
            rt.flush()
    rt.flush()


def drive_partitioned(rt):
    send(rt, [cols(range(8), 10.0, 1, T0),                  # dense
              cols([1, 5, 20], 10.0, 1, T0 + 1)])           # gather


def drive_absent(rt):
    send(rt, [cols(range(4), 10.0, 1, T0)])
    rt.query_runtimes["q"].on_timer(T0 + 5_000)


def drive_block(rt):
    send(rt, [cols([0] * 4, 10.0, 1, T0 + np.arange(4)),
              cols([0] * 4, 10.0, 2, T0 + 9)])


def drive_fused(rt):
    # a full stack of K = 4 equal-shaped sends dispatches as one program
    # (a flush in between would drain the stack through the plain steps)
    send(rt, [cols([1, 5, 20, 0], 10.0, 1 + i % 2, T0 + i) for i in range(4)],
         flush_each=False)


VARIANTS = {
    # name: (app text, needs mesh, drive, roles that must have run)
    "gather_dense": (PART_QL % (KEYS, ""), False, drive_partitioned,
                     {"step[T]", "dense_step[T]"}),
    "timer": (ABSENT_QL, False, drive_absent, {"timer_step"}),
    "block": (BLOCK_QL % "", False, drive_block, {"step[T]"}),
    # one-chip @fuse takes the non-partitioned (block) pattern only
    "fused_block": (BLOCK_QL % "@fuse(batches='4')", False, drive_fused,
                    {"fused_step[pattern]"}),
    "sharded": (PART_QL % (KEYS, ""), True, drive_partitioned, {"step[T]"}),
    "sharded_fused": (PART_QL % (KEYS, "@fuse(batches='4')"), True,
                      drive_fused, {"shard_fused_step[T]"}),
}


def boundary_leaves(fn, argspecs):
    """Every argument and result leaf of the jitted program, from the
    lowering's avals (no backend is asked anything)."""
    lowered = fn.lower(*argspecs)
    return jax.tree.leaves((argspecs, lowered.out_info))


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_no_64_bit_key_axis_array_crosses_a_jit_boundary(variant, request):
    text, sharded, drive, want_roles = VARIANTS[variant]
    mesh = request.getfixturevalue("mesh4") if sharded else None
    m, rt, _got, errors = deploy(text, mesh)
    try:
        drive(rt)
        assert not errors, errors
        qr = rt.query_runtimes["q"]
        kcap = qr.planned.key_capacity
        ran = {role: (fn, specs)
               for role, fn, specs in compiled_steps(qr) if specs is not None}
        assert want_roles <= set(ran), (want_roles, sorted(ran))
        for role, (fn, specs) in ran.items():
            packed = jax.tree.leaves(
                specs[0][0] if role.startswith(("fused", "shard_fused"))
                else specs[0])
            # the packed state goes in as int32 + two uint32 planes (+ 0-d)
            assert [str(x.dtype) for x in packed if x.ndim][:3] == \
                ["int32", "uint32", "uint32"], (role, packed)
            wide = [(x.shape, str(x.dtype))
                    for x in boundary_leaves(fn, specs)
                    if np.dtype(x.dtype).itemsize == 8 and
                    (kcap in x.shape if kcap > 1 else
                     x.ndim == 2 and x.shape[-1] == 1)]
            assert not wide, (role, wide)
            if role.startswith(("step[", "dense_step[")):
                # steady traffic's timestamps: (base, delta), no i64 [B]
                ts = [(x.shape, str(x.dtype)) for x in specs[3:5]]
                B = specs[2][0].shape[0]
                assert ts == [((), "int64"), ((B,), "int32")], (role, ts)
        # ... and what the runtime holds between steps is the same
        b32, lo64, hi64, _scalars = qr.state[0]
        assert (b32.dtype, lo64.dtype, hi64.dtype) == \
            (jnp.int32, jnp.uint32, jnp.uint32)
        assert lo64.shape == hi64.shape and lo64.shape[1] == kcap
    finally:
        m.shutdown()


# -- the ts wire's wide fall-back is the same callable ----------------------

WIDE = T0 + 2**33          # 99 days after T0: no i32 delta reaches it
GAPPY = [cols([1, 5, 20], 10.0, 1, T0),
         cols([1, 5, 20], 99.0, 2, [T0 + 5, T0 + 7, WIDE]),
         cols([1, 5, 20], 10.0, 1, WIDE + 10),
         cols([1, 5, 20], 99.0, 2, WIDE + 20)]
# name: (app text, needs mesh, the plan's step table, warm-up sends,
#        [narrow, WIDE, narrow, narrow] of one shape)
FALLBACK = {
    # keys 0..7 bind slots 0..7; {1, 5, 20} then sit on slots 1, 5, 8
    "gather": (PART_QL % (KEYS, ""), False, "steps",
               [cols(range(8), 10.0, 1, T0 - 20_000)],
               GAPPY),
    "dense": (PART_QL % (KEYS, ""), False, "dense_steps", [],
              [cols(range(8), 10.0, 1, T0),
               cols(range(8), 99.0, 2, [T0 + 5] * 7 + [WIDE]),
               cols(range(8), 10.0, 1, WIDE + 10),
               cols(range(8), 99.0, 2, WIDE + 20)]),
    "block": (BLOCK_QL % "", False, "steps", [],
              [cols([0] * 4, 10.0, 1, T0 + np.arange(4)),
               cols([0] * 4, 10.0, 2, [T0 + 9] * 3 + [WIDE]),
               cols([0] * 4, 10.0, 1, WIDE + 10 + np.arange(4)),
               cols([0] * 4, 10.0, 2, WIDE + 20)]),
    "sharded": (PART_QL % (KEYS, ""), True, "steps", [],
                GAPPY),
}


def row_by_row(batches):
    """The same rows, one a send: every batch spans 0 ms."""
    return [([x[i:i + 1] for x in c], ts[i:i + 1])
            for c, ts in batches for i in range(len(ts))]


def delta_dtype(fn):
    """The ts delta's dtype in the specialisation `fn` traced last."""
    return str(fn._siddhi_argspec["argspecs"][4].dtype)


@pytest.mark.parametrize("path", sorted(FALLBACK))
def test_wide_batch_goes_through_the_same_step(path, request):
    text, sharded, table, warm, batches = FALLBACK[path]
    mesh = request.getfixturevalue("mesh4") if sharded else None
    m, rt, got, errors = deploy(text, mesh)
    m2, rt2, want, errors2 = deploy(text, mesh)
    try:
        send(rt, warm + batches[:1])
        qr = rt.query_runtimes["q"]
        fn = getattr(qr.planned, table)["T"]
        owner, role = fn._siddhi_owner, fn._siddhi_role
        assert owner == "q" and delta_dtype(fn) == "int32"
        compiles, cached = RECOMPILES.count(owner), fn._cache_size()
        send(rt, batches[1:2])                 # spans 2**33 ms
        # one more specialisation of THAT callable, under its owner
        assert getattr(qr.planned, table)["T"] is fn
        assert (fn._siddhi_owner, fn._siddhi_role) == (owner, role)
        assert delta_dtype(fn) == "int64"
        assert RECOMPILES.count(owner) == compiles + 1
        assert fn._cache_size() == cached + 1
        send(rt, batches[2:])                  # narrow again: nothing new
        assert RECOMPILES.count(owner) == compiles + 1
        assert fn._cache_size() == cached + 1
        send(rt2, row_by_row(warm + batches))
        assert not errors and not errors2
        assert sorted(got) == sorted(want)
        # block: 4 + 4 pairs; keyed: the WIDE e2 is past `within`, all match
        # in the second round
        n_keys = len(batches[0][1])
        assert len(got) == (8 if path == "block" else 2 * n_keys - 1)
        assert max(r[0] for r in got) == WIDE + 20
    finally:
        m.shutdown()
        m2.shutdown()


@pytest.mark.parametrize("path", ["gather", "block", "sharded"])
def test_empty_batch_runs_the_wire_step(path, request):
    """No rows: zeros on the wire, through the step a one-key batch
    already compiled (a dense slice needs two keys: no empty batch takes
    it)."""
    text, sharded, table, warm, _ = FALLBACK[path]
    mesh = request.getfixturevalue("mesh4") if sharded else None
    m, rt, got, errors = deploy(text, mesh)
    try:
        one, ts = cols([2], 10.0, 1, T0)
        send(rt, warm + [(one, ts)])
        qr = rt.query_runtimes["q"]
        fn = getattr(qr.planned, table)["T"]
        compiles, cached = RECOMPILES.count("q"), fn._cache_size()
        B = ev.bucket_size(1)
        empty = ev.StagedBatch(
            np.zeros(B, np.int64), np.zeros(B, np.int32),
            np.zeros(B, np.bool_), [np.zeros(B, x.dtype) for x in one], 0)
        qr.process_staged("T", empty, T0 + 1)
        rt.flush()
        assert not errors and not got
        assert delta_dtype(fn) == "int32"
        assert (RECOMPILES.count("q"), fn._cache_size()) == (compiles, cached)
    finally:
        m.shutdown()


# -- (c) the planes shard as _shard_specs says ------------------------------

def test_planes_take_the_shard_specs_before_and_after_a_step(mesh4):
    m, rt, got, errors = deploy(PART_QL % (KEYS, ""), mesh4)
    try:
        qr = rt.query_runtimes["q"]
        p = qr.planned
        pspec, _ = pattern_planner._shard_specs(
            StatePacker(p.exec.init_state(1)), p.selector_exec)
        assert pspec[:3] == (P(None, "shard"),) * 3

        def check():
            for plane, spec in zip(qr.state[0][:3], pspec[:3]):
                assert plane.sharding.is_equivalent_to(
                    NamedSharding(mesh4, spec), plane.ndim)
                assert {s.data.shape for s in plane.addressable_shards} == \
                    {(plane.shape[0], KEYS // 4)}
        check()
        send(rt, [cols(range(16), 10.0, 1, T0),
                  cols(range(16), 20.0, 2, T0 + 1_000)])
        assert not errors and len(got) == 16
        check()
    finally:
        m.shutdown()


# -- (d) purge and incremental persistence past 2**32 -----------------------

# an interval past T0: the playback clock's jump to epoch milliseconds
# must not owe the purger half a million catch-up ticks; the test calls
# the reset itself
PURGE_QL = PART_QL % (
    16, "@purge(enable='true', interval='1000000 hour', "
        "idle.period='1000000 hour')")
E1 = [cols(range(8), 10.0, 1, T0 + np.arange(8))]
E2 = [cols(range(4), 99.0, 2, T0 + 5_000),          # inside `within`
      cols(range(4, 8), 99.0, 2, T0 + 15_000)]      # e1 expired by then


def test_purged_keys_reset_in_both_planes():
    m, rt, got, errors = deploy(PURGE_QL)
    m2, rt2, got2, errors2 = deploy(PURGE_QL)
    try:
        purged = np.array([1, 2], np.int64)
        kept = [k for k in range(8) if k not in purged]
        send(rt, E1)
        qr = rt.query_runtimes["q"]
        slots = qr.slot_allocator.slots_for([purged],
                                            np.ones(2, np.bool_))
        before = StatePacker.to_host(qr.state[0])
        rt._partition_purgers[0]._reset_pattern_keys(qr, np.asarray(slots))
        after = StatePacker.to_host(qr.state[0])
        fresh = StatePacker.to_host(qr.planned.init_columns())
        for a, b, f in zip(after[:2], before[:2], fresh[:2]):
            np.testing.assert_array_equal(a[:, slots], np.repeat(f, 2, 1))
            rest = np.setdiff1d(np.arange(a.shape[1]), slots)
            np.testing.assert_array_equal(a[:, rest], b[:, rest])
        send(rt, E2)
        # the run that never saw the purged keys' e1 delivers the same rows
        send(rt2, [cols(kept, 10.0, 1, T0 + np.asarray(kept))])
        send(rt2, E2)
        assert not errors and not errors2
        assert got == got2 and sorted(r[1] for r in got) == [0.0, 3.0]
    finally:
        m.shutdown()
        m2.shutdown()


@pytest.mark.parametrize("sharded", [False, True])
def test_incremental_persist_restore_past_2_32(sharded, request):
    mesh = request.getfixturevalue("mesh4") if sharded else None
    text = PART_QL % (16, "")
    m, rt, got, errors = deploy(text, mesh)
    m2, rt2, got2, errors2 = deploy(text, mesh)
    try:
        send(rt, E1[:1])
        base = rt.snapshot()
        send(rt, [cols([9, 2], 50.0, 1, [T0 + 100, T0 + 101])])
        inc = rt.snapshot_incremental()
        delta = pickle.loads(inc)["deltas"]["q"]
        assert delta["kind"] == "keyed" and delta["b64"].dtype == np.int64
        assert delta["b64"].shape[1] == len(delta["slots"]) == 2
        assert (delta["b64"] >> 32).max() > 0        # high words are there
        rt2.restore(base)
        rt2.restore_increment(inc)
        for a, b in zip(jax.tree.leaves(rt.query_runtimes["q"].state),
                        jax.tree.leaves(rt2.query_runtimes["q"].state)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            assert a.dtype == b.dtype
        after = [E2[0], cols([9], 99.0, 2, T0 + 6_000), E2[1]]
        send(rt, after)
        send(rt2, after)
        assert not errors and not errors2
        # keys 0..3 inside the window (key 2 twice: two pending e1), key 9
        assert got == got2 and len(got) == 6
    finally:
        m.shutdown()
        m2.shutdown()


# -- snapshot interop: the host / on-disk format did not move ---------------

@pytest.fixture(scope="module")
def parent_written():
    """A full snapshot, an increment and the rows that followed, written
    by the commit before the planes existed (PR 26's tree; the script that
    made it is quoted in CHANGES.md, PR 27)."""
    path = os.path.join(ROOT, "tests", "golden", "pattern_snapshot_pr26.pkl")
    with open(path, "rb") as fh:
        return pickle.load(fh)


def test_parent_written_snapshot_restores_row_for_row(parent_written):
    fx = parent_written
    m, rt, got, errors = deploy(fx["app"])
    try:
        rt.restore(fx["full"])
        rt.restore_increment(fx["inc"])
        send(rt, fx["after"])
        assert not errors
        assert got == fx["rows"] and len(got) == 7
    finally:
        m.shutdown()


def test_own_snapshot_is_the_parents_array_for_array(parent_written):
    """The same sends on this tree snapshot to the parent's payload: `b64`
    one int64 [W64, K] array, every array equal (so the parent restores
    what this tree writes as surely as the other way round)."""
    fx = parent_written
    m, rt, _got, errors = deploy(fx["app"])
    try:
        send(rt, fx["before_full"])
        full = pickle.loads(rt.snapshot())
        send(rt, fx["before_inc"])
        inc = pickle.loads(rt.snapshot_incremental())
        assert not errors
    finally:
        m.shutdown()
    want = pickle.loads(fx["full"])["states"]["q"]
    have = full["states"]["q"]
    (b32, b64, scalars), _sel = have["state"]
    assert b64.dtype == np.int64 and b32.dtype == np.int32
    assert jax.tree.structure(have["state"]) == \
        jax.tree.structure(want["state"])
    for a, b in zip(jax.tree.leaves(have["state"]),
                    jax.tree.leaves(want["state"])):
        assert np.asarray(a).dtype == np.asarray(b).dtype
        np.testing.assert_array_equal(a, b)
    assert have["layout"] == want["layout"]
    wd = pickle.loads(fx["inc"])["deltas"]["q"]
    hd = inc["deltas"]["q"]
    assert set(hd) == set(wd)
    for name in ("slots", "b32", "b64"):
        assert np.asarray(hd[name]).dtype == np.asarray(wd[name]).dtype
        np.testing.assert_array_equal(hd[name], wd[name])


def test_relayout_4_2_4_shards_equals_the_unsharded_state(request):
    """The sharded runtimes' snapshots, re-bucketed through
    sharding/snapshot.py 4 -> 2 -> 4 -> 1, are the unsharded runtime's
    host state; b64 stays one int64 array on the way."""
    from siddhi_tpu.sharding.snapshot import rebucket_state
    devs = np.array(jax.devices())
    if devs.size < 4:
        pytest.skip("needs 4 virtual devices")
    text = PART_QL % (16, "")
    sends = E1 + [cols([9, 2], 50.0, 1, [T0 + 100, T0 + 101])]

    def host_state(n, restore_from=None):
        mesh = Mesh(devs[:n], ("shard",)) if n > 1 else None
        m, rt, got, errors = deploy(text, mesh)
        try:
            if restore_from is None:
                send(rt, sends)
            else:
                rt.restore(restore_from)
            assert not errors
            blob = rt.snapshot()
            data = pickle.loads(blob)["states"]["q"]
            return blob, data["state"], data["layout"], rt.query_runtimes[
                "q"].planned
        finally:
            m.shutdown()

    _b1, want, l1, _p = host_state(1)
    blob4, s4, l4, planned = host_state(4)
    blob2, s2, l2, _p = host_state(2, restore_from=blob4)
    _b4, s4b, l4b, _p = host_state(4, restore_from=blob2)
    assert (l4["n"], l2["n"], l4b["n"], l1["n"]) == (4, 2, 4, 1)
    for st in (s4, s2, s4b):
        assert st[0][1].dtype == np.int64
    for a, b in zip(jax.tree.leaves(s4), jax.tree.leaves(s4b)):
        np.testing.assert_array_equal(a, b)
    for st, layout in ((s4, l4), (s2, l2), (s4b, l4b)):
        flat = rebucket_state(st, layout, l1, planned)
        for a, b in zip(jax.tree.leaves(flat), jax.tree.leaves(want)):
            np.testing.assert_array_equal(a, b)


# -- compile guard: v5e:2x2, no chip ----------------------------------------

@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here: skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def flagship_plan():
    """`pattern_1m`'s app text planned at any key capacity and mesh:
    planning allocates nothing (the state is made by `init_state`)."""
    d = os.path.join(ROOT, "benchmarks", "configs", "pattern_1m")
    with open(os.path.join(d, "config.json")) as fh:
        sizes = json.load(fh)["sizes"]
    with open(os.path.join(d, "app.siddhi")) as fh:
        text = fh.read().format(**dict(sizes, n_keys=1024))
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(text)
    base = rt.query_runtimes["flagship"]._replan.__defaults__[0]

    def plan(key_capacity, mesh=None):
        return functools.partial(base.func, *base.args, **dict(
            base.keywords, key_capacity=key_capacity, mesh=mesh))()
    yield plan
    m.shutdown()


def step_args(p, kcap, B, Kb, E, place_state, rep, batch, dense):
    def sds(shape, dtype, sharding):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    packed, sel = jax.tree.map(
        place_state, jax.eval_shape(lambda: p.init_state.__wrapped__(kcap)))
    raw_cols = (sds((B,), np.int64, rep), sds((B,), np.float32, rep),
                sds((B,), np.int32, rep))
    ts = (sds((), np.int64, rep), sds((B,), np.int32, rep))
    key_ref = sds((), np.int32, rep) if dense else sds((Kb,), np.int32, batch)
    return (packed, sel, raw_cols, *ts, sds((Kb, E), np.int32, batch),
            key_ref, sds((), np.int64, rep), ())


def x64_boundary_ops(hlo_text, kcap):
    """X64SplitLow / X64SplitHigh / X64Combine custom calls with the key
    capacity in an operand or result shape: the whole-blob passes."""
    return [hit.group(1) for line in hlo_text.splitlines()
            for hit in [re.search(r'custom_call_target="(X64\w+)"', line)]
            if hit and re.search(r"[\[,]%d[\],]" % kcap, line)]


def test_the_guard_sees_a_whole_blob_pass():
    """The parent's gather step, three lines of its compiled text."""
    hlo = """
  %custom-call.1 = u32[40,1048576]{1,0:T(8,128)} custom-call(%packed_1_.1), custom_call_target="X64SplitLow", metadata={op_name="packed[1]"}
  %custom-call.8 = s64[40,1048576]{1,0:T(8,128)} custom-call(%get-tuple-element.375, %get-tuple-element.376), custom_call_target="X64Combine"
  %custom-call.2 = u32[8192]{0:T(1024)} custom-call(%raw_cols_0_.1), custom_call_target="X64SplitHigh"
"""
    assert x64_boundary_ops(hlo, 1048576) == ["X64SplitLow", "X64Combine"]
    assert x64_boundary_ops(hlo, 8192) == ["X64SplitHigh"]


# the cells' shapes: paced (gather), saturated (dense), mesh (sharded, a
# chip's share of the keys) — but the dense slice an eighth of the cell's
# 131,072 keys: that shape takes 37 s to compile against 3 s, and its own
# [.., 131072] work temporaries (202,139,136 B, by hand, PR 27) would
# hide a stray whole-plane copy (167,772,160 B)
GUARD = {
    "gather": dict(kcap=1048576, chips=1, B=8192, Kb=2048, dense=False),
    "dense": dict(kcap=1048576, chips=1, B=65536, Kb=16384, dense=True),
    "sharded": dict(kcap=33554432, chips=4, B=524288, Kb=32768, dense=False),
}


@pytest.fixture(scope="module")
def guard_compiled(topo, flagship_plan):
    """step name -> (plan, compiled program) at GUARD's shapes, each
    compiled once for the guards below."""
    done = {}

    def compiled(step):
        if step in done:
            return done[step]
        g = GUARD[step]
        if g["chips"] == 1:
            one = jax.sharding.SingleDeviceSharding(topo.devices[0])
            rep = batch = one
            p = flagship_plan(g["kcap"])

            def place(x):
                return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one)
            fn = (p.dense_steps if g["dense"] else p.steps)["TradeStream"]
            n_rows = g["Kb"]
        else:
            mesh = Mesh(np.array(topo.devices), ("shard",))
            rep = NamedSharding(mesh, P())
            batch = NamedSharding(mesh, P("shard"))
            p = flagship_plan(g["kcap"], mesh)
            specs = pattern_planner._shard_specs(
                StatePacker(p.exec.init_state(1)), p.selector_exec)
            shapes = jax.eval_shape(
                lambda: p.init_state.__wrapped__(g["kcap"]))
            placed = jax.tree.map(
                lambda x, s: jax.ShapeDtypeStruct(
                    x.shape, x.dtype, sharding=NamedSharding(mesh, s)),
                shapes, specs)
            flat = iter(jax.tree.leaves(placed))

            def place(_x):
                return next(flat)
            fn = p.steps["TradeStream"]
            n_rows = g["Kb"] * g["chips"]
        args = step_args(p, g["kcap"], g["B"], n_rows, 4, place, rep, batch,
                         g["dense"])
        # this process sees the CPU backend; the program the chip runs
        # moves its state rows by the block (state_rows.block_form)
        state_rows._FORM = "blocks"
        try:
            done[step] = (p, fn.lower(*args).compile())
        finally:
            state_rows._FORM = None
        return done[step]
    return compiled


@pytest.mark.parametrize("step", sorted(GUARD))
def test_v5e_compile_has_no_whole_blob_x64_pass(step, guard_compiled):
    g = GUARD[step]
    p, compiled = guard_compiled(step)
    per_chip = g["kcap"] // g["chips"]
    assert x64_boundary_ops(compiled.as_text(), per_chip) == []
    w64 = StatePacker(p.exec.init_state(1)).w64
    plane = w64 * per_chip * 4
    assert w64 == 40 and plane in (167772160, 1342177280)
    # under ONE plane's bytes: no copy of the resident state, whole or half
    assert compiled.memory_analysis().temp_size_in_bytes < plane


# the one-chip programs take the event columns grouped by the host (PR 29)
# and reshape them: the dense step gathers nothing.  Since PR 36 the scan
# step gathers nothing either and the sharded step only the replicated [B]
# event columns — ONE gather of the columns' six u32 planes stacked (key
# and the decoded i64 timestamp as two halves each, price, stage; PR 31:
# the chip gathers by the slice): their keys' rows of the three state
# arrays move by the 128-key block, two Pallas kernels
# (`core/state_rows.py`), where there were three gathers `s32[50, Kb]`,
# `u32[40, Kb]`, `u32[40, Kb]` and three scatters, a serial loop over the
# indices each
GATHERS = {
    "dense": [],
    "gather": [],
    "sharded": ["u32[6,32768,4]"],
}


@pytest.mark.parametrize("step", sorted(GUARD))
def test_v5e_compile_gathers_only_what_the_step_must(step, guard_compiled):
    _p, compiled = guard_compiled(step)
    found = re.findall(r"= (\S+?)\{\S* gather\(", compiled.as_text())
    assert len(found) == len(GATHERS[step]), found
    assert all(want in got for want, got in zip(GATHERS[step], found)), found
    assert " scatter(" not in compiled.as_text()


# the row-mover's two kernels, by the name `pallas_call` gives their
# custom calls.  `test_v5e_compile_has_no_whole_blob_x64_pass` holds all
# three programs' `temp_size_in_bytes` under ONE plane: that is the test
# that catches a whole-blob copy.  Why the three arrays are NOT folded
# into one `u32[130, K]` blob (one pass a step instead of three): compiled
# for v5e:2x2, a gather + scatter on `u32[W, 1048576]` by `s32[4096]`
# indices has `temp_size_in_bytes` 0 at W = 40 and 50, 536,935,424 at
# W = 64, 80, 96, 120, 127, 128 and 1,078,114,304 at W = 130 / 136: from
# W = 64 up layout assignment wants the blob key-major for the scatter and
# copies the WHOLE blob there and back every step (ISSUE 36; PR 27 met the
# same copy with the stacked `[2, W64, K]` planes)
MOVER = {"gather": 1, "dense": 0, "sharded": 1}


@pytest.mark.parametrize("step", sorted(GUARD))
def test_v5e_compile_moves_state_rows_by_the_block(step, guard_compiled):
    _p, compiled = guard_compiled(step)
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    for name in ("state_rows_load", "state_rows_store"):
        mine = [line for line in calls if "%" + name in line]
        assert len(mine) == MOVER[step], (name, len(mine))
    if not MOVER[step]:
        return
    g = GUARD[step]
    per_chip, rows = g["kcap"] // g["chips"], g["Kb"]
    load = next(line for line in calls if "%state_rows_load" in line)
    store = next(line for line in calls if "%state_rows_store" in line)
    # the load hands over the three [W, Kb] sub-arrays; the store's
    # results ARE the three resident arrays (aliased operands: no copy)
    for w, dt in ((50, "s32"), (40, "u32"), (40, "u32")):
        assert "%s[%d,%d]" % (dt, w, rows) in load.split("custom-call(")[0]
        assert "%s[%d,%d]" % (dt, w, per_chip) in \
            store.split("custom-call(")[0]
    assert "output_to_operand_aliasing" in store
    # each under its section of the step (what a device trace books it to)
    assert "state_load" in load and "state_store" in store


def test_v5e_sharded_event_gather_lands_in_fast_memory(guard_compiled):
    """PR 31: XLA's memory-space assignment put five of the six one-plane
    event gathers' results in HBM once the program's outputs changed
    (1.87 -> 5.3-7.2 ms each on the chip).  The one stacked gather's
    result is in the fast memory, `S(1)`, here too."""
    _p, compiled = guard_compiled("sharded")
    # the gather fusions' results, as the entry computation places them:
    # three rows-of-state gathers, then the stacked columns'
    layouts = re.findall(
        r"^\s*%fusion\S* = u32\[\d+,6\]\{(\S*?)\} fusion\(.*gather",
        compiled.as_text(), re.M)
    assert len(layouts) == 1 and layouts[0].endswith("S(1)"), layouts


ROLES = {"gather": "pattern_step", "dense": "pattern_dense",
         "sharded": "pattern_step_sharded"}


@pytest.mark.parametrize("step", sorted(GUARD))
def test_v5e_compile_keeps_every_op_in_one_section_and_rectangle(
        step, guard_compiled):
    """What a device trace of the cells' programs will show: each compiled
    instruction that runs as an op and carries an `op_name` of the program
    names one section and the program's one rectangle
    (tests/test_step_sections.py judges the CPU's compile the same way);
    what the v5e's compiler adds of its own is printed by opcode."""
    from test_step_sections import judged
    g = GUARD[step]
    _p, compiled = guard_compiled(step)
    named, short, compilers = judged(compiled.as_text(), ROLES[step])
    total = sum(named.values()) + len(short)
    print(f"{step}: {total} instructions by (section, rectangle): "
          f"{dict(named)}; naming none: {short}; the compiler's own: "
          f"{dict(compilers)}")
    assert {rect for _, rect in named} == \
        {"rect_%dx4" % (g["Kb"] * g["chips"])}
    assert {"state_load", "nfa_advance", "state_store",
            "emission_compaction", "emission_bands"} <= \
        {s for s, _ in named}
    assert len(short) < 0.05 * total, short
