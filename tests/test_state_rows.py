"""The row-mover of the gather-path pattern step (`core/state_rows.py`):
the block form — two Pallas kernels, here in interpret mode on the CPU —
against XLA's gather / scatter, bit for bit on all three state arrays;
the flagship's `pattern_step` with the mover and without, over 20 sends;
the counter that says how often the mover finds keys sharing a block."""
import functools
import json
import os
import pickle

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from siddhi_tpu import SiddhiManager
from siddhi_tpu.core import state_rows
from siddhi_tpu.core.pattern_planner import StatePacker

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the flagship's widths: b32 s32[50, K] (a last tile of 2 rows), lo64 and
# hi64 u32[40, K]
WIDTHS = ((50, np.int32), (40, np.uint32), (40, np.uint32))


@pytest.fixture()
def interpret():
    """The block form on the CPU: Pallas' interpret mode."""
    state_rows._FORM = "interpret"
    yield
    state_rows._FORM = None


@pytest.fixture()
def mesh4():
    devs = np.array(jax.devices())
    if devs.size < 4:
        pytest.skip("needs 4 virtual devices")
    return Mesh(devs[:4], ("shard",))


def make(K, Kb, seed=36):
    rng = np.random.default_rng(seed)
    arrays = [rng.integers(0, 2**32, (w, K), np.uint64).astype(np.uint32)
              .view(dt) for w, dt in WIDTHS]
    news = [rng.integers(0, 2**32, (w, Kb), np.uint64).astype(np.uint32)
            .view(dt) for w, dt in WIDTHS]
    return arrays, news


def padded(keys, Kb, K):
    keys = np.asarray(keys, np.int32)
    return np.concatenate([keys, np.full(Kb - len(keys), K, np.int32)])


def move(arrays, news, key_idx):
    """What a step does with the mover: (sub-arrays, arrays after)."""
    n_live = state_rows.live_count(key_idx, arrays[0].shape[1])
    return (state_rows.load(arrays, key_idx, n_live),
            state_rows.store(arrays, news, key_idx, n_live))


@functools.lru_cache(maxsize=None)
def jitted(form, K, Kb):
    """One program a form and shape: the keys are run-time data (the
    lambda: a jit of `move` itself would share one cache over the forms)."""
    return jax.jit(lambda a, n, k: move(a, n, k))


def both(K, Kb, key_idx):
    arrays, news = make(K, Kb)
    out = {}
    for form in (None, "interpret"):
        state_rows._FORM = form
        try:
            assert state_rows.block_form(K, Kb) == (form is not None)
            out[form] = jitted(form, K, Kb)(arrays, news, key_idx)
        finally:
            state_rows._FORM = None
    return arrays, news, out[None], out["interpret"]


def same(want, got):
    want, got = jax.tree.leaves(want), jax.tree.leaves(got)
    assert len(want) == len(got) == 6
    for a, b in zip(want, got):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


RNG = np.random.default_rng(7)
# name: (K, Kb, live keys ascending)
CASES = {
    "scattered_one_a_block": (1024, 128, np.arange(8) * 128 + [5, 0, 127, 64,
                                                               1, 99, 33, 7]),
    "scattered_more_blocks_than_slots": (
        8192, 128, np.arange(60) * 128 + RNG.integers(0, 128, 60)),
    "contiguous_run_from_a_block_start": (1024, 128, 256 + np.arange(128)),
    "contiguous_run_across_blocks": (1024, 128, 250 + np.arange(128)),
    "contiguous_run_over_two_row_blocks": (1024, 256, 128 + np.arange(256)),
    "several_keys_in_one_block": (1024, 128, [130, 131, 140, 200, 255]),
    "mixed_blocks_and_a_partial_row_block": (
        1024, 256, np.sort(RNG.choice(1024, 200, replace=False))),
    "live_0": (1024, 128, []),
    "live_1": (1024, 128, [517]),
    "live_Kb": (1024, 128, np.sort(RNG.choice(1024, 128, replace=False))),
    "key_in_the_last_block": (1024, 128, [3, 1000, 1023]),
    "K_128": (128, 128, np.sort(RNG.choice(128, 77, replace=False))),
    "K_128_all": (128, 128, np.arange(128)),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_blocks_equal_xla_bit_for_bit(name):
    K, Kb, keys = CASES[name]
    key_idx = padded(keys, Kb, K)
    arrays, news, want, got = both(K, Kb, key_idx)
    same(want, got)
    # and both are what numpy says: the live rows move, a pad row reads
    # column K - 1 and writes nothing
    n = len(keys)
    for a, nw, sub, after in zip(arrays, news, got[0], got[1]):
        np.testing.assert_array_equal(
            np.asarray(sub), a[:, np.minimum(key_idx, K - 1)])
        ref = a.copy()
        ref[:, key_idx[:n]] = nw[:, :n]
        np.testing.assert_array_equal(np.asarray(after), ref)


@pytest.mark.parametrize("K,Kb,blocks", [
    (1024, 64, False),       # a rectangle under one block of rows
    (1000, 128, False),      # a key axis that is no whole blocks
    (1024, 128, True), (1048576, 4096, True), (128, 512, True)])
def test_the_form_is_read_from_the_shapes(K, Kb, blocks):
    assert not state_rows.block_form(K, Kb)      # the backend is the CPU
    state_rows._FORM = "blocks"
    try:
        assert state_rows.block_form(K, Kb) == blocks
    finally:
        state_rows._FORM = None


def test_sharded_local_rows(mesh4):
    """Inside a `shard_map` the kernels run on each chip's `[W, K / n]`
    share, the key indices local rows: four shards with four key sets
    (scattered, a run, pads only, one block)."""
    K, Kb, n = 512, 128, 4
    local = [np.array([3, 130, 131, 300, 511]), 128 + np.arange(128),
             np.array([], np.int32), 256 + np.arange(0, 100, 3)]
    key_idx = np.concatenate([padded(k, Kb, K) for k in local])
    arrays, news = make(K * n, Kb * n)

    def sharded(form):
        state_rows._FORM = form
        try:
            # check_vma: the INTERPRETER's scratch buffers are no varying
            # values (the compiled kernels pass the check:
            # tests/test_state_planes.py compiles the sharded step)
            return jax.jit(jax.shard_map(
                lambda a, nw, k: move(a, nw, k), mesh=mesh4,
                in_specs=(P(None, "shard"), P(None, "shard"), P("shard")),
                out_specs=P(None, "shard"),
                check_vma=form is None))(arrays, news, key_idx)
        finally:
            state_rows._FORM = None
    want, got = sharded(None), sharded("interpret")
    same(want, got)
    for d in range(n):                   # shard d's rows of shard d's share
        ref = arrays[0][:, d * K:(d + 1) * K].copy()
        ref[:, local[d]] = news[0][:, d * Kb:d * Kb + len(local[d])]
        np.testing.assert_array_equal(
            np.asarray(got[1][0])[:, d * K:(d + 1) * K], ref)


def test_inside_a_scan():
    """The `@fuse` shape: the mover in the body of a `lax.scan` whose
    carry is the state, one key set a step."""
    K, Kb, steps = 1024, 128, 3
    arrays, news = make(K, Kb)
    keys = np.stack([padded([1, 2, 700], Kb, K),
                     padded(384 + np.arange(128), Kb, K),
                     padded([2, 129, 1023], Kb, K)])

    def run(form):
        state_rows._FORM = form
        try:
            def body(carry, k):
                subs, after = move(carry, [x + 1 for x in news], k)
                return list(after), subs
            return jax.jit(lambda a, ks: lax.scan(body, a, ks))(arrays, keys)
        finally:
            state_rows._FORM = None
    want, got = run(None), run("interpret")
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert jax.tree.leaves(got)[3].shape == (steps, 50, Kb)


# -- the step: the flagship over 20 sends, with the mover and without -------

def flagship(n_keys, **kw):
    d = os.path.join(ROOT, "benchmarks", "configs", "pattern_1m")
    with open(os.path.join(d, "config.json")) as fh:
        sizes = json.load(fh)["sizes"]
    with open(os.path.join(d, "app.siddhi")) as fh:
        text = fh.read().format(**dict(sizes, n_keys=n_keys))
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(text, **kw)
    rows, errors = [], []
    rt.set_exception_listener(errors.append)
    rt.add_callback("flagship", lambda ts, i, o: rows.extend(
        (int(e.timestamp), *[float(x) for x in e.data]) for e in (i or [])))
    rt.start()
    return m, rt, rows, errors


def sends(n_keys, count=20, per=160, seed=3):
    """`count` sends of `per` scattered keys, each key's four stages in
    order (one match a visit): the paced cell's traffic, small."""
    rng = np.random.default_rng(seed)
    for i in range(count):
        keys = np.sort(rng.choice(n_keys, per, replace=False))
        k = np.repeat(keys, 4).astype(np.int64)
        stage = np.tile(np.arange(1, 5, dtype=np.int32), per)
        price = (100 + rng.integers(0, 50, per).repeat(4) +
                 stage).astype(np.float32)
        ts = 1_760_000_000_000 + 10 * i + stage.astype(np.int64)
        yield [k, price, stage], ts


def drive(form, n_keys=2048, **kw):
    state_rows._FORM = form
    try:
        m, rt, rows, errors = flagship(n_keys, **kw)
        try:
            h = rt.get_input_handler("TradeStream")
            for cols, ts in sends(n_keys):
                h.send_columns([c.copy() for c in cols], timestamps=ts.copy())
                rt.flush()
            assert not errors, errors
            qr = rt.query_runtimes["flagship"]
            state = [np.asarray(x) for x in jax.tree.leaves(qr.state)]
            snap = pickle.loads(rt.snapshot())["states"]["flagship"]
            return rows, state, snap, rt.state_report(), rt
        finally:
            m.shutdown()
    finally:
        state_rows._FORM = None


def test_flagship_step_with_the_mover_and_without():
    rows, state, snap, _, _ = drive(None)
    rows_b, state_b, snap_b, _, _ = drive("interpret")
    assert len(rows) == 20 * 160 and rows == rows_b, (len(rows), len(rows_b))
    for a, b in zip(state, state_b):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)
    # a snapshot taken after the 20 sends, array for array
    assert jax.tree.structure(snap["state"]) == \
        jax.tree.structure(snap_b["state"])
    for a, b in zip(jax.tree.leaves(snap["state"]),
                    jax.tree.leaves(snap_b["state"])):
        np.testing.assert_array_equal(a, b)


# -- the counter ------------------------------------------------------------

STATS_QL = """
@app:playback @app:statistics('BASIC')
define stream T (key long, price float, volume int);
partition with (key of T)
begin
  @capacity(keys='1024', slots='4') @info(name='q')
  from every e1=T[volume == 1] -> e2=T[volume == 2]
  select e1.key as k insert into M;
end;
"""


def counted(*sends_of_keys, **kw):
    """The counters' (keys, blocks) after each of the sends; keys bind to
    slots in the order they first come, so a first send of 0 .. 1023
    makes key k's state row k."""
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(STATS_QL, **kw)
    rt.start()
    try:
        h = rt.get_input_handler("T")
        out = []
        for i, ks in enumerate(sends_of_keys):
            ks = np.asarray(ks, np.int64)
            h.send_columns([ks, np.ones(ks.shape, np.float32),
                            np.ones(ks.shape, np.int32)],
                           timestamps=np.full(ks.shape, 1_000 * (i + 1),
                                              np.int64))
            rt.flush()
            q = rt.state_report()["state_rows"].get(
                "q", {"keys": 0, "blocks": 0, "keys_per_block": 0.0})
            out.append((q["keys"], q["blocks"], q["keys_per_block"]))
        return out
    finally:
        m.shutdown()


def test_counter_scattered_and_contiguous():
    all_keys = np.arange(1024)
    got = counted(
        all_keys,                       # contiguous: 1,024 keys of 8 blocks
        np.arange(8) * 128 + 5,         # scattered: a key a block
        np.arange(512),                 # a run the DENSE step takes whole
        np.concatenate([np.arange(128, 256), np.arange(300, 428)]))
    # (the first send's rectangle is the 4,096-row bucket, over the 1,024
    # keys' capacity: the gather path, though its keys are one run)
    assert got[0] == (1024, 8, 128.0)
    assert got[1] == (1024 + 8, 8 + 8, 1032 / 16)
    assert got[2][:2] == got[1][:2]     # the dense step moves no row by
    #                                     the mover: nothing is counted
    # two runs with a gap between them: 256 keys of 3 blocks
    assert got[3][:2] == (1032 + 256, 16 + 3)


def test_counter_on_the_mesh_counts_each_chips_rows(mesh4):
    """Slot s lies on shard s % 4, row s // 4: the sweep's 1,024 keys are
    256 consecutive local rows a chip, two blocks each; eight scattered
    slots of one shard are eight rows of that chip, 32 apart."""
    got = counted(np.arange(1024), np.arange(8) * 128, mesh=mesh4)
    assert got[0] == (1024, 8, 128.0)
    assert got[1][:2] == (1024 + 8, 8 + 2)


def test_counter_is_in_the_metrics_text():
    from siddhi_tpu.observability.exposition import render_prometheus
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(STATS_QL)
    rt.start()
    try:
        h = rt.get_input_handler("T")
        ks = np.arange(1024, dtype=np.int64)
        h.send_columns([ks, np.ones(1024, np.float32),
                        np.ones(1024, np.int32)],
                       timestamps=np.full(1024, 5, np.int64))
        rt.flush()
        text = render_prometheus({rt.name: rt})
    finally:
        m.shutdown()
    assert 'siddhi_state_row_keys_total{' in text
    keys = [l for l in text.splitlines()
            if l.startswith("siddhi_state_row_keys_total{")]
    blocks = [l for l in text.splitlines()
              if l.startswith("siddhi_state_row_blocks_total{")]
    assert keys[0].endswith(" 1024") and blocks[0].endswith(" 8")


def test_counter_off_without_statistics():
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(STATS_QL.replace(
        "@app:statistics('BASIC')", ""))
    rt.start()
    try:
        h = rt.get_input_handler("T")
        ks = np.arange(1024, dtype=np.int64)
        h.send_columns([ks, np.ones(1024, np.float32),
                        np.ones(1024, np.int32)],
                       timestamps=np.full(1024, 5, np.int64))
        rt.flush()
        assert rt.state_report()["state_rows"] == {}
    finally:
        m.shutdown()
