"""The pattern plan's ONE state-init path (`pattern_planner._init_program`),
without a chip: what it compiles to, what it leaves on the device, and that
no two calls share a buffer."""
import gc

import jax
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from siddhi_tpu import SiddhiManager
from siddhi_tpu.core import pattern_planner

K = 65536
APP = """
@app:playback
define stream TradeStream (key long, price float, volume int);
partition with (key of TradeStream)
begin
  @capacity(keys='%d', slots='4')
  @emit(rows='2')
  @info(name='flagship')
  from every e1=TradeStream[volume == 1]
       -> e2=TradeStream[volume == 2 and price >= e1.price]
       -> e3=TradeStream[volume == 3]
       -> e4=TradeStream[volume == 4 and price >= e3.price]
  select e1.key as k, e1.price as p1, e2.price as p2, e4.price as p4,
         count() as n
  insert into Matches;
end;
""" % K


def deploy(mesh=None):
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(APP, mesh=mesh)
    return m, rt, rt.query_runtimes["flagship"]


def buffers(tree):
    """Device-buffer addresses of every shard of every leaf."""
    return [s.data.unsafe_buffer_pointer()
            for x in jax.tree.leaves(tree) for s in x.addressable_shards]


@pytest.fixture()
def mesh4():
    devs = np.array(jax.devices())
    if devs.size < 4:
        pytest.skip("needs 4 virtual devices")
    return Mesh(devs[:4], ("shard",))


def test_compiled_out_shardings_are_the_shard_specs(mesh4):
    m, _rt, qr = deploy(mesh4)
    try:
        p = qr.planned
        compiled = p.init_state.lower(p.key_capacity).compile()
        want = jax.tree.map(
            lambda spec: NamedSharding(mesh4, spec),
            pattern_planner._shard_specs(
                pattern_planner.StatePacker(p.exec.init_state(1)),
                p.selector_exec),
            is_leaf=lambda x: isinstance(x, P))
        got = compiled.output_shardings
        flat_want = jax.tree.leaves(want)
        flat_got = jax.tree.leaves(got)
        assert len(flat_want) == len(flat_got) >= 3
        for leaf, w, g in zip(jax.tree.leaves(qr.state), flat_want,
                              flat_got):
            assert g.is_equivalent_to(w, leaf.ndim), (leaf.shape, g, w)
            assert leaf.sharding.is_equivalent_to(w, leaf.ndim)
        # the blobs really are split: a quarter of the key axis a device
        for blob in qr.state[0][:3]:         # b32 and the two 64-bit planes
            assert {s.data.shape for s in blob.addressable_shards} == \
                {(blob.shape[0], p.key_capacity // 4)}
    finally:
        m.shutdown()


def test_init_program_needs_no_more_than_the_state_itself():
    m, _rt, qr = deploy()
    try:
        p = qr.planned
        state_bytes = sum(x.nbytes for x in jax.tree.leaves(qr.state))
        biggest = max(x.nbytes for x in jax.tree.leaves(qr.state))
        mem = p.init_state.lower(p.key_capacity).compile().memory_analysis()
        assert mem.output_size_in_bytes >= state_bytes
        # no per-leaf slab, no concatenated second copy: what the program
        # holds beside its output is under one leaf
        assert mem.temp_size_in_bytes <= biggest
        assert mem.output_size_in_bytes + mem.temp_size_in_bytes <= \
            state_bytes + biggest
    finally:
        m.shutdown()


def test_live_bytes_after_deploy_are_within_one_leaf_of_the_state():
    gc.collect()
    before = sum(a.nbytes for a in jax.live_arrays())
    m, _rt, qr = deploy()
    try:
        gc.collect()
        live = sum(a.nbytes for a in jax.live_arrays()) - before
        leaves = jax.tree.leaves(qr.state)
        state_bytes = sum(x.nbytes for x in leaves)
        assert state_bytes >= K * 520
        assert state_bytes <= live <= state_bytes + max(
            x.nbytes for x in leaves)
    finally:
        m.shutdown()


@pytest.mark.parametrize("sharded", [False, True])
def test_no_two_states_of_one_plan_share_a_buffer(sharded, request):
    mesh = request.getfixturevalue("mesh4") if sharded else None
    m, _rt, qr = deploy(mesh)
    try:
        p = qr.planned
        first = buffers(qr.state)
        # no leaf aliases another inside one state (identical selector
        # slabs must not fold into one buffer: the steps donate them)
        assert len(set(first)) == len(first)
        second = p.init_state(p.key_capacity)
        assert not set(first) & set(buffers(second))
        # ... and a second runtime of the same plan runs beside the first
        from siddhi_tpu.core.runtime import PatternQueryRuntime
        qr2 = PatternQueryRuntime(p, qr.app, slot_allocator=None)
        assert not set(first) & set(buffers(qr2.state))
        assert not set(buffers(second)) & set(buffers(qr2.state))
    finally:
        m.shutdown()


def test_init_equals_the_per_leaf_pack_it_replaced():
    """Value for value what `packer.pack(pexec.init_state(K))` built."""
    m, _rt, qr = deploy()
    try:
        p = qr.planned
        small = 64
        packer = pattern_planner.StatePacker(p.exec.init_state(1))
        *want, want_s = packer.pack(p.exec.init_state(small))
        (*got, scal), sel_state = p.init_state(small)
        assert len(got) == len(want) == 3    # b32, low words, high words
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
            assert g.dtype == w.dtype
        for a, b in zip(scal, want_s):
            assert np.asarray(a) == np.asarray(b) and a.dtype == b.dtype
        for a, b in zip(sel_state, p.selector_exec.init_state()):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            assert a.dtype == b.dtype
    finally:
        m.shutdown()


def test_state_init_span_carries_bytes_and_shards(mesh4, tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        m, _rt, qr = deploy(mesh4)
        state_bytes = sum(x.nbytes for x in jax.tree.leaves(qr.state))
        m.shutdown()
    finally:
        jax.profiler.stop_trace()
    import glob
    path = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                         "*.xplane.pb"))[0]
    data = jax.profiler.ProfileData.from_file(path)
    spans = [dict(ev.stats) for pl in data.planes
             if pl.name.startswith("/host:CPU")
             for ln in pl.lines for ev in ln.events
             if ev.name == "siddhi:state_init"]
    assert len(spans) == 1
    assert int(spans[0]["bytes"]) == state_bytes
    assert int(spans[0]["shards"]) == 4 and spans[0]["q"] == "flagship"
