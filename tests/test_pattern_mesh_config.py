"""`pattern_32m` through its own app text, on the CPU's 8 virtual devices:
the benchmark configuration's `@app:mesh(shards='N')` deploys the sharded
runtime with no `mesh=` argument, four shards deliver the plain reference's
rows and the one-shard program's rows, the state is built a quarter per
device by one jitted init, and the annotation's errors name both numbers."""
import importlib.util
import json
import os

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from siddhi_tpu import SiddhiManager
from siddhi_tpu.exceptions import SiddhiAppValidationError

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG_DIR = os.path.join(ROOT, "benchmarks", "configs", "pattern_32m")
N_KEYS = 4096
TRAFFIC = {"keys_per_send": 128, "key_order": "contiguous_sweep",
           "active_keys": 1024}


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(CFG_DIR, "config.json")) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def model():
    spec = importlib.util.spec_from_file_location(
        "bench_model_pattern_32m_t1", os.path.join(CFG_DIR, "model.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def app_text(config, shards, n_keys=N_KEYS):
    sizes = dict(config["sizes"], n_keys=n_keys, shards=shards)
    with open(os.path.join(CFG_DIR, "app.siddhi")) as fh:
        return fh.read().format(**sizes)


def drive(config, model, shards, passes=3, **deploy_kw):
    """Deploy by the app text alone, send `passes` passes of the active
    range, and return (runtime facts, sends, delivered rows per send)."""
    if len(jax.devices()) < 8:
        pytest.skip("needs the 8 virtual devices of tests/conftest.py")
    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime(app_text(config, shards),
                                         **deploy_kw)
        errors, batches = [], []
        rt.set_exception_listener(errors.append)

        def on_batch(_ts, b):
            sel = b["valid"] & (b["kind"] == 0)
            batches.append({n: np.asarray(b["cols"][n])[sel]
                            for n in config["columns"]})

        rt.add_batch_callback(config["query"], on_batch)
        rt.start()
        h = rt.get_input_handler(config["stream"])
        plan = model.plan(7, TRAFFIC, {"n_keys": N_KEYS})
        n_sends = passes * TRAFFIC["active_keys"] // TRAFFIC["keys_per_send"]
        sends, rows = [], []
        clock = 1000
        for i in range(n_sends):
            clock += model.clock_step_ms(TRAFFIC)
            send = model.make_send(np.random.default_rng([7, i]), i,
                                   TRAFFIC, plan, clock)
            sends.append(send)
            before = len(batches)
            h.send_columns([c.copy() for c in send["cols"]],
                           timestamps=send["ts"].copy())
            rt.flush()
            got = batches[before:]
            rows.append({n: np.concatenate([g[n] for g in got])
                         for n in config["columns"]} if got else None)
        assert not errors, errors[:1]
        qr = rt.query_runtimes[config["query"]]
        facts = {
            "mesh": rt.mesh,
            "leaves": [(x.shape, x.sharding,
                        [s.data.shape for s in x.addressable_shards])
                       for x in jax.tree.leaves(qr.state)],
            "key_capacity": qr.planned.key_capacity,
            "explain": rt.explain(),
        }
        return facts, sends, rows, plan
    finally:
        m.shutdown()


def test_four_shards_by_annotation_equal_the_reference_and_one_shard(
        config, model):
    facts4, sends, rows4, plan = drive(config, model, shards=4)
    facts1, _, rows1, _ = drive(config, model, shards=1)
    assert facts4["mesh"] is not None and facts4["mesh"].devices.size == 4
    assert facts1["mesh"] is None            # shards='1': unsharded runtime
    refs = model.reference(sends, plan)
    assert all(v == 0 for v in model.LIMITS.values())
    for i, want in enumerate(refs):
        assert rows4[i] is not None and rows1[i] is not None, i
        got4 = model.canonical(rows4[i])
        got1 = model.canonical(rows1[i])
        nums = model.compare(got4, model.canonical(want))
        assert all(nums[n] <= model.LIMITS[n] for n in model.LIMITS), \
            (i, nums)
        assert got4["k"].shape[0] == model.expected_rows(sends[i])
        for n in config["columns"]:          # row for row, bit for bit
            np.testing.assert_array_equal(got4[n], got1[n])


def test_every_key_axis_leaf_is_a_quarter_per_device(config, model):
    facts, _, _, _ = drive(config, model, shards=4, passes=1)
    K = facts["key_capacity"]
    keyed = 0
    for shape, sharding, shard_shapes in facts["leaves"]:
        if K not in shape:
            continue
        keyed += 1
        axis = shape.index(K)
        assert len(shard_shapes) == 4
        for ss in shard_shapes:
            assert ss[axis] * 4 == K, (shape, ss)
    assert keyed >= 3                        # b32 and the two 64-bit planes


def test_explain_and_lint_see_the_annotation_mesh(config, model):
    facts, _, _, _ = drive(config, model, shards=4, passes=1)
    assert "sharding" in json.dumps(facts["explain"], default=str)
    # lint PART002 takes its device count from the annotation
    from siddhi_tpu.analysis import analyze
    small = app_text(config, shards=4, n_keys=2)
    assert "PART002" in [f.rule_id for f in analyze(small)]
    assert "PART002" not in [
        f.rule_id for f in analyze(app_text(config, shards=1, n_keys=2))]


def test_phase_report_lists_shard_group_under_stage_host(config, model):
    """Statistics BASIC: the router's regroup is a part of `stage_host`,
    beside `route_keys` (whose self time is then slot resolution alone)."""
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime(
            "@app:statistics('BASIC')\n" + app_text(config, shards=4))
        rt.add_batch_callback(config["query"], lambda ts, b: b["n_valid"])
        rt.start()
        h = rt.get_input_handler(config["stream"])
        plan = model.plan(7, TRAFFIC, {"n_keys": N_KEYS})
        for i in range(3):
            send = model.make_send(np.random.default_rng([7, i]), i,
                                   TRAFFIC, plan, 1000 + 10 * i)
            h.send_columns(send["cols"], timestamps=send["ts"])
        rt.flush()
        stage = rt.phase_report()["queries"][config["query"]]["phases"][
            "stage_host"]
        assert list(stage["parts"]) == ["stage", "route_keys", "shard_group",
                                        "obs_feed"]
        assert stage["parts"]["shard_group"]["count"] == 3
        assert stage["parts"]["shard_group"]["seconds"] > 0
    finally:
        m.shutdown()


def test_more_shards_than_devices_names_both_numbers(config):
    n = len(jax.devices())
    m = SiddhiManager()
    try:
        with pytest.raises(SiddhiAppValidationError) as ei:
            m.create_siddhi_app_runtime(app_text(config, shards=2 * n))
        assert str(2 * n) in str(ei.value) and f"has {n}" in str(ei.value)
    finally:
        m.shutdown()


@pytest.mark.parametrize("shards, given, ok", [(4, 2, False), (4, 4, True),
                                               (1, 2, False)])
def test_a_mesh_argument_must_agree_with_the_annotation(config, shards,
                                                         given, ok):
    devs = np.array(jax.devices())
    if devs.size < 4:
        pytest.skip("needs 4 virtual devices")
    mesh = Mesh(devs[-given:], ("shard",))   # NOT the first devices
    m = SiddhiManager()
    try:
        if ok:
            rt = m.create_siddhi_app_runtime(app_text(config, shards),
                                             mesh=mesh)
            assert rt.mesh is mesh           # the agreeing argument wins
        else:
            with pytest.raises(SiddhiAppValidationError) as ei:
                m.create_siddhi_app_runtime(app_text(config, shards),
                                            mesh=mesh)
            assert f"'{shards}'" in str(ei.value)
            assert f"{given} device" in str(ei.value)
    finally:
        m.shutdown()


@pytest.mark.parametrize("raw", ["0", "-2", "four", ""])
def test_a_malformed_shard_count_is_a_deploy_error(config, raw):
    m = SiddhiManager()
    try:
        with pytest.raises(SiddhiAppValidationError, match="shards"):
            m.create_siddhi_app_runtime(app_text(config, shards=raw))
    finally:
        m.shutdown()
