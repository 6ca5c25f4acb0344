"""`pattern_32m`: its plain reference against a hand-worked case, its sweep of
the traffic's active range, one chip's `least_bytes`, and the whole of a run
with a send dropped underneath it."""
import numpy as np
import pytest

from benchmarks.harness import loader
from test_bench_doctored import run_with

CELL = "pattern_32m.mesh4_saturated"


def test_reference_by_hand():
    m = loader.resolve(CELL, rehearse=True).model
    # key 5 matches (2>=1, 9>=3); key 6 fails e2 (0.5 < 1); key 7 fails e4
    keys = np.repeat(np.array([5, 6, 7], np.int64), 4)
    price = np.array([1, 2, 3, 9, 1, .5, 3, 9, 1, 2, 3, 2], np.float32)
    vol = np.tile(np.array([1, 2, 3, 4], np.int32), 3)
    ref = m.reference([{"cols": [keys, price, vol]}], {})[0]
    assert ref["k"].tolist() == [5]
    assert (ref["p1"][0], ref["p2"][0], ref["p4"][0]) == (1.0, 2.0, 9.0)
    # the control: a bfloat16 payload fails the exact comparison
    want = {"k": np.arange(8, dtype=np.int64),
            "p1": np.linspace(.1, .8, 8).astype(np.float32),
            "p2": np.linspace(.2, .9, 8).astype(np.float32),
            "p4": np.linspace(.3, 1., 8).astype(np.float32)}
    assert m.compare(want, want) == dict.fromkeys(m.LIMITS, 0)
    assert m.compare(m.control_rows(want), want)["rows_differing"] >= 6


def test_send_keys_sweep_the_active_range_only():
    cell = loader.resolve(CELL)
    m, t = cell.model, cell.traffic
    assert cell.sizes["n_keys"] == 33554432 and t["active_keys"] == 4194304
    plan = {"n_keys": cell.sizes["n_keys"]}
    per_pass = t["active_keys"] // t["keys_per_send"]
    assert per_pass == 32
    first, last = m.send_keys(0, t, plan), m.send_keys(per_pass - 1, t, plan)
    assert (first[0], first[-1]) == (0, 131071)
    assert (last[0], last[-1]) == (4194304 - 131072, 4194303)
    # round and round: send 32 is send 0's block again, never a key beyond
    np.testing.assert_array_equal(m.send_keys(per_pass, t, plan), first)
    assert max(int(m.send_keys(i, t, plan).max())
               for i in range(2 * per_pass)) == t["active_keys"] - 1
    # with no active range named, the whole key space (pattern_1m's sweep)
    whole = {k: v for k, v in t.items() if k != "active_keys"}
    assert m.send_keys(255, whole, plan)[-1] == 33554431
    with pytest.raises(ValueError, match="active_keys"):
        m.send_keys(0, dict(t, active_keys=2 * 33554432), plan)
    # a send: 4 stages a key in stage order, one match a key
    s = m.make_send(np.random.default_rng(0), 3, t, plan, 1000)
    assert s["events"] == 524288 == m.events_per_send(t)
    assert s["cols"][2][:8].tolist() == [1, 2, 3, 4, 1, 2, 3, 4]
    assert m.expected_rows(s) == 131072


def test_least_bytes_is_one_chips_share():
    cell = loader.resolve(CELL)
    whole = 131072 * (2 * 520 + 4 * 24 + 28)
    assert cell.sizes["shards"] == 4 == cell.chips
    assert cell.model.least_bytes(cell.traffic, cell.sizes, cell.config) \
        == whole // 4 == 38141952
    # a rehearsal deploys one shard: the whole send's bytes on its one chip
    r = loader.resolve(CELL, rehearse=True)
    assert r.sizes["shards"] == 1
    assert r.model.least_bytes(r.traffic, r.sizes, r.config) \
        == 128 * (2 * 520 + 4 * 24 + 28)


def test_config_states_what_the_contract_asks():
    cell = loader.resolve(CELL)
    cfg, one_m = cell.config, loader.resolve("pattern_1m.saturated").config
    for key in ("source", "deployment", "assumed", "reduced", "guarantees"):
        assert cfg[key]
    assert len(cfg["source"]) <= 200
    # shapes are pattern_1m's; only the scale and the mesh differ
    for key in ("stream", "query", "columns", "state_bytes_per_key"):
        assert cfg[key] == one_m[key]
    assert {k: v for k, v in cfg["sizes"].items()
            if k not in ("n_keys", "shards")} == \
        {k: v for k, v in one_m["sizes"].items() if k != "n_keys"}
    assert set(one_m["guarantees"]) < set(cfg["guarantees"])
    assert set(one_m["assumed"]) < set(cfg["assumed"])
    assert cfg["sizes"]["n_keys"] * cfg["state_bytes_per_key"] \
        == 17448304640 > 16e9
    assert "@app:mesh(shards='4')" in cell.app_text
    with open(loader.BENCH_DIR + "/configs/pattern_32m/model.py") as fh:
        imports = [ln for ln in fh if ln.startswith(("import ", "from "))]
    assert imports and not any("siddhi_tpu" in ln for ln in imports)


def test_a_dropped_send_is_not_correct(monkeypatch, capsys):
    rc, last, out = run_with(monkeypatch, capsys, CELL, "drop_send")
    assert rc == 0
    assert last["correct"] is False, out[-1500:]
    assert last["failed"] >= 1
    assert "OVER" in out
