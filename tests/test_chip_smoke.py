"""chip_smoke.py on the CPU: the rehearsal walks the whole script at tiny
sizes (same phases, same by-value checks, the 4-way mesh on the virtual
devices conftest sets up), and the contract around it holds — one JSON
object on the last stdout line naming the device as jax reports it, a
non-zero exit and NO result line when the platform is not a TPU and
`--rehearsal` was not passed, and a compile cache that is placed from
outside (JAX_COMPILATION_CACHE_DIR set => jax's config is left alone).
"""
import importlib.util
import json
import os
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def chip_smoke():
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _cache_config():
    return (jax.config.jax_compilation_cache_dir,
            jax.config.jax_persistent_cache_min_compile_time_secs,
            jax.config.read("jax_dump_ir_to"))


def test_rehearsal_runs_every_phase_and_prints_the_contract(
        chip_smoke, tmp_path, monkeypatch, capsys):
    # a cache placed from outside: the script must not touch jax's config
    # (which also keeps this test from switching the persistent cache on
    # for the rest of the pytest process)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    before = _cache_config()
    rc = chip_smoke.main(["--rehearsal", "--out", str(tmp_path / "out")])
    out = capsys.readouterr().out
    assert _cache_config() == before
    assert "REHEARSAL" in out
    lines = out.strip().splitlines()
    devs = jax.devices()
    # the verdict line carries exactly "ok" and "device", nothing else
    assert json.loads(lines[-1]) == {
        "ok": True,
        "device": {"platform": devs[0].platform,
                   "kind": devs[0].device_kind, "count": len(devs)}}, out
    assert devs[0].platform == "cpu"
    assert lines[-2].startswith("chip_smoke report: ")
    summary = json.loads(lines[-2].split(": ", 1)[1])
    assert rc == 0 and summary["ok"] is True and summary["failed"] == []
    assert summary["rehearsal"] is True
    assert summary["native_staging"] is True
    assert summary["mesh4"] == "ran"       # conftest: 8 virtual devices
    with open(tmp_path / "out" / "report.json") as fh:
        report = json.load(fh)
    assert report["environment"]["compile_cache_placed_by_env"] is True
    phases = report["phases"]
    for name in ("environment", "flagship_blocking", "flagship_served",
                 "length_batch_avg", "time_groupby_having",
                 "windowed_join", "sequence_within", "rest_service",
                 "mesh4_blocking", "mesh4_served", "no_f64"):
        assert phases[name]["ok"] is True, (name, phases[name])
    n_keys = chip_smoke.REHEARSAL["n_keys"]
    for name in ("flagship_blocking", "flagship_served",
                 "mesh4_blocking", "mesh4_served"):
        assert phases[name]["matches"] == n_keys
    assert phases["flagship_served"]["ring"]["occupancy"] == 0
    assert phases["mesh4_served"]["sharded_ring_leaves"] > 0
    assert phases["rest_service"]["explain_steps_unavailable"] == []
    assert phases["no_f64"]["ir"]["modules"] > 0
    assert phases["no_f64"]["ir"]["with_f64"] == []


def test_cpu_without_rehearsal_flag_is_an_error(chip_smoke, tmp_path,
                                                monkeypatch, capsys):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    rc = chip_smoke.main(["--out", str(tmp_path / "out")])
    captured = capsys.readouterr()
    assert rc != 0
    assert captured.out.strip() == ""       # no result line at all
    assert "no TPU" in captured.err
    assert not (tmp_path / "out").exists()


def test_cache_helper_sets_only_the_checkout_dir_when_env_is_unset(
        monkeypatch):
    from siddhi_tpu.utils.compile_cache import enable_compile_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/outside")
    before = _cache_config()
    assert enable_compile_cache() == "/placed/outside"
    assert _cache_config() == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        path = enable_compile_cache()
        assert path == os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert jax.config.jax_persistent_cache_min_compile_time_secs == \
            before[1]
        # either way the op names are part of an entry's key: a cache a
        # tree with other named scopes filled must not name this tree's ops
        assert jax.config.jax_compilation_cache_include_metadata_in_key
    finally:
        jax.config.update("jax_compilation_cache_dir", before[0])


def _third_dispatch(monkeypatch, action):
    """Replace the junction's third dispatch: 'refuse' raises as a step
    the device rejects at run time does (the junction catches it, logs it
    and drops the batch — on_error=LOG); 'lose' drops the batch without
    any error at all."""
    from siddhi_tpu.core import runtime
    orig = runtime.StreamJunction._dispatch_one
    calls = [0]

    def third(self, *a, **kw):
        calls[0] += 1
        if calls[0] != 3:
            return orig(self, *a, **kw)
        if action == "refuse":
            raise RuntimeError("UNIMPLEMENTED: injected device refusal")
    monkeypatch.setattr(runtime.StreamJunction, "_dispatch_one", third)


def test_a_step_the_runtime_swallowed_fails_the_smoke_phase_with_its_cause(
        chip_smoke, tmp_path, monkeypatch):
    _third_dispatch(monkeypatch, "refuse")
    smoke = chip_smoke.Smoke(chip_smoke.REHEARSAL, 0, str(tmp_path))
    with smoke.phase("sequence_within") as rec:
        chip_smoke.phase_sequence(smoke, rec)
    assert rec["ok"] is False
    assert "injected device refusal" in rec["error"]


def test_bench_drivers_raise_on_a_swallowed_step_and_on_a_wrong_count(
        monkeypatch):
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    import bench
    # clean runs: the closed-form row counts hold
    bench.config_sequence_within(n_batches=3, B=1 << 8)
    bench.config_length_batch(n_batches=2, B=1 << 11)
    with monkeypatch.context() as mp:
        _third_dispatch(mp, "refuse")
        with pytest.raises(RuntimeError, match="injected device refusal"):
            bench.config_sequence_within(n_batches=3, B=1 << 8)
    with monkeypatch.context() as mp:
        _third_dispatch(mp, "lose")
        with pytest.raises(RuntimeError,
                           match="delivered 4000 rows, expected 6000"):
            bench.config_length_batch(n_batches=2, B=1 << 11)
