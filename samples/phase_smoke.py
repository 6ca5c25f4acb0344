"""Phase-profiler smoke (README "Phase profiling").

End-to-end assertions over the phase-attribution surface in <30 s:

1. every phase of the canonical taxonomy (stage_host, h2d,
   dispatch_submit, device_compute, ring_wait, d2h_drain, demux, sink)
   is nonzero for a @serve query under sampled deep mode — one trace
   spans the dispatch thread AND the drainer thread;
2. the drainer's delivery spans carry the SAME trace id as the
   dispatch-side spans (cross-thread handoff/adopt), tagged with the
   "drain" track;
3. the sampled deep mode's overhead stays bounded (< 20% of per-send
   p50 on a worst-case near-zero-work query — the only
   block_until_ready it ever takes is the every-Nth fence), and the
   always-on layer costs < 2% flagship served ev/s against an arm
   with every profiler hook compiled out;
4. the surfaces agree: phase_report() accounts the e2e budget,
   /metrics carries siddhi_phase_seconds_total, EXPLAIN gains a
   `phases` node, and none of them touch the device.
"""
import sys
import time

sys.path.insert(0, ".")

import numpy as np  # noqa: E402

from siddhi_tpu import SiddhiManager  # noqa: E402
from siddhi_tpu.utils.config import InMemoryConfigManager  # noqa: E402

PHASES = ("stage_host", "h2d", "dispatch_submit", "device_compute",
          "ring_wait", "d2h_drain", "demux", "sink")

SERVED_QL = """
@app:name('PhaseSmoke')
@app:statistics('DETAIL')
define stream S (k long, price float, vol int);
@serve
@info(name='q') from S[price > 1.0]
select k, price * 2.0 as p2 insert into Out;
"""


def _run(sample_every, n_sends=64, B=256):
    manager = SiddhiManager()
    manager.set_config_manager(InMemoryConfigManager(
        {"profile.sample.every": str(sample_every)}))
    rt = manager.create_siddhi_app_runtime(SERVED_QL)
    got = [0]
    rt.add_callback("q", lambda ts, cur, exp: got.__setitem__(
        0, got[0] + len(cur or [])))
    rt.start()
    h = rt.get_input_handler("S")
    cols = [np.arange(B, dtype=np.int64),
            np.full(B, 2.0, np.float32), np.ones(B, np.int32)]
    lat = []
    for i in range(n_sends):
        t0 = time.perf_counter()
        h.send_columns([c.copy() for c in cols],
                       timestamps=np.full(B, 1000 + i, np.int64))
        lat.append(time.perf_counter() - t0)
    rt.flush()
    p50 = sorted(lat)[len(lat) // 2]
    return manager, rt, got[0], p50


def main():
    # 1. every phase nonzero under sampled deep mode
    manager, rt, rows, _ = _run(sample_every=8)
    rep = rt.phase_report()
    node = rep["queries"]["q"]
    assert rows, "served query delivered nothing"
    missing = [p for p in PHASES
               if node["phases"].get(p, {}).get("ns",
                                                node["phases"].get(
                                                    p, {}).get(
                                                    "seconds", 0)) <= 0]
    assert not missing, f"phases never recorded: {missing}"
    assert node["sampled_dispatches"] >= 1
    assert node["accounted"] >= 0.5, node
    print(f"phases: all {len(PHASES)} recorded, "
          f"accounted={node['accounted']}, "
          f"sampled={node['sampled_dispatches']}")

    # 2. cross-thread trace: drain spans share the dispatch trace id
    traces = rt.trace_dump("q", 16)
    linked = [t for t in traces
              if any(s.get("track") == "drain" for s in t["spans"])
              and any(s.get("track") is None for s in t["spans"])]
    assert linked, "no trace spans both the dispatch and drainer threads"
    drained = {s["stage"] for t in linked for s in t["spans"]
               if s.get("track") == "drain"}
    assert {"fetch", "demux", "sink"} <= drained, drained
    print(f"trace: {len(linked)} cross-thread traces, drain-side spans "
          f"{sorted(drained)}")

    # 4. (before shutdown) surfaces agree and never touch the device
    import jax
    from siddhi_tpu.observability.exposition import render_prometheus

    def _bomb(*a, **k):
        raise AssertionError("observability surface touched the device")

    orig_get, orig_block = jax.device_get, jax.block_until_ready
    jax.device_get = jax.block_until_ready = _bomb
    try:
        text = render_prometheus(manager.runtimes)
        rt.phase_report()
        from siddhi_tpu.observability.explain import explain_query
        exp = explain_query(rt, "q", deep=False)["phases"]
    finally:
        jax.device_get, jax.block_until_ready = orig_get, orig_block
    assert "siddhi_phase_seconds_total" in text
    assert "siddhi_phase_dispatches_sampled_total" in text
    assert exp["available"] and exp["phases"]["dispatch_submit"]["count"]
    print("surfaces: /metrics families + EXPLAIN phases node, "
          "zero device fetches")
    manager.shutdown()

    # 3. sampled-mode overhead stays bounded: < 20% of per-send p50
    # even on this near-zero-work filter query, where the every-Nth
    # fence is at its proportionally worst (interleaved best-of-four
    # medians; the hard never-block/never-fetch guarantees are sync-
    # counted in tests/test_phases.py — this is the timing sanity bar)
    p50s = {0: [], 8: []}
    for _ in range(4):
        for every in (0, 8):
            m, _, _, p50 = _run(sample_every=every)
            m.shutdown()
            p50s[every].append(p50)
    overhead = min(p50s[8]) / min(p50s[0]) - 1.0
    assert overhead < 0.20, f"sampled deep mode costs {overhead:.1%}"
    print(f"overhead: sampled deep mode {overhead:+.1%} vs always-on "
          "(< 20%)")

    # 5. always-on phase profiling costs <2% FLAGSHIP served ev/s
    # (the acceptance A/B, against the real workload where a send
    # carries device compute — not an empty filter).  The B arm keeps
    # statistics at BASIC but neutralizes every always-on profiler
    # hook (_step_phase timing, the rebind-wait attribution, and
    # PhaseProfiler.add for stage_host/h2d/ring_wait/d2h/demux/sink),
    # so the delta is exactly what THIS layer adds on a hot send.
    # BASIC's pre-existing cost (latency histograms, e2e stamping)
    # is the same in both arms by construction — it predates the
    # profiler and is not what the bar measures.  Arms interleave and
    # take best-of-N so one CI scheduling blip can't fail the bar.
    from siddhi_tpu.analysis.corpus import FLAGSHIP_QL_TEMPLATE
    from siddhi_tpu.core import runtime as _rt
    from siddhi_tpu.observability.phases import PhaseProfiler

    def _plain_step(qr, fn, name=None, mult=1):
        return fn()

    def _plain_rebind(qr, v, mult=1, name=None, attr="state"):
        setattr(qr, attr, v)

    def flagship_eps(profiled, n_keys=512, n_sends=24):
        ql = FLAGSHIP_QL_TEMPLATE.format(
            async_ann="", pipe_ann="@serve", n_keys=n_keys, slots=4)
        keys = np.repeat(np.arange(n_keys, dtype=np.int64), 4)
        vol4 = np.tile(np.array([1, 2, 3, 4], np.int32), n_keys)
        price4 = vol4.astype(np.float32)
        saved = (_rt._step_phase, _rt._rebind_state, PhaseProfiler.add)
        if not profiled:
            _rt._step_phase = _plain_step
            _rt._rebind_state = _plain_rebind
            PhaseProfiler.add = lambda self, q, p, ns, **kw: None
        try:
            m = SiddhiManager()
            rt = m.create_siddhi_app_runtime(ql)
            rt.set_statistics_level("BASIC")
            rt.add_batch_callback("flagship", lambda ts, b: None)
            rt.start()
            h = rt.get_input_handler("TradeStream")
            clock = [1000]

            def send():
                clock[0] += 10
                ts = clock[0] + np.tile(np.arange(4, dtype=np.int64),
                                        n_keys)
                h.send_columns([keys, price4, vol4], timestamps=ts)

            send()
            rt.flush()                                  # warm/compile
            t0 = time.perf_counter()
            for _ in range(n_sends):
                send()
            rt.flush()
            eps = n_sends * 4 * n_keys / (time.perf_counter() - t0)
            m.shutdown()
            return eps
        finally:
            (_rt._step_phase, _rt._rebind_state,
             PhaseProfiler.add) = saved

    eps_on = eps_off = 0.0
    for _ in range(4):                       # interleave the two arms
        eps_on = max(eps_on, flagship_eps(profiled=True))
        eps_off = max(eps_off, flagship_eps(profiled=False))
    cost = 1.0 - eps_on / eps_off
    assert cost < 0.02, \
        f"always-on profiling costs {cost:.1%} flagship served ev/s"
    print(f"always-on: {cost:+.1%} flagship served ev/s vs profiler "
          "hooks compiled out (< 2%)")
    print("phase smoke OK")


if __name__ == "__main__":
    main()
