"""Admission-control layer: deploy-time memory gate, token-bucket
ingest quotas, the block/shed/degrade overload ladder, state-ceiling
growth denial, the shared compile-admission gate, and the @async
queue.policy='shed' satellite — all FakeClock-driven, zero real sleeps
(core/admission.py)."""
import json
import queue as _pyqueue
import urllib.request

import jax
import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.core.admission import (
    COMPILE_GATE,
    AdmissionController,
    CompileGate,
    TokenBucket,
    check_deploy,
)
from siddhi_tpu.exceptions import AdmissionDeniedError
from siddhi_tpu.utils.chaos import FakeClock
from siddhi_tpu.utils.config import InMemoryConfigManager

BIG_QL = """
@app:name('Big')
define stream S (sym string, price double, v long);
@info(name='big') from S#window.length(10000000)
select sym, avg(price) as ap insert into Out;
"""

SMALL_QL = """
@app:name('Small')
@app:statistics('BASIC')
define stream In (k long, v float);
@info(name='q') from In[v > 0] select k, v insert into Out;
"""


def _mgr(props=None):
    m = SiddhiManager()
    if props:
        m.set_config_manager(InMemoryConfigManager(system_configs={
            k: str(v) for k, v in props.items()}))
    return m


def _fake_controller(rt, **over):
    """Rebuild the app's controller on a FakeClock (constructor reads
    config; tests then own the timeline)."""
    clock = FakeClock(1000.0)
    adm = AdmissionController(rt, clock=clock, sleep=clock.sleep)
    for k, v in over.items():
        setattr(adm, k, v)
    rt.admission = adm
    return adm, clock


# -- token bucket -------------------------------------------------------------

def test_token_bucket_refill_math():
    clock = FakeClock(0.0)
    b = TokenBucket(rate=100.0, burst=50.0, clock=clock)
    assert b.try_take(50)                  # full burst available
    assert not b.try_take(1)               # empty
    clock.advance(0.1)                     # +10 tokens
    assert b.try_take(10)
    assert not b.try_take(1)
    clock.advance(10.0)                    # refill caps at burst
    assert b.tokens <= b.burst or b.try_take(50)
    assert b.try_take(50) or True
    # need_s is the exact time until n tokens exist
    clock.advance(100.0)
    assert b.try_take(50)
    assert b.need_s(25) == pytest.approx(0.25)


def test_token_bucket_all_or_nothing():
    clock = FakeClock(0.0)
    b = TokenBucket(rate=10.0, burst=10.0, clock=clock)
    assert not b.try_take(11)              # over burst: never admits...
    assert b.tokens == pytest.approx(10.0)  # ...and never partially takes
    assert b.try_take(10)


# -- ingest quotas: shed ------------------------------------------------------

def test_shed_accounting_is_exact(manager):
    rt = manager.create_siddhi_app_runtime(SMALL_QL)
    rt.start()
    adm, clock = _fake_controller(rt)
    adm.policy = "shed"
    adm.base_rate = 100.0
    adm.bucket = TokenBucket(100.0, burst=10.0, clock=clock)
    h = rt.get_input_handler("In")
    offered = 200
    for i in range(offered):
        h.send([i, 1.0])
    accepted = rt.stats.exposition_snapshot()["stream_in"].get("In", 0)
    # the zero-silent-drop ledger: every offered event is either
    # accepted or counted shed — exactly
    assert offered == accepted + adm.shed_total
    assert adm.shed_by_stream == {"In": adm.shed_total}
    assert adm.shed_total > 0
    # tenant accounting carries the charge
    from siddhi_tpu.observability.timeseries import tenant_account
    acct = tenant_account(rt)
    assert acct["admission_shed"] == adm.shed_total


def test_shed_never_routes_downstream(manager):
    rt = manager.create_siddhi_app_runtime(SMALL_QL)
    got = []
    rt.add_callback("q", lambda ts, cur, exp: got.extend(cur or []))
    rt.start()
    adm, clock = _fake_controller(rt)
    adm.policy = "shed"
    adm.bucket = TokenBucket(1.0, burst=2.0, clock=clock)
    h = rt.get_input_handler("In")
    for i in range(10):
        h.send([i, 1.0])
    rt.flush()
    accepted = rt.stats.exposition_snapshot()["stream_in"].get("In", 0)
    assert len(got) == accepted == 2
    assert adm.shed_total == 8


# -- ingest quotas: block (deadline-bounded backpressure) ---------------------

def test_block_waits_for_refill_then_admits(manager):
    rt = manager.create_siddhi_app_runtime(SMALL_QL)
    rt.start()
    adm, clock = _fake_controller(rt)
    adm.policy = "block"
    adm.block_timeout_ms = 5000.0
    adm.bucket = TokenBucket(100.0, burst=1.0, clock=clock)
    assert adm.admit_ingest("In", 1)       # burst token
    t0 = clock()
    assert adm.admit_ingest("In", 1)       # waits ~10ms on the fake clock
    assert clock() - t0 == pytest.approx(0.01, abs=5e-3)
    assert adm.blocked_sends == 1
    assert adm.blocked_ms_total >= 9


def test_block_deadline_expiry_raises_typed(manager):
    rt = manager.create_siddhi_app_runtime(SMALL_QL)
    rt.start()
    adm, clock = _fake_controller(rt)
    adm.policy = "block"
    adm.block_timeout_ms = 50.0
    adm.bucket = TokenBucket(1.0, burst=1.0, clock=clock)
    assert adm.admit_ingest("In", 1)
    # 1 ev/s refill: the next send needs 1s >> the 50ms deadline
    with pytest.raises(AdmissionDeniedError):
        adm.admit_ingest("In", 1)
    assert adm.block_timeouts == 1
    # the deadline was respected on the virtual timeline (no overshoot
    # past deadline + one pacing quantum)
    assert clock() - 1000.0 <= 0.06


# -- degrade ladder: rate halving + hysteresis --------------------------------

def test_degrade_halves_under_firing_and_recovers_with_hysteresis(manager):
    rt = manager.create_siddhi_app_runtime(SMALL_QL)
    rt.start()
    adm, clock = _fake_controller(rt)
    adm.policy = "degrade"
    adm.base_rate = 800.0
    adm.recovery_ticks = 3
    adm.bucket = TokenBucket(800.0, burst=10.0, clock=clock)

    firing = {"verdict": "firing"}
    ok = {"verdict": "ok"}
    assert adm.effective_rate() == 800.0
    adm.on_slo(firing, clock())
    assert adm.effective_rate() == 400.0
    assert adm.bucket.rate == 400.0
    adm.on_slo(firing, clock())
    assert adm.effective_rate() == 200.0
    assert adm.quota_state == "degraded"
    # hysteresis: recovery needs `recovery_ticks` CONSECUTIVE ok ticks
    adm.on_slo(ok, clock())
    adm.on_slo(ok, clock())
    assert adm.effective_rate() == 200.0   # not yet
    adm.on_slo(firing, clock())            # relapse resets the streak
    assert adm.effective_rate() == 100.0
    for _ in range(3):
        adm.on_slo(ok, clock())
    assert adm.effective_rate() == 200.0   # one level back
    for _ in range(6):
        adm.on_slo(ok, clock())
    assert adm.effective_rate() == 800.0   # fully recovered
    assert adm.quota_state == "ok"


def test_degrade_floor_is_bounded(manager):
    rt = manager.create_siddhi_app_runtime(SMALL_QL)
    rt.start()
    adm, clock = _fake_controller(rt)
    adm.policy = "degrade"
    adm.base_rate = 640.0
    adm.bucket = TokenBucket(640.0, burst=10.0, clock=clock)
    for _ in range(20):
        adm.on_slo({"verdict": "firing"}, clock())
    assert adm.effective_rate() == 640.0 / 64    # floor: /2^6


# -- deploy-time memory gate --------------------------------------------------

def test_deploy_denied_before_any_compile():
    m = _mgr({"admission.max.state.bytes": 1 << 20})
    compiles = []
    orig_jit = jax.jit

    def counting_jit(*a, **k):
        compiles.append(a)
        return orig_jit(*a, **k)

    jax.jit = counting_jit
    try:
        with pytest.raises(AdmissionDeniedError) as ei:
            m.create_siddhi_app_runtime(BIG_QL)
    finally:
        jax.jit = orig_jit
    # typed rejection lists the offending component breakdown — the
    # same breakdown lint MEM001 cites
    assert "big/window" in str(ei.value)
    assert ei.value.components and "big/window" in ei.value.components
    assert "Big" not in m.runtimes
    assert compiles == []               # nothing was planned or traced
    from siddhi_tpu.core.admission import denied_deploys
    assert denied_deploys() >= 1
    m.shutdown()


def test_deploy_gate_matches_lint_mem001_estimate():
    from siddhi_tpu.analysis import analyze
    from siddhi_tpu.analysis.registry import LintConfig
    from siddhi_tpu.compiler import SiddhiCompiler
    from siddhi_tpu.core.plan_facts import static_state_components
    app = SiddhiCompiler.parse(BIG_QL)
    est = sum(sum(c.values())
              for c in static_state_components(app).values())
    mem = [f for f in analyze(BIG_QL,
                              config=LintConfig(state_budget_bytes=1))
           if f.rule_id == "MEM001"]
    # one estimator: the MiB lint prints is the MiB the gate enforces
    assert mem and f"{est / (1024 * 1024):.1f} MiB" in mem[0].message


def test_global_ceiling_counts_resident_apps():
    m = _mgr({"admission.global.max.state.bytes": 2 << 20})
    # first app fits under the global ceiling
    m.create_siddhi_app_runtime("""
@app:name('A')
define stream S (v long);
@info(name='w') from S#window.length(40000) select v insert into Out;
""")
    # an identical second app must be denied: resident + estimate > cap
    with pytest.raises(AdmissionDeniedError):
        m.create_siddhi_app_runtime("""
@app:name('B')
define stream S (v long);
@info(name='w') from S#window.length(40000) select v insert into Out;
""")
    assert "B" not in m.runtimes
    m.shutdown()


# -- state-ceiling growth denial ----------------------------------------------

GROW_QL = """
@app:name('GrowPat')
@app:playback
@app:statistics('BASIC')
define stream S (k long, v int, p float);
partition with (k of S) begin
@capacity(keys='16', slots='16') @info(name='q')
from every e1=S[v == 1] -> e2=S[v == 2]
select e1.k as k, e1.p as p1 insert into Out;
end;
"""


def _overflow_pattern(rt, key, ts):
    """12 pendings on one key completed in ONE batch -> 12 rows > the
    implicit per-key cap of 8 -> the runtime wants a cap growth (the
    test_pattern_corpus adaptive-growth shape)."""
    h = rt.get_input_handler("S")
    h.send([[key, 1, float(i)] for i in range(12)], timestamp=ts)
    h.send([[key, 2, 0.0]], timestamp=ts + 1)
    rt.flush()


def test_growth_denied_flips_shedding_instead_of_growing(manager):
    rt = manager.create_siddhi_app_runtime(GROW_QL)
    got = []
    rt.add_callback("q", lambda ts, i, o: got.extend(i or []))
    rt.start()
    adm, clock = _fake_controller(rt)
    adm.max_state_bytes = 1.0          # any growth is over the ceiling
    qr = rt.query_runtimes["q"]
    cap_before = qr.planned.compact_rows
    _overflow_pattern(rt, key=5, ts=1000)
    snap = rt.stats.exposition_snapshot()["counters"]
    assert adm.growth_denials >= 1
    assert adm.quota_state == "shedding"
    assert qr.planned.compact_rows == cap_before      # never grew
    assert snap.get("q.cap_growths", 0) == 0
    assert snap.get("q.growth_denied", 0) >= 1
    # capped delivery continued: 8 of 12 rows delivered, app alive
    assert len(got) == 8
    hz = rt.health()
    assert hz["admission"]["quota_state"] == "shedding"
    assert hz["degraded"] is True
    # same fan-out again: still capped (no OOM, no growth), still alive
    _overflow_pattern(rt, key=7, ts=2000)
    assert qr.planned.compact_rows == cap_before


def test_growth_allowed_under_ceiling(manager):
    rt = manager.create_siddhi_app_runtime(GROW_QL)
    # a subscriber: statistics alone are not a reader, and an emission
    # nobody reads is not fetched — header and overflow count included
    rt.add_batch_callback("q", lambda ts, b: None)
    rt.start()
    adm, clock = _fake_controller(rt)
    adm.max_state_bytes = float(1 << 30)
    qr = rt.query_runtimes["q"]
    cap_before = qr.planned.compact_rows
    _overflow_pattern(rt, key=5, ts=1000)
    assert qr.planned.compact_rows > cap_before
    assert adm.growth_denials == 0
    assert adm.quota_state == "ok"
    assert rt.stats.exposition_snapshot()["counters"].get(
        "q.cap_growths", 0) >= 1


# -- compile-admission gate ---------------------------------------------------

class _GateCtrl:
    """Minimal controller contract the gate needs."""

    def __init__(self, budget, penalty_ms=250.0):
        self.max_recompiles_per_min = budget
        self.compile_penalty_ms = penalty_ms
        self.penalties = 0
        self.compiles = 0

    def note_compile(self, owner):
        self.compiles += 1

    def note_compile_penalty(self, s):
        self.penalties += 1


def test_compile_gate_penalizes_only_over_budget_owner():
    clock = FakeClock(0.0)
    gate = CompileGate(clock=clock, sleep=clock.sleep)
    noisy = _GateCtrl(budget=2)
    victim = _GateCtrl(budget=None)
    gate.register("noisy:q", noisy)
    gate.register("victim:q", victim)
    for _ in range(5):
        with gate.admit("noisy:q"):
            pass
    for _ in range(5):
        with gate.admit("victim:q"):
            pass
    # compiles 3..5 were over budget, with ESCALATING penalties (one
    # quantum per compile past the budget in the trailing minute)
    assert noisy.penalties == 3
    assert victim.penalties == 0
    assert gate.penalized_total == 3
    assert gate.waiting == 0              # bookkeeping balanced
    assert clock.sleeps == [0.25, 0.5, 0.75]


def test_compile_gate_penalty_escalation_cap_is_configurable():
    """Default cap is MAX_PENALTY_S; `compile.penalty.max.ms` raises it
    so the penalty can exceed a storm's per-compile busy time (a cap
    below that only lags the storm, it never converges its rate)."""
    clock = FakeClock(0.0)
    gate = CompileGate(clock=clock, sleep=clock.sleep)
    capped = _GateCtrl(budget=1, penalty_ms=4000.0)
    gate.register("capped:q", capped)
    for _ in range(4):
        with gate.admit("capped:q"):
            pass
    # escalation 4s, 8s, 12s wants to exceed the 5s default cap
    assert clock.sleeps == [4.0, 5.0, 5.0]
    clock2 = FakeClock(0.0)
    gate2 = CompileGate(clock=clock2, sleep=clock2.sleep)
    parked = _GateCtrl(budget=1, penalty_ms=4000.0)
    parked.compile_penalty_max_ms = 60000.0
    gate2.register("parked:q", parked)
    for _ in range(4):
        with gate2.admit("parked:q"):
            pass
    assert clock2.sleeps == [4.0, 8.0, 12.0]


def test_compile_penalty_max_configurable_via_put(manager):
    rt = manager.create_siddhi_app_runtime(SMALL_QL)
    adm = rt.admission
    assert adm.compile_penalty_max_ms == \
        CompileGate.MAX_PENALTY_S * 1e3             # default
    rep = adm.configure({"compile.penalty.max.ms": 120000})
    assert adm.compile_penalty_max_ms == 120000.0
    assert rep["compile_penalty_max_ms"] == 120000.0


def test_compile_gate_budget_survives_redeploy_churn():
    """The deploy-churn loophole: a tenant hot-redeploying its app gets
    a fresh controller each cycle, but the per-LABEL compile history in
    the gate keeps counting — the storm stays penalized."""
    clock = FakeClock(0.0)
    gate = CompileGate(clock=clock, sleep=clock.sleep)
    for cycle in range(4):
        ctrl = _GateCtrl(budget=2)        # fresh controller per deploy
        gate.register("storm:q", ctrl)
        with gate.admit("storm:q"):
            pass
        gate.unregister_app(ctrl)
    assert gate.penalized_total == 2      # cycles 3 and 4
    # the window slides: an hour later the label history is stale
    clock.advance(3600.0)
    ctrl = _GateCtrl(budget=2)
    gate.register("storm:q", ctrl)
    with gate.admit("storm:q"):
        pass
    assert ctrl.penalties == 0


def test_compile_gate_is_reentrant_and_unregisters():
    clock = FakeClock(0.0)
    gate = CompileGate(clock=clock, sleep=clock.sleep)
    c = _GateCtrl(budget=None)
    gate.register("a", c)
    with gate.admit("a"):
        with gate.admit("a"):             # fused step tracing inner body
            pass
    gate.unregister_app(c)
    assert gate.controller_of("a") is None


def test_real_compiles_flow_through_shared_gate(manager):
    baseline = COMPILE_GATE.penalized_total
    rt = manager.create_siddhi_app_runtime(SMALL_QL)
    rt.start()
    adm = rt.admission
    assert COMPILE_GATE.controller_of("q") is adm
    h = rt.get_input_handler("In")
    h.send([1, 1.0])
    rt.flush()
    assert adm.compiles_total >= 1        # the step trace was admitted
    assert COMPILE_GATE.penalized_total == baseline   # within budget
    rt.shutdown()
    assert COMPILE_GATE.controller_of("q") is None    # released


def test_recompile_budget_penalty_windows():
    clock = FakeClock(0.0)

    class _RT:
        name = "x"
        stats = None

        class app:
            @staticmethod
            def get_annotation(_):
                return None

        class manager:
            config_manager = None

    adm = AdmissionController(_RT(), clock=clock, sleep=clock.sleep)
    adm.max_recompiles_per_min = 2.0
    adm.compile_penalty_ms = 100.0
    assert adm.compile_penalty_s() == 0.0
    adm.note_compile("x")
    adm.note_compile("x")
    assert adm.compile_penalty_s() == pytest.approx(0.1)
    clock.advance(61.0)                   # the window slides empty
    assert adm.compile_penalty_s() == 0.0
    assert adm.compiles_last_min() == 0


# -- @async queue.policy='shed' satellite -------------------------------------

ASYNC_SHED_QL = """
@app:name('AsyncShed')
@app:statistics('BASIC')
@async(buffer.size='4', workers='1', queue.policy='shed')
define stream In (k long, v float);
@info(name='q') from In[v > 0] select k, v insert into Out;
"""


def test_async_shed_policy_counts_exactly(manager):
    rt = manager.create_siddhi_app_runtime(ASYNC_SHED_QL)
    rt.start()
    j = rt.junctions["In"]
    assert j._async_policy == "shed"
    # deterministic overflow: park the worker queue full, then enqueue
    # more — put_nowait must shed, not block
    j.stop_async()
    j._async_q = _pyqueue.Queue(maxsize=1)
    try:
        from siddhi_tpu.core import event as ev
        schema = rt.schemas["In"]
        staged = ev.pack_np(schema, [ev.Event(0, [1, 1.0])])
        j._async_q.put(("stop", None, 0, None))     # queue now full
        offered = 5
        for _ in range(offered):
            j.enqueue("staged", staged, 0)
        sheds = rt.stats.exposition_snapshot()["counters"].get(
            "async.In.shed", 0)
        assert sheds == offered * staged.n
        # exposition renders the family
        from siddhi_tpu.observability import render_prometheus
        text = render_prometheus({"AsyncShed": rt})
        assert ('siddhi_async_shed_total{app="AsyncShed",stream="In"}'
                in text)
        # healthz classifies the stream as shedding (sheds happened and
        # the queue is still backed up)
        hz = rt.health()
        assert hz["streams"]["In"]["status"] == "shedding"
        assert hz["streams"]["In"]["async_shed"] == sheds
    finally:
        j._async_q = None               # let shutdown proceed cleanly


def test_async_block_policy_unchanged_by_default(manager):
    rt = manager.create_siddhi_app_runtime("""
@app:name('AsyncBlock')
@async(buffer.size='4')
define stream In (k long, v float);
@info(name='q') from In[v > 0] select k, v insert into Out;
""")
    rt.start()
    assert rt.junctions["In"]._async_policy == "block"
    h = rt.get_input_handler("In")
    for i in range(32):
        h.send([i, 1.0])
    rt.flush()
    snap = rt.stats.exposition_snapshot()
    assert "async.In.shed" not in snap.get("counters", {})


# -- REST surface -------------------------------------------------------------

def test_rest_get_put_admission(manager):
    from siddhi_tpu.service import SiddhiRestService
    manager.create_siddhi_app_runtime(SMALL_QL).start()
    svc = SiddhiRestService(manager).start()
    try:
        base = f"http://127.0.0.1:{svc.port}"
        rep = json.load(urllib.request.urlopen(
            f"{base}/siddhi-apps/Small/admission"))
        assert rep["app"] == "Small"
        assert rep["policy"] == "block"
        assert rep["quota_state"] == "ok"
        req = urllib.request.Request(
            f"{base}/siddhi-apps/Small/admission",
            data=json.dumps({"overload": "shed",
                             "max.events.per.sec": 123}).encode(),
            method="PUT")
        rep2 = json.load(urllib.request.urlopen(req))
        assert rep2["policy"] == "shed"
        assert rep2["max_events_per_sec"] == 123.0
        # bad policy -> 400, typed
        req = urllib.request.Request(
            f"{base}/siddhi-apps/Small/admission",
            data=json.dumps({"overload": "explode"}).encode(),
            method="PUT")
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req)
        assert ei.value.code == 400
        # unknown app -> 404
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"{base}/siddhi-apps/nope/admission")
        assert ei.value.code == 404
    finally:
        svc._server.shutdown()
        svc._server.server_close()


def test_explain_carries_admission_section(manager):
    rt = manager.create_siddhi_app_runtime(SMALL_QL)
    rt.start()
    exp = rt.explain()
    assert exp["admission"]["policy"] == "block"
    assert exp["admission"]["quota_state"] == "ok"


# -- lint rule ADM001 ---------------------------------------------------------

def test_adm001_over_global_ceiling():
    from siddhi_tpu.analysis import analyze
    from siddhi_tpu.analysis.registry import LintConfig
    fs = [f for f in analyze(
        BIG_QL, config=LintConfig(global_state_ceiling_bytes=1 << 20))
        if f.rule_id == "ADM001"]
    assert fs and "global admission ceiling" in fs[0].message
    assert fs[0].severity == "WARN"
    # silent without a configured ceiling
    assert not [f for f in analyze(BIG_QL) if f.rule_id == "ADM001"]


SOURCE_QL = """
@app:name('Feed')
@source(type='tcp', port='0')
define stream In (k long, v float);
@info(name='q') from In[v > 0] select k, v insert into Out;
"""


def test_adm001_source_without_policy():
    from siddhi_tpu.analysis import analyze
    fs = [f for f in analyze(SOURCE_QL) if f.rule_id == "ADM001"]
    assert fs and "admission.overload" in fs[0].message
    assert fs[0].pos is not None          # cites the @source annotation
    declared = SOURCE_QL.replace(
        "@app:name('Feed')",
        "@app:name('Feed')\n@app:admission(overload='shed')")
    assert not [f for f in analyze(declared) if f.rule_id == "ADM001"]
    # inmemory sources are hand-fed test transports, not feeds
    inmem = SOURCE_QL.replace("type='tcp', port='0'", "type='inmemory'")
    assert not [f for f in analyze(inmem) if f.rule_id == "ADM001"]


def test_adm001_in_catalog():
    from siddhi_tpu.analysis.registry import catalog
    assert any(r["id"] == "ADM001" for r in catalog())


# -- decisions never touch the device -----------------------------------------

def test_admission_decisions_never_fetch_or_trace(manager, monkeypatch):
    """Every admission decision path — deploy gate, ingest quota, SLO
    ladder, growth check, report/REST rendering — runs with jax.jit and
    jax.device_get booby-trapped: a decision that traces or fetches is
    a regression (the ISSUE's guard requirement)."""
    rt = manager.create_siddhi_app_runtime(SMALL_QL)
    rt.start()
    adm, clock = _fake_controller(rt)
    adm.policy = "shed"
    adm.bucket = TokenBucket(100.0, burst=5.0, clock=clock)
    adm.max_state_bytes = float(1 << 40)

    def boom(*a, **k):
        raise AssertionError("admission decision touched the device")

    monkeypatch.setattr(jax, "jit", boom)
    monkeypatch.setattr(jax, "device_get", boom)

    # deploy gate (static estimator only)
    m2 = _mgr({"admission.max.state.bytes": 1})
    from siddhi_tpu.compiler import SiddhiCompiler
    with pytest.raises(AdmissionDeniedError):
        check_deploy(SiddhiCompiler.parse(BIG_QL), m2)
    # ingest quota decisions
    for i in range(20):
        adm.admit_ingest("In", 1)
    assert adm.shed_total > 0
    # growth admission (metadata-only accounting)
    assert adm.admit_growth("q", 1024)
    # ladder + report + healthz admission section
    adm.on_slo({"verdict": "firing"}, clock())
    rep = adm.report()
    assert rep["shed_total"] == adm.shed_total
    assert rt.health()["admission"]["shed_total"] == adm.shed_total
