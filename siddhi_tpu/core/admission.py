"""Admission control & graceful degradation: decide overload, don't
discover it.

Reference (what): the reference engine degrades *deliberately* under
overload — the `@async` ingress is a bounded Disruptor ring that
backpressures producers (StreamJunction.java:276-313), and
`OnErrorAction` policies choose what happens to events the engine
cannot process (PAPER.md L4/L6).  It never OOMs from one bad tenant:
capacity is decided at the edges.

TPU design (how): a multi-tenant TPU server has three scarce resources
a single tenant can exhaust for everyone — **HBM** (state slabs are
dense device arrays sized at plan time), the **XLA compile path** (one
recompile stalls its thread for seconds — tens of seconds for a large
step on the TPU), and **host dispatch** (the drainer and query locks).
This module gates all three:

1. **Deploy-time memory gate** (`check_deploy`): before anything is
   planned or traced, the app's static state estimate — the SAME
   shape×dtype estimator lint MEM001 uses
   (`core/plan_facts.static_state_components`) — is checked against
   `admission.max.state.bytes` (per app) and
   `admission.global.max.state.bytes` (the box).  Denial is a typed
   `AdmissionDeniedError` listing the offending components; nothing
   was compiled, nothing leaks.

2. **Runtime quotas** (`AdmissionController`, one per app):
   - a token-bucket ingest rate (`admission.max.events.per.sec`)
     enforced at the external edges (InputHandler sends + @source
     delivery — internal routing is never throttled);
   - the state ceiling re-checked on every adaptive emission-cap
     growth (`_grow_emission_cap`): growth past the ceiling is DENIED
     and the app flips to a `shedding` quota state — overflow rows
     drop loudly (counted) instead of OOMing the chip;
   - a recompile-rate budget (`admission.max.recompiles.per.min`)
     enforced by the shared `CompileGate`: every non-diagnostic XLA
     trace passes through one process-wide admission lock, and an
     owner over its budget is penalized (`admission.compile.penalty.ms`
     sleep) BEFORE it may take the lock — a storming tenant's compiles
     queue behind everyone else's dispatch instead of in front of it.

3. **Mitigation ladder** (`admission.overload`):
   - `'block'`   — caller backpressure: the send waits for bucket
     refill up to `admission.block.timeout.ms`, then raises
     `AdmissionDeniedError` (the resilience `wait` contract:
     deadline-bounded blocking with a typed timeout);
   - `'shed'`    — the send is dropped at the edge, counted per
     stream (`siddhi_admission_shed_total{app,stream}`), never routed;
   - `'degrade'` — sheds like `'shed'`, but the effective rate HALVES
     each sampler tick the app's SLO verdict is FIRING and recovers
     one halving per `admission.degrade.recovery.ticks` consecutive
     ok ticks (hysteresis) — the ladder the SLO engine climbs down.

Every decision is observable: controller counters feed
`siddhi_admission_{shed_total,blocked_ms,denied_deploys,
compile_queue_depth,quota_state}` in /metrics, an `admission` section
in /healthz and EXPLAIN, sampler series, and
`GET/PUT /siddhi-apps/<app>/admission`.

Invariant shared with the whole scrape path: admission decisions read
host counters, config, and shape/dtype metadata ONLY — never a device
fetch, never a trace (tests/test_admission.py guards this by
monkeypatching `jax.jit` and `jax.device_get` over every decision
path).  Clock and sleep are injectable so the quota ladder is tested
on a virtual timeline with zero real sleeps.
"""
from __future__ import annotations

import contextlib
import logging
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional

from ..exceptions import AdmissionDeniedError
from .plan_facts import format_component_bytes, static_state_components

log = logging.getLogger("siddhi_tpu")

OVERLOAD_POLICIES = ("block", "shed", "degrade")

# quota_state gauge encoding (siddhi_admission_quota_state)
QUOTA_OK, QUOTA_DEGRADED, QUOTA_SHEDDING = "ok", "degraded", "shedding"
QUOTA_GAUGE = {QUOTA_OK: 0, QUOTA_DEGRADED: 1, QUOTA_SHEDDING: 2}

_DEFAULT_BLOCK_TIMEOUT_MS = 1000.0
_DEFAULT_COMPILE_PENALTY_MS = 100.0
_DEFAULT_RECOVERY_TICKS = 5
_MAX_DEGRADE_LEVEL = 6          # rate floor: configured / 64
_COMPILE_WINDOW_S = 60.0        # the "per.min" of the recompile budget


def _mib(n: float) -> str:
    return f"{n / (1024 * 1024):.1f} MiB"


class TokenBucket:
    """Classic token bucket: `rate` tokens/s refill up to `burst`.
    All-or-nothing takes (a batch is admitted whole or not at all) so
    accounting reconciles exactly: offered == accepted + shed."""

    def __init__(self, rate: float, burst: Optional[float] = None,
                 clock: Callable[[], float] = time.monotonic):
        self._lock = threading.Lock()
        self._clock = clock
        self.rate = max(1e-9, float(rate))
        self.burst = float(burst) if burst else max(self.rate, 1.0)
        self.tokens = self.burst
        self._last = clock()

    def _refill(self, now: float) -> None:
        dt = now - self._last
        if dt > 0:
            self.tokens = min(self.burst, self.tokens + dt * self.rate)
            self._last = now

    def try_take(self, n: int) -> bool:
        with self._lock:
            self._refill(self._clock())
            if self.tokens >= n:
                self.tokens -= n
                return True
            return False

    def need_s(self, n: int) -> float:
        """Seconds until `n` tokens could be available (0 when they
        already are; capped at the time to fill from empty)."""
        with self._lock:
            self._refill(self._clock())
            missing = min(float(n), self.burst) - self.tokens
            return max(0.0, missing / self.rate)

    def set_rate(self, rate: float) -> None:
        with self._lock:
            self._refill(self._clock())
            self.rate = max(1e-9, float(rate))


class CompileGate:
    """Process-wide XLA compile admission: every non-diagnostic trace
    (steputil.jit_step) enters through `admit(owner)`.

    Two mechanisms compose:
    - **serialization**: one RLock means at most one tenant traces at a
      time — tenant N+1's compile storm queues instead of interleaving
      with (and GIL-starving) tenant 1's dispatch.  Re-entrant, so a
      fused step tracing its inner bodies on the same thread cannot
      deadlock.
    - **deprioritization**: an owner whose app is over its
      `admission.max.recompiles.per.min` budget sleeps its app's
      compile penalty BEFORE contending for the lock, so a within-
      budget tenant already waiting wins the next slot.

    Owners register via their app's AdmissionController (labels are the
    recompile-accounting owners: query names, `fused:<q>`, `table:<t>`,
    …).  Colliding labels across apps resolve to the most recently
    registered app — acceptable blame blur, never a correctness issue.
    Clock/sleep injectable; `waiting` is the
    siddhi_admission_compile_queue_depth gauge."""

    # escalation cap: a persistently-storming owner's penalty grows one
    # quantum per over-budget compile but never past this bound (the
    # app's `admission.compile.penalty.max.ms` raises/lowers it — a cap
    # shorter than the owner's per-compile busy time can never converge
    # a storm's compile rate down to its budget, it only lags it)
    MAX_PENALTY_S = 5.0

    def __init__(self, clock: Callable[[], float] = time.monotonic,
                 sleep: Callable[[float], None] = time.sleep):
        self._lock = threading.RLock()
        self._meta = threading.Lock()
        self._owners: Dict[str, "AdmissionController"] = {}
        # per-owner-LABEL compile history: the budget must survive
        # deploy/undeploy churn (a tenant hot-redeploying its app gets
        # a fresh AdmissionController each cycle — if the history lived
        # there, churn would reset the budget and the storm would never
        # be penalized)
        self._label_times: Dict[str, deque] = {}
        self._clock = clock
        self._sleep = sleep
        self.waiting = 0
        self.penalized_total = 0

    def register(self, owner: str, ctrl: "AdmissionController") -> None:
        with self._meta:
            self._owners[owner] = ctrl

    def unregister_app(self, ctrl: "AdmissionController") -> None:
        with self._meta:
            for k in [k for k, v in self._owners.items() if v is ctrl]:
                del self._owners[k]

    def controller_of(self, owner: str) -> Optional["AdmissionController"]:
        with self._meta:
            return self._owners.get(owner)

    def _penalty_for(self, owner: str,
                     ctrl: Optional["AdmissionController"]) -> float:
        """Escalating pre-lock penalty for an over-budget owner: one
        `compile.penalty.ms` quantum per compile past the budget in the
        trailing minute, capped at MAX_PENALTY_S — the owner's compile
        rate converges toward its budget instead of merely lagging it."""
        if ctrl is None:
            return 0.0
        budget = ctrl.max_recompiles_per_min
        if budget is None:
            return 0.0
        now = self._clock()
        with self._meta:
            dq = self._label_times.get(owner)
            if dq is None:
                return 0.0
            while dq and now - dq[0] > _COMPILE_WINDOW_S:
                dq.popleft()
            recent = len(dq)
        if recent < budget:
            return 0.0
        over = recent - budget + 1
        cap = getattr(ctrl, "compile_penalty_max_ms",
                      self.MAX_PENALTY_S * 1e3) / 1e3
        return min(cap, ctrl.compile_penalty_ms / 1e3 * over)

    def _note_label_compile(self, owner: str) -> None:
        with self._meta:
            dq = self._label_times.get(owner)
            if dq is None:
                dq = self._label_times[owner] = deque(maxlen=4096)
            dq.append(self._clock())

    @contextlib.contextmanager
    def admit(self, owner: str):
        ctrl = self.controller_of(owner)
        penalty = self._penalty_for(owner, ctrl)
        with self._meta:
            self.waiting += 1
            if penalty > 0:
                self.penalized_total += 1
        acquired = False
        try:
            if penalty > 0:
                # over-budget owners pay the penalty OUTSIDE the lock:
                # within-budget tenants overtake them at the gate
                self._sleep(penalty)
                if ctrl is not None:
                    ctrl.note_compile_penalty(penalty)
            self._lock.acquire()
            acquired = True
            with self._meta:
                self.waiting -= 1
            yield
        finally:
            if acquired:
                self._note_label_compile(owner)
                if ctrl is not None:
                    ctrl.note_compile(owner)
                self._lock.release()
            else:
                # the penalty sleep (or the caller) raised before the
                # lock body balanced `waiting`
                with self._meta:
                    self.waiting -= 1


# the one gate steputil.jit_step routes every trace through
COMPILE_GATE = CompileGate()

# process-wide deploy denials (deploys denied before a runtime exists
# have no app to hang a counter on)
_denied_lock = threading.Lock()
_denied_deploys = 0


def denied_deploys() -> int:
    return _denied_deploys


def _count_denied() -> None:
    global _denied_deploys
    with _denied_lock:
        _denied_deploys += 1


def _flat_components(app, mesh_devices: int = 0,
                     merged: bool = True) -> Dict[str, int]:
    """{'query/component': bytes} — the deploy gate's breakdown keys.
    Merge-aware (core/plan_facts): a window buffer the multi-query
    optimizer will share across a group is charged ONCE, under its
    `merged:<group>` owner, exactly as the live accounting reports it."""
    out: Dict[str, int] = {}
    for qname, comps in static_state_components(
            app, mesh_devices=mesh_devices, merged=merged).items():
        for comp, nb in comps.items():
            out[f"{qname}/{comp}"] = nb
    return out


def _ann_element(app, key: str) -> Optional[str]:
    ann = app.get_annotation("app:admission")
    if ann is None:
        return None
    v = ann.element(key)
    return None if v is None else str(v)


def _prop(manager, key: str) -> Optional[str]:
    try:
        cm = getattr(manager, "config_manager", None)
        v = cm.extract_property(key) if cm is not None else None
        return None if v is None else str(v)
    except Exception:  # noqa: BLE001 — config must not break admission
        return None


def _resolve(app, manager, ann_key: str, prop_key: str) -> Optional[str]:
    """@app:admission(<ann_key>=…) wins over the manager property."""
    v = _ann_element(app, ann_key)
    return v if v is not None else _prop(manager, prop_key)


def _opt_float(v: Optional[str]) -> Optional[float]:
    if v is None or str(v).strip() == "":
        return None
    f = float(v)
    return f if f > 0 else None


def resident_state_bytes(manager, exclude=None) -> int:
    """Measured device-state bytes across every deployed app (metadata
    walk only — observability/memory)."""
    from ..observability.memory import total_bytes
    total = 0
    for rt in list(getattr(manager, "runtimes", {}).values()):
        if rt is exclude:
            continue
        try:
            total += int(total_bytes(rt))
        except Exception:  # noqa: BLE001 — one sick app must not block
            pass
    return total


def check_deploy(app, manager, mesh=None) -> None:
    """Deploy-time memory gate: runs BEFORE SiddhiAppRuntime is
    constructed, so a denial provably precedes any planning, tracing,
    or device allocation.  Raises AdmissionDeniedError listing the
    offending components (the MEM001 breakdown) when the app's static
    state estimate exceeds `admission.max.state.bytes`, or would push
    the box past `admission.global.max.state.bytes` on top of the
    measured resident state of the already-deployed apps.  `mesh` is
    the deploy target (merge-aware sharing is off on a multi-device
    mesh, matching the optimizer pass)."""
    per_app = _opt_float(_resolve(app, manager, "max.state.bytes",
                                  "admission.max.state.bytes"))
    global_ceiling = _opt_float(
        _prop(manager, "admission.global.max.state.bytes"))
    if per_app is None and global_ceiling is None:
        return
    mesh_n = int(mesh.devices.size) if mesh is not None else 0
    merge_prop = _prop(manager, "optimizer.merge.enabled")
    merged = merge_prop is None or \
        str(merge_prop).strip().lower() not in ("false", "0", "off", "no")
    comps = _flat_components(app, mesh_devices=mesh_n, merged=merged)
    estimate = sum(comps.values())
    name = app.name or "SiddhiApp"
    if per_app is not None and estimate > per_app:
        _count_denied()
        raise AdmissionDeniedError(
            f"deploy of {name!r} denied: static state estimate "
            f"{_mib(estimate)} exceeds admission.max.state.bytes "
            f"{_mib(per_app)} ({format_component_bytes(comps)})",
            components=comps)
    if global_ceiling is not None:
        resident = resident_state_bytes(manager)
        if resident + estimate > global_ceiling:
            _count_denied()
            raise AdmissionDeniedError(
                f"deploy of {name!r} denied: static state estimate "
                f"{_mib(estimate)} on top of {_mib(resident)} already "
                f"resident exceeds admission.global.max.state.bytes "
                f"{_mib(global_ceiling)} "
                f"({format_component_bytes(comps)})",
                components=comps)


class AdmissionController:
    """Per-app runtime quota enforcement + the overload ladder.  Created
    unconditionally on every SiddhiAppRuntime (cheap, host-only); does
    nothing on the ingest path until a rate is configured."""

    def __init__(self, rt, clock: Callable[[], float] = time.monotonic,
                 sleep: Callable[[float], None] = time.sleep):
        self.rt = rt
        self._clock = clock
        self._sleep = sleep
        self._lock = threading.Lock()

        app, manager = rt.app, rt.manager

        def res(ann_key, prop_key):
            return _resolve(app, manager, ann_key, prop_key)

        policy = (res("overload", "admission.overload") or "block").lower()
        if policy not in OVERLOAD_POLICIES:
            raise AdmissionDeniedError(
                f"unknown admission.overload policy {policy!r}; one of "
                f"{OVERLOAD_POLICIES}")
        self.policy = policy
        # whether the operator SAID anything (lint ADM001 wants to know
        # explicit-vs-defaulted, not the resolved value)
        self.policy_explicit = res("overload",
                                   "admission.overload") is not None
        self.base_rate = _opt_float(res("max.events.per.sec",
                                        "admission.max.events.per.sec"))
        self.burst = _opt_float(res("burst", "admission.burst"))
        self.max_state_bytes = _opt_float(
            res("max.state.bytes", "admission.max.state.bytes"))
        self.global_max_state_bytes = _opt_float(
            _prop(manager, "admission.global.max.state.bytes"))
        self.block_timeout_ms = float(
            res("block.timeout.ms", "admission.block.timeout.ms")
            or _DEFAULT_BLOCK_TIMEOUT_MS)
        self.max_recompiles_per_min = _opt_float(
            res("max.recompiles.per.min",
                "admission.max.recompiles.per.min"))
        self.compile_penalty_ms = float(
            res("compile.penalty.ms", "admission.compile.penalty.ms")
            or _DEFAULT_COMPILE_PENALTY_MS)
        self.compile_penalty_max_ms = float(
            res("compile.penalty.max.ms",
                "admission.compile.penalty.max.ms")
            or CompileGate.MAX_PENALTY_S * 1e3)
        self.recovery_ticks = int(
            res("degrade.recovery.ticks",
                "admission.degrade.recovery.ticks")
            or _DEFAULT_RECOVERY_TICKS)

        self.bucket: Optional[TokenBucket] = None
        if self.base_rate is not None:
            self.bucket = TokenBucket(self.base_rate, self.burst,
                                      clock=clock)

        # counters (plain ints read lock-free by the scrape path)
        self.shed_total = 0
        self.shed_by_stream: Dict[str, int] = {}
        self.blocked_ms_total = 0
        self.blocked_sends = 0
        self.block_timeouts = 0
        self.growth_denials = 0
        self.compiles_total = 0
        self.compile_penalties = 0
        self.compile_penalty_ms_total = 0
        self._compile_times: deque = deque(maxlen=4096)

        # ladder state
        self.degrade_level = 0
        self._ok_ticks = 0
        self.ceiling_hit = False
        self._warned_shed = 0.0

    # -- ingest edge -----------------------------------------------------------
    @property
    def ingest_enabled(self) -> bool:
        return self.bucket is not None

    def effective_rate(self) -> Optional[float]:
        if self.base_rate is None:
            return None
        return self.base_rate / (1 << self.degrade_level)

    @property
    def quota_state(self) -> str:
        if self.ceiling_hit:
            return QUOTA_SHEDDING
        if self.degrade_level > 0:
            return QUOTA_DEGRADED
        return QUOTA_OK

    def admit_ingest(self, stream_id: str, n: int) -> bool:
        """Decide one external send of `n` events.  True = route it.
        False = SHED (already counted; the caller just drops).  `block`
        policy never returns False — it waits for bucket refill up to
        the deadline, then raises AdmissionDeniedError."""
        bucket = self.bucket
        if bucket is None or n <= 0:
            return True
        if bucket.try_take(n):
            return True
        if self.policy == "block":
            return self._block(stream_id, n, bucket)
        self._note_shed(stream_id, n)
        return False

    def _block(self, stream_id: str, n: int, bucket: TokenBucket) -> bool:
        deadline = self._clock() + self.block_timeout_ms / 1e3
        t0 = self._clock()
        while True:
            need = bucket.need_s(n)
            now = self._clock()
            if now + need > deadline:
                waited_ms = int((now - t0) * 1e3)
                with self._lock:
                    self.blocked_ms_total += waited_ms
                    self.block_timeouts += 1
                raise AdmissionDeniedError(
                    f"send of {n} events to {stream_id!r} blocked "
                    f"{self.block_timeout_ms:.0f}ms at the admission "
                    f"rate limit ({bucket.rate:.0f} ev/s) without "
                    "tokens (admission.overload='block' deadline)")
            self._sleep(max(need, 1e-4))
            if bucket.try_take(n):
                waited_ms = int((self._clock() - t0) * 1e3)
                with self._lock:
                    self.blocked_ms_total += waited_ms
                    self.blocked_sends += 1
                return True

    def _note_shed(self, stream_id: str, n: int) -> None:
        with self._lock:
            self.shed_total += n
            self.shed_by_stream[stream_id] = \
                self.shed_by_stream.get(stream_id, 0) + n
        now = self._clock()
        if now - self._warned_shed >= 10.0:   # loud but rate-limited
            self._warned_shed = now
            log.warning(
                "%s: admission shed %d events on %r (policy=%s, "
                "effective rate %.0f ev/s, %d shed total)",
                self.rt.name, n, stream_id, self.policy,
                self.effective_rate() or 0.0, self.shed_total)

    # -- state ceiling (growth admission) --------------------------------------
    def admit_growth(self, owner: str, delta_bytes: int) -> bool:
        """Re-check the state ceilings before an adaptive emission-cap
        (or other state) growth of `delta_bytes`.  Denial flips the app
        into the `shedding` quota state: the overflow that wanted the
        growth keeps dropping loudly (counted by the existing overflow
        path) instead of allocating past the ceiling."""
        lim_app = self.max_state_bytes
        lim_glob = self.global_max_state_bytes
        if lim_app is None and lim_glob is None:
            return True
        from ..observability.memory import total_bytes
        try:
            cur = int(total_bytes(self.rt))
        except Exception:  # noqa: BLE001 — accounting must not block
            cur = 0
        deny_reason = None
        if lim_app is not None and cur + delta_bytes > lim_app:
            deny_reason = (f"app state {_mib(cur)} + growth "
                           f"{_mib(delta_bytes)} exceeds "
                           f"admission.max.state.bytes {_mib(lim_app)}")
        elif lim_glob is not None:
            resident = resident_state_bytes(self.rt.manager,
                                            exclude=self.rt) + cur
            if resident + delta_bytes > lim_glob:
                deny_reason = (
                    f"box state {_mib(resident)} + growth "
                    f"{_mib(delta_bytes)} exceeds "
                    f"admission.global.max.state.bytes {_mib(lim_glob)}")
        if deny_reason is None:
            return True
        with self._lock:
            self.growth_denials += 1
            self.ceiling_hit = True
        log.error(
            "%s: state growth for %r DENIED (%s); app enters degraded "
            "shedding mode — overflow rows drop at the current cap",
            self.rt.name, owner, deny_reason)
        stats = getattr(self.rt, "stats", None)
        if stats is not None and stats.enabled:
            stats.counter_inc(f"{owner}.growth_denied")
        return False

    # -- recompile budget ------------------------------------------------------
    def compile_penalty_s(self) -> float:
        """Penalty the CompileGate applies before this app's next trace
        may contend for the lock: 0 while within budget."""
        budget = self.max_recompiles_per_min
        if budget is None:
            return 0.0
        now = self._clock()
        with self._lock:
            while self._compile_times and \
                    now - self._compile_times[0] > _COMPILE_WINDOW_S:
                self._compile_times.popleft()
            if len(self._compile_times) < budget:
                return 0.0
        return self.compile_penalty_ms / 1e3

    def note_compile(self, owner: str) -> None:
        with self._lock:
            self.compiles_total += 1
            self._compile_times.append(self._clock())

    def note_compile_penalty(self, penalty_s: float) -> None:
        with self._lock:
            self.compile_penalties += 1
            self.compile_penalty_ms_total += int(penalty_s * 1e3)

    def compiles_last_min(self) -> int:
        now = self._clock()
        with self._lock:
            return sum(1 for t in self._compile_times
                       if now - t <= _COMPILE_WINDOW_S)

    # -- SLO ladder ------------------------------------------------------------
    def on_slo(self, slo_state: Optional[Dict], now: float) -> None:
        """One sampler tick of the mitigation ladder: under the
        `degrade` policy the effective rate halves each tick the SLO
        verdict is FIRING and recovers one halving per
        `recovery_ticks` consecutive non-firing ticks."""
        if self.policy != "degrade" or self.bucket is None:
            return
        firing = bool(slo_state) and slo_state.get("verdict") == "firing"
        changed = False
        with self._lock:
            if firing:
                self._ok_ticks = 0
                if self.degrade_level < _MAX_DEGRADE_LEVEL:
                    self.degrade_level += 1
                    changed = True
            elif self.degrade_level > 0:
                self._ok_ticks += 1
                if self._ok_ticks >= self.recovery_ticks:
                    self._ok_ticks = 0
                    self.degrade_level -= 1
                    changed = True
        if changed:
            rate = self.effective_rate()
            self.bucket.set_rate(rate)
            log.warning(
                "%s: admission ladder %s -> effective rate %.0f ev/s "
                "(level %d/%d)", self.rt.name,
                "halved under FIRING SLO" if firing else "recovered",
                rate, self.degrade_level, _MAX_DEGRADE_LEVEL)

    # -- registration ----------------------------------------------------------
    def register_owners(self, owners: List[str]) -> None:
        for o in owners:
            COMPILE_GATE.register(o, self)

    def unregister(self) -> None:
        COMPILE_GATE.unregister_app(self)

    # -- surfaces --------------------------------------------------------------
    def report(self) -> Dict[str, Any]:
        """The `admission` section of /healthz, EXPLAIN, and
        GET /siddhi-apps/<app>/admission — host-side reads only."""
        return {
            "policy": self.policy,
            "quota_state": self.quota_state,
            "max_events_per_sec": self.base_rate,
            "effective_events_per_sec": self.effective_rate(),
            "degrade_level": self.degrade_level,
            "burst": self.bucket.burst if self.bucket else None,
            "tokens": round(self.bucket.tokens, 3)
            if self.bucket else None,
            "max_state_bytes": self.max_state_bytes,
            "global_max_state_bytes": self.global_max_state_bytes,
            "block_timeout_ms": self.block_timeout_ms,
            "max_recompiles_per_min": self.max_recompiles_per_min,
            "compile_penalty_ms": self.compile_penalty_ms,
            "compile_penalty_max_ms": self.compile_penalty_max_ms,
            "shed_total": self.shed_total,
            "shed_by_stream": dict(self.shed_by_stream),
            "blocked_ms_total": self.blocked_ms_total,
            "blocked_sends": self.blocked_sends,
            "block_timeouts": self.block_timeouts,
            "growth_denials": self.growth_denials,
            "compiles_total": self.compiles_total,
            "compiles_last_min": self.compiles_last_min(),
            "compile_penalties": self.compile_penalties,
            "compile_penalty_ms_total": self.compile_penalty_ms_total,
        }

    def configure(self, updates: Dict[str, Any]) -> Dict[str, Any]:
        """Apply a REST PUT: accepts the config-key spellings
        ('overload', 'max.events.per.sec', 'max.state.bytes', 'burst',
        'block.timeout.ms', 'max.recompiles.per.min',
        'compile.penalty.ms').  Returns the post-change report."""
        known = {"overload", "max.events.per.sec", "max.state.bytes",
                 "burst", "block.timeout.ms", "max.recompiles.per.min",
                 "compile.penalty.ms", "compile.penalty.max.ms",
                 "degrade.recovery.ticks"}
        unknown = set(updates) - known
        if unknown:
            raise AdmissionDeniedError(
                f"unknown admission keys {sorted(unknown)}; "
                f"known: {sorted(known)}")
        if "overload" in updates:
            policy = str(updates["overload"]).lower()
            if policy not in OVERLOAD_POLICIES:
                raise AdmissionDeniedError(
                    f"unknown admission.overload policy {policy!r}; "
                    f"one of {OVERLOAD_POLICIES}")
            self.policy = policy
            self.policy_explicit = True
        if "max.events.per.sec" in updates:
            self.base_rate = _opt_float(updates["max.events.per.sec"])
            if self.base_rate is None:
                self.bucket = None
                self.degrade_level = 0
            else:
                self.bucket = TokenBucket(
                    self.effective_rate(), self.burst, clock=self._clock)
        if "burst" in updates:
            self.burst = _opt_float(updates["burst"])
            if self.bucket is not None:
                self.bucket = TokenBucket(
                    self.effective_rate(), self.burst, clock=self._clock)
        if "max.state.bytes" in updates:
            self.max_state_bytes = _opt_float(updates["max.state.bytes"])
            self.ceiling_hit = False       # operator raised it: re-check
        if "block.timeout.ms" in updates:
            self.block_timeout_ms = float(updates["block.timeout.ms"])
        if "max.recompiles.per.min" in updates:
            self.max_recompiles_per_min = _opt_float(
                updates["max.recompiles.per.min"])
        if "compile.penalty.ms" in updates:
            self.compile_penalty_ms = float(updates["compile.penalty.ms"])
        if "compile.penalty.max.ms" in updates:
            self.compile_penalty_max_ms = float(
                updates["compile.penalty.max.ms"])
        if "degrade.recovery.ticks" in updates:
            self.recovery_ticks = int(updates["degrade.recovery.ticks"])
        return self.report()
