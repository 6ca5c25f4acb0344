"""Multi-query optimizer: compile co-resident queries into shared
dispatches.

Reference role (what): the reference plans strictly per query off a
shared async junction (CORE/query/QueryRuntime.java — each query gets
its own processor chain even when dozens hang off one StreamJunction),
so N queries on one stream cost N traversals per event.

TPU design (how): here each query compiles to one jitted step, so N
co-resident queries cost N device dispatches, N emission fetches, and N
recompile owners per batch — and every perf round since r04 names the
per-dispatch host round-trip as the bottleneck.  This pass runs AFTER
per-query planning and BEFORE traffic: it partitions an app's plain
stream queries into **merge groups** keyed on (stream, @async/@pipeline/
@fuse decorations), stacks the member bodies into ONE jitted step per
group (`merged:<group>` recompile owner), fetches every member's
emission block in ONE device_get, and demultiplexes host-side so each
query's sinks, callbacks, rate limits, table writes, and error-store
semantics are untouched.  Members whose pre-window chain + window spec
+ group-by layout agree form a **shared unit** inside the group: they
reference one window buffer and one group-slot allocator (the
`window[shared]` component in state accounting) instead of per-query
duplicates.

Grouping is decided by `core/plan_facts.merge_plan` — the same single
source lint MQO001 and EXPLAIN's `merge` node read — and validated here
against the actual plans (any surprise demotes the query back to its
own dispatch with a recorded reason).  `optimizer.merge.enabled=false`
(manager config property) disables the pass app-wide.

Semantics kept exact, per query: outputs are byte-identical to the
unmerged plan (tests/test_mqo.py asserts this across filters, windows,
group-by, @fuse, @async, rate limits, and fault routing); snapshots
store each member's state view (shared window included once per member
record, identical bytes), so merged<->unmerged and mesh-resize restores
ride the existing per-query snapshot machinery unchanged.  The one
relaxation matches @fuse: a member's table writes become visible to
co-members at dispatch granularity, not mid-batch.
"""
from __future__ import annotations

import logging
import time
from typing import Dict, List, Tuple

import jax

from ..core import event as ev
from ..core import plan_facts
from ..core.runtime import _QueryRuntimeBase
from ..core.steputil import jit_step
from ..core.window import NO_WAKEUP
from ..observability import phases as _phases

jnp = jax.numpy
log = logging.getLogger("siddhi_tpu")


def merge_enabled(rt) -> bool:
    """`optimizer.merge.enabled` manager config property (default on);
    any of false/0/off/no disables the pass."""
    try:
        cm = getattr(rt.manager, "config_manager", None)
        v = cm.extract_property("optimizer.merge.enabled") \
            if cm is not None else None
    except Exception:  # noqa: BLE001 — config must not break deploy
        v = None
    if v is None:
        return True
    return str(v).strip().lower() not in ("false", "0", "off", "no")


class MergedGroupRuntime(_QueryRuntimeBase):
    """One merge group's host wrapper: stages each batch once, runs the
    stacked member bodies as ONE jitted step, and demuxes per-query
    emissions.  Subscribes to the junction in place of its members;
    members stay in `rt.query_runtimes` (snapshots, callbacks, metrics,
    EXPLAIN all keep addressing them by name) and read/write their state
    through `member_state`/`set_member_state` views.  It runs no plan of
    its own (`planned` is None) and emits nothing itself: of the base it
    uses the lock, the @fuse stack and the dispatcher's ingest stamp."""

    _kind = "merged"

    def __init__(self, rt, gmeta: Dict,
                 members: List[Tuple[str, object]],
                 units: List[Tuple[str, List[int]]]):
        super().__init__(None, rt)
        self.group = gmeta["group"]
        self.stream_id = gmeta["stream"]
        self.members = [qr for _, qr in members]
        self.units = units
        self._junction = rt.junctions[self.stream_id]
        self.in_schema = self.members[0].planned.in_schema
        # ONE lock for the group (the base's `_qlock`, handed to every
        # member below): demux re-enters member emission paths (pipeline
        # deques, table writes), and quiesce/flush take member locks —
        # sharing the RLock keeps every such path serialized exactly as
        # the per-query lock did unmerged
        # member position map: id(member) -> (unit idx, pos in unit, mode)
        self._slots: Dict[int, Tuple[int, int, str]] = {}
        state: List = []
        for u, (mode, idxs) in enumerate(units):
            if mode == "solo":
                m = self.members[idxs[0]]
                self._slots[id(m)] = (u, 0, mode)
                state.append(m._state)
            else:
                lead = self.members[idxs[0]]
                astates = []
                for j, i in enumerate(idxs):
                    m = self.members[i]
                    self._slots[id(m)] = (u, j, mode)
                    astates.append(m._state[1])
                state.append((lead._state[0], tuple(astates)))
                # shared group-slot space: every member resolves group
                # keys through the LEADER's allocator (identical key
                # layout is the shared-unit precondition), so the slot
                # maps — and MEM001's key-slot bytes — exist once
                for i in idxs[1:]:
                    self.members[i].planned.slot_allocator = \
                        lead.planned.slot_allocator
        self._state = tuple(state)
        for m in self.members:
            m._merged = self
            m._state = None
            m._qlock = self._qlock
        self.raw_body = self._build_body()
        self._step = jit_step(self.raw_body, owner=self.name,
                              role="merged_step", donate_argnums=(0,))
        # @fuse(batches=K) on every member: the MERGED dispatch owns the
        # stack (kind 'merged' in core/fusion.py); members drop theirs
        k = int(gmeta.get("decorations", {}).get("fuse", 0) or 0)
        if k > 0:
            from ..core import fusion as _fusion
            for m in self.members:
                if m._fuse is not None:
                    m._fuse = None
                    m._fuse_excluded = (
                        f"query dispatch is merged — {self.name} owns "
                        f"the @fuse stack")
            self._fuse = _fusion.FuseBuffer(self, k, self._kind)

    @property
    def name(self):
        return f"merged:{self.group}"

    # -- state views (snapshots/restore address members by name) ---------------
    def member_state(self, qr):
        u, j, mode = self._slots[id(qr)]
        st = self._state[u]
        return st if mode == "solo" else (st[0], st[1][j])

    def set_member_state(self, qr, v) -> None:
        u, j, mode = self._slots[id(qr)]
        state = list(self._state)
        if mode == "solo":
            state[u] = v
        else:
            w_new, a_new = v
            astates = list(state[u][1])
            astates[j] = a_new
            state[u] = (w_new, tuple(astates))
        self._state = tuple(state)

    def mode_of(self, qr) -> str:
        _, _, mode = self._slots[id(qr)]
        return "shared" if mode == "shared" else "stacked"

    # -- state accounting (observability/memory.py) ----------------------------
    def member_components(self, qr) -> Dict[str, int]:
        """A member's EXCLUSIVE state bytes: shared-unit members carry
        only their selector slab — the shared window buffer is reported
        once, under the group (shared_components)."""
        from ..observability.memory import tree_nbytes
        u, j, mode = self._slots[id(qr)]
        st = self._state[u]
        if mode == "solo":
            return {"window": tree_nbytes(st[0]),
                    "selector": tree_nbytes(st[1])}
        return {"selector": tree_nbytes(st[1][j])}

    def shared_components(self) -> Dict[str, int]:
        """{component: bytes} the GROUP owns: shared window buffers
        (counted once) + any pending @fuse stack."""
        from ..observability.memory import leaf_nbytes, tree_nbytes
        out: Dict[str, int] = {}
        shared = 0
        for u, (mode, _idxs) in enumerate(self.units):
            if mode == "shared":
                shared += tree_nbytes(self._state[u][0])
        if shared:
            out[plan_facts.MERGE_SHARED_COMPONENT] = shared
        fb = self._fuse
        if fb is not None and fb.items:
            total = 0
            for staged, _now in fb.items:
                total += leaf_nbytes(staged.ts) + \
                    leaf_nbytes(staged.kind) + leaf_nbytes(staged.valid)
                total += sum(leaf_nbytes(c) for c in staged.cols)
            if total:
                out["fuse_stack"] = total
        return out

    # -- the merged step -------------------------------------------------------
    def _build_body(self):
        units = self.units
        members = self.members

        def merged_body(state, ts, kind, valid, cols, gslots, now,
                        in_tabs, pslots):
            outs: List = [None] * len(members)
            new_state: List = []
            for u, (mode, idxs) in enumerate(units):
                if mode == "solo":
                    i = idxs[0]
                    p = members[i].planned
                    st, out, _wake = p.raw_step(
                        state[u], ts, kind, valid, cols, gslots[u], now,
                        in_tabs[i], pslots[i])
                    new_state.append(st)
                    outs[i] = out
                else:
                    wstate, astates = state[u]
                    lead = members[idxs[0]].planned
                    wstate, orows, _wake = lead.stage_body(
                        wstate, ts, kind, valid, cols, gslots[u], now,
                        in_tabs[idxs[0]])
                    new_as = []
                    for j, i in enumerate(idxs):
                        a, out = members[i].planned.select_body(
                            astates[j], orows, now, in_tabs[i],
                            pslots[i])
                        new_as.append(a)
                        outs[i] = out
                    new_state.append((wstate, tuple(new_as)))
            return (tuple(new_state), tuple(outs),
                    jnp.asarray(NO_WAKEUP, jnp.int64))
        return merged_body

    # -- dispatch --------------------------------------------------------------
    def _prep(self, staged: ev.StagedBatch, now: int) -> Tuple:
        """Host slot staging, ONCE per unit: shared units resolve group
        keys through the leader (one allocator), solo units through
        their own member."""
        gslots: List = []
        pslots: List = [()] * len(self.members)
        for mode, idxs in self.units:
            lead = self.members[idxs[0]]
            g, ps = lead._slots_for_batch(staged, now)
            gslots.append(g)
            if mode == "solo" and ps:
                pslots[idxs[0]] = tuple(ps)
        return tuple(gslots), tuple(pslots)

    def _in_tabs(self) -> Tuple:
        return tuple(self.app.in_probe_tables(m.planned.in_deps)
                     for m in self.members)

    def process_staged(self, staged: ev.StagedBatch, now: int) -> None:
        dbg = self.app._debugger
        if dbg is not None:
            for m in self.members:
                dbg.check_break_point(m.name, "IN", staged)
        fb = self._fuse
        if fb is not None and fb.offer((staged, now), staged, None):
            return
        self._dispatch(staged, now)

    def _dispatch(self, staged: ev.StagedBatch, now: int) -> None:
        from ..core.runtime import _staged_nbytes
        stats = self.app.stats
        t0 = time.perf_counter_ns() if stats.enabled else 0
        gslots, pslots = self._prep(staged, now)
        with _phases.phase(stats, self.name, "h2d",
                           bytes=_staged_nbytes(staged)):
            batch = staged.to_device(self.in_schema)
            gslots = tuple(jnp.asarray(g) for g in gslots)
            pslots = tuple(tuple(jnp.asarray(s) for s in ps)
                           for ps in pslots)
            now_d = jnp.asarray(now, jnp.int64)
        self._state, outs, _wake = _phases.dispatch(
            self, self._step, self._state, batch.ts, batch.kind,
            batch.valid, batch.cols, gslots, now_d, self._in_tabs(),
            pslots)
        if stats.enabled:
            stats.counter_inc(f"merged.{self.group}.dispatches")
            stats.counter_inc(f"merged.{self.group}.member_batches",
                              len(self.members))
        self._demux([(outs, staged, now, self._ingest_ns)], t0)

    # -- demux: one combined fetch, per-query delivery -------------------------
    def _demux(self, batches: List[Tuple], t0: int) -> None:
        """Deliver per-query emissions for one or more dispatched
        batches.  `batches` entries are (outs, staged, now, ingest_ns)
        where `outs` is the per-member output tuple of ONE batch.

        Sync mode fetches every consumed member's block across all
        batches in ONE `device_get`; @async/@pipeline members get device
        slices and re-enter their deferred paths (the drainer/deque
        already batch their fetches).  A member's delivery failure
        routes through the junction's fault handling exactly as an
        unmerged query's would, without blocking its co-members.  Step
        wall time splits evenly across members; each member's own demux
        time is measured around its delivery — the per-query latency
        accounting admission/tenant blame rides on."""
        from ..core import runtime as _rt
        stats = self.app.stats
        members = self.members
        deferred = members[0].defers_delivery()
        consumers = [i for i, m in enumerate(members)
                     if _rt._has_consumers(m)]
        hosted: Dict[int, List] = {}
        if consumers and not deferred:
            flat = _phases.fetch(
                stats, self.name, "rows",
                [[b[0][i] for b in batches] for i in consumers])
            hosted = dict(zip(consumers, flat))
        elif consumers:
            hosted = {i: [b[0][i] for b in batches] for i in consumers}
        share = 0
        if stats.enabled:
            share = (time.perf_counter_ns() - t0) // \
                max(1, len(members) * len(batches))
        for k, (_outs, staged, now, stamp) in enumerate(batches):
            for i, m in enumerate(members):
                td = time.perf_counter_ns() if stats.enabled else 0
                try:
                    if i in hosted:
                        m._ingest_ns = stamp
                        try:
                            _rt._emit_output(m, hosted[i][k], now,
                                             wake=None)
                        finally:
                            m._ingest_ns = None
                except Exception as exc:  # noqa: BLE001 — per-query fault
                    self._junction._handle_error_staged(staged, exc, now)
                finally:
                    if stats.enabled:
                        stats.query_latency(
                            m.name, staged.n,
                            share + time.perf_counter_ns() - td)
                        owed, m._e2e_owed = m._e2e_owed, False
                        if owed and stamp is not None:
                            stats.e2e_latency(
                                m.name,
                                time.perf_counter_ns() - stamp)


def apply_merge(rt) -> None:
    """Run the merge pass over a freshly-constructed SiddhiAppRuntime:
    build a MergedGroupRuntime per group from `plan_facts.merge_plan`,
    swap junction subscriptions, and record the exact ineligibility
    reason on every unmerged query for EXPLAIN/lint."""
    from ..core import runtime as _rt
    rt.merged_groups = {}
    rt._merge_reasons = {}
    mesh_n = int(rt.mesh.devices.size) if rt.mesh is not None else 0
    if not merge_enabled(rt):
        why = "multi-query merge disabled (optimizer.merge.enabled=false)"
        for name, qr in rt.query_runtimes.items():
            qr._merge_excluded = why
            rt._merge_reasons[name] = why
        return
    try:
        plan = plan_facts.merge_plan(rt.app, mesh_devices=mesh_n)
    except Exception as exc:  # noqa: BLE001 — the pass must not break deploy
        log.warning("multi-query merge pass skipped: %r", exc)
        return
    reasons = dict(plan["reasons"])
    for g in plan["groups"]:
        junction = rt.junctions.get(g["stream"])
        members: List[Tuple[str, object]] = []
        for name in g["members"]:
            qr = rt.query_runtimes.get(name)
            p = qr.planned if isinstance(qr, _rt.QueryRuntime) else None
            ok = (p is not None
                  and p.raw_step is not None
                  and p.stage_body is not None
                  and not p.needs_timer
                  and not p.keyed_window
                  and p.partition_key_fn is None
                  and junction is not None and qr in junction.queries)
            if ok:
                members.append((name, qr))
            else:
                # static plan said mergeable but the actual plan is not:
                # demote loudly instead of merging a surprise
                reasons[name] = ("planner produced no mergeable step "
                                 "body for this query (demoted)")
        if len(members) < 2:
            for name, _qr in members:
                reasons[name] = (
                    f"no co-resident query shares stream "
                    f"{g['stream']!r} and its @async/@pipeline/@fuse/"
                    f"@serve decorations")
            continue
        kept = {n for n, _ in members}
        pos_of = {n: i for i, (n, _) in enumerate(members)}
        units: List[Tuple[str, List[int]]] = []
        for u in g["units"]:
            names = [n for n in u["members"] if n in kept]
            if not names:
                continue
            if u["mode"] == "shared" and len(names) >= 2:
                units.append(("shared", [pos_of[n] for n in names]))
            else:
                for n in names:
                    units.append(("solo", [pos_of[n]]))
        mg = MergedGroupRuntime(rt, g, members, units)
        rt.merged_groups[mg.group] = mg
        # swap subscriptions: the merged runtime takes the FIRST
        # member's junction slot (members subscribe in query order, so
        # relative order vs unmerged co-subscribers is preserved)
        qs = junction.queries
        pos = qs.index(members[0][1])
        for _name, qr in members:
            qs.remove(qr)
        qs.insert(pos, mg)
        junction._sub_names_memo = None
        log.info("multi-query merge: %s merges %d queries on %r "
                 "(%d shared unit(s))", mg.name, len(members),
                 g["stream"],
                 sum(1 for mode, _ in units if mode == "shared"))
    for name, why in reasons.items():
        qr = rt.query_runtimes.get(name)
        if qr is not None:
            qr._merge_excluded = why
    rt._merge_reasons = reasons
