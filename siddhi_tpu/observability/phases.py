"""Phase-level hot-path profiler, and the ONE span primitive that feeds it.

Reference (what): the reference's DETAIL statistics level leaves per-event
breadcrumbs (StreamJunction.sendEvent :147, QuerySelector.process :77);
every open perf question here is instead a per-PHASE budget question —
which slice of the batch pipeline (host staging, H2D upload, dispatch
submit, device compute, ring residency, D2H drain, demux, sink fan-out)
owns the wall time.  TPU design (how): every hot-path boundary is ONE
`with phase(stats, query, name, ...)` call site, and that one call feeds
three readers:

- always: a `jax.profiler.TraceAnnotation("siddhi:<name>", q=, batch=,
  ...)` — a TraceMe enter/exit (about a microsecond) when no profiler
  session is live, and when one is, a host span on the SAME timeline as
  the device's `XLA Ops`, which is what says why the chip was idle.
  There is no switch: the profiler session is the switch;
- statistics BASIC and up: the span's SELF time (its wall minus the spans
  nested in it on the same thread) into the per-(query, phase)
  `PhaseProfiler` behind `/metrics`, `/phases` and `phase_report()`;
- DETAIL with a batch trace active: a span on the `PipelineTracer` ring.

Host clocks only — zero device fetches and zero `block_until_ready` on
the steady path.  The instrument never changes what the program does.

The async-dispatch blind spot: a jitted step call returns at SUBMIT, so
the `dispatch` span says nothing about device time — that is paid later
inside whichever `fetch` drains the output.  Device time is the profiler
trace's; the sampled deep mode (`profile.sample.every=N`) fences every
Nth dispatch per query with `block_until_ready` and books the fence wall
as `device_compute`.

Inside the device step the host has no spans: the sequential pattern
programs (core/pattern_planner.py) put every op under a `jax.named_scope`
SECTION — `event_load`, `state_load`, `nfa_advance`, `state_store`,
`match_rows`, `selector`, `emission_compaction`, `emission_bands`, on a
mesh `mesh_reduce` — inside one `rect_<Kb>x<E>` scope a program.  Scopes
are op-name metadata: in a device trace they are the `tf_op` stat of each
op's EVENT METADATA (the plane's `event_metadata`, beside `program_id` and
`hlo_category`), not a stat of the event itself, so
`jax.profiler.ProfileData` does not show them; TensorBoard / xprof group
by them, and `benchmarks/harness/step_sections.py` reads the `XSpace`
message for them (device time by section and by rectangle).  The plain
query step (core/planner.py `jit_plain_step`) has its own, each named where
the work is: `plain_chain`, `window_fill`, `window_state`, `window_order`,
`agg_layout`, `agg_scan`, `project` (`benchmarks/harness/
plain_sections.py`), and the join's two side programs (core/join.py)
`join_window`, `join_lanes`, `join_probe`, `join_pairs`, `join_select`,
`join_compact` (`benchmarks/harness/join_sections.py`).  A tiered send's
dispatches are told apart on the device side, by their `rect_<Kb>x<E>`.

Inside a section that owns a step a second scope level names PARTS: what a
group of its ops is FOR, in the words of the code that owns it.  Every op
of such a section stands under one part, so an op a later edit adds outside
them shows as part `""`.  A device op's `tf_op` then reads
`jit(plain_step)/agg_layout/to_sorted/gather:` — section, part, ... jax
primitive — and `benchmarks/harness/section_ops.py` books every op of a
traced slice under (program, section, part, primitive):

  agg_layout    (selector.AggregatorBank.process)
    keys        sign, slot, reset epochs, segment ids, segment heads
    order       the stable argsort by (slot, reset epoch)
    invert      the inverse permutation, `.at[order].set(arange)`
    to_sorted   ONE packed gather by `order`: sign, slot, epoch, every `vals`
    from_sorted ONE packed gather by the inverse: every spec's scan
  agg_scan      (same)
    scan        contributions, the carry at segment heads, the segmented
                scan
    store       the last row a slot and the write of the new state
  window_order  (window.sort_rows)
    order       the key and its stable argsort
    to_sorted   one gather an array by it
  join_pairs    (join.make_step)
    index       the flags, `pos` / `li` / `ri`, the composed group slot
    take_this   what is gathered by `li`
    take_other  what is gathered by `ri`

Two pattern sections name parts for SOME of their ops — what a count atom
(`B<1:5>`) adds to a tick and to the match rows; the rest of the section
stays part `""`.  Inside `nfa_advance` they stand below the scan's own
`while/body` (`.../nfa_advance/while/body/fork_spawn/...`), so a reader
looks for them at any depth under the section
(`benchmarks/harness/nested_parts.py`):

  nfa_advance   (pattern.PatternExec.tick)
    count_capture  a count atom's capture write: one of its D rows, by the
                   slot's count
    fork_spawn     `_spawn`: candidates ranked against free slots, every
                   leaf pulled by a one-hot contraction, the captures too
  match_rows    (pattern_planner._match_rows)
    last_capture   `e2[last]`: the fill depth and a contraction over D

(`pattern_block`'s `event_load` is four gathers by one index and has none.)

Spans (`siddhi:<name>`) and the scrape phase each feeds:

  send        whole InputHandler.send / send_columns call;
              `minflt` while a session records it: the minor
              page faults the sending thread took in the call  (no phase:
              its self time is what no child span covers)
  stage       pad/adopt or pack_np into a StagedBatch        stage_host
  route_keys  key -> slot routing, grouping, ts-wire build;
              `grouped` = view | take: the one-chip pattern
              path's columns put in the per-key order;
              `tiers`, `cells`, `max_e`, `ticks`: the send's
              [Kb, E] layout (LAYOUT_STATS)                  stage_host
  shard_group the router's [n, Kb, E] regroup of a sharded
              send, nested in route_keys                     stage_host
  obs_feed    state observatory feed, liveness, dirty marks  stage_host
  h2d         every host->device upload (host wall)          h2d
  dispatch    the jitted step call (submit only); under
              @serve also the ring's two programs: `step` =
              ring_append on the sender's thread (`occupancy`:
              the ring's entries once it is in), ring_read on
              the drainer's                                  dispatch_submit
  fetch       every device_get on a delivery path; a banded
              pattern emission's `rows` fetches carry `ranks`
              (ranks fetched: the bands below `ranks_used`)
              and `ranks_cap` (R, summed over the send's
              tiers) (FETCH_STATS); a drain cycle's one
              `what=ring` fetch carries `items` (the sends it
              serves) and `ring_wait_us` (their append ->
              take residency, summed)                        d2h_drain
  demux       header decode, ts-order restore, unpack        demux
  sink        callbacks, table op, rate limit, re-publish    sink
  compile     jit_step's body while tracing a new signature  (none)
  timer       the scheduler firing a timer step              (none)
  state_init  a pattern runtime's one jitted state init, at
              deploy (`bytes`, `shards`)                     (none)

`ring_wait` (emission-ring / drainer-queue residency, append -> take) is
a difference of two stamps on two threads, not a span: `waited()`, with
statistics on; the served path's `what=ring` fetch span says the same
residency as `ring_wait_us` whether they are on or not.

Counters are per-query LATENCY attribution, not wall-clock utilization:
a batched drainer fetch serving three queries charges its full wall to
each of them, exactly as each query's `<q>:e2e` histogram sample does —
so per query, sum(phases) tracks the e2e histogram and the unattributed
remainder surfaces as `other` in `runtime.phase_report()`.
"""
from __future__ import annotations

import threading
import time
from typing import Dict, Optional

import jax
from jax.profiler import TraceAnnotation

from . import tracing as _tracing
from .memory import tree_nbytes

try:
    import resource
    _RUSAGE_THREAD = getattr(resource, "RUSAGE_THREAD", None)
except ImportError:          # no `resource` module on this platform
    resource, _RUSAGE_THREAD = None, None

# canonical order — every surface (report, /metrics, /timeseries, PERF
# tables) lists phases in pipeline order, not dict order
PHASES = ("stage_host", "h2d", "dispatch_submit", "device_compute",
          "ring_wait", "d2h_drain", "demux", "sink")

# span name -> the scrape phase its self time feeds.  `stage_host` is the
# sum of four spans, which snapshot()/phase_report() list beneath it as
# `parts`.  `send`, `compile`, `timer` and `state_init` feed no phase.
SPAN_PHASE = {"stage": "stage_host", "route_keys": "stage_host",
              "shard_group": "stage_host",
              "obs_feed": "stage_host", "h2d": "h2d",
              "dispatch": "dispatch_submit", "fetch": "d2h_drain",
              "demux": "demux", "sink": "sink"}
STAGE_PARTS = ("stage", "route_keys", "shard_group", "obs_feed")
# what `route_keys` says of a partitioned pattern send's device layout:
# the [Kb, E] rectangles it was split into, their cells, the hottest
# key's events, the sum of the rectangles' E (the scan ticks it costs)
LAYOUT_STATS = ("tiers", "cells", "max_e", "ticks")
# what a `rows` fetch says of the banded pattern emission it reads: the
# ranks it fetched, the ranks the emission could hold
FETCH_STATS = ("ranks", "ranks_cap")
SPAN_PREFIX = "siddhi:"


class PhaseProfiler:
    """Always-on per-(query, phase) ns accumulator.  One per
    StatisticsManager (i.e. per app runtime); `add` is the single
    hot-path entry — a dict upsert under a short lock, no allocation
    beyond the first sample of a (query, phase) pair."""

    __slots__ = ("_lock", "_ns", "_count", "_grouped", "_layout",
                 "_dispatches", "_sampled")

    def __init__(self):
        self._lock = threading.Lock()
        # (query, phase, part) -> total ns / samples; part None = the
        # phase itself, else one of the spans that sum to it
        self._ns: Dict[tuple, int] = {}
        self._count: Dict[tuple, int] = {}
        # (query, phase, part) -> {"view" | "take": samples}: how the
        # pattern path's route_keys span grouped the columns
        self._grouped: Dict[tuple, Dict[str, int]] = {}
        # (query, phase, part) -> sums of the same span's LAYOUT_STATS
        self._layout: Dict[tuple, Dict[str, int]] = {}
        self._dispatches: Dict[str, int] = {}  # query -> dispatch counter
        self._sampled: Dict[str, int] = {}     # query -> fenced dispatches

    def add(self, query: str, phase: str, ns: int,
            part: Optional[str] = None, meta: Optional[Dict] = None) -> None:
        """`meta`: the span's stats; `grouped` is tallied by value and the
        LAYOUT_STATS are summed (both are `route_keys`'s), and so are a
        `fetch`'s FETCH_STATS."""
        if ns <= 0:
            return
        key = (query, phase, part)
        with self._lock:
            self._ns[key] = self._ns.get(key, 0) + int(ns)
            self._count[key] = self._count.get(key, 0) + 1
            if meta and "grouped" in meta:
                tally = self._grouped.setdefault(key, {})
                tally[meta["grouped"]] = tally.get(meta["grouped"], 0) + 1
            for stats in (LAYOUT_STATS, FETCH_STATS):
                if meta and stats[0] in meta:
                    sums = self._layout.setdefault(key, {})
                    for stat in stats:
                        sums[stat] = sums.get(stat, 0) + \
                            int(meta.get(stat, 0))

    def should_sample(self, query: str, every: int) -> bool:
        """Per-query dispatch modulus for the deep mode: True on every
        Nth dispatch (the caller then fences with block_until_ready and
        records `device_compute`).  Counts the sampled dispatch so the
        exposition can report what fraction of traffic paid the fence."""
        if every <= 0:
            return False
        with self._lock:
            n = self._dispatches.get(query, 0) + 1
            self._dispatches[query] = n
            if n % every:
                return False
            self._sampled[query] = self._sampled.get(query, 0) + 1
        return True

    def snapshot(self) -> Dict:
        """{"queries": {q: {phase: {"ns", "count"[, "parts"]}}},
        "sampled": {q: n}} — phases in canonical order; shallow int
        copies, scrape-safe.  A phase fed by several spans (`stage_host`)
        sums their ns, lists each under `parts`, and counts batches: its
        `stage` part's samples (one a staged batch), or the most-sampled
        part's where a query never sees one (timer-fired steps).  The
        `route_keys` part of a pattern query also lists `grouped`:
        {"view": n, "take": n}, its spans counted by that stat, and
        `layout`: the sums of its spans' LAYOUT_STATS; `d2h_drain` of
        one whose emission is banded lists `layout` too: the sums of
        its fetches' FETCH_STATS."""
        with self._lock:
            ns = dict(self._ns)
            count = dict(self._count)
            grouped = {k: dict(v) for k, v in self._grouped.items()}
            layout = {k: dict(v) for k, v in self._layout.items()}
            sampled = dict(self._sampled)
        queries: Dict[str, Dict] = {}
        for (q, p, part), total in ns.items():
            ent = queries.setdefault(q, {}).setdefault(
                p, {"ns": 0, "count": 0})
            ent["ns"] += total
            n = count.get((q, p, part), 0)
            if part is None:
                ent["count"] += n
                if (q, p, part) in layout:
                    ent["layout"] = layout[(q, p, part)]
            else:
                ent.setdefault("parts", {})[part] = {"ns": total,
                                                     "count": n}
                if (q, p, part) in grouped:
                    ent["parts"][part]["grouped"] = grouped[(q, p, part)]
                if (q, p, part) in layout:
                    ent["parts"][part]["layout"] = layout[(q, p, part)]
        for q, phases in queries.items():
            for ent in phases.values():
                parts = ent.get("parts")
                if parts:
                    ent["count"] += parts["stage"]["count"] \
                        if "stage" in parts \
                        else max(v["count"] for v in parts.values())
                    ent["parts"] = {k: parts[k] for k in STAGE_PARTS
                                    if k in parts}
            queries[q] = {p: phases[p] for p in PHASES if p in phases}
        return {"queries": queries, "sampled": sampled}

    def reset(self) -> None:
        with self._lock:
            self._ns.clear()
            self._count.clear()
            self._grouped.clear()
            self._layout.clear()
            self._dispatches.clear()
            self._sampled.clear()


# ---------------------------------------------------------------------------
# the span primitive
# ---------------------------------------------------------------------------

_tls = threading.local()


def current_batch() -> int:
    """The send this thread is working for: the per-junction sequence
    number `siddhi:send` opened with, carried across thread handoffs by
    `handoff()` / `adopt()` and, over the @async ingress queue, beside
    the staged batch.  0 = none (a timer firing, a flush)."""
    return getattr(_tls, "batch", 0)


class batch_scope:
    """Make `batch` the thread's current send for the scope of a
    dispatch or a delivery (an @async ingress worker picking a
    StagedBatch off its queue, a drainer delivering a handed-off
    emission).  0 keeps whatever is current."""

    __slots__ = ("batch", "prev")

    def __init__(self, batch: int):
        self.batch = batch

    def __enter__(self):
        self.prev = getattr(_tls, "batch", 0)
        if self.batch:
            _tls.batch = self.batch
        return self

    def __exit__(self, *exc):
        _tls.batch = self.prev
        return False


def handoff():
    """Token for a cross-thread delivery (the @async drainer queue, the
    @pipeline deque, the serving ring): the DETAIL trace armed for
    concurrent appends (tracing.handoff) and the send's batch number.
    The drain side wraps its delivery in `adopt(token)`."""
    return (_tracing.handoff(), getattr(_tls, "batch", 0))


class adopt:
    """Deliver under a handed-off token: spans opened inside carry the
    originating send's `batch`, and DETAIL spans join its trace on the
    `drain` track (tracing.adopt)."""

    __slots__ = ("token", "scope", "tr")

    def __init__(self, token):
        self.token = token

    def __enter__(self):
        trace, batch = self.token if self.token is not None else (None, 0)
        self.scope = batch_scope(batch)
        self.scope.__enter__()
        self.tr = _tracing.adopt(trace)
        self.tr.__enter__()
        return self

    def __exit__(self, *exc):
        self.tr.__exit__(*exc)
        return self.scope.__exit__(*exc)


class _Timed:
    """A span with statistics on: the TraceAnnotation plus a host clock.
    On exit its SELF time (wall minus the `_Timed` spans nested in it on
    this thread) goes to the PhaseProfiler under the span's scrape phase,
    once per query it is charged to and `mult` times (a fused dispatch
    serves `mult` batches, and each batch's e2e sample contains the whole
    wall); a DETAIL batch trace active on the thread gets the span too."""

    __slots__ = ("ann", "stats", "queries", "name", "mult", "meta",
                 "t0", "kids", "prev")

    def __init__(self, ann, stats, queries, name, mult, meta):
        self.ann, self.stats, self.queries = ann, stats, queries
        self.name, self.mult, self.meta = name, mult, meta

    def set_metadata(self, **kw):
        self.ann.set_metadata(**kw)
        self.meta.update(kw)

    def __enter__(self):
        self.ann.__enter__()
        self.prev = getattr(_tls, "open", None)
        _tls.open = self
        self.kids = 0
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        _tls.open = self.prev
        wall = t1 - self.t0
        if self.prev is not None:
            self.prev.kids += wall
        phase_name = SPAN_PHASE.get(self.name)
        if phase_name is not None and self.queries and \
                self.stats is not None and self.stats.enabled:
            own = (wall - self.kids) * self.mult
            part = self.name if phase_name == "stage_host" else None
            add = self.stats.phases.add
            for q in self.queries:
                add(q, phase_name, own, part, self.meta)
        tr = _tracing.active()
        if tr is not None:
            meta = self.meta
            if self.queries:
                meta = dict(meta, query=self.queries[0])
            tr.add_span(self.name, self.t0, t1, meta,
                        getattr(_tracing._tls, "track", None))
        return self.ann.__exit__(*exc)


def phase(stats, query, name: str, mult: int = 1, **meta):
    """THE span primitive: `with phase(stats, query, "fetch", what="header")`.

    `query` is the query the time is charged to, a tuple of them (a
    junction-level span charges every subscriber, as each one's e2e
    sample contains it), or None.  With statistics OFF (`stats` None
    counts as OFF) and no DETAIL trace on the thread this returns the bare
    `jax.profiler.TraceAnnotation` — nothing else runs; stats known only
    at the end go on with `.set_metadata(rows=n)` either way."""
    queries = (query,) if isinstance(query, str) else (query or ())
    ann = TraceAnnotation(
        SPAN_PREFIX + name, q=queries[0] if queries else "",
        batch=getattr(_tls, "batch", 0), **meta)
    if (stats is None or not stats.enabled) and _tracing.active() is None:
        return ann
    return _Timed(ann, stats, queries, name, mult, meta)


def _recorded() -> bool:
    """Whether somebody records the spans now (a profiler session or a
    DETAIL trace): what costs something to say is said only then."""
    return TraceAnnotation.is_enabled() or _tracing.active() is not None


def _minor_faults() -> Optional[int]:
    """Minor page faults of the calling thread so far; None where the
    platform has no RUSAGE_THREAD."""
    if _RUSAGE_THREAD is None:
        return None
    return resource.getrusage(_RUSAGE_THREAD).ru_minflt


class send:
    """`siddhi:send` over one InputHandler call, under `batch` — the next
    number of the junction's send sequence, which every span the send
    causes then carries (batch_scope + phase entered as one).

    While somebody records it (a profiler session or a DETAIL trace, as
    `fetch` decides for `bytes`) the span also says `minflt`: the minor
    page faults the sending thread took inside the call.  A send makes and
    frees its staging arrays; fresh pages from the kernel show here (some
    250 faults a MB without huge pages).  On the v5e hosts it reads 0 in
    every cell and allocator mode measured so far (PERF.md, PR 35)."""

    __slots__ = ("scope", "stats", "stream", "events", "span", "faults0")

    def __init__(self, stats, stream: str, batch: int,
                 events: Optional[int]):
        self.scope = batch_scope(batch)
        self.stats, self.stream, self.events = stats, stream, events

    def __enter__(self):
        self.scope.__enter__()
        meta = {} if self.events is None else {"events": self.events}
        self.span = phase(self.stats, None, "send", stream=self.stream,
                          **meta)
        self.faults0 = _minor_faults() if _recorded() else None
        return self.span.__enter__()

    def __exit__(self, *exc):
        if self.faults0 is not None:
            self.span.set_metadata(minflt=_minor_faults() - self.faults0)
        self.span.__exit__(*exc)
        return self.scope.__exit__(*exc)


def dispatch(qr, step, *args, name: Optional[str] = None, mult: int = 1):
    """Call one jitted step inside a `dispatch` span (async dispatch: the
    call returns at SUBMIT, so the span says nothing about device time).
    Every `profile.sample.every` dispatches per query the deep mode
    fences the returned pytree with `block_until_ready` and books the
    fence wall as `device_compute` — the only block the profiler ever
    takes, and never on the steady (unsampled) path.  The span's `step`
    is the jit role (`jit_<role>` is the XLA module's name in the same
    trace); `mult` as in `_Timed`."""
    st = qr.app.stats
    qname = name or qr.name
    with phase(st, qname, "dispatch", mult,
               step=getattr(step, "_siddhi_role", "step")):
        res = step(*args)
    if st.enabled:
        every = sample_every(qr.app)
        if every and st.phases.should_sample(qname, every):
            t1 = time.perf_counter_ns()
            jax.block_until_ready(res)
            st.phases.add(qname, "device_compute",
                          (time.perf_counter_ns() - t1) * mult)
    return res


def nbytes(*arrays) -> int:
    """Bytes of the host arrays an `h2d` span is about to upload (the hot
    path's plain sum; `memory.tree_nbytes` walks a pytree)."""
    return sum(int(getattr(a, "nbytes", 0)) for a in arrays)


def fetch(stats, query, what: str, tree, mult: int = 1, **meta):
    """THE device->host fetch of every delivery path: `jax.device_get`
    inside a `fetch` span (`what` = header | rows | ring).  In blocking
    delivery the first fetch after a dispatch also holds the wait for
    the step.  `bytes` is summed only while somebody records it; `meta`:
    what the caller knows of the fetch beforehand (FETCH_STATS)."""
    with phase(stats, query, "fetch", mult, what=what, **meta) as sp:
        out = jax.device_get(tree)
        if _recorded():
            sp.set_metadata(bytes=tree_nbytes(out))
    return out


def waited(stats, query: str, since_ns: int, extra_ns: int = 0) -> None:
    """Book `ring_wait`: how long an emission sat between the dispatch
    side's handoff and this delivery (ring or drainer-queue residency,
    plus the serialized wait behind its predecessors' deliveries)."""
    if stats.enabled:
        stats.phases.add(query, "ring_wait",
                         extra_ns + time.perf_counter_ns() - since_ns)


def sample_every(rt) -> int:
    """`profile.sample.every=N` config (0 = deep mode off, the default),
    memoized on the runtime like serving_config — the hot path reads one
    dict slot, never the ConfigManager."""
    every = rt.__dict__.get("_profile_sample_every")
    if every is None:
        every = 0
        try:
            cm = getattr(rt, "config_manager", None)
            v = cm.extract_property("profile.sample.every") \
                if cm is not None else None
            if v is not None:
                every = max(0, int(v))
        except Exception:  # noqa: BLE001 — profiling must not throw
            every = 0
        rt.__dict__["_profile_sample_every"] = every
    return every


def phase_report(rt) -> Dict:
    """Per-query phase budget vs the `<q>:e2e` histogram: seconds + share
    per phase, with the unattributed remainder reported as `other` (the
    acceptance bar: phases account >=90% of measured e2e wall for a
    @serve flagship run).  Queries with phase samples but no e2e
    histogram (statistics OFF mid-flight) report shares of the phase sum
    instead."""
    st = rt.stats
    snap = st.phases.snapshot()
    queries = {}
    for q, phases in snap["queries"].items():
        total_ns = sum(v["ns"] for v in phases.values())
        e2e = st.e2e_sum_ns(q)
        base = e2e if e2e > 0 else total_ns
        entry = {
            p: {"seconds": round(v["ns"] / 1e9, 6),
                "count": v["count"],
                "share": round(v["ns"] / base, 4) if base else 0.0}
            for p, v in phases.items()}
        for p, v in phases.items():
            if "layout" in v:
                entry[p]["layout"] = v["layout"]
            if "parts" in v:
                entry[p]["parts"] = {
                    k: {"seconds": round(pv["ns"] / 1e9, 6),
                        **{f: x for f, x in pv.items() if f != "ns"}}
                    for k, pv in v["parts"].items()}
        other_ns = max(0, e2e - total_ns) if e2e > 0 else 0
        queries[q] = {
            "phases": entry,
            "e2e_seconds": round(e2e / 1e9, 6),
            "other_seconds": round(other_ns / 1e9, 6),
            "accounted": round(min(total_ns / base, 1.0), 4)
            if base else 0.0,
            "sampled_dispatches": snap["sampled"].get(q, 0),
        }
    return {"app": rt.name, "sample_every": sample_every(rt),
            "queries": queries}
