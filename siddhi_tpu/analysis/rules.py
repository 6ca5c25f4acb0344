"""Built-in lint rules: TPU hazards detectable before an app ever runs.

Every rule is grounded in a runtime hazard this engine actually has —
the rationale strings name the mechanism.  Severity policy: ERROR is
reserved for "this will break or silently lose data as written"; WARN
for "this degrades or explodes under production traffic"; INFO for
"you should know, but it may be intentional".  A clean production app
should lint with zero ERRORs; the shipped samples do.

Rule IDs are stable API: dashboards, CI configs, and severity overrides
key on them.  Never renumber — retire IDs instead.
"""
from __future__ import annotations

from typing import Iterator, List, Optional

from ..query_api.query import (
    EveryStateElement,
    InsertIntoStream,
    Partition,
    Query,
    ReturnStream,
    ValuePartitionType,
)
from .facts import (
    _BATCH_CAPACITY,
    AnalysisContext,
    iter_named_queries,
    pattern_atoms,
    query_kind,
)
from .findings import Finding
from .registry import rule


def _f(message: str, query: Optional[str] = None, node=None,
       hint: Optional[str] = None) -> Finding:
    """Finding skeleton — the driver stamps rule id / severity /
    source; `node` contributes its parser position when it has one."""
    return Finding(rule_id="", severity="", message=message, query=query,
                   pos=getattr(node, "pos", None), hint=hint)


def _mb(n: int) -> str:
    return f"{n / (1024 * 1024):.1f} MiB"


# ---------------------------------------------------------------------------
# state growth
# ---------------------------------------------------------------------------

@rule("STATE001", "WARN",
      "unbounded pattern state (`every` without `within`)",
      "An `every`-repeated pattern with no `within` bound keeps every "
      "pending partial match alive forever; the NFA slot block fills and "
      "new matches evict old ones unpredictably under sustained traffic.",
      "add `within <time>` to the pattern so stale partial matches "
      "expire")
def _every_without_within(ctx: AnalysisContext) -> Iterator[Finding]:
    for f in ctx.queries:
        if f.kind != "pattern":
            continue
        ist = f.query.input_stream
        if getattr(ist, "within_time", None) is not None:
            continue
        every = None

        def find_every(el):
            nonlocal every
            if isinstance(el, EveryStateElement) and every is None:
                every = el
            for attr in ("state_element", "next_state_element",
                         "stream_state_element",
                         "stream_state_element_1",
                         "stream_state_element_2"):
                sub = getattr(el, attr, None)
                if sub is not None:
                    find_every(sub)

        find_every(ist.state_element)
        if every is not None:
            yield _f("`every` pattern has no `within` bound — pending "
                     "match state accumulates without expiry "
                     f"({f.nfa_slots} NFA slots/key, eviction under "
                     "overflow)", query=f.name,
                     node=every if getattr(every, "pos", None)
                     else f.query)


@rule("STATE002", "INFO",
      "pattern emission block is effectively uncapped",
      "Non-partitioned patterns default to the 1<<30 'uncapped' "
      "compact_rows sentinel: the device emission block is sized by "
      "worst-case match fan-out, so a pathological batch can emit an "
      "arbitrarily large block in one dispatch.",
      "set `@emit(rows='N')` to bound the per-dispatch emission block")
def _uncapped_pattern_emission(ctx: AnalysisContext) -> Iterator[Finding]:
    for f in ctx.queries:
        if f.kind == "pattern" and f.emission_cap is None and \
                not f.emission_cap_explicit:
            yield _f("pattern emission cap is the uncapped sentinel — "
                     "worst-case match fan-out sizes the emission block",
                     query=f.name, node=f.query)


@rule("MEM001", "WARN",
      "query state exceeds the device-memory budget",
      "Window buffers, keyed-window slabs, and NFA slot blocks are "
      "dense device arrays sized at plan time (shape × dtype); a few "
      "oversized queries exhaust HBM before the first event arrives.",
      "shrink the window / `@capacity(keys=…, slots=…, window=…)`, or "
      "raise the lint budget if the deployment really has the HBM")
def _state_over_budget(ctx: AnalysisContext) -> Iterator[Finding]:
    from ..core.plan_facts import format_component_bytes
    budget = getattr(ctx.config, "state_budget_bytes",
                     128 * 1024 * 1024)
    for f in ctx.queries:
        if f.state_bytes is not None and f.state_bytes > budget:
            # same breakdown string the admission deploy gate prints in
            # its AdmissionDeniedError (core/plan_facts estimator)
            detail = f" ({format_component_bytes(f.state_components)})" \
                if f.state_components else ""
            yield _f(f"{f.state_bytes_origin} device state "
                     f"{_mb(f.state_bytes)} exceeds the "
                     f"{_mb(budget)} budget{detail}", query=f.name,
                     node=f.query)
    # merge-group shared buffers live under `merged:<group>` owners
    # (counted once, never per member) — grade them against the same
    # budget so sharing can't hide an oversized window from MEM001
    try:
        if ctx.runtime is not None:
            from ..observability.memory import component_bytes
            owners = component_bytes(ctx.runtime)
            origin = "measured"
        else:
            from ..core.plan_facts import static_state_components
            owners = static_state_components(ctx.app)
            origin = "estimated"
    except Exception:  # noqa: BLE001 — accounting must not kill lint
        owners = {}
        origin = "estimated"
    for owner in sorted(owners):
        if not owner.startswith("merged:"):
            continue
        comps = owners[owner]
        total = sum(comps.values())
        if total > budget:
            yield _f(f"{origin} shared device state {_mb(total)} of "
                     f"merge group {owner[len('merged:'):]!r} exceeds "
                     f"the {_mb(budget)} budget "
                     f"({format_component_bytes(comps)})")


# ---------------------------------------------------------------------------
# fusion / dispatch
# ---------------------------------------------------------------------------

@rule("FUSE001", "WARN",
      "@fuse requested but the wiring will exclude it",
      "A @fuse(batches=K) on a timer-bearing, keyed, sharded, or "
      "partitioned query is silently ignored at wiring time — the "
      "operator expects K× dispatch amortization and gets none.  The "
      "runtime only logs the exclusion at deploy; lint surfaces it "
      "before.",
      "remove the @fuse annotation, or restructure the query onto a "
      "fusable path")
def _fuse_excluded(ctx: AnalysisContext) -> Iterator[Finding]:
    for f in ctx.queries:
        if f.fuse_requested and f.fusion_exclusion:
            yield _f(f"@fuse(batches={f.fuse_requested}) will be "
                     f"ignored: {f.fusion_exclusion}", query=f.name,
                     node=f.query)


# ---------------------------------------------------------------------------
# emission caps
# ---------------------------------------------------------------------------

@rule("JOIN001", "WARN",
      "explicit join emission cap can overflow under worst-case "
      "cross-product",
      "An explicit @emit(rows='N') on a join warns-and-drops on "
      "overflow instead of growing; a batch joining against a full "
      "window can produce batch×window rows, silently truncated to N.",
      "raise @emit(rows=…) to cover batch_capacity × window rows, or "
      "drop the annotation and let the cap grow adaptively")
def _join_cap_overflow(ctx: AnalysisContext) -> Iterator[Finding]:
    for f in ctx.queries:
        if f.kind != "join" or not f.emission_cap_explicit or \
                f.emission_cap is None or f.join_side_rows is None:
            continue
        left, right = f.join_side_rows
        worst = _BATCH_CAPACITY * max(left, right)
        if f.emission_cap < worst:
            yield _f(f"explicit emission cap {f.emission_cap} rows < "
                     f"worst-case cross-product {worst} rows "
                     f"({_BATCH_CAPACITY}-row batch × "
                     f"{max(left, right)}-row window); overflow rows "
                     "are dropped", query=f.name, node=f.query)


@rule("JOIN002", "INFO",
      "equi-join fast path: ACTIVE (INFO) or inapplicable (WARN)",
      "The join ON-condition has a top-level equality conjunct.  When "
      "the equi-join fast path applies (both sides plain stream "
      "windows -> device key bucketing; or an indexed table side with "
      "a windowless trigger -> host hash probe) the plan evaluates "
      "only same-key candidate pairs and this rule reports INFO with "
      "the key attributes.  When the conjunct exists but the fast path "
      "cannot apply, the plan still evaluates the full [rows × rows] "
      "grid every batch — bytes-accessed scales with the grid, not the "
      "matches — and this rule WARNs with the wiring's exact reason "
      "(core/plan_facts.join_fastpath).",
      "bucket mode needs plain stream windows with no side [filter]; "
      "table mode needs an @Index/@PrimaryKey on the join key and a "
      "windowless trigger side; shrink the windows if the grid cost "
      "hurts")
def _equi_join_grid(ctx: AnalysisContext) -> Iterator[Finding]:
    from ..core.plan_facts import join_fastpath, table_probe_attrs_of
    app = ctx.app

    def side_kind(sid: str) -> str:
        if sid in app.aggregation_definition_map:
            return "aggregation"
        if sid in app.window_definition_map:
            return "named_window"
        if sid in app.table_definition_map:
            return "table"
        return "stream"

    def probe_attrs(sid: str):
        d = app.table_definition_map.get(sid)
        return table_probe_attrs_of(d) if d is not None else []

    for f in ctx.queries:
        if f.kind != "join":
            continue
        try:
            mode, pairs, reason = join_fastpath(
                f.query.input_stream, side_kind, probe_attrs)
        except Exception:  # noqa: BLE001 — analysis must not kill lint
            continue
        if not pairs:
            continue
        keys = ", ".join(
            f"{lv.stream_id}.{lv.attribute_name} == "
            f"{rv.stream_id}.{rv.attribute_name}"
            for _c, lv, rv in pairs)
        node = pairs[0][0] if getattr(pairs[0][0], "pos", None) \
            else f.query
        if mode is not None:
            fd = _f(f"equi-join fast path ACTIVE ({mode}): only "
                    f"same-key candidates are probed for {keys}",
                    query=f.name, node=node,
                    hint="no action needed")
            fd.severity = "INFO"
        else:
            fd = _f(f"ON-condition equality {keys} found but the fast "
                    f"path cannot apply: {reason} — the full "
                    "[rows × rows] grid is evaluated every batch",
                    query=f.name, node=node)
            fd.severity = "WARN"
        yield fd


# ---------------------------------------------------------------------------
# dataflow
# ---------------------------------------------------------------------------

def _stream_reads(app) -> set:
    reads = set()
    for _, q, _part in iter_named_queries(app):
        kind = query_kind(q)
        if kind == "plain":
            reads.add(q.input_stream.stream_id)
        elif kind == "join":
            reads.add(q.input_stream.left_input_stream.stream_id)
            reads.add(q.input_stream.right_input_stream.stream_id)
        else:
            for a in pattern_atoms(q.input_stream.state_element):
                reads.add(a.basic_single_input_stream.stream_id)
    for agg in app.aggregation_definition_map.values():
        sis = agg.basic_single_input_stream
        if sis is not None:
            reads.add(sis.stream_id)
    return reads


def _stream_writes(app) -> set:
    writes = set(app.trigger_definition_map)
    for _, q, _part in iter_named_queries(app):
        out = q.output_stream
        if out is not None and out.target_id:
            writes.add(out.target_id)
    return writes


@rule("DEAD001", "WARN",
      "stream defined but never referenced",
      "A stream no query reads and nothing writes is dead weight: its "
      "junction is wired, its schema interned, and a misspelled stream "
      "name elsewhere usually hides behind it.",
      "delete the definition, or fix the query that should be using it")
def _dead_stream(ctx: AnalysisContext) -> Iterator[Finding]:
    app = ctx.app
    reads = _stream_reads(app)
    writes = _stream_writes(app)
    for sid, sdef in app.stream_definition_map.items():
        if sid.startswith(("!", "#")) or sid in app.trigger_definition_map:
            continue
        if sdef.get_annotation("source") is not None or \
                sdef.get_annotation("sink") is not None:
            continue
        if sid not in reads and sid not in writes:
            yield _f(f"stream {sid!r} is never read or written by any "
                     "query, trigger, source, or sink", query=None,
                     node=sdef)


@rule("DEAD002", "INFO",
      "query output feeds nothing visible statically",
      "The query inserts into a stream that no downstream query reads "
      "and no @sink consumes.  Runtime callbacks may consume it — but "
      "if none is attached, every device step and emission fetch for "
      "this query is wasted work.",
      "add a downstream query or @sink, attach a runtime callback, or "
      "remove the query")
def _dead_output(ctx: AnalysisContext) -> Iterator[Finding]:
    app = ctx.app
    reads = _stream_reads(app)
    for f in ctx.queries:
        out = f.query.output_stream
        if not isinstance(out, InsertIntoStream) or \
                isinstance(out, ReturnStream):
            continue
        tgt = out.target_id
        if not tgt or tgt in app.table_definition_map or \
                tgt in app.window_definition_map:
            continue                 # tables/windows are stateful sinks
        sdef = app.stream_definition_map.get(tgt)
        if sdef is not None and sdef.get_annotation("sink") is not None:
            continue
        if tgt not in reads:
            yield _f(f"output stream {tgt!r} has no downstream query or "
                     "@sink (a runtime callback may still consume it)",
                     query=f.name, node=out)


# ---------------------------------------------------------------------------
# partitioning
# ---------------------------------------------------------------------------

@rule("PART001", "WARN",
      "partition key has unbounded cardinality",
      "Partition keys map to a finite device key slab (default 4096 "
      "slots).  A continuous-valued (float/double) key makes nearly "
      "every event a new key: the slab exhausts, purge churn replaces "
      "useful state, and per-key isolation degrades to noise.",
      "partition by a bounded-cardinality attribute (id, symbol, "
      "category), or bucket the value upstream")
def _float_partition_key(ctx: AnalysisContext) -> Iterator[Finding]:
    from ..query_api.expression import Variable
    for element in ctx.app.execution_element_list:
        if not isinstance(element, Partition):
            continue
        for sid, pt in element.partition_type_map.items():
            if not isinstance(pt, ValuePartitionType) or \
                    not isinstance(pt.expression, Variable):
                continue
            sdef = ctx.app.stream_definition_map.get(sid)
            if sdef is None:
                continue
            try:
                atype = sdef.attribute_type(
                    pt.expression.attribute_name)
            except KeyError:
                continue
            if atype in ("FLOAT", "DOUBLE"):
                yield _f(f"partition key {sid}.{pt.expression.attribute_name} "
                         f"is {atype} — continuous values exhaust the "
                         "finite partition key slab", query=None,
                         node=element)


def _mesh_devices(ctx: AnalysisContext) -> int:
    """Deploy-target mesh size: the live runtime's mesh when analyzing a
    runtime, else the app's own `@app:mesh(shards='N')`, else
    LintConfig.mesh_devices (CLI --mesh-size), else 0 = unknown (PART002
    stays silent — without the annotation mesh size is a deploy
    property)."""
    rt = ctx.runtime
    if rt is not None:
        from ..sharding import shard_count
        n = shard_count(rt)
        if n > 1:
            return n
    from ..core.plan_facts import mesh_shards
    from ..exceptions import SiddhiAppValidationError
    try:
        n = mesh_shards(ctx.app)
    except SiddhiAppValidationError:   # a malformed count: deploy's error
        n = None
    if n is not None:
        return n
    return int(getattr(ctx.config, "mesh_devices", 0) or 0)


@rule("PART002", "WARN",
      "partition key capacity below the mesh size",
      "A mesh-sharded partition spreads key slots round-robin over the "
      "devices (sharding/router.py), so at most key-capacity shards can "
      "ever hold a key.  A capacity below the mesh size guarantees idle "
      "shards: their state slabs are allocated, their collectives run, "
      "and they never process a key — the deployment pays for devices "
      "that cannot do work.",
      "raise @capacity(keys='N') to at least the mesh size — ideally a "
      "large multiple of it so routing balances — or serve the app "
      "unsharded")
def _undersized_partition_keys(ctx: AnalysisContext) -> Iterator[Finding]:
    from .facts import capacity_annotation
    n = _mesh_devices(ctx)
    if n < 2:
        return
    for f in ctx.queries:
        if f.partition is None:
            continue
        # the CONFIGURED capacity (runtime rounds it up to a mesh
        # multiple, so the planned value can never show the hazard)
        keys = capacity_annotation(f.query, f.partition).get("keys")
        if keys is None:
            from .facts import _PARTITION_KEYS
            keys = _PARTITION_KEYS
        if keys < n:
            yield _f(
                f"partition key capacity {keys} is below the {n}-device "
                f"mesh — at least {n - keys} shard(s) are guaranteed "
                f"idle", query=f.name, node=f.partition)


# ---------------------------------------------------------------------------
# expressions
# ---------------------------------------------------------------------------

def _filter_compares(app, q: Query):
    """(Compare node, owning stream def) for every filter expression in
    the query's input chains, plus the selector's having clause."""
    from ..query_api.expression import Compare, walk
    from ..query_api.query import Filter

    def sources(iq):
        kind = query_kind(iq)
        if kind == "plain":
            yield iq.input_stream
        elif kind == "join":
            yield iq.input_stream.left_input_stream
            yield iq.input_stream.right_input_stream
        else:
            for a in pattern_atoms(iq.input_stream.state_element):
                yield a.basic_single_input_stream

    for sis in sources(q):
        sdef = app.stream_definition_map.get(sis.stream_id) or \
            app.window_definition_map.get(sis.stream_id) or \
            app.table_definition_map.get(sis.stream_id)
        for h in getattr(sis, "stream_handlers", ()):
            if isinstance(h, Filter):
                for node in walk(h.expression):
                    if isinstance(node, Compare):
                        yield node, sdef
    if q.selector is not None and q.selector.having_expression is not None:
        for node in walk(q.selector.having_expression):
            from ..query_api.expression import Compare as _C
            if isinstance(node, _C):
                yield node, None


@rule("TYPE001", "WARN",
      "lossy type coercion in filter comparison",
      "Comparing a LONG attribute against a float/double literal "
      "coerces i64 to floating point on device; LONG values above 2^53 "
      "(and above 2^24 where DOUBLE lowers to f32 on TPU) compare "
      "wrongly — timestamps and ids are exactly the values that hit "
      "this.",
      "use an integer literal, or cast/scale the attribute explicitly")
def _lossy_filter_compare(ctx: AnalysisContext) -> Iterator[Finding]:
    from ..query_api.expression import Constant, Variable

    def attr_type(var, sdef):
        for d in ((ctx.app.stream_definition_map.get(var.stream_id),)
                  if var.stream_id else (sdef,)):
            if d is None:
                continue
            try:
                return d.attribute_type(var.attribute_name)
            except (KeyError, AttributeError):
                continue
        # pattern event refs (e1.price) resolve against the handler's
        # own stream definition
        if var.stream_id and sdef is not None:
            try:
                return sdef.attribute_type(var.attribute_name)
            except (KeyError, AttributeError):
                pass
        return None

    for f in ctx.queries:
        for cmp_node, sdef in _filter_compares(ctx.app, f.query):
            for a, b in ((cmp_node.left, cmp_node.right),
                         (cmp_node.right, cmp_node.left)):
                if isinstance(a, Variable) and isinstance(b, Constant) \
                        and b.type in ("FLOAT", "DOUBLE") and \
                        attr_type(a, sdef) == "LONG":
                    from ..observability.explain import render_expr
                    yield _f("LONG attribute "
                             f"{a.attribute_name!r} compared against "
                             f"{b.type} literal {b.value!r} — i64→float "
                             "coercion loses precision "
                             f"({render_expr(cmp_node)})", query=f.name,
                             node=cmp_node if getattr(cmp_node, "pos",
                                                      None)
                             else f.query)
                    break


@rule("NULL001", "WARN",
      "nullable attribute hits the in-band null encoding's divergences",
      "Nulls are in-band reserved values on device (INT/LONG use the "
      "dtype minimum, BOOL has no spare value — PARITY.md).  When the "
      "null-flow pass proves an attribute can be null (outer-join "
      "unmatched side, optional pattern atom, empty-set aggregation) "
      "and it flows into a compare or arithmetic, semantics diverge "
      "from the reference: a legitimate INT_MIN/LONG_MIN value is "
      "treated as null, and a null BOOL compares as False instead of "
      "making the comparison false.  This is the static half of "
      "ROADMAP item 5 (validity bit-planes delete the divergence).",
      "guard with `is null` / coalesce() before comparing, use a "
      "FLOAT/DOUBLE column (NaN null is out-of-band for comparisons), "
      "or accept the documented INT_MIN-as-value semantics")
def _nullable_sentinel_flow(ctx: AnalysisContext) -> Iterator[Finding]:
    from ..query_api import expression as ex
    from .typeflow import SENTINEL_DIVERGENT, infer_app
    try:
        flow = infer_app(ctx.app)
    except Exception:  # noqa: BLE001 — inference must not kill lint
        return
    for f in ctx.queries:
        qf = flow.queries.get(f.name)
        if qf is None:
            continue
        seen = set()
        for use in qf.uses:
            if not isinstance(use.node, (ex.Compare, ex.Add,
                                         ex.Subtract, ex.Multiply,
                                         ex.Divide, ex.Mod)):
                continue
            if use.context == "on":
                continue      # join ON null-keys simply never match
            for side, info in zip((use.node.left, use.node.right),
                                  use.operands):
                if not info.nullable or \
                        info.type not in SENTINEL_DIVERGENT:
                    continue
                if id(use.node) in seen:
                    break
                seen.add(id(use.node))
                what = side.attribute_name \
                    if isinstance(side, ex.Variable) else "expression"
                op = "compared" if isinstance(use.node, ex.Compare) \
                    else "used in arithmetic"
                divergence = (
                    "null decodes as False, so `== false` matches "
                    "nulls" if info.type == "BOOL" else
                    f"a legitimate {info.type}_MIN value is treated "
                    "as null")
                yield _f(
                    f"nullable {info.type} {what!r} "
                    f"({info.why or 'null-flow'}) is {op} — "
                    f"{divergence}; reference semantics diverge "
                    "(PARITY.md in-band nulls)", query=f.name,
                    node=use.node if getattr(use.node, "pos", None)
                    else f.query)
                break


# ---------------------------------------------------------------------------
# rate limiting
# ---------------------------------------------------------------------------

@rule("RATE001", "WARN",
      "rate limit interacts with batch emission to drop events",
      "The rate limiter samples the emission stream AFTER device "
      "compaction and batch stacking: an explicit @emit cap truncates "
      "rows before first/last selection sees them, and under @fuse the "
      "limiter's clock only advances at dispatch — up to K-1 batches "
      "late for time/snapshot limiters.",
      "drop the explicit @emit cap, or un-fuse the query, or accept "
      "the documented loss semantics")
def _ratelimit_batch_interaction(ctx: AnalysisContext
                                 ) -> Iterator[Finding]:
    for f in ctx.queries:
        rate = f.query.output_rate
        if rate is None:
            continue
        if f.emission_cap_explicit and f.emission_cap is not None:
            yield _f(f"explicit @emit(rows={f.emission_cap}) drops "
                     "overflow rows before the "
                     f"`output {rate.behavior.lower()} every …` limiter "
                     "samples them", query=f.name, node=rate)
        elif f.fuse_requested and rate.type in ("TIME", "SNAPSHOT"):
            yield _f(f"@fuse(batches={f.fuse_requested}) delays "
                     "emission up to "
                     f"{f.fuse_requested - 1} batches behind the "
                     f"{rate.type.lower()}-based rate limiter's clock",
                     query=f.name, node=rate)


# ---------------------------------------------------------------------------
# deployment hygiene
# ---------------------------------------------------------------------------

@rule("APP001", "INFO",
      "app has no @app:name",
      "The REST service keys deployments by app name and rejects "
      "duplicates; every unnamed app collides on the default "
      "'SiddhiApp', so at most one can ever be deployed.",
      "add @app:name('…') at the top of the app")
def _unnamed_app(ctx: AnalysisContext) -> Iterator[Finding]:
    if not ctx.app.name:
        yield _f("app is unnamed — REST deployments collide on the "
                 "default name 'SiddhiApp'")


# ---------------------------------------------------------------------------
# I/O resilience
# ---------------------------------------------------------------------------

@rule("SINK001", "WARN",
      "@sink on a high-rate stream silently drops failed events",
      "The default @sink(on.error='log') policy logs a transport "
      "failure and DROPS the affected events.  On a stream fed at "
      "engine rate (a query output or an @async ingress) a short "
      "broker/socket outage silently loses a window of output with "
      "nothing but a log line to show for it — and no fault stream is "
      "defined to catch them either.",
      "set @sink(on.error='retry') (buffered redelivery), 'store' "
      "(error store + replay), 'wait' (backpressure), or 'stream' + a "
      "`!stream` consumer, or add @OnError(action='STREAM') to the "
      "stream")
def _sink_silent_drop(ctx: AnalysisContext) -> Iterator[Finding]:
    app = ctx.app
    writes = _stream_writes(app)
    for sid, sdef in app.stream_definition_map.items():
        if sid.startswith(("!", "#")):
            continue
        # high-rate: events arrive at engine rate (query output) or
        # through an async ingress ring, not hand-fed test traffic
        if sid not in writes and sdef.get_annotation("async") is None:
            continue
        on_err = sdef.get_annotation("OnError")
        if on_err is not None and \
                str(on_err.element("action", "LOG")).upper() == "STREAM":
            continue
        for ann in sdef.annotations:
            if ann.name.lower() != "sink":
                continue
            policy = str(ann.element("on.error", "log")).lower()
            if policy != "log":
                continue
            stype = ann.element("type") or ann.element(None)
            yield _f(f"@sink(type={str(stype)!r}) on high-rate stream "
                     f"{sid!r} uses the default on.error='log' and no "
                     "fault stream is defined — a transport outage "
                     "silently drops every event published during it",
                     query=None, node=ann)


# ---------------------------------------------------------------------------
# admission control
# ---------------------------------------------------------------------------

def _global_ceiling(ctx: AnalysisContext) -> int:
    """Deploy-target global state ceiling, bytes: the live manager's
    `admission.global.max.state.bytes` when analyzing a runtime, else
    LintConfig.global_state_ceiling_bytes (CLI --global-ceiling), else
    0 = unknown (the size half of ADM001 stays silent)."""
    rt = ctx.runtime
    if rt is not None:
        try:
            cm = getattr(getattr(rt, "manager", None),
                         "config_manager", None)
            v = cm.extract_property("admission.global.max.state.bytes") \
                if cm is not None else None
            if v:
                return int(float(v))
        except Exception:  # noqa: BLE001 — config must not break lint
            pass
    return int(getattr(ctx.config, "global_state_ceiling_bytes", 0) or 0)


def _overload_explicit(ctx: AnalysisContext) -> bool:
    """Did anyone CHOOSE an overload policy for this app?  Runtime:
    the controller's policy_explicit (annotation, manager property, or
    REST PUT).  Static: the @app:admission annotation alone."""
    rt = ctx.runtime
    if rt is not None:
        adm = getattr(rt, "admission", None)
        if adm is not None:
            return bool(getattr(adm, "policy_explicit", False))
    ann = ctx.app.get_annotation("app:admission")
    return ann is not None and ann.element("overload") is not None


@rule("ADM001", "WARN",
      "app will collide with the admission controller at deploy or "
      "under load",
      "Two deploy-time hazards the admission layer (core/admission.py) "
      "turns into runtime denials: an app whose static state estimate "
      "already exceeds the box's configured global memory ceiling will "
      "be REJECTED at deploy (`admission.global.max.state.bytes`), and "
      "an app fed at transport rate by a @source with no explicit "
      "`admission.overload` policy gets the default 'block' ladder — "
      "under overload its transport delivery thread backpressures to "
      "the deadline and then errors, which for a socket feed usually "
      "means disconnects, not throttling.",
      "shrink the state (window/@capacity) below the global ceiling, "
      "and declare @app:admission(overload='shed'|'degrade'|'block', "
      "max.events.per.sec='…') so overload behavior is chosen, not "
      "defaulted")
def _admission_hazards(ctx: AnalysisContext) -> Iterator[Finding]:
    ceiling = _global_ceiling(ctx)
    if ceiling > 0:
        total = sum(f.state_bytes or 0 for f in ctx.queries)
        if total > ceiling:
            worst = max((f for f in ctx.queries if f.state_bytes),
                        key=lambda f: f.state_bytes, default=None)
            yield _f(f"total {'measured' if ctx.runtime is not None else 'estimated'} "
                     f"device state {_mb(total)} exceeds the global "
                     f"admission ceiling {_mb(ceiling)} — deploy would "
                     "be denied on a box honoring it",
                     query=worst.name if worst is not None else None,
                     node=worst.query if worst is not None else None)
    # transport-rate ingest with a defaulted overload policy
    if _overload_explicit(ctx):
        return
    for sid, sdef in ctx.app.stream_definition_map.items():
        if sid.startswith(("!", "#")):
            continue
        for ann in sdef.annotations:
            if ann.name.lower() != "source":
                continue
            stype = str(ann.element("type") or ann.element(None) or "")
            if stype.lower() == "inmemory":
                continue      # hand-fed test transport, not a feed
            yield _f(f"@source(type={stype!r}) feeds {sid!r} at "
                     "transport rate but no admission.overload policy "
                     "is declared — overload backpressures the "
                     "delivery thread with the default 'block' ladder",
                     query=None, node=ann)


@rule("MQO001", "INFO",
      "multi-query merge: groups formed (and why queries stay out)",
      "N co-resident queries on one stream normally cost N device "
      "dispatches, N emission fetches, and N recompile owners per "
      "batch.  The whole-app optimizer (siddhi_tpu/optimizer) merges "
      "eligible queries into ONE jitted dispatch per group — and "
      "queries with identical pre-window chains + window specs + "
      "group-by layouts additionally share one window buffer.  This "
      "rule reports each group the planner will form and, for every "
      "query left out, the planner's exact ineligibility reason "
      "(core/plan_facts.merge_plan — the same single source the "
      "runtime pass and EXPLAIN's `merge` node read).",
      "align @async/@pipeline/@fuse/@serve decorations, window specs, "
      "and "
      "pre-window filters across co-resident queries to widen merge "
      "groups; set optimizer.merge.enabled=false to opt out")
def _merge_groups(ctx: AnalysisContext) -> Iterator[Finding]:
    from ..core.plan_facts import merge_plan
    # a single-query app has nothing to merge: stay silent instead of
    # explaining why one query is alone
    if len(ctx.queries) < 2:
        return
    rt = ctx.runtime
    if rt is not None and hasattr(rt, "merged_groups"):
        # live runtime: report what the pass ACTUALLY did (config may
        # have disabled it; dynamic demotions may have shrunk groups)
        by_name = {f.name: f for f in ctx.queries}
        for gid in sorted(rt.merged_groups):
            mg = rt.merged_groups[gid]
            shared = sum(1 for mode, _ in mg.units if mode == "shared")
            first = by_name.get(mg.members[0].name)
            yield _f(f"merge group {gid!r} compiles "
                     f"{len(mg.members)} queries into one dispatch "
                     f"({shared} shared window unit(s)): "
                     + ", ".join(m.name for m in mg.members),
                     query=first.name if first is not None else None,
                     node=first.query if first is not None else None,
                     hint="no action needed")
        for name in sorted(getattr(rt, "_merge_reasons", {})):
            f = by_name.get(name)
            yield _f(f"not merged: {rt._merge_reasons[name]}",
                     query=name,
                     node=f.query if f is not None else None)
        return
    try:
        plan = merge_plan(ctx.app,
                          mesh_devices=int(getattr(ctx.config,
                                                   "mesh_devices", 0)
                                           or 0))
    except Exception:  # noqa: BLE001 — analysis must not kill lint
        return
    by_name = {f.name: f for f in ctx.queries}
    for g in plan["groups"]:
        shared = sum(1 for u in g["units"] if u["mode"] == "shared")
        first = by_name.get(g["members"][0])
        yield _f(f"merge group {g['group']!r} compiles "
                 f"{len(g['members'])} queries into one dispatch "
                 f"({shared} shared window unit(s)): "
                 + ", ".join(g["members"]),
                 query=first.name if first is not None else None,
                 node=first.query if first is not None else None,
                 hint="no action needed")
    for name in sorted(plan["reasons"]):
        f = by_name.get(name)
        yield _f(f"not merged: {plan['reasons'][name]}", query=name,
                 node=f.query if f is not None else None)


@rule("SERVE001", "WARN",
      "@serve query drains into a synchronous-blocking sink",
      "Device-resident serving (siddhi_tpu/serving) moves delivery onto "
      "ONE shared drainer thread per app: the send path only appends to "
      "an on-device ring, and the drainer fetches and publishes later.  "
      "A sink with on.error='wait' blocks its publish call until the "
      "transport recovers — on the drainer thread that stall is "
      "head-of-line blocking for EVERY serving query's ring: occupancy "
      "climbs to high-water, producers fall back to bounded ring "
      "backpressure, and the app's serving path degrades to the "
      "synchronous behavior @serve was meant to remove.",
      "use @sink(on.error='retry'|'store'|'stream') on streams fed by "
      "@serve queries so the drainer never parks, or drop @serve from "
      "the query feeding the 'wait' sink")
def _serve_blocking_sink(ctx: AnalysisContext) -> Iterator[Finding]:
    from ..core.plan_facts import serve_enabled
    app = ctx.app
    rt = ctx.runtime
    for f in ctx.queries:
        q = f.query
        # serving? live runtime wins (serving.enabled config can turn
        # the app on wholesale); statically only annotations decide
        if rt is not None:
            qr = rt.query_runtimes.get(f.name)
            serving = qr is not None and qr.serve_emit
        else:
            try:
                serving = bool(serve_enabled(app, q))
            except Exception:  # noqa: BLE001 — analysis must not die
                serving = False
        if not serving:
            continue
        out = q.output_stream
        tgt = getattr(out, "target_id", None)
        sdef = app.stream_definition_map.get(tgt) if tgt else None
        if sdef is None:
            continue
        for ann in sdef.annotations:
            if ann.name.lower() != "sink":
                continue
            if str(ann.element("on.error", "log")).lower() != "wait":
                continue
            stype = ann.element("type") or ann.element(None)
            yield _f(f"@serve query {f.name!r} feeds "
                     f"@sink(type={str(stype)!r}, on.error='wait') on "
                     f"{tgt!r} — a transport stall parks the shared "
                     "drainer thread and backpressures every serving "
                     "ring in the app", query=f.name, node=ann)


@rule("STATE003", "WARN",
      "sized state capacity far from observed high-water",
      "Every stateful structure here occupies FIXED device shapes sized "
      "at compile time: keyed window slabs, group-slot arenas, NFA key "
      "blocks, join key lanes.  The state observatory "
      "(observability/stateobs.py) tracks each structure's occupancy "
      "and high-water from its host mirror.  A capacity 4x or more "
      "above the observed high-water wastes HBM against admission's "
      "state ceilings for the whole app lifetime; an occupancy at 90%+ "
      "of a NON-growable cap means the next new key raises a slot-"
      "exhaustion error instead of degrading gracefully.",
      "resize via the cited config key (e.g. @capacity(keys='N')) to "
      "~2x the observed high-water; the high-water persists across "
      "restarts in snapshots, so a bench-scale soak gives a durable "
      "sizing hint")
def _state_capacity_mismatch(ctx: AnalysisContext) -> Iterator[Finding]:
    rt = ctx.runtime
    if rt is None:
        return          # utilization is measured, never guessed
    from ..observability.stateobs import (
        _NEAR_CAPACITY_EXEMPT, collect, near_capacity, obs_enabled)
    if not obs_enabled(rt):
        return
    try:
        collect(rt)
        snap = rt.stats.stateobs.snapshot()
    except Exception:  # noqa: BLE001 — analysis must not die
        return
    for q, structures in snap["structures"].items():
        for s, rec in structures.items():
            hwm, cap = rec["high_water"], rec["capacity"]
            if rec["growable"] or s in _NEAR_CAPACITY_EXEMPT:
                continue
            # oversized: enough traffic to trust the high-water, and
            # the configured cap dwarfs it
            if hwm >= 8 and cap >= 4 * hwm:
                ck = rec.get("config_key") or "its capacity annotation"
                yield _f(f"{s} capacity {cap} is {cap / hwm:.0f}x the "
                         f"observed high-water {hwm} — device state is "
                         "sized for traffic that never arrived",
                         query=q,
                         hint=f"shrink {ck} toward ~{max(16, 2 * hwm)} "
                              "(2x observed high-water)")
    for rec in near_capacity(rt, snap):
        ck = rec.get("config_key") or "its capacity annotation"
        yield _f(f"{rec['structure']} occupancy {rec['occupancy']}/"
                 f"{rec['capacity']} "
                 f"({rec['utilization'] * 100:.0f}%) on a non-growable "
                 "cap — the next new key past the cap raises instead "
                 "of degrading", query=rec["query"],
                 hint=f"raise {ck} before the arena exhausts")


ALL_RULE_IDS: List[str] = [
    "STATE001", "STATE002", "MEM001", "FUSE001", "JOIN001", "JOIN002",
    "DEAD001", "DEAD002", "NULL001", "PART001", "PART002", "TYPE001",
    "RATE001", "APP001", "SINK001", "ADM001", "MQO001", "SERVE001",
    "STATE003",
]
