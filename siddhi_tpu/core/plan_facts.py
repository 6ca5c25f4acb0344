"""Shared compiled-plan fact helpers: uncapped-sentinel rendering,
fusion-exclusion reasons, and the static state-bytes estimator.

Three surfaces report the same two plan facts — whether a query's
emission cap is real or the 1<<30 "effectively uncapped" sentinel, and
why a requested `@fuse` was skipped at wiring time: the static analyzer
(`siddhi_tpu/analysis`), EXPLAIN (`observability/explain.py`), and
`/healthz` (`observability/health.py`).  Each used to re-derive them
locally (the sentinel rendering lived only in explain; the exclusion
reason only in a wiring-time log line), so the renderings could drift.
This module is the single source of truth all three import.

The same single-source rule applies to the *static state-bytes
estimate*: lint's MEM001 rule and the admission controller's
deploy-time memory gate (core/admission.py) must agree on how big an
app's device state will be BEFORE anything is planned or traced, or an
app could lint green and still be denied at deploy (or vice versa).
`static_state_components` below is that one implementation — a pure
AST walk mirroring the planner/runtime capacity defaults, shape×dtype
arithmetic only, never touching jax — and both consumers cite the same
per-component breakdown it returns.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

# pattern_planner's compact_rows default for non-partitioned patterns:
# "effectively uncapped" (a per-key cap with K=1 would cap the batch).
# Every surface that renders an emission cap must treat values at or
# above this sentinel as "no cap", never as a 1073741824-row budget.
UNCAPPED_SENTINEL = 1 << 30


def render_cap(rows: Optional[int]) -> Optional[int]:
    """Human-facing emission cap: None when absent or at/above the
    uncapped sentinel, else the concrete row count."""
    if rows is None:
        return None
    rows = int(rows)
    return None if rows >= UNCAPPED_SENTINEL else rows


def fusion_exclusion(qr) -> Optional[str]:
    """The concrete reason @fuse was requested but skipped for this query
    runtime, or None (fusing, eligible, or never requested).

    Prefers the reason stored at wiring time (runtime `_register`) and
    falls back to recomputing from the plan's static properties, so a
    runtime restored from a snapshot still reports it.  Attribute reads
    only — safe on the scrape path."""
    why = qr._fuse_excluded
    if why is not None:
        return why
    if qr._fuse_requested and qr._fuse is None:
        from . import fusion
        try:
            return fusion.ineligible_reason(qr, qr._kind)
        except Exception:  # noqa: BLE001 — diagnostics must not throw
            return "unknown (plan facts unavailable)"
    return None


def fusion_exclusions(rt) -> Dict[str, str]:
    """{query: exclusion reason} for every runtime of an app whose @fuse
    request was skipped at wiring time (empty when none were)."""
    out: Dict[str, str] = {}
    for name, qr in list(getattr(rt, "query_runtimes", {}).items()):
        why = fusion_exclusion(qr)
        if why is not None:
            out[name] = why
    return out


# ---------------------------------------------------------------------------
# static state-bytes estimator (shared by lint MEM001 and the admission
# deploy gate — one implementation, one component breakdown)
# ---------------------------------------------------------------------------

# mirrors of the planner/runtime defaults (planner.plan_single_query,
# runtime._add_query/_add_partition) — the static estimates must predict
# what those paths would build
BATCH_CAPACITY = 512
WINDOW_HINT = 2048
PARTITION_WINDOW_HINT = 128
PARTITION_KEYS = 4096
NFA_SLOTS = 8
# default serving emission-ring slot count (serving/ring.py) when
# neither @serve(ring.capacity=) nor `serving.ring.capacity` says
# otherwise — kept here so the static state estimator and the runtime
# agree on the ring's footprint
SERVE_RING_SLOTS = 8
# columnar buffer overhead per row beyond the payload columns:
# ts i64 + seq i64 + gslot i32 + alive bool (core/window.py empty_buffer)
ROW_OVERHEAD = 8 + 8 + 4 + 1


def iter_named_queries(app):
    """(name, query, partition|None) with runtime-identical naming
    (mirrors SiddhiAppRuntime._query_name: @info name, else `query<i>`
    numbered across top-level queries and partition bodies)."""
    from ..query_api.query import Partition, Query
    qi = 0

    def name_of(q) -> str:
        info = q.get_annotation("info")
        if info:
            n = info.element("name")
            if n:
                return n
        return f"query{qi + 1}"

    for element in app.execution_element_list:
        if isinstance(element, Query):
            yield name_of(element), element, None
            qi += 1
        elif isinstance(element, Partition):
            for q in element.query_list:
                yield name_of(q), q, element
                qi += 1


def query_kind(q) -> str:
    from ..query_api.query import JoinInputStream, StateInputStream
    if isinstance(q.input_stream, JoinInputStream):
        return "join"
    if isinstance(q.input_stream, StateInputStream):
        return "pattern"
    return "plain"


def window_handler(sis):
    from ..query_api.query import Window
    for h in getattr(sis, "stream_handlers", ()):
        if isinstance(h, Window):
            return h
    return None


def pattern_atoms(el) -> List:
    """Flat list of the stream/absent atoms of a state-element tree."""
    from ..query_api.query import (
        AbsentStreamStateElement,
        CountStateElement,
        EveryStateElement,
        LogicalStateElement,
        NextStateElement,
        StreamStateElement,
    )
    out: List = []

    def rec(e):
        if isinstance(e, (StreamStateElement, AbsentStreamStateElement)):
            out.append(e)
        elif isinstance(e, CountStateElement):
            rec(e.stream_state_element)
        elif isinstance(e, LogicalStateElement):
            rec(e.stream_state_element_1)
            rec(e.stream_state_element_2)
        elif isinstance(e, NextStateElement):
            rec(e.state_element)
            rec(e.next_state_element)
        elif isinstance(e, EveryStateElement):
            rec(e.state_element)

    rec(el)
    return out


def window_capacity(win, hint: int) -> int:
    """Resident-row capacity the planner would give this window: the
    first non-time integer parameter (length/lengthBatch/sort/... row
    counts), else the capacity hint time-based windows are built with."""
    if win is None:
        return BATCH_CAPACITY
    from ..query_api.expression import Constant
    for p in win.parameters:
        if isinstance(p, Constant) and p.type in ("INT", "LONG") and \
                not getattr(p, "is_time", False):
            return max(1, int(p.value))
    return hint


def join_window_hints(caps: Dict[str, int], default: int
                      ) -> Tuple[int, int]:
    """The rows a join's (left, right) `window.time` may hold, as
    `runtime._add_join_query` reads `@capacity`: a side's own key, else
    `window`, else the default hint."""
    both = caps.get("window", default)
    return (caps.get("window.left", both), caps.get("window.right", both))


def capacity_annotation(q, part) -> Dict[str, int]:
    """@capacity(keys=, slots=, window=) merged across the query and its
    partition (runtime._add_partition scans both); a join also says
    `window.left` / `window.right`, a side's bound of its own
    (runtime._add_join_query)."""
    out: Dict[str, int] = {}
    anns = list(q.annotations)
    if part is not None:
        anns += list(part.annotations)
        for pq in part.query_list:
            anns += list(pq.annotations)
    for ann in anns:
        if ann.name.lower() == "capacity":
            for k in ("keys", "slots", "window", "window.left",
                      "window.right"):
                v = ann.element(k)
                if v is not None:
                    out[k] = int(v)
    return out


def row_bytes(sdef) -> int:
    """Bytes per buffered window row: payload columns (device dtypes via
    event.dtype_of — STRING is an interned i32, DOUBLE an f32 on TPU)
    plus the fixed Buffer bookkeeping columns."""
    import numpy as np

    from . import event as ev
    n = ROW_OVERHEAD
    for a in getattr(sdef, "attribute_list", ()):
        try:
            n += int(np.dtype(ev.dtype_of(a.type)).itemsize)
        except Exception:  # noqa: BLE001 — OBJECT columns etc.
            n += 8
    return n


def query_state_components(app, q, kind: str, part,
                           caps: Dict[str, int],
                           keys: int) -> Dict[str, int]:
    """Per-component shape×dtype estimate of the device state the
    planner would allocate for ONE query (windows and NFA slot blocks;
    group-by slabs are bounded and small by comparison).  Empty dict
    when the query holds no estimable state."""
    defs = app.stream_definition_map

    def stream_def(sid):
        return defs.get(sid) or app.window_definition_map.get(sid)

    hint = caps.get(
        "window",
        PARTITION_WINDOW_HINT if part is not None else WINDOW_HINT)
    if kind == "plain":
        win = window_handler(q.input_stream)
        if win is None:
            return {}
        rows = window_capacity(win, hint)
        per_key = rows * row_bytes(stream_def(q.input_stream.stream_id))
        return {"window": per_key * (keys if part is not None else 1)}
    if kind == "join":
        out: Dict[str, int] = {}

        def _kind_of(sid):
            if sid in app.aggregation_definition_map:
                return "aggregation"
            if sid in app.window_definition_map:
                return "named_window"
            if sid in app.table_definition_map:
                return "table"
            return "stream"

        def _probe_attrs(sid):
            d = app.table_definition_map.get(sid)
            return table_probe_attrs_of(d) if d is not None else []

        try:
            fp_mode, _, _ = join_fastpath(q.input_stream, _kind_of,
                                          _probe_attrs)
        except Exception:  # noqa: BLE001 — estimator must not throw
            fp_mode = None
        # bucketed sides carry one extra i32 key-slot column per row
        extra = 4 if fp_mode == "bucket" else 0
        hints = join_window_hints(caps, WINDOW_HINT)
        for side, sis, hint in (
                ("join.left", q.input_stream.left_input_stream, hints[0]),
                ("join.right", q.input_stream.right_input_stream,
                 hints[1])):
            win = window_handler(sis)
            if win is not None:
                out[side] = window_capacity(win, hint) * \
                    (row_bytes(stream_def(sis.stream_id)) + extra)
        return out
    # pattern: per-key NFA slot block — `slots` pending matches per key,
    # each capturing one row per pattern state
    atoms = pattern_atoms(q.input_stream.state_element)
    slots = caps.get("slots", NFA_SLOTS)
    per_state = max(
        (row_bytes(stream_def(a.basic_single_input_stream.stream_id))
         for a in atoms), default=ROW_OVERHEAD)
    return {"pattern_slots": (keys if part is not None else 1) * slots *
            max(1, len(atoms)) * per_state}


def static_state_components(app, mesh_devices: int = 0,
                            merged: bool = True
                            ) -> Dict[str, Dict[str, int]]:
    """{owner: {component: bytes}} static state estimate for every query
    of a parsed (unplanned) app — THE shared MEM001/deploy-gate numbers.
    Pure AST walk; never plans, traces, or allocates.

    When the multi-query optimizer would share a window buffer between
    co-resident queries (`merge_plan` shared units), the shared buffer
    is counted ONCE under the ``merged:<group>`` owner and the member
    queries keep only their exclusive bytes — the same no-double-count
    contract the live accounting (observability/memory.py) honors.
    Pass ``merged=False`` (or a multi-device mesh) to estimate the
    unmerged layout."""
    out: Dict[str, Dict[str, int]] = {}
    for name, q, part in iter_named_queries(app):
        kind = query_kind(q)
        caps = capacity_annotation(q, part)
        keys = caps.get("keys", PARTITION_KEYS)
        comps = query_state_components(app, q, kind, part, caps, keys)
        if serve_enabled(app, q):
            # serving emission ring (serving/ring.py): device-resident,
            # so it counts against the same MEM001/deploy-gate budget
            # window buffers do
            comps = dict(comps)
            comps["serve_ring"] = serve_ring_bytes(app, q, kind, part,
                                                   caps)
        if comps:
            out[name] = comps
    if merged and mesh_devices <= 1:
        try:
            plan = merge_plan(app, mesh_devices)
        except Exception:  # noqa: BLE001 — estimator must not throw
            plan = {"groups": []}
        for g in plan["groups"]:
            shared_total = 0
            for u in g["units"]:
                if u["mode"] != "shared":
                    continue
                lead = u["members"][0]
                shared_total += out.get(lead, {}).get("window", 0)
                for m in u["members"]:
                    comps = out.get(m)
                    if comps and "window" in comps:
                        comps = dict(comps)
                        del comps["window"]
                        if comps:
                            out[m] = comps
                        else:
                            del out[m]
            if shared_total:
                out[f"merged:{g['group']}"] = {
                    MERGE_SHARED_COMPONENT: shared_total}
    return out


def static_state_bytes(app) -> int:
    """Total static state estimate across the app's queries."""
    return sum(sum(c.values())
               for c in static_state_components(app).values())


# ---------------------------------------------------------------------------
# equi-join fast-path facts (shared by the join planner, lint JOIN002,
# and EXPLAIN — one implementation, one set of reason strings, so lint
# prints exactly the condition the wiring tested)
# ---------------------------------------------------------------------------

def join_equi_pairs(jis) -> List[Tuple[object, object, object]]:
    """Top-level `==` conjuncts of a join ON-condition comparing one
    side-qualified attribute from each side: [(Compare node, left
    Variable, right Variable)], the left side's variable first whatever
    the written order.  The same shape analysis/typeflow._equi_conjuncts
    reports — kept AST-only so the planner can run it pre-compile."""
    from ..query_api import expression as ex
    on = getattr(jis, "on_compare", None)
    if on is None:
        return []
    ls, rs = jis.left_input_stream, jis.right_input_stream
    left_keys = {ls.stream_reference_id or ls.stream_id, ls.stream_id}
    right_keys = {rs.stream_reference_id or rs.stream_id, rs.stream_id}

    def conjuncts(e):
        if isinstance(e, ex.And):
            yield from conjuncts(e.left)
            yield from conjuncts(e.right)
        else:
            yield e

    def side_of(v):
        if v.stream_id in left_keys:
            return "left"
        if v.stream_id in right_keys:
            return "right"
        return None

    out: List[Tuple[object, object, object]] = []
    for c in conjuncts(on):
        if not isinstance(c, ex.Compare) or c.operator != "==":
            continue
        if not (isinstance(c.left, ex.Variable) and
                isinstance(c.right, ex.Variable)):
            continue
        sides = (side_of(c.left), side_of(c.right))
        if sides == ("left", "right"):
            out.append((c, c.left, c.right))
        elif sides == ("right", "left"):
            out.append((c, c.right, c.left))
    return out


# lane width floor for the bucketed join probe; host occupancy tracking
# grows it in power-of-two steps (core/join.py JoinKeyTracker)
JOIN_LANE_K_MIN = 8


def join_fastpath(jis, side_kind, table_probe_attrs=None
                  ) -> Tuple[Optional[str], List, Optional[str]]:
    """Equi-join fast-path decision: (mode, pairs, reason).

    mode 'bucket' — both sides are stream windows: key slots ride the
    window buffers and the step probes only same-bucket pairs.
    mode 'table' — one side is an indexed table and the trigger side is
    a windowless stream: the table's AttributeIndex/primary-key hash
    answers candidates host-side.  mode None + reason — an equality
    conjunct exists but the fast path cannot apply (lint JOIN002 WARNs
    with exactly this string).  mode None + reason None — no equality
    conjunct (nothing to accelerate, JOIN002 stays silent).

    `side_kind(sid)` -> 'stream'|'table'|'named_window'|'aggregation';
    `table_probe_attrs(sid)` -> attribute names probe-able through a
    single-column @PrimaryKey or an @Index (table mode only)."""
    pairs = join_equi_pairs(jis)
    if not pairs:
        return None, [], None
    sides = {}
    for label, sis in (("left", jis.left_input_stream),
                       ("right", jis.right_input_stream)):
        sides[label] = (sis, side_kind(sis.stream_id))
    kinds = {label: k for label, (_, k) in sides.items()}
    for label, (sis, kind) in sides.items():
        if kind in ("named_window", "aggregation"):
            return None, pairs, (
                f"{label} side {sis.stream_id!r} is a {kind} — its rows "
                f"are probed from a shared buffer the join cannot carry "
                f"key slots through")
    if kinds["left"] == "stream" and kinds["right"] == "stream":
        from ..query_api.query import Filter
        for label, (sis, _) in sides.items():
            if any(isinstance(h, Filter) for h in sis.stream_handlers):
                return None, pairs, (
                    f"{label} side {sis.stream_id!r} has a stream filter "
                    f"— host key-retention tracking would under-count "
                    f"the window and could free live key buckets")
        return "bucket", pairs, None
    # stream-table: the stream side triggers, the table answers probes
    t_label = "left" if kinds["left"] == "table" else "right"
    s_label = "right" if t_label == "left" else "left"
    t_sis = sides[t_label][0]
    s_sis = sides[s_label][0]
    if kinds[s_label] != "stream":
        return None, pairs, "cannot join two table-like sides"
    if window_handler(s_sis) is not None:
        return None, pairs, (
            f"windowed stream side {s_sis.stream_id!r} joining table "
            f"{t_sis.stream_id!r} — buffered rows cannot re-probe the "
            f"table index at step time")
    probe_attrs = set(table_probe_attrs(t_sis.stream_id)) \
        if table_probe_attrs is not None else set()
    usable = []
    for c, lv, rv in pairs:
        t_var = lv if t_label == "left" else rv
        if t_var.attribute_name in probe_attrs:
            usable.append((c, lv, rv))
    if not usable:
        attrs = ", ".join(
            repr((lv if t_label == "left" else rv).attribute_name)
            for _, lv, rv in pairs)
        return None, pairs, (
            f"table {t_sis.stream_id!r} has no single-column @PrimaryKey "
            f"or @Index on join key {attrs} — equality probes stay "
            f"linear scans")
    return "table", usable, None


def table_probe_attrs_of(tdef) -> List[str]:
    """Attribute names of a TableDefinition probe-able by hash: a
    single-column @PrimaryKey plus every @Index attribute (reference:
    EventHolderPasser.java builds exactly these maps)."""
    out: List[str] = []
    pk = tdef.get_annotation("PrimaryKey")
    if pk is not None:
        names = pk.positional_elements()
        if len(names) == 1:
            out.append(names[0])
    idx = tdef.get_annotation("Index")
    if idx is not None:
        out.extend(n for n in idx.positional_elements() if n not in out)
    return out


# ---------------------------------------------------------------------------
# multi-query merge facts (whole-app optimizer, siddhi_tpu/optimizer).
# ONE implementation decides which co-resident queries share a merged
# dispatch: the runtime optimizer pass, lint MQO001, and EXPLAIN's
# `merge` node all read the plan built here, so the reason lint prints
# is exactly the one the wiring applied.
# ---------------------------------------------------------------------------

# component label the shared window buffer of a merge group is reported
# under (observability/memory + the static estimator below): bytes held
# ONCE for the whole group, never per member
MERGE_SHARED_COMPONENT = "window[shared]"


def _expr_fp(e) -> str:
    """Stable structural fingerprint of a query_api expression tree —
    two filters with this fingerprint compile to the identical device
    program, which is the merge pass's sharing precondition."""
    from ..query_api import expression as ex
    if e is None:
        return "-"
    if isinstance(e, ex.Constant):
        return f"c:{e.type}:{e.value!r}"
    if isinstance(e, ex.Variable):
        idx = "" if e.stream_index is None else f"[{e.stream_index}]"
        return f"v:{e.stream_id or ''}{idx}.{e.attribute_name}"
    if isinstance(e, ex.Compare):
        return f"({_expr_fp(e.left)}{e.operator}{_expr_fp(e.right)})"
    if isinstance(e, ex.Not):
        return f"not({_expr_fp(e.expression)})"
    if isinstance(e, ex.IsNull):
        if getattr(e, "expression", None) is not None:
            return f"isnull({_expr_fp(e.expression)})"
        return f"isnull({e.stream_id})"
    if isinstance(e, ex.In):
        return f"in({_expr_fp(e.expression)},{e.source_id})"
    if isinstance(e, ex.AttributeFunction):
        ns = f"{e.namespace}:" if e.namespace else ""
        args = ",".join(_expr_fp(p) for p in e.parameters)
        return f"f:{ns}{e.name}({args})"
    left = getattr(e, "left", None)
    right = getattr(e, "right", None)
    if left is not None and right is not None:
        return f"{type(e).__name__}({_expr_fp(left)},{_expr_fp(right)})"
    return type(e).__name__


def handler_fingerprints(sis) -> Tuple[Tuple[str, ...], str,
                                       Tuple[str, ...]]:
    """(pre-window chain, window, post-window chain) fingerprints of a
    SingleInputStream's handler chain.  Queries can only share one
    window buffer when the pre-chain AND window fingerprints agree —
    different pre-filters would admit different rows into the buffer."""
    from ..query_api.query import Filter, StreamFunction, Window
    pre: List[str] = []
    post: List[str] = []
    win = "-"
    seen = False
    for h in getattr(sis, "stream_handlers", ()):
        if isinstance(h, Window):
            ns = f"{h.namespace}:" if h.namespace else ""
            win = f"w:{ns}{h.name}(" + ",".join(
                _expr_fp(p) for p in h.parameters) + ")"
            seen = True
        elif isinstance(h, Filter):
            (post if seen else pre).append(f"filt:{_expr_fp(h.expression)}")
        elif isinstance(h, StreamFunction):
            ns = f"{h.namespace}:" if h.namespace else ""
            fp = f"fn:{ns}{h.name}(" + ",".join(
                _expr_fp(p) for p in h.parameters) + ")"
            (post if seen else pre).append(fp)
    return tuple(pre), win, tuple(post)


def async_enabled(app, q) -> bool:
    """@async on the app, the query, or any input stream definition —
    the ONE implementation runtime wiring (`_register`) and the
    merge planner share."""
    if app.get_annotation("async") is not None:
        return True
    if q.get_annotation("async") is not None:
        return True
    ist = q.input_stream
    sids = getattr(ist, "all_stream_ids", None) or \
        [getattr(ist, "stream_id", None)]
    for sid in sids:
        sdef = app.stream_definition_map.get(sid)
        if sdef is not None and sdef.get_annotation("async") is not None:
            return True
    return False


def pipeline_depth(app, q) -> int:
    """@pipeline(depth=k) on the query (wins) or @app:pipeline; 0 = off
    (shared by runtime `_register` and the merge planner)."""
    ann = q.get_annotation("pipeline")
    if ann is None:
        ann = app.get_annotation("app:pipeline")
    if ann is None:
        return 0
    return max(1, int(ann.element("depth", 1) or 1))


def fuse_depth(app, q) -> int:
    """@fuse(batches=K) on the query, any input stream definition, or
    @app:fuse; 0 = off (shared by runtime `_register`, lint's
    `fuse_requested`, and the merge planner)."""
    ann = q.get_annotation("fuse")
    if ann is None:
        ist = q.input_stream
        sids = getattr(ist, "all_stream_ids", None) or \
            [getattr(ist, "stream_id", None)]
        for sid in sids:
            sdef = app.stream_definition_map.get(sid)
            if sdef is not None and \
                    sdef.get_annotation("fuse") is not None:
                ann = sdef.get_annotation("fuse")
                break
    if ann is None:
        ann = app.get_annotation("app:fuse")
    if ann is None:
        return 0
    k = ann.element("batches", ann.element(None, 8)) or 8
    return max(1, int(k))


def mesh_shards(app) -> Optional[int]:
    """@app:mesh(shards='N'): the shard-mesh size the app asks for in its
    own text, or None when it says nothing.  Shared by the deploy path
    (`SiddhiManager.create_siddhi_app_runtime` builds the mesh from it)
    and lint PART002; a value that is not a whole number >= 1 is a
    deploy error, not a default."""
    ann = app.get_annotation("app:mesh")
    if ann is None:
        return None
    raw = ann.element("shards", ann.element(None))
    try:
        n = int(str(raw))
    except (TypeError, ValueError):
        n = 0
    if n < 1:
        from ..exceptions import SiddhiAppValidationError
        raise SiddhiAppValidationError(
            f"@app:mesh(shards={raw!r}): shards must be a whole number "
            f">= 1")
    return n


def serve_enabled(app, q) -> bool:
    """@serve on the query, any input stream definition, or @app:serve —
    the device-resident serving loop (siddhi_tpu/serving): emissions
    append to an on-device ring and the async drainer delivers them;
    the send path never fetches.  `enabled='false'` opts a query out of
    an app-wide @app:serve.  The ONE implementation runtime wiring
    (`_serve_enabled`), the merge planner, EXPLAIN, and lint SERVE001
    share.  (The `serving.enabled` config property enables serving at
    the runtime level without annotations — that path is resolved in
    runtime wiring, not here: plan facts stay pure AST.)"""
    ann = q.get_annotation("serve")
    if ann is None:
        ist = q.input_stream
        sids = getattr(ist, "all_stream_ids", None) or \
            [getattr(ist, "stream_id", None)]
        for sid in sids:
            sdef = app.stream_definition_map.get(sid)
            if sdef is not None and \
                    sdef.get_annotation("serve") is not None:
                ann = sdef.get_annotation("serve")
                break
    if ann is None:
        ann = app.get_annotation("app:serve")
    if ann is None:
        return False
    flag = str(ann.element("enabled", "true") or "true").lower()
    return flag not in ("false", "0", "no", "off")


def serve_ring_capacity(app, q) -> int:
    """@serve(ring.capacity=S) on the query (wins) or @app:serve; 0
    means "use the `serving.ring.capacity` config property / default"."""
    ann = q.get_annotation("serve")
    if ann is None:
        ann = app.get_annotation("app:serve")
    if ann is None:
        return 0
    try:
        return max(0, int(ann.element("ring.capacity", 0) or 0))
    except Exception:  # noqa: BLE001 — malformed element reads as unset
        return 0


def serve_ring_bytes(app, q, kind: str, part, caps: Dict[str, int]) -> int:
    """Static estimate of one query's serving emission ring
    (serving/ring.py): SERVE_RING_SLOTS stacked output blocks.  Output
    rows bound by the window/batch capacity; row width is ts i64 +
    kind i32 + valid bool + one device word per selected column."""
    hint = caps.get(
        "window",
        PARTITION_WINDOW_HINT if part is not None else WINDOW_HINT)
    if kind == "plain":
        rows = window_capacity(window_handler(q.input_stream), hint)
    else:
        rows = hint
    slots = serve_ring_capacity(app, q) or SERVE_RING_SLOTS
    ncols = max(1, len(q.selector.selection_list))
    return slots * rows * (12 + 1 + 8 * ncols)


def merge_decorations(app, q) -> Tuple:
    """The emission/dispatch decorations that must agree across a merge
    group: members of one dispatch share the demux path, so @async,
    @pipeline depth, @fuse K, and @serve cannot differ within a
    group."""
    return (async_enabled(app, q), pipeline_depth(app, q),
            fuse_depth(app, q), serve_enabled(app, q))


def merge_ineligibility(app, q, kind: str, part,
                        mesh_devices: int = 0) -> Optional[str]:
    """Why ONE query can never join any merge group (None = eligible).
    Static AST properties only — the runtime optimizer pass re-validates
    against the actual plan and demotes on any surprise."""
    if mesh_devices > 1:
        return (f"app deployed on a {mesh_devices}-device mesh — "
                f"sharded dispatch is not merged")
    if part is not None:
        return "partitioned query — per-key dispatch is not merged"
    if kind == "pattern":
        return "pattern/sequence NFA keeps its own per-stream steps"
    if kind == "join":
        return "join side steps keep their own dispatch"
    sid = q.input_stream.unique_stream_id
    if sid in getattr(app, "window_definition_map", {}):
        return ("named-window input is delivered by the window "
                "runtime, not a stream junction")
    win = window_handler(q.input_stream)
    if win is not None:
        from .window import WINDOW_TYPES
        full = (win.namespace + ":" if win.namespace else "") + win.name
        cls = WINDOW_TYPES.get(full)
        if cls is not None and getattr(cls, "needs_timer", False):
            return (f"timer-bearing window ({full}) — the device wake "
                    f"scalar cannot ride a merged dispatch")
        if win.name == "session" and len(win.parameters) >= 2:
            return ("session(gap, key) runs the keyed-window slab — "
                    "per-key dispatch is not merged")
    return None


def _in_table_deps(app, q) -> set:
    """Tables this query probes with the `in` operator (filters +
    selector expressions) — merge-relevant because an unmerged plan
    lets a query observe a co-resident query's SAME-BATCH table writes,
    which a merged dispatch (one table snapshot per dispatch) would
    relax; the planner demotes such probers instead of relaxing."""
    from ..query_api.expression import In, walk
    from ..query_api.query import Filter
    exprs = []
    for h in getattr(q.input_stream, "stream_handlers", ()):
        if isinstance(h, Filter):
            exprs.append(h.expression)
    sel = q.selector
    exprs += [oa.expression for oa in sel.selection_list]
    if sel.having_expression is not None:
        exprs.append(sel.having_expression)
    deps = set()
    for e in exprs:
        for node in walk(e):
            if isinstance(node, In):
                deps.add(node.source_id)
    return {d for d in deps if d in app.table_definition_map}


def merge_plan(app, mesh_devices: int = 0) -> Dict:
    """The whole-app merge decision, statically.

    Returns ``{"groups": [...], "reasons": {query: reason}}`` where each
    group is ``{"group", "stream", "members", "decorations", "units"}``
    and each unit is ``{"mode": "shared"|"solo", "members": [...]}``.
    A *shared* unit's members stage one window buffer and one group-slot
    space (identical pre-chain + window + group-by); *solo* units run
    their full per-query body inside the merged dispatch.  Every query
    in no group appears in ``reasons`` with the planner's exact
    ineligibility string — lint MQO001, EXPLAIN, and the runtime
    optimizer pass (siddhi_tpu/optimizer) all read THIS plan."""
    reasons: Dict[str, str] = {}
    eligible: List[Tuple[str, object, Tuple]] = []
    for name, q, part in iter_named_queries(app):
        kind = query_kind(q)
        why = merge_ineligibility(app, q, kind, part, mesh_devices)
        if why is not None:
            reasons[name] = why
            continue
        eligible.append((name, q, merge_decorations(app, q)))

    # dispatch groups: same stream + same @async/@pipeline/@fuse
    by_key: Dict[Tuple, List[Tuple[str, object]]] = {}
    order: List[Tuple] = []
    for name, q, deco in eligible:
        key = (q.input_stream.unique_stream_id, deco)
        if key not in by_key:
            by_key[key] = []
            order.append(key)
        by_key[key].append((name, q))

    groups: List[Dict] = []
    per_stream: Dict[str, int] = {}
    for key in order:
        sid, deco = key
        members = by_key[key]
        # exactness demotions: merging must stay BYTE-IDENTICAL per
        # query, so (a) a member inserting into the group's own input
        # stream keeps its own dispatch (the unmerged plan interleaves
        # the feedback recursion mid-fanout; a merged demux would
        # reorder what co-members' windows see), and (b) a member
        # probing a table a CO-MEMBER writes keeps its own dispatch
        # (unmerged, it observes same-batch writes; a merged dispatch
        # snapshots tables once)
        written = {q.output_stream.target_id: name
                   for name, q in members
                   if q.output_stream is not None and
                   q.output_stream.target_id in app.table_definition_map}
        demoted: List[Tuple[str, str]] = []
        for name, q in members:
            if q.output_stream is not None and \
                    q.output_stream.target_id == sid:
                demoted.append((name, (
                    f"inserts into its own input stream {sid!r} — "
                    f"merging would reorder the feedback loop the "
                    f"unmerged fan-out interleaves")))
                continue
            hit = sorted(t for t in _in_table_deps(app, q)
                         if written.get(t) not in (None, name))
            if hit:
                demoted.append((name, (
                    f"probes table {hit[0]!r} written by co-resident "
                    f"query {written[hit[0]]!r} — same-batch "
                    f"read-your-writes must stay exact")))
        if demoted:
            dropped = {n for n, _ in demoted}
            for name, why in demoted:
                reasons[name] = why
            members = [(n, q) for n, q in members if n not in dropped]
        if len(members) < 2:
            for name, _q in members:
                reasons[name] = (
                    f"no co-resident query shares stream {sid!r} and "
                    f"its @async/@pipeline/@fuse/@serve decorations")
            continue
        gi = per_stream.get(sid, 0)
        per_stream[sid] = gi + 1
        gid = f"{sid}#{gi}"
        # state-share units: identical pre-chain + window + group-by
        # (and window capacity) members reference ONE window buffer and
        # ONE group-slot space; windowless members stay solo (their
        # window state is a scalar seq counter — nothing to share)
        units: List[Dict] = []
        shared: Dict[Tuple, List[str]] = {}
        shared_order: List[Tuple] = []
        for name, q in members:
            pre, win, _post = handler_fingerprints(q.input_stream)
            if win == "-":
                units.append({"mode": "solo", "members": [name]})
                continue
            caps = capacity_annotation(q, None)
            gby = tuple(_expr_fp(v) for v in q.selector.group_by_list)
            skey = (pre, win, gby, caps.get("window", 0))
            if skey not in shared:
                shared[skey] = []
                shared_order.append(skey)
                units.append({"mode": "solo", "members": [],
                              "_skey": skey})
            shared[skey].append(name)
        resolved: List[Dict] = []
        for u in units:
            skey = u.pop("_skey", None)
            if skey is None:
                resolved.append(u)
                continue
            names = shared[skey]
            resolved.append({
                "mode": "shared" if len(names) >= 2 else "solo",
                "members": names})
        groups.append({
            "group": gid, "stream": sid,
            "members": [n for n, _ in members],
            "decorations": {"async": bool(deco[0]),
                            "pipeline": int(deco[1]),
                            "fuse": int(deco[2]),
                            "serve": bool(deco[3])},
            "units": resolved,
        })
    return {"groups": groups, "reasons": reasons}


def merge_facts(qr) -> Dict:
    """Per-query merge fact for EXPLAIN and the audit fingerprint.

    ``{"merged": True, "group", "owner", "mode", "members",
    "group_dispatch_programs": 1}`` for a merged member;
    ``{"merged": False, "reason": ...}`` otherwise.  Attribute reads
    only — safe on diagnostic paths."""
    mg = qr._merged
    if mg is not None:
        return {
            "merged": True,
            "group": mg.group,
            "owner": mg.name,
            "mode": mg.mode_of(qr),
            "members": [m.name for m in mg.members],
            "group_dispatch_programs": 1,
        }
    why = qr._merge_excluded
    if why is not None:
        return {"merged": False, "reason": why}
    return {"merged": False}


def format_component_bytes(comps: Dict[str, int],
                           limit: int = 6) -> str:
    """Human-facing component breakdown, largest first — the SAME string
    shape in lint MEM001 findings and AdmissionDeniedError messages, so
    an operator can line the two up by eye."""
    items: List[Tuple[str, int]] = sorted(
        comps.items(), key=lambda kv: (-kv[1], kv[0]))
    parts = [f"{k}={v / (1024 * 1024):.1f} MiB" for k, v in items[:limit]]
    if len(items) > limit:
        parts.append(f"... +{len(items) - limit} more")
    return ", ".join(parts)
