"""Per-query plan facts for the static analyzer.

Two builders produce the same `QueryFacts` shape:

- `facts_from_app(app)` — pure AST walk plus a *static mini-planner*
  that predicts the facts the real planner would compute (window
  processor class and its `needs_timer`, key/slot capacities, the
  emission-cap sentinel, shape×dtype state-byte estimates) without
  constructing a runtime or touching jax.  Fusion-exclusion reasons are
  NOT re-derived: a shim `planned` carrying the statically-known
  properties is fed through the real `core.fusion.ineligible_reason`,
  so lint reports the exact string the wiring would log at first
  dispatch.

- `facts_from_runtime(rt)` — reads the *actual* planned-query
  dataclasses of a live SiddhiAppRuntime: `describe()` plan facts,
  `core.plan_facts.fusion_exclusion`, and the metadata-only
  `observability.memory` accounting.  Attribute and shape/dtype reads
  only — analysis never executes, traces, or fetches (the lint guard
  test monkeypatches `jax.jit`/`jax.device_get` over a full run).

Query naming mirrors `SiddhiAppRuntime._query_name` exactly (`@info`
name, else `query<i>` numbered across top-level queries and partition
bodies), so findings join against explain/metrics/healthz by name.
"""
from __future__ import annotations

import dataclasses
import types
from typing import Any, Dict, List, Optional, Tuple

from ..query_api.app import SiddhiApp
from ..query_api.query import (
    AbsentStreamStateElement,
    Partition,
    Query,
    RangePartitionType,
    Window,
)

# the static mini-planner's capacity mirrors and AST helpers live in
# core/plan_facts.py so the admission deploy gate shares the EXACT
# implementation (one estimate, one component breakdown — no drift);
# the underscore aliases are this module's historical public names
from ..core.plan_facts import (  # noqa: F401  (re-exported API)
    BATCH_CAPACITY as _BATCH_CAPACITY,
    NFA_SLOTS as _NFA_SLOTS,
    PARTITION_KEYS as _PARTITION_KEYS,
    PARTITION_WINDOW_HINT as _PARTITION_WINDOW_HINT,
    ROW_OVERHEAD as _ROW_OVERHEAD,
    WINDOW_HINT as _WINDOW_HINT,
    capacity_annotation,
    iter_named_queries,
    join_window_hints,
    pattern_atoms,
    query_kind,
    query_state_components,
    row_bytes as _row_bytes,
    window_capacity,
    window_handler,
)


@dataclasses.dataclass
class QueryFacts:
    """What the analyzer knows about one query, from either builder."""

    name: str
    query: Query
    kind: str                           # plain | pattern | join
    origin: str = "static"              # static | planned
    partition: Optional[Partition] = None
    needs_timer: bool = False
    keyed_window: bool = False
    fuse_requested: int = 0
    fusion_exclusion: Optional[str] = None
    # rendered emission cap (None = uncapped / capacity-bounded)
    emission_cap: Optional[int] = None
    emission_cap_explicit: bool = False
    # per-query device state, bytes (shape×dtype arithmetic), with the
    # per-component breakdown MEM001 and the admission deploy gate both
    # cite (static: plan_facts estimator; runtime: measured accounting)
    state_bytes: Optional[int] = None
    state_components: Optional[Dict[str, int]] = None
    state_bytes_origin: str = "estimated"   # estimated | measured
    key_capacity: int = 1
    nfa_slots: int = _NFA_SLOTS
    # join sides: (left rows, right rows) worst-case resident window rows
    join_side_rows: Optional[Tuple[int, int]] = None

    def pos(self) -> Optional[Tuple[int, int]]:
        return getattr(self.query, "pos", None)


@dataclasses.dataclass
class AnalysisContext:
    """Everything a rule may look at."""

    app: SiddhiApp
    queries: List[QueryFacts]
    config: Any = None                  # registry.LintConfig
    source_name: str = "<app>"
    runtime: Any = None                 # live SiddhiAppRuntime, or None


# ---------------------------------------------------------------------------
# shared AST helpers (used by facts builders AND rules)
# ---------------------------------------------------------------------------

def window_needs_timer(win: Optional[Window]) -> bool:
    """needs_timer of the processor class the planner would pick —
    resolved from the live WINDOW_TYPES registry, never re-listed here."""
    if win is None:
        return False
    from ..core.window import WINDOW_TYPES
    full = (win.namespace + ":" if win.namespace else "") + win.name
    cls = WINDOW_TYPES.get(full)
    return bool(getattr(cls, "needs_timer", False)) if cls else False


def fuse_requested(app: SiddhiApp, q: Query) -> int:
    """@fuse on the query, any input stream definition, or @app:fuse.
    Returns K (0 = off).  Delegates to core.plan_facts.fuse_depth — the
    one implementation runtime wiring and the merge planner also use."""
    from ..core.plan_facts import fuse_depth
    return fuse_depth(app, q)


def emit_annotation_rows(q: Query) -> Optional[int]:
    ann = q.get_annotation("emit")
    if ann is None:
        return None
    v = ann.element("rows")
    return int(v) if v is not None else None


# ---------------------------------------------------------------------------
# static path
# ---------------------------------------------------------------------------

def _static_exclusion(app: SiddhiApp, q: Query, kind: str,
                      part: Optional[Partition],
                      needs_timer: bool, keyed: bool) -> Optional[str]:
    """Feed statically-known plan properties through the REAL
    core.fusion.ineligible_reason via a shim `planned`, so the string
    lint prints is the one the wiring would log.  Mesh sharding is a
    deploy-time property (unknowable from source), so the static path
    assumes unsharded — the runtime path reports the sharded reasons."""
    from ..core import fusion
    ist = q.input_stream
    present = object()      # stands in for "this step/body exists"
    if kind == "plain":
        range_part = part is not None and any(
            isinstance(pt, RangePartitionType)
            for pt in part.partition_type_map.values())
        planned = types.SimpleNamespace(
            needs_timer=needs_timer, keyed_window=keyed,
            partition_key_fn=present if range_part else None,
            raw_step=present)
    elif kind == "pattern":
        has_absent = any(
            isinstance(a, AbsentStreamStateElement)
            for a in pattern_atoms(ist.state_element))
        planned = types.SimpleNamespace(
            timer_step=present if has_absent else None,
            partition_positions={"_": [0]} if part is not None else None,
            mesh=None, step_bodies=present)
    else:
        planned = types.SimpleNamespace(
            needs_timer=needs_timer,
            step_left=present, raw_left=present,
            step_right=present, raw_right=present)
    try:
        return fusion.ineligible_reason(
            types.SimpleNamespace(planned=planned), kind)
    except Exception:  # noqa: BLE001 — a shim gap must not kill lint
        return None


def facts_from_app(app: SiddhiApp) -> List[QueryFacts]:
    # merge-aware static estimate (core/plan_facts): a window buffer the
    # multi-query optimizer will share across a group appears ONCE under
    # its `merged:<group>` owner, so per-query facts carry exclusive
    # bytes only and totals (ADM001) agree with the deploy gate
    from ..core.plan_facts import static_state_components
    try:
        merged_comps = static_state_components(app)
    except Exception:  # noqa: BLE001 — estimator must not kill lint
        merged_comps = None
    out: List[QueryFacts] = []
    for name, q, part in iter_named_queries(app):
        kind = query_kind(q)
        caps = capacity_annotation(q, part)
        keys = caps.get("keys", _PARTITION_KEYS)
        win = None
        if kind == "plain":
            win = window_handler(q.input_stream)
            needs_timer = window_needs_timer(win)
            session_keyed = win is not None and win.name == "session" \
                and len(win.parameters) >= 2
            keyed = session_keyed or (part is not None and win is not None)
        elif kind == "join":
            needs_timer = any(
                window_needs_timer(window_handler(s))
                for s in (q.input_stream.left_input_stream,
                          q.input_stream.right_input_stream))
            keyed = False
        else:
            needs_timer = any(
                isinstance(a, AbsentStreamStateElement)
                for a in pattern_atoms(q.input_stream.state_element))
            keyed = False

        from ..core.plan_facts import UNCAPPED_SENTINEL, render_cap
        emit_rows = emit_annotation_rows(q)
        cap = None
        explicit = emit_rows is not None
        if kind == "pattern":
            cap = render_cap(
                emit_rows if explicit
                else (8 if part is not None else UNCAPPED_SENTINEL))
        elif kind == "join":
            cap = render_cap(emit_rows) if explicit else None

        k = fuse_requested(app, q)
        # the ONE static estimator shared with the admission deploy gate
        # (core/plan_facts.query_state_components; merge-aware when the
        # app-level pass computed — the merged view drops a shared
        # window from members and reports it under the group owner)
        if merged_comps is not None:
            comps = merged_comps.get(name, {})
        else:
            comps = query_state_components(app, q, kind, part, caps,
                                           keys)
        f = QueryFacts(
            name=name, query=q, kind=kind, origin="static",
            partition=part, needs_timer=needs_timer, keyed_window=keyed,
            fuse_requested=k,
            fusion_exclusion=_static_exclusion(
                app, q, kind, part, needs_timer, keyed) if k else None,
            emission_cap=cap, emission_cap_explicit=explicit,
            state_bytes=sum(comps.values()) if comps else None,
            state_components=comps or None,
            state_bytes_origin="estimated",
            key_capacity=keys if (part is not None or keyed) else 1,
            nfa_slots=caps.get("slots", _NFA_SLOTS),
        )
        if kind == "join":
            defs = app.stream_definition_map
            sides = []
            # a side's bound: @capacity(window.left / window.right /
            # window), as the runtime's join wiring reads it
            for sis, hint in zip((q.input_stream.left_input_stream,
                                  q.input_stream.right_input_stream),
                                 join_window_hints(caps, _WINDOW_HINT)):
                w = window_handler(sis)
                sides.append(window_capacity(w, hint)
                             if w is not None else _BATCH_CAPACITY)
            f.join_side_rows = (sides[0], sides[1])
        out.append(f)
    return out


# ---------------------------------------------------------------------------
# planned (live runtime) path
# ---------------------------------------------------------------------------

def facts_from_runtime(rt) -> List[QueryFacts]:
    """QueryFacts from a live runtime's compiled plans.  Reads
    `describe()` dicts, plan attributes, and metadata-only state-byte
    accounting — never executes, traces, or fetches device data."""
    from ..core.plan_facts import fusion_exclusion, render_cap
    from ..observability.memory import query_component_bytes

    static_by_name = {f.name: f for f in facts_from_app(rt.app)}
    out: List[QueryFacts] = []
    for name, qr in sorted(rt.query_runtimes.items()):
        q = qr._query_ast
        kind = qr._kind
        p = qr.planned
        try:
            desc = p.describe()
        except Exception:  # noqa: BLE001 — diagnostics must not throw
            desc = {}
        comp = query_component_bytes(qr)
        sf = static_by_name.get(name)
        fb = qr._fuse
        f = QueryFacts(
            name=name,
            query=q if q is not None else Query(),
            kind=kind, origin="planned",
            partition=sf.partition if sf is not None else None,
            needs_timer=bool(desc.get("needs_timer", p.needs_timer)),
            keyed_window=bool(p.keyed_window),
            fuse_requested=(fb.k if fb is not None
                            else qr._fuse_requested),
            fusion_exclusion=fusion_exclusion(qr),
            emission_cap=render_cap(p.compact_rows),
            emission_cap_explicit=bool(p.emit_explicit),
            state_bytes=sum(comp.values()) if comp else None,
            state_components=dict(comp) if comp else None,
            state_bytes_origin="measured",
            key_capacity=int(p.key_capacity or 1),
            nfa_slots=int(p.slots or _NFA_SLOTS) if kind == "pattern"
            else _NFA_SLOTS,
        )
        if kind == "join" and any(p.ring_caps):
            # the planned windows' own bounds
            f.join_side_rows = tuple(p.ring_caps)
        elif sf is not None and sf.join_side_rows is not None:
            f.join_side_rows = sf.join_side_rows
        out.append(f)
    return out
