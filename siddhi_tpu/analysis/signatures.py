"""Canonical step signatures: argument ShapeDtypeStructs WITHOUT traffic.

`steputil.jit_step` captures the argument avals of the last real trace,
and EXPLAIN re-lowers from them — which means cost analysis is only
available after a query has served traffic.  The plan auditor
(analysis/audit.py) must grade a compiled plan in CI *before* anything
runs, so this module synthesizes the same ShapeDtypeStructs from plan
metadata alone: state leaves come from the runtime's allocated state
pytree (shape/dtype reads, never fetched), batch axes from the plan's
capacities, and scalar/now/selection columns from the exact layouts the
runtime paths build (`core/runtime.py` process_staged variants — each
synthesizer cites its path).

The synthesized signature is CANONICAL, not "whatever the last batch
happened to be": full batch of `batch_capacity` rows, and for keyed/NFA
layouts a deterministic grouping of G = min(key_capacity, B) key rows
of E = B // G events each.  Canonical signatures make fingerprints
comparable across commits — the auditor diffs like against like — and
`tests/test_audit.py` asserts the synthesized plain-step signature is
byte-identical to the signature real traffic traces.

Everything here is metadata arithmetic: no jax dispatch, no transfer,
no trace (lowering happens in the consumer, under RECOMPILES.suppress).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np


def _sds(shape, dtype):
    import jax
    return jax.ShapeDtypeStruct(tuple(shape), np.dtype(dtype))


def _tree_specs(tree):
    """ShapeDtypeStruct twin of an allocated state pytree (metadata
    reads only)."""
    import jax
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(tuple(x.shape), np.dtype(x.dtype)),
        tree)


def _table_specs(app, deps) -> Tuple:
    """Spec twin of `SiddhiAppRuntime.in_probe_tables` snapshots:
    (first column, validity) per dep."""
    out = []
    for d in deps or ():
        t = app.tables[d]
        out.append((_sds(t.cols[0].shape, t.cols[0].dtype),
                    _sds(t.valid.shape, t.valid.dtype)))
    return tuple(out)


def _canonical_grouping(key_capacity: int, B: int) -> Tuple[int, int]:
    """Deterministic [G, E] key grouping for keyed/NFA layouts: G keys
    of E events each covering one full batch (G=1 ⇒ the single-key
    steady state non-partitioned patterns run)."""
    G = max(1, min(int(key_capacity or 1), B))
    E = max(1, B // G)
    return G, E


# np staging dtypes (event.np_dtype) — pattern steps receive the raw
# host staging columns; plain/join steps receive device-schema columns
def _staging_cols(schema, B: int) -> Tuple:
    from ..core import event as ev
    return tuple(_sds((B,), ev.np_dtype(t)) for t in schema.types)


def _device_cols(schema, B: int) -> Tuple:
    return tuple(_sds((B,), d) for d in schema.dtypes)


# ---------------------------------------------------------------------------
# per-kind synthesizers
# ---------------------------------------------------------------------------

def _plain_specs(qr) -> Dict[str, Tuple]:
    """QueryRuntime.process_staged / _process_keyed argument layouts."""
    p = qr.planned
    B = int(p.batch_capacity)
    state = _tree_specs(qr.state)
    ts = _sds((B,), np.int64)
    kind = _sds((B,), np.int32)
    valid = _sds((B,), np.bool_)
    cols = _device_cols(p.in_schema, B)
    gslot = _sds((B,), np.int32)
    now = _sds((), np.int64)
    in_tabs = _table_specs(qr.app, p.in_deps)
    if p.keyed_window:
        G, E = _canonical_grouping(p.key_capacity, B)
        key_idx = _sds((G,), np.int32)
        sel = _sds((G, E), np.int32)
        return {"step": (state, ts, kind, valid, cols, gslot, key_idx,
                         sel, now, in_tabs)}
    pslots = tuple(_sds((B,), np.int32) for _ in p.pair_allocs)
    return {"step": (state, ts, kind, valid, cols, gslot, now, in_tabs,
                     pslots)}


def _pattern_specs(qr) -> Dict[str, Tuple]:
    """PatternQueryRuntime.process_staged argument layouts, one entry
    per compiled step variant (scan / dense slice / block / sharded /
    timer); timestamps ride the wire of `core.event.encode_ts`."""
    from ..core.plan_facts import BATCH_CAPACITY
    p = qr.planned
    B = BATCH_CAPACITY
    pstate, sel_state = (_tree_specs(qr.state[0]),
                         _tree_specs(qr.state[1]))
    now = _sds((), np.int64)
    in_tabs = _table_specs(qr.app, p.exec.in_deps)
    sharded = p.mesh is not None
    if p.partition_positions or sharded:
        G, E = _canonical_grouping(p.key_capacity, B)
    else:
        G, E = 1, B
    key_idx = _sds((G,), np.int32)
    sel = _sds((G, E), np.int32)
    # the one-chip scan programs take the columns and the ts delta in
    # `sel`'s order, flat [G * E] (runtime._group_columns); the block
    # step and the sharded one take the staged [B] batch
    n = G * E if p.grouped_input else B
    out: Dict[str, Tuple] = {}
    for sid in p.spec.stream_ids:
        schema = p.in_schemas[sid]
        raw_cols = _staging_cols(schema, n)
        # the steady specialisation: (base i64 scalar, delta i32 [n])
        ts = (_sds((), np.int64), _sds((n,), np.int32))
        out[f"step[{sid}]"] = (pstate, sel_state, raw_cols, *ts,
                               sel, key_idx, now, in_tabs)
        if p.dense_steps is not None and sid in p.dense_steps:
            # contiguous-slot fast path takes a scalar key_lo
            out[f"dense_step[{sid}]"] = (
                pstate, sel_state, raw_cols, *ts, sel,
                _sds((), np.int32), now, in_tabs)
    if p.timer_step is not None:
        out["timer_step"] = (pstate, sel_state, now, in_tabs)
    return out


def _join_side_other(qr, is_left: bool) -> Optional[Tuple]:
    """Spec twin of JoinQueryRuntime._other_table: live table / named
    window buffer metadata, or the (1,)-dummy for stream sides."""
    p = qr.planned
    other = p.right if is_left else p.left
    app = qr.app
    if other.is_aggregation:
        return None                 # aggregation view: duration-dependent
    if other.is_named_window:
        nw = app.named_windows[other.stream_id]
        buf = nw.wproc.current_buffer(nw.state)
        return (tuple(_sds(c.shape, c.dtype) for c in buf.cols),
                _sds(buf.ts.shape, buf.ts.dtype),
                _sds(buf.alive.shape, buf.alive.dtype))
    if other.is_table:
        t = app.tables[other.stream_id]
        return (tuple(_sds(c.shape, c.dtype) for c in t.cols),
                _sds(t.ts.shape, t.ts.dtype),
                _sds(t.valid.shape, t.valid.dtype))
    f1 = _sds((1,), np.float32)     # jnp.zeros((1,)) default dtype is f32
    return (f1, f1, f1)


def _join_specs(qr) -> Dict[str, Tuple]:
    """JoinQueryRuntime.process_staged argument layout per side."""
    p = qr.planned
    B = int(p.batch_capacity)
    state = _tree_specs(qr.state)
    now = _sds((), np.int64)
    out: Dict[str, Tuple] = {}
    for role, is_left, side, step in (("step[left]", True, p.left,
                                       p.step_left),
                                      ("step[right]", False, p.right,
                                       p.step_right)):
        if step is None:
            continue
        other = _join_side_other(qr, is_left)
        if other is None:
            continue
        args = [state, _sds((B,), np.int64), _sds((B,), np.int32),
                _sds((B,), np.bool_), _device_cols(side.schema, B),
                _sds((B,), np.int32)]
        # equi-join fast-path probe arg (core/join.py): bucket slots or
        # host table candidates ride between gslot and the other-side
        # snapshot
        if p.fastpath == "bucket":
            args.append(_sds((B,), np.int32))
        elif p.fastpath == "table":
            tid = (p.left if p.table_is_left else p.right).stream_id
            t = qr.app.tables[tid]
            w = (t.indexes[p.table_pos].lanes.shape[1]
                 if p.table_pos in t.indexes else 1)
            args.append((_sds((B, w), np.int32), _sds((B, w), np.bool_)))
        args += [other, now]
        out[role] = tuple(args)
    return out


def synthesize(qr, kind: str) -> Dict[str, Tuple]:
    """{step role: argspec pytree} for every compiled step variant of a
    query runtime the auditor can grade statically.  Roles match
    `observability.explain._steps_of` naming so fingerprints, EXPLAIN
    and recompile owners line up.  Unsupported variants are simply
    absent (the auditor reports them unavailable, never guesses)."""
    try:
        if kind == "pattern":
            return _pattern_specs(qr)
        if kind == "join":
            return _join_specs(qr)
        return _plain_specs(qr)
    except Exception:  # noqa: BLE001 — diagnostics must not throw
        return {}


def spec_for_role(qr, kind: str, role: str) -> Optional[Tuple]:
    return synthesize(qr, kind).get(role)


def primary_roles(qr, kind: str) -> List[str]:
    """The steady-state hot-path program per batch: what ONE dispatch
    of real traffic runs, summed across pattern streams / join sides by
    the auditor's totals."""
    p = qr.planned
    if kind == "pattern":
        return [f"step[{sid}]" for sid in p.spec.stream_ids]
    if kind == "join":
        return [r for r, s in (("step[left]", p.step_left),
                               ("step[right]", p.step_right))
                if s is not None]
    return ["step"]
