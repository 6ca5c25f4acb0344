"""Query selector: projection, group-by aggregation, having, order-by/limit.

Reference behavior (what): CORE/query/selector/QuerySelector.java:44 — per
event: update aggregators (keyed by group-by key), evaluate select
expressions, apply having, order-by/limit per chunk; EXPIRED events subtract
from aggregators, RESET events clear them (batch windows).
Attribute aggregators: CORE/query/selector/attribute/aggregator/*.

TPU-native design (how): rows arrive seq-ordered with a precomputed group
slot id per row (host-side vectorized key->slot allocation, see
core/keyslots.py).  Running aggregate values — Siddhi's "value after this
event's update" semantics — are computed with *segmented associative scans*:
rows are stably sorted by (group slot, reset epoch), an inclusive
associative scan runs per segment, carry-in state is injected at segment
heads, and results are unsorted back — every column that crosses the
permutation in ONE packed gather each way (`window.gather_packed`).
O(B log B), no per-event control flow, exact sequential semantics.  A query
whose plan allocates no group slot holds one segment per reset epoch,
already in row order: its rows are scanned where they stand, no sort and no
permutation (`layout`).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..query_api.expression import (
    AttributeFunction,
    Compare,
    Constant,
    Expression,
    Variable,
    Add,
    Subtract,
    Multiply,
    Divide,
    Mod,
    And,
    Or,
    Not,
    IsNull,
    In,
)
from ..query_api.query import Selector
from . import event as ev
from .executor import (
    AGGREGATOR_NAMES,
    CompileError,
    CompiledExpr,
    Scope,
    compile_expression,
)
from .window import Rows, gather_packed

BIG = jnp.iinfo(jnp.int64).max // 4


# ---------------------------------------------------------------------------
# segmented inclusive scan over seg-sorted rows
# ---------------------------------------------------------------------------

def _segmented_scan(vals, segs, op):
    """Inclusive scan of `op` within runs of equal `segs` (must be sorted)."""
    def combine(a, b):
        va, sa = a
        vb, sb = b
        return jnp.where(sa == sb, op(va, vb), vb), sb
    out, _ = lax.associative_scan(combine, (vals, segs))
    return out


@dataclasses.dataclass
class _AggSpec:
    """One physical accumulator column (a scan over signed contributions)."""

    key: str                      # dedupe key
    op: Callable                  # associative op
    init: Any                     # identity scalar
    dtype: Any
    # vals_fn(env, sign) -> [B] contribution per row; may read
    # env['__scanres__'][after], the running values of the spec it names
    vals_fn: Callable
    # segment by a pair-slot column (env['__pslot__<j>']) instead of the
    # group slot — used by distinctCount's per-(group, value) refcounts
    slot_src: Optional[int] = None
    K_override: Optional[int] = None
    # the one spec whose running values `vals_fn` reads: `process` scans
    # this spec a wave later (distinctCount's `dc:` reads its `ref:`)
    after: Optional[int] = None


@dataclasses.dataclass
class _Layout:
    """One (slot, reset epoch) order of a step's rows: the permutation and
    its inverse (None: the rows stand in that order already), the slot
    column it sorts by, and — once a gather has moved them —
    (seg_s, first, sign_s, slot_s, epoch_s) in that order."""

    order: Any
    unorder: Any
    slot_vec: Any
    sorted: Optional[Tuple] = None


class AggregatorBank:
    """Compiles all aggregator calls of a query into a set of scan columns
    plus per-slot carry state [K]."""

    def __init__(self, group_slots: int, single_slot: bool = False):
        self.K = group_slots
        self.single_slot = single_slot
        self.specs: List[_AggSpec] = []
        self._index: Dict[str, int] = {}
        # distinctCount: Variables whose (group, value) pairs get host
        # slot allocation; planner resolves them to column positions
        self.pair_sources: List[Variable] = []

    def _add(self, spec: _AggSpec) -> int:
        if spec.key in self._index:
            return self._index[spec.key]
        self._index[spec.key] = len(self.specs)
        self.specs.append(spec)
        return len(self.specs) - 1

    def init_state(self):
        return tuple(
            jnp.full((s.K_override or self.K,), s.init, dtype=s.dtype)
            for s in self.specs)

    @property
    def layout(self) -> str:
        """How `process` lays rows out for its scans: `in_order` — one
        group slot, so the rows as they stand are in (slot, reset epoch)
        order and nothing is sorted or permuted; `sorted` — the argsort
        and the permutation back (a slot column, distinctCount's pairs)."""
        return "in_order" if self.single_slot and not self.pair_sources \
            else "sorted"

    # -- aggregator compilation ----------------------------------------------
    def compile_call(self, fn_expr: AttributeFunction, scope: Scope,
                     expr_key: str) -> Tuple[str, Callable, str]:
        """Returns (result_type, result_fn(scan_results)->array, name).
        `scan_results` is the tuple of per-row running values, one per spec."""
        name = fn_expr.name
        full = f"{fn_expr.namespace}:{name}" if fn_expr.namespace else name
        from .extension import attribute_aggregator_registry
        ext = attribute_aggregator_registry().get(full)
        if ext is not None:
            # custom aggregator: contributes scan columns through the same
            # bank as the built-ins (jits and shards identically)
            ext_args = [compile_expression(p, scope)
                        for p in fn_expr.parameters]

            def add_spec(suffix, op, init, dtype, vals_fn,
                         _full=full, _key=expr_key):
                return self._add(_AggSpec(
                    f"{_full}:{suffix}:{_key}", op, init, dtype, vals_fn))

            built = ext().build(ext_args, add_spec, expr_key)
            if isinstance(built, tuple):
                out_t, result = built
            else:
                out_t, result = ext.return_type, built
            return out_t.upper(), result, full
        if name == "distinctCount":
            orig = fn_expr.parameters[0]
            if not isinstance(orig, Variable):
                raise CompileError(
                    "distinctCount needs a plain attribute argument")
            i_dc = self._distinct_spec(orig, expr_key)
            return "LONG", (lambda res, _i=i_dc: res[_i]), name
        if name == "unionSet":
            # reference: UnionSetAttributeAggregatorExecutor over
            # createSet(attr) values.  The set itself cannot materialize in
            # a columnar output; sizeOfSet(unionSet(createSet(x))) — the
            # reference's canonical composition — maps onto the exact
            # distinct machinery, so the 'SET' pseudo-value carries the
            # running distinct count.  (Handled before arg compilation:
            # bare createSet deliberately fails to compile.)
            inner = fn_expr.parameters[0]
            if not (isinstance(inner, AttributeFunction) and
                    not inner.namespace and inner.name == "createSet" and
                    len(inner.parameters) == 1 and
                    isinstance(inner.parameters[0], Variable)):
                raise CompileError(
                    "unionSet expects createSet(<attribute>) in this build")
            i_dc = self._distinct_spec(inner.parameters[0], expr_key)
            return "SET", (lambda res, _i=i_dc: res[_i]), name
        args = [compile_expression(p, scope) for p in fn_expr.parameters]

        def fvals(c: CompiledExpr, dtype):
            # null arguments contribute nothing (reference: every aggregator
            # executor skips null inputs — Sum/Avg/StdDev processAdd)
            def vals(env, sign):
                v = c.fn(env)
                contrib = jnp.asarray(v, dtype) * jnp.asarray(sign, dtype)
                return jnp.where(ev.null_mask(v, c.type),
                                 jnp.asarray(0, dtype), contrib)
            return vals

        def fcount_nonnull(c: CompiledExpr):
            def vals(env, sign):
                v = c.fn(env)
                return jnp.where(ev.null_mask(v, c.type),
                                 jnp.asarray(0, jnp.int64),
                                 jnp.asarray(sign, jnp.int64))
            return vals

        if name == "sum" or name == "avg" or name == "stdDev":
            (a,) = args
            out_t = "LONG" if (name == "sum" and a.type in ("INT", "LONG")) \
                else "DOUBLE"
            acc_dtype = ev.dtype_of("LONG") if out_t == "LONG" \
                else ev.dtype_of("DOUBLE")
            i_sum = self._add(_AggSpec(
                f"sum:{expr_key}", jnp.add, 0, acc_dtype, fvals(a, acc_dtype)))
            i_cnt = self._add(_AggSpec(
                f"cnt:{expr_key}", jnp.add, 0, jnp.int64, fcount_nonnull(a)))
            if name == "sum":
                # null until the first non-null value arrives (and again if
                # the window retracts every contribution — reference: Sum
                # returns null at count 0)
                def fsum(res, _s=i_sum, _c=i_cnt, _t=out_t):
                    return jnp.where(
                        res[_c] != 0, res[_s],
                        jnp.asarray(ev.null_value(_t), res[_s].dtype))
                return out_t, fsum, name
            if name == "avg":
                def favg(res, _s=i_sum, _c=i_cnt):
                    c = res[_c]
                    # zero non-null contributions -> null (reference: Avg
                    # returns null before the first value arrives)
                    return jnp.where(
                        c != 0,
                        res[_s].astype(jnp.float32) / c.astype(jnp.float32),
                        jnp.asarray(jnp.nan, jnp.float32))
                return "DOUBLE", favg, name
            # stdDev = sqrt(E[x^2] - E[x]^2)
            def sqvals(env, sign, _a=a):
                v0 = _a.fn(env)
                v = jnp.asarray(v0, jnp.float32)
                return jnp.where(ev.null_mask(v0, _a.type),
                                 jnp.asarray(0.0, jnp.float32),
                                 v * v * jnp.asarray(sign, jnp.float32))
            i_sq = self._add(_AggSpec(
                f"sumsq:{expr_key}", jnp.add, 0, jnp.float32, sqvals))
            def fstd(res, _s=i_sum, _c=i_cnt, _q=i_sq):
                c = jnp.maximum(res[_c], 1).astype(jnp.float32)
                m = res[_s].astype(jnp.float32) / c
                var = jnp.maximum(res[_q] / c - m * m, 0.0)
                return jnp.where(res[_c] != 0, jnp.sqrt(var),
                                 jnp.asarray(jnp.nan, jnp.float32))
            return "DOUBLE", fstd, name

        if name == "count":
            i_cnt = self._add(_AggSpec(
                f"count:{expr_key}", jnp.add, 0, jnp.int64,
                lambda env, sign: jnp.asarray(sign, jnp.int64)))
            return "LONG", (lambda res, _i=i_cnt: res[_i]), name

        if name in ("min", "max", "minForever", "maxForever"):
            (a,) = args
            if a.type not in ("INT", "LONG", "FLOAT", "DOUBLE"):
                raise CompileError(f"{name}() needs a numeric argument")
            dtype = ev.dtype_of(a.type)
            big = jnp.asarray(
                jnp.inf if dtype in (jnp.float32, jnp.float64)
                else jnp.iinfo(dtype).max, dtype)
            is_min = name.startswith("min")
            ident = big if is_min else (-big if dtype in (jnp.float32,) else
                                        jnp.asarray(jnp.iinfo(dtype).min, dtype)
                                        if dtype not in (jnp.float32, jnp.float64)
                                        else -big)
            opf = jnp.minimum if is_min else jnp.maximum
            def vals(env, sign, _a=a, _id=ident, _d=dtype):
                v0 = _a.fn(env)
                v = jnp.asarray(v0, _d)
                # only CURRENT rows contribute; EXPIRED need window exposure;
                # null inputs contribute the identity (reference: MinMax
                # aggregators skip nulls)
                contribute = jnp.logical_and(
                    jnp.asarray(sign) > 0,
                    jnp.logical_not(ev.null_mask(v0, _a.type)))
                return jnp.where(contribute, v, _id)
            i = self._add(_AggSpec(
                f"{name}:{expr_key}", opf, ident, dtype, vals))
            # null until the first non-null CURRENT value is seen — the
            # accumulator identity must never leak to callbacks (reference:
            # MinMax aggregators return null before the first value).  The
            # seen-count is monotone because this min/max does not retract.
            def seen_vals(env, sign, _a=a):
                v = _a.fn(env)
                hit = jnp.logical_and(
                    jnp.asarray(sign) > 0,
                    jnp.logical_not(ev.null_mask(v, _a.type)))
                return jnp.where(hit, jnp.asarray(1, jnp.int64),
                                 jnp.asarray(0, jnp.int64))
            i_seen = self._add(_AggSpec(
                f"seen:{expr_key}", jnp.add, 0, jnp.int64, seen_vals))

            def fminmax(res, _i=i, _s=i_seen, _t=a.type, _d=dtype):
                return jnp.where(res[_s] > 0, res[_i],
                                 jnp.asarray(ev.null_value(_t), _d))
            return a.type, fminmax, name

        if name in ("and", "or"):
            (a,) = args
            want = name == "or"   # or: count trues; and: count falses
            def vals(env, sign, _a=a, _w=want):
                v = jnp.asarray(_a.fn(env), jnp.bool_)
                hit = v if _w else jnp.logical_not(v)
                return jnp.where(hit, jnp.asarray(sign, jnp.int64), 0)
            i = self._add(_AggSpec(
                f"{name}:{expr_key}", jnp.add, 0, jnp.int64, vals))
            if want:
                return "BOOL", (lambda res, _i=i: res[_i] > 0), name
            return "BOOL", (lambda res, _i=i: res[_i] == 0), name

        raise CompileError(f"unknown aggregator {name!r}")

    def _distinct_spec(self, var: Variable, expr_key: str) -> int:
        """Exact distinct count (reference: DistinctCountAttribute-
        AggregatorExecutor's per-value refcount map).  TPU design:
        (group, value) pairs resolve to pair slots on the host; a
        pair-segmented scan maintains refcounts, and 0<->1 refcount
        transitions feed a group-segmented scan as +-1 contributions."""
        j = len(self.pair_sources)
        self.pair_sources.append(var)
        i_ref = self._add(_AggSpec(
            f"ref:{expr_key}", jnp.add, 0, jnp.int64,
            lambda env, sign: jnp.asarray(sign, jnp.int64),
            slot_src=j, K_override=self.K * 8))

        def dvals(env, sign, _r=i_ref):
            r = env["__scanres__"][_r]
            return jnp.where(
                jnp.logical_and(jnp.asarray(sign) > 0, r == 1),
                jnp.asarray(1, jnp.int64),
                jnp.where(
                    jnp.logical_and(jnp.asarray(sign) < 0, r == 0),
                    jnp.asarray(-1, jnp.int64),
                    jnp.asarray(0, jnp.int64)))
        return self._add(_AggSpec(
            f"dc:{expr_key}", jnp.add, 0, jnp.int64, dvals, after=i_ref))

    # -- runtime -------------------------------------------------------------
    def process(self, state, rows: Rows, env) -> Tuple[Any, Tuple]:
        """Returns (new_state, per-row running values per spec)."""
        if not self.specs:
            return state, ()
        # two device-trace sections (jax.named_scope: op-name metadata):
        # `agg_layout` is every sort and unsort — the segment ids, the
        # argsort by (slot, reset epoch), the rows moved into that order
        # and back — and `agg_scan` the contributions, the segmented scans
        # and the carry.  Inside each, every op stands under a PART (a
        # second scope level, listed in observability/phases.py):
        # `agg_layout` / `keys`, `order`, `invert`, `to_sorted`,
        # `from_sorted`; `agg_scan` / `scan`, `store`
        B = rows.capacity
        in_order = self.layout == "in_order"
        with jax.named_scope("agg_layout"):
            with jax.named_scope("keys"):
                sign = jnp.where(
                    jnp.logical_and(rows.valid, rows.kind == ev.CURRENT), 1,
                    jnp.where(jnp.logical_and(rows.valid,
                                              rows.kind == ev.EXPIRED),
                              -1, 0))
                gslot = None if in_order else jnp.where(
                    rows.gslot >= 0, rows.gslot, 0).astype(jnp.int32)

                is_reset = jnp.logical_and(rows.valid,
                                           rows.kind == ev.RESET)
                # after row i
                reset_epoch = jnp.cumsum(is_reset.astype(jnp.int64))
                epoch_before = reset_epoch - is_reset.astype(jnp.int64)
                total_resets = reset_epoch[-1]

            def heads(seg_s):
                return jnp.concatenate([
                    jnp.ones((1,), jnp.bool_), seg_s[1:] != seg_s[:-1]])

            def layout(slot_vec):
                if slot_vec is None:
                    # one slot: the segment id is epoch_before, a running
                    # count that never decreases along the rows, so the
                    # stable argsort below would be arange and every gather
                    # by it a copy — the rows are scanned where they stand
                    with jax.named_scope("keys"):
                        first = heads(epoch_before)
                    return _Layout(order=None, unorder=None, slot_vec=None,
                                   sorted=(epoch_before, first, sign, None,
                                           epoch_before))
                with jax.named_scope("keys"):
                    # segment id: (slot, epoch); rows already seq-ordered
                    seg = slot_vec.astype(jnp.int64) * (B + 2) + epoch_before
                with jax.named_scope("order"):
                    order = jnp.argsort(seg, stable=True)
                with jax.named_scope("invert"):
                    unorder = jnp.zeros((B,), jnp.int32).at[order].set(
                        jnp.arange(B, dtype=jnp.int32))
                return _Layout(order, unorder, slot_vec)

            layouts = {None: layout(gslot)}
            for j in range(len(self.pair_sources)):
                ps = env.get(f"__pslot__{j}")
                if ps is not None:
                    with jax.named_scope("keys"):
                        pslot = jnp.where(ps >= 0, ps, 0).astype(jnp.int32)
                    layouts[j] = layout(pslot)

        def to_sorted(lay, vals):
            """`vals` in `lay`'s order: ONE packed gather, which the first
            time takes the layout's own columns along — the segment ids are
            made again from the moved (slot, epoch), not moved."""
            if lay.order is None:
                return vals
            own = () if lay.sorted else (sign, lay.slot_vec, epoch_before)
            with jax.named_scope("agg_layout"):
                with jax.named_scope("to_sorted"):
                    moved = gather_packed((*own, *vals), lay.order)
                if own:
                    with jax.named_scope("keys"):
                        sign_s, slot_s, epoch_s = moved[:3]
                        seg_s = slot_s.astype(jnp.int64) * (B + 2) + epoch_s
                        lay.sorted = (seg_s, heads(seg_s), sign_s, slot_s,
                                      epoch_s)
            return moved[len(own):]

        # WAVES: a spec is scanned after the spec whose running values its
        # contributions read (`after`); the specs of a wave that share a
        # layout cross its permutation together.  No permutation
        # (`in_order`), nothing to share: each spec by itself, in its turn
        depth: List[int] = []
        waves: List[Dict[Any, List[int]]] = []
        for i, spec in enumerate(self.specs):
            depth.append(0 if spec.after is None else depth[spec.after] + 1)
            if depth[i] == len(waves):
                waves.append({})
            waves[depth[i]].setdefault(
                i if in_order else spec.slot_src, []).append(i)

        env = dict(env)
        env["__scanres__"] = results = [None] * len(self.specs)
        new_state = [None] * len(self.specs)
        for members in (m for wave in waves for m in wave.values()):
            lay = layouts[self.specs[members[0]].slot_src]
            with jax.named_scope("agg_scan"), jax.named_scope("scan"):
                vals = []
                for i in members:
                    spec = self.specs[i]
                    v = spec.vals_fn(env, sign)
                    # rows that don't contribute carry the identity
                    vals.append(jnp.where(
                        sign != 0, v, jnp.asarray(spec.init, spec.dtype)))
            vals_s = to_sorted(lay, vals)
            seg_s, first, sign_s, slot_s, epoch_s = lay.sorted
            scans = []
            for i, v_s in zip(members, vals_s):
                spec, st = self.specs[i], state[i]
                # slot count from the STATE shape, not the plan: under
                # shard_map each device owns a K/n slice of the slot axis
                K = st.shape[0]
                with jax.named_scope("agg_scan"), jax.named_scope("scan"):
                    # inject carry state at heads of epoch-0 segments
                    carry = st[0] if slot_s is None else st[slot_s]
                    v_s = jnp.where(
                        jnp.logical_and(first, epoch_s == 0),
                        spec.op(carry, v_s), v_s)
                    scanned = _segmented_scan(v_s, seg_s, spec.op)
                scans.append(scanned)

                with jax.named_scope("agg_scan"), jax.named_scope("store"):
                    # new state: per slot, value after the last row in the
                    # final epoch
                    contrib = jnp.logical_and(sign_s != 0,
                                              epoch_s == total_resets)
                    idx = jnp.arange(B)
                    if slot_s is None:
                        # slot 0's is a max-reduce; no row names another
                        # slot
                        last = jnp.max(jnp.where(contrib, idx, -1))
                        last_idx = jnp.where(
                            jnp.arange(K) == 0, last, -1).astype(jnp.int32)
                    else:
                        # scatter-max of sorted index per contributing slot
                        last_idx = jnp.full((K,), -1, jnp.int32).at[
                            jnp.where(contrib, slot_s, K).astype(jnp.int32)
                        ].max(jnp.where(contrib, idx, -1).astype(jnp.int32),
                              mode="drop")
                    has = last_idx >= 0
                    gathered = scanned[jnp.clip(last_idx, 0, B - 1)]
                    base = jnp.where(
                        total_resets > 0,
                        jnp.full((K,), spec.init, spec.dtype), st)
                    # carry survives only if no reset happened
                    new_state[i] = jnp.where(has, gathered, base)
            if lay.unorder is not None:
                with jax.named_scope("agg_layout"), \
                        jax.named_scope("from_sorted"):
                    scans = gather_packed(scans, lay.unorder)
            for i, scanned in zip(members, scans):
                results[i] = scanned

        return tuple(new_state), tuple(results)


# ---------------------------------------------------------------------------
# Selector executor
# ---------------------------------------------------------------------------

class SelectorExec:
    """Compiled select clause over ordered Rows.

    `single_slot`: the plan allocates no group slot for this query (no
    group by, no partition key, no range-partition key function), so every
    row carries slot 0 and `AggregatorBank.layout` is `in_order`; a site
    that cannot prove it keeps the default, the sorted layout."""

    def __init__(self, selector: Selector, scope: Scope,
                 in_schema: ev.Schema, group_slots: int,
                 out_stream_id: str, interner: ev.StringInterner,
                 single_slot: bool = False):
        self.selector = selector
        self.scope = scope
        self.group_by_positions: List[int] = []
        for v in selector.group_by_list:
            _, pos, _ = scope.resolve(v)
            self.group_by_positions.append(pos)

        self.bank = AggregatorBank(group_slots, single_slot)
        self._agg_calls: List[AttributeFunction] = []

        # select list (select-all expands to the input schema)
        sel_list = selector.selection_list
        if not sel_list:
            from ..query_api.query import OutputAttribute
            sel_list = [
                OutputAttribute(None, Variable(n)) for n in in_schema.names]

        self.out_names: List[str] = []
        self._proj: List[Tuple[Expression, str]] = []  # rewritten expr
        for oa in sel_list:
            rewritten = _rewrite_aggregators(oa.expression, self._agg_calls,
                                             "__agg")
            self.out_names.append(oa.name if oa.rename or isinstance(
                oa.expression, Variable) else oa.name)
            self._proj.append((rewritten, oa.name))

        # compile aggregator calls -> result fns; bind pseudo-columns
        self._agg_results: List[Tuple[str, Callable]] = []
        for i, call in enumerate(self._agg_calls):
            ekey = f"{out_stream_id}:{i}:{_expr_fingerprint(call)}"
            t, fn, _ = self.bank.compile_call(call, scope, ekey)
            self._agg_results.append((t, fn))
            scope.bind(f"__agg{i}",
                       CompiledExpr(fn=None, type=t))  # type only; fn later

        # compile projections / having with pseudo-columns resolved lazily:
        # we compile in process() env style: pseudo columns injected into env
        self._compiled_proj: List[CompiledExpr] = []
        for rewritten, name in self._proj:
            self._compiled_proj.append(
                _compile_with_pseudo(rewritten, scope, self._agg_results))
        self.out_types = [c.type for c in self._compiled_proj]
        if "SET" in self.out_types:
            raise CompileError(
                "set values cannot materialize in columnar outputs; wrap "
                "with sizeOfSet(...)")

        self.having = None
        if selector.having_expression is not None:
            # having may reference select ALIASES (reference: having runs
            # over the output event); substitute them with the projected
            # expression before aggregator rewriting
            alias_map = {}
            for oa, (expr, _) in zip(sel_list, self._proj):
                if oa.rename:
                    alias_map[oa.rename] = oa.expression
            hre = _substitute_aliases(
                selector.having_expression, alias_map, scope)
            hre = _rewrite_aggregators(hre, self._agg_calls, "__agg")
            # new aggs may have been appended by having
            while len(self._agg_results) < len(self._agg_calls):
                i = len(self._agg_results)
                call = self._agg_calls[i]
                ekey = f"{out_stream_id}:h{i}:{_expr_fingerprint(call)}"
                t, fn, _ = self.bank.compile_call(call, scope, ekey)
                self._agg_results.append((t, fn))
                scope.bind(f"__agg{i}", CompiledExpr(fn=None, type=t))
            self.having = _compile_with_pseudo(hre, scope, self._agg_results)

        # order-by / limit
        self._order_by = []
        for ob in selector.order_by_list:
            c = compile_expression(ob.variable, _projection_scope(
                self.out_names, self.out_types, interner))
            self._order_by.append((c, ob.order))
        self.interner = interner

    @property
    def has_aggregation(self) -> bool:
        return bool(self.bank.specs)

    def init_state(self):
        return self.bank.init_state()

    def process(self, state, rows: Rows, env: Dict[str, Any]):
        """env must contain the scope's source cols; returns
        (state', out_ts, out_kind, out_valid, out_cols tuple)."""
        new_state, scans = self.bank.process(state, rows, env)
        env = dict(env)
        env["__aggscan__"] = scans

        # device-trace section `project`: the select list over the scans'
        # running values, having, the valid mask, order-by / limit
        with jax.named_scope("project"):
            out_cols = tuple(c.fn(env) for c in self._compiled_proj)
            valid = jnp.logical_and(
                rows.valid,
                jnp.logical_or(rows.kind == ev.CURRENT,
                               rows.kind == ev.EXPIRED))
            if self.having is not None:
                valid = jnp.logical_and(valid, self.having.fn(env))

            ts, kind = rows.ts, rows.kind
            if self._order_by or self.selector.limit is not None \
                    or self.selector.offset is not None:
                ts, kind, valid, out_cols = self._order_limit(
                    ts, kind, valid, out_cols)
        return new_state, (ts, kind, valid, out_cols)

    def _order_limit(self, ts, kind, valid, out_cols):
        B = ts.shape[0]
        if self._order_by:
            env = {"__out__": out_cols}
            keys = []
            for c, order in reversed(self._order_by):
                k = c.fn(env)
                if order == "DESC":
                    k = -k if k.dtype != jnp.bool_ else jnp.logical_not(k)
                keys.append(k)
            idx = jnp.arange(B)
            for k in keys:  # last applied = primary (stable sorts)
                big = jnp.asarray(
                    jnp.inf if k.dtype in (jnp.float32, jnp.float64)
                    else jnp.iinfo(k.dtype).max
                    if k.dtype not in (jnp.bool_,) else True)
                kk = jnp.where(valid[idx], k[idx], big)
                s = jnp.argsort(kk, stable=True)
                idx = idx[s]
            ts, kind, valid = ts[idx], kind[idx], valid[idx]
            out_cols = tuple(c[idx] for c in out_cols)
        if self.selector.offset is not None or self.selector.limit is not None:
            rank = jnp.cumsum(valid.astype(jnp.int32)) - 1
            lo = self.selector.offset or 0
            keep = rank >= lo
            if self.selector.limit is not None:
                keep = jnp.logical_and(keep, rank < lo + self.selector.limit)
            valid = jnp.logical_and(valid, keep)
        return ts, kind, valid, out_cols


def _rewrite_aggregators(expr: Expression, found: List[AttributeFunction],
                         prefix: str) -> Expression:
    """Replace aggregator calls with bound pseudo-variables __agg<i>."""
    if isinstance(expr, AttributeFunction):
        is_agg = not expr.namespace and expr.name in AGGREGATOR_NAMES
        if not is_agg:
            from .extension import attribute_aggregator_registry
            full = f"{expr.namespace}:{expr.name}" if expr.namespace \
                else expr.name
            is_agg = full in attribute_aggregator_registry()
        if is_agg:
            found.append(expr)
            return Variable(f"{prefix}{len(found) - 1}")
        return AttributeFunction(expr.namespace, expr.name, [
            _rewrite_aggregators(p, found, prefix) for p in expr.parameters])
    if isinstance(expr, (Add, Subtract, Multiply, Divide, Mod)):
        return type(expr)(_rewrite_aggregators(expr.left, found, prefix),
                          _rewrite_aggregators(expr.right, found, prefix))
    if isinstance(expr, Compare):
        return Compare(_rewrite_aggregators(expr.left, found, prefix),
                       expr.operator,
                       _rewrite_aggregators(expr.right, found, prefix))
    if isinstance(expr, (And, Or)):
        return type(expr)(_rewrite_aggregators(expr.left, found, prefix),
                          _rewrite_aggregators(expr.right, found, prefix))
    if isinstance(expr, Not):
        return Not(_rewrite_aggregators(expr.expression, found, prefix))
    if isinstance(expr, IsNull) and expr.expression is not None:
        return IsNull(_rewrite_aggregators(expr.expression, found, prefix))
    if isinstance(expr, In):
        return In(_rewrite_aggregators(expr.expression, found, prefix),
                  expr.source_id)
    return expr


def _substitute_aliases(e: Expression, alias_map, scope) -> Expression:
    """Replace unqualified Variables naming a select alias with the aliased
    expression, unless the name also resolves to a real input attribute
    (input attributes win, matching single-source behavior)."""
    if isinstance(e, Variable) and e.stream_id is None and \
            e.attribute_name in alias_map:
        try:
            scope.resolve(e)
            return e              # a real input attribute shadows the alias
        except CompileError:
            return alias_map[e.attribute_name]
    for f in getattr(e, "__dataclass_fields__", {}):
        v = getattr(e, f)
        if isinstance(v, Expression):
            setattr(e, f, _substitute_aliases(v, alias_map, scope))
        elif isinstance(v, list):
            setattr(e, f, [
                _substitute_aliases(x, alias_map, scope)
                if isinstance(x, Expression) else x for x in v])
    return e


def _expr_fingerprint(e: Expression) -> str:
    if isinstance(e, Variable):
        return f"v:{e.stream_id}.{e.attribute_name}[{e.stream_index}]"
    if isinstance(e, Constant):
        return f"c:{e.value}"
    if isinstance(e, AttributeFunction):
        inner = ",".join(_expr_fingerprint(p) for p in e.parameters)
        return f"f:{e.namespace}:{e.name}({inner})"
    if isinstance(e, Compare):
        return f"({_expr_fingerprint(e.left)}{e.operator}{_expr_fingerprint(e.right)})"
    if isinstance(e, (Add, Subtract, Multiply, Divide, Mod, And, Or)):
        return (f"({_expr_fingerprint(e.left)}{type(e).__name__}"
                f"{_expr_fingerprint(e.right)})")
    if isinstance(e, Not):
        return f"!({_expr_fingerprint(e.expression)})"
    return repr(e)


def _compile_with_pseudo(expr: Expression, scope: Scope,
                         agg_results: List[Tuple[str, Callable]]) -> CompiledExpr:
    """Compile an expression where __aggN variables read from env['__aggscan__']."""

    class _PseudoScope:
        def __init__(self, base: Scope):
            self.base = base

        def __getattr__(self, item):
            return getattr(self.base, item)

        def resolve(self, var):
            return self.base.resolve(var)

    # bind real fns for pseudo vars
    for i, (t, fn) in enumerate(agg_results):
        def make(fn):
            return lambda env: fn(env["__aggscan__"])
        scope.bind(f"__agg{i}", CompiledExpr(fn=make(fn), type=t))
    return compile_expression(expr, scope)


def _projection_scope(names, types, interner) -> Scope:
    """Scope over the projected output columns (for order-by)."""
    from ..query_api.definition import StreamDefinition

    d = StreamDefinition("__out__")
    for n, t in zip(names, types):
        d.attribute(n, t)
    s = Scope()
    s.add_source("__out__", ev.Schema(d, interner))
    return s
