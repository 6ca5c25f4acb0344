"""Pattern / sequence matching as a vectorized slot-slab NFA.

Reference behavior (what): CORE/query/input/stream/state/* — chains of
Pre/Post state processors holding per-pending-StateEvent lists, supporting
`every`, count quantifiers <m:n>, logical and/or, absent (`not X for t`) and
`within` (StreamPreStateProcessor.java:363-403 is the per-event O(pending)
inner loop; StateInputStreamParser.java:76-146 builds the chain).

TPU-native design (how): a pattern compiles to a *linear chain of atoms*.
Runtime state is a fixed slab of P pending slots per key with captured event
columns per atom.  One `step` consumes a micro-batch laid out per key as
[K,E] (the host groups events by partition key): a lax.scan walks the E
event columns — sequential semantics within a key — and each tick evaluates
every chain position for every (key, slot) in parallel, so the reference's
O(pending × events) Java loop becomes a handful of [K,P] vector ops per
tick.  Forked continuations (count quantifiers, `every` seeds) allocate free
slots by masked ranking with drop-on-overflow; completions emit capture rows
consumed by the query selector.

Tick phase order (strict): within-expiry -> absent-deadline advance ->
match eval (pre-capture state) -> in-place capture -> emission gather ->
fork/seed spawn -> in-place advance / kill / deactivate.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..query_api.expression import Expression
from ..query_api.query import (
    AbsentStreamStateElement,
    CountStateElement,
    EveryStateElement,
    Filter,
    LogicalStateElement,
    NextStateElement,
    SingleInputStream,
    StateElement,
    StateInputStream,
    StreamStateElement,
)
from . import event as ev
from .executor import CompileError, CompiledExpr, Scope, compile_expression

BIG = jnp.iinfo(jnp.int64).max // 4


# ---------------------------------------------------------------------------
# Compilation: StateElement tree -> linear atom chain
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Atom:
    pos: int
    stream_id: str
    ref: str
    filter_expr: Optional[Expression]
    min_count: int = 1
    max_count: int = 1            # -1 == ANY
    absent: bool = False
    waiting_time: Optional[int] = None
    every: bool = False
    logical: Optional[str] = None  # 'AND' | 'OR' (self = side 0)
    partner: Optional["Atom"] = None
    capture_depth: int = 1

    @property
    def is_count(self) -> bool:
        return self.max_count != 1 or self.min_count != 1

    @property
    def ckey(self) -> str:
        return f"{self.pos}:{self.ref}"


@dataclasses.dataclass
class PatternSpec:
    atoms: List[Atom]
    state_type: str               # PATTERN | SEQUENCE
    within: Optional[int]
    count_cap: int = 8

    @property
    def n_states(self) -> int:
        return len(self.atoms)

    @property
    def stream_ids(self) -> List[str]:
        out = []
        for a in self.all_atoms():
            if a.stream_id not in out:
                out.append(a.stream_id)
        return out

    def all_atoms(self):
        for a in self.atoms:
            yield a
            if a.partner is not None:
                yield a.partner

    @property
    def has_absent(self) -> bool:
        """True when timer-driven absent machinery is needed: standalone
        `not X for t` atoms, or timed absent sides of logical pairs
        (instant `not A and B` needs no timers)."""
        return any(
            a.absent or (a.partner is not None and a.partner.absent and
                         a.partner.waiting_time is not None)
            for a in self.atoms)


def linearize(sis: StateInputStream, count_cap: int = 8) -> PatternSpec:
    atoms: List[Atom] = []

    def mk_atom(stream: SingleInputStream, pos: int, every: bool) -> Atom:
        filt = None
        for h in stream.stream_handlers:
            if isinstance(h, Filter):
                if filt is not None:
                    raise CompileError("multiple filters on a pattern element")
                filt = h.expression
            else:
                raise CompileError(
                    "windows/functions on pattern elements not supported")
        ref = stream.stream_reference_id or f"__p{pos}"
        return Atom(pos, stream.stream_id, ref, filt, every=every)

    def rec(el: StateElement, every: bool):
        if isinstance(el, NextStateElement):
            rec(el.state_element, every)
            rec(el.next_state_element, False)
        elif isinstance(el, EveryStateElement):
            rec(el.state_element, True)
        elif isinstance(el, StreamStateElement):
            atoms.append(mk_atom(el.basic_single_input_stream,
                                 len(atoms), every))
        elif isinstance(el, AbsentStreamStateElement):
            a = mk_atom(el.basic_single_input_stream, len(atoms), every)
            a.absent = True
            a.waiting_time = el.waiting_time
            if a.waiting_time is None:
                raise CompileError(
                    "absent pattern elements need 'for <time>' in this build")
            atoms.append(a)
        elif isinstance(el, CountStateElement):
            inner = el.stream_state_element
            a = mk_atom(inner.basic_single_input_stream, len(atoms), every)
            a.min_count = el.min_count
            a.max_count = el.max_count
            cap = count_cap if el.max_count == CountStateElement.ANY \
                else min(el.max_count, count_cap)
            a.capture_depth = max(cap, 1)
            atoms.append(a)
        elif isinstance(el, LogicalStateElement):
            def to_parts(x):
                if isinstance(x, StreamStateElement):
                    return x.basic_single_input_stream, False, None
                if isinstance(x, AbsentStreamStateElement):
                    return x.basic_single_input_stream, True, x.waiting_time
                raise CompileError(
                    "logical pattern sides must be plain or absent stream "
                    "elements")
            s1, ab1, wt1 = to_parts(el.stream_state_element_1)
            s2, ab2, wt2 = to_parts(el.stream_state_element_2)
            if ab1 and ab2:
                raise CompileError(
                    "both sides of a logical pattern cannot be absent")
            if (ab1 or ab2) and el.type == "OR":
                raise CompileError(
                    "'not X or Y' is not a valid pattern (reference: "
                    "logical absent combines with 'and' only)")
            pos = len(atoms)
            wt = wt1 if ab1 else wt2
            if (ab1 or ab2) and wt is not None and pos == 0:
                raise CompileError(
                    "leading 'not X for <time> and Y' is not supported in "
                    "this build (the wait clock starts at a preceding "
                    "stage); precede it with a stage or drop 'for <time>'")
            # the PRESENCE side is always the primary atom (it seeds and
            # captures); an absent side rides as the partner: its arrival
            # kills the pending state until the waiting time (if any) has
            # elapsed, after which the absence obligation is satisfied
            # (reference: AbsentLogicalPreStateProcessor)
            if ab1:
                a = mk_atom(s2, pos, every)
                b = mk_atom(s1, pos, False)
                b.absent = True
            else:
                a = mk_atom(s1, pos, every)
                b = mk_atom(s2, pos, False)
                b.absent = ab2
            b.waiting_time = wt if (ab1 or ab2) else None
            if b.ref == a.ref or b.ref == f"__p{pos}":
                b.ref = f"__p{pos}b"
            a.logical = el.type
            a.partner = b
            atoms.append(a)
        else:
            raise CompileError(
                f"unsupported pattern element {type(el).__name__}")

    rec(sis.state_element, False)
    if not atoms:
        raise CompileError("empty pattern")
    return PatternSpec(atoms, sis.state_type, sis.within_time,
                       count_cap=count_cap)


# ---------------------------------------------------------------------------
# Runtime
# ---------------------------------------------------------------------------

class PatternState(NamedTuple):
    """Per-key NFA slab.  The key axis K is LAST on every leaf so the whole
    state pipeline (blob [W,K] <-> leaves <-> tick ops) stays key-minor: K
    rides the TPU lane dimension and pack/unpack are pure reshapes, no
    transposes (a [K,P] convention cost ~80ms/step in layout churn at 131k
    keys)."""
    active: Any       # bool[P,K]
    pos: Any          # i32[P,K]
    count: Any        # i32[P,K] captures at current pos
    lmask: Any        # i32[P,K] logical sides satisfied (bit0/bit1)
    start_ts: Any     # i64[P,K]
    entry_ts: Any     # i64[P,K] ts of entering current pos
    seed_on: Any      # bool[K]
    done: Any         # bool[K]  non-every pattern already matched
    dropped: Any      # i64 scalar: forks dropped on slab overflow
    caps: Dict[str, Tuple]   # atom.ckey -> (ts[P,D,K], cols tuple [P,D,K])
    # i64 scalar: continuations forked off a slot (a count atom past its
    # `min`, an epsilon skip), lost ones included.  None — no leaf, the
    # packed state and every program as they were — where no atom forks
    forked: Any = None


class PatternExec:
    def __init__(self, spec: PatternSpec, schemas: Dict[str, ev.Schema],
                 interner: ev.StringInterner, slots: int = 8,
                 emit_refs: Optional[set] = None, script_functions=None):
        self.spec = spec
        self.schemas = schemas
        self.P = slots
        self.S = spec.n_states
        self.interner = interner
        # a count atom is the one thing that forks a slot's continuation
        # (a zero-min one is also what an epsilon skip forks past)
        self.forks = any(a.is_count for a in spec.atoms)
        # emission pruning: only captures referenced by the query's selector
        # are materialized into per-match output rows (None = all)
        self.emit_refs = emit_refs

        # selector-facing scope: every non-absent atom ref is a source
        self.scope = Scope()
        self.scope.interner = interner
        self.scope.script_functions = script_functions
        for a in spec.all_atoms():
            if not a.absent:
                self.scope.add_source(a.ref, schemas[a.stream_id])

        # per-atom filter scopes: unqualified attrs bind to the atom's OWN
        # stream (the incoming event); qualified refs reach earlier captures.
        # `x in Table` conditions compile to device probes against the
        # table's column snapshot, shipped into the step as in_tabs
        # (reference: InConditionExpressionExecutor inside NFA filters)
        self._filters: Dict[str, Optional[CompiledExpr]] = {}
        self.in_deps: List[str] = []
        for a in spec.all_atoms():
            if a.filter_expr is None:
                self._filters[a.ckey] = None
                continue
            fscope = Scope()
            fscope.interner = interner
            fscope.script_functions = script_functions
            fscope.add_source(a.ref, schemas[a.stream_id], default=True)
            for other in spec.all_atoms():
                if other.ckey != a.ckey and not other.absent:
                    fscope.add_source(other.ref, schemas[other.stream_id],
                                      default=False)
            from ..query_api.expression import In, walk
            for n in walk(a.filter_expr):
                if isinstance(n, In) and n.source_id not in self.in_deps:
                    self.in_deps.append(n.source_id)
            self._filters[a.ckey] = compile_expression(a.filter_expr, fscope)

    # -- state ----------------------------------------------------------------
    def init_state(self, K: int) -> PatternState:
        P = self.P
        caps: Dict[str, Tuple] = {}
        for a in self.spec.all_atoms():
            if a.absent:
                continue
            schema = self.schemas[a.stream_id]
            D = a.capture_depth
            # unfilled captures are NULL, not zero: an unmatched OR branch
            # and uncollected count rows (e1[i] beyond the collected depth)
            # emit null attributes (reference: LogicalPreStateProcessor
            # leaves the partner's StreamEvent null; e1[i] out of range
            # returns null)
            cols = tuple(
                ev.typed_full((P, D, K), ev.null_value(t), d)
                for t, d in zip(schema.types, schema.dtypes))
            # ts plane -1 == unfilled: fill-depth tests use >= 0, so a
            # legitimate playback event at timestamp 0 still counts
            caps[a.ckey] = (jnp.full((P, D, K), -1, jnp.int64), cols)
        return PatternState(
            active=jnp.zeros((P, K), jnp.bool_),
            pos=jnp.zeros((P, K), jnp.int32),
            count=jnp.zeros((P, K), jnp.int32),
            lmask=jnp.zeros((P, K), jnp.int32),
            start_ts=jnp.zeros((P, K), jnp.int64),
            entry_ts=jnp.zeros((P, K), jnp.int64),
            seed_on=jnp.ones((K,), jnp.bool_),
            done=jnp.zeros((K,), jnp.bool_),
            dropped=jnp.asarray(0, jnp.int64),
            caps=caps,
            forked=jnp.asarray(0, jnp.int64) if self.forks else None,
        )

    # -- one event per key ----------------------------------------------------
    def tick(self, st: PatternState, stream_id: str, ev_cols, ev_ts,
             ev_valid, now_k, in_tabs=()):
        spec = self.spec
        S = self.S
        P, K = st.active.shape
        a0 = spec.atoms[0]
        F = jnp.zeros((P, K), jnp.bool_)

        # ---- phase 1: within expiry ----------------------------------------
        if spec.within is not None:
            alive = now_k[None, :] - st.start_ts <= spec.within
            st = st._replace(active=jnp.logical_and(st.active, alive))

        # ---- phase 2: absent deadlines -------------------------------------
        absent_complete = F
        absent_ts = jnp.zeros((P, K), jnp.int64)
        for a in spec.atoms:
            if not a.absent:
                continue
            at_pos = jnp.logical_and(st.active, st.pos == a.pos)
            due = jnp.logical_and(
                at_pos, st.entry_ts + a.waiting_time <= now_k[None, :])
            if a.pos == S - 1:
                absent_complete = jnp.logical_or(absent_complete, due)
                absent_ts = jnp.where(due, st.entry_ts + a.waiting_time,
                                      absent_ts)
                st = st._replace(active=jnp.logical_and(
                    st.active, jnp.logical_not(due)))
            else:
                st = st._replace(
                    pos=jnp.where(due, a.pos + 1, st.pos).astype(jnp.int32),
                    count=jnp.where(due, 0, st.count).astype(jnp.int32),
                    lmask=jnp.where(due, 0, st.lmask).astype(jnp.int32),
                    entry_ts=jnp.where(due, st.entry_ts + a.waiting_time,
                                       st.entry_ts),
                )

        # timed logical-absent pairs (`not A for t and B`): when the wait
        # elapses without a matching A, the absence obligation is SATISFIED
        # (bit 2 in lmask); the state fires once B has also arrived —
        # whichever of {deadline, B} comes last triggers the completion
        for a in spec.atoms:
            p = a.partner
            if p is None or not p.absent or p.waiting_time is None:
                continue
            at_pos = jnp.logical_and(st.active, st.pos == a.pos)
            pend = jnp.logical_and(at_pos, (st.lmask & 2) == 0)
            due = jnp.logical_and(
                pend, st.entry_ts + p.waiting_time <= now_k[None, :])
            have_b = (st.lmask & 1) != 0
            fire = jnp.logical_and(due, have_b)
            st = st._replace(lmask=jnp.where(due, st.lmask | 2, st.lmask)
                             .astype(jnp.int32))
            if a.pos == S - 1:
                absent_complete = jnp.logical_or(absent_complete, fire)
                absent_ts = jnp.where(fire, st.entry_ts + p.waiting_time,
                                      absent_ts)
                st = st._replace(active=jnp.logical_and(
                    st.active, jnp.logical_not(fire)))
            else:
                st = st._replace(
                    pos=jnp.where(fire, a.pos + 1, st.pos).astype(jnp.int32),
                    count=jnp.where(fire, 0, st.count).astype(jnp.int32),
                    lmask=jnp.where(fire, 0, st.lmask).astype(jnp.int32),
                    entry_ts=jnp.where(fire, st.entry_ts + p.waiting_time,
                                       st.entry_ts),
                )

        # ---- phase 3: match evaluation (pre-capture state) -----------------
        env = self._build_env(st, stream_id, ev_cols, ev_ts, in_tabs)
        ev_ok = jnp.logical_and(ev_valid, jnp.logical_not(st.done))   # [K]

        advance_inplace = F
        complete = absent_complete
        deactivate = absent_complete
        fork = F
        kill = F
        matched_any = F
        capture: Dict[str, Any] = {}
        lmask_new = st.lmask
        # epsilon closure over zero-min count atoms (e1? / e1*): a thread
        # parked at position q that has collected NOTHING there may match a
        # later atom p directly when every atom in [q, p) is a plain count
        # with min_count == 0 (reference: a <0:n> state's next processor is
        # reachable without any occurrence).  Matched-from-skip threads
        # advance/collect AS IF at p, so the position updates below carry
        # explicit targets instead of pos+1.
        skip_srcs: Dict[int, List[int]] = {}
        for a_ in spec.atoms:
            srcs: List[int] = []
            if a_.logical is None and not a_.absent:
                q = a_.pos - 1
                while q >= 0 and spec.atoms[q].is_count \
                        and spec.atoms[q].min_count == 0 \
                        and spec.atoms[q].partner is None \
                        and not spec.atoms[q].absent:
                    srcs.append(q)
                    q -= 1
            skip_srcs[a_.pos] = srcs
        fork_tgt = st.pos + 1      # [P,K] forked continuation's position
        fork_cnt = jnp.zeros_like(st.count)   # forked slot's start count
        capture_here = {}          # captures owned by the slot's own
                                   # position (drives its count); skip
                                   # captures ride `capture` for forks/
                                   # emission but must not advance the
                                   # surviving origin's count
        skip_marks = {}            # atom ckey -> [P,K] skip-match mask:
                                   # after forks inherit, the surviving
                                   # origin reverts these captures to null

        def mark(d, key, m):
            d[key] = jnp.logical_or(d.get(key, F), m)

        for a in spec.atoms:
            last = a.pos == S - 1
            sides = [(a, 0)] + ([(a.partner, 1)] if a.partner else [])
            for atom, side in sides:
                if atom.stream_id != stream_id:
                    continue
                filt = self._filters[atom.ckey]
                if filt is None:
                    cond = jnp.ones((P, K), jnp.bool_)
                else:
                    # the atom under evaluation sees the INCOMING event under
                    # its own ref; other refs stay bound to captures (binding
                    # by stream id wrongly aliased e1.price to the current
                    # event for same-stream patterns)
                    env_a = dict(env)
                    env_a[atom.ref] = tuple(
                        jnp.broadcast_to(c[None, :], (P, K))
                        for c in ev_cols)
                    cond = jnp.broadcast_to(filt.fn(env_a), (P, K))
                at_here = jnp.logical_and(st.active, st.pos == a.pos)
                m_here = jnp.logical_and(jnp.logical_and(at_here, cond),
                                         ev_ok[None, :])
                m_skip = F
                if atom is a and skip_srcs.get(a.pos):
                    from_skip = F
                    for q2 in skip_srcs[a.pos]:
                        from_skip = jnp.logical_or(from_skip,
                                                   st.pos == q2)
                    from_skip = jnp.logical_and(
                        jnp.logical_and(st.active, from_skip),
                        st.count == 0)
                    m_skip = jnp.logical_and(
                        jnp.logical_and(from_skip, cond), ev_ok[None, :])
                m = jnp.logical_or(m_here, m_skip)
                if atom is a and skip_srcs.get(a.pos):
                    mark(skip_marks, atom.ckey, m_skip)
                if atom.absent:
                    # absence violated — unless the obligation was already
                    # satisfied (timed pair whose wait elapsed, bit 1<<side)
                    live = (st.lmask & (1 << side)) == 0
                    kill = jnp.logical_or(kill, jnp.logical_and(m, live))
                    continue
                matched_any = jnp.logical_or(matched_any, m)
                if a.logical is not None:
                    bit = 1 << side
                    have_other = (lmask_new & (3 ^ bit)) != 0
                    # only OR and INSTANT absent pairs advance on the
                    # presence side alone; AND-of-presences needs the other
                    # side's bit and TIMED absent pairs need the
                    # satisfied-absence bit the deadline pass sets — both
                    # ride have_other
                    pair_absent = a.partner is not None and a.partner.absent
                    instant_pair = pair_absent and \
                        a.partner.waiting_time is None
                    adv = m if (a.logical == "OR" or instant_pair) \
                        else jnp.logical_and(m, have_other)
                    lmask_new = jnp.where(m, lmask_new | bit, lmask_new)
                    mark(capture, atom.ckey, m)
                    mark(capture_here, atom.ckey, m)
                    if last:
                        complete = jnp.logical_or(complete, adv)
                        deactivate = jnp.logical_or(deactivate, adv)
                    else:
                        advance_inplace = jnp.logical_or(advance_inplace, adv)
                elif not a.is_count:
                    mark(capture, atom.ckey, m)
                    mark(capture_here, atom.ckey, m_here)
                    if last:
                        # skip-completions (m_skip) emit but do NOT kill the
                        # slot: the zero-collect continuation survives to
                        # keep collecting, mirroring the reference's
                        # separate pending state per interpretation
                        complete = jnp.logical_or(complete, m)
                        deactivate = jnp.logical_or(deactivate, m_here)
                    else:
                        advance_inplace = jnp.logical_or(advance_inplace,
                                                         m_here)
                        # skip-advances FORK a continuation at the target
                        # position; the collector stays where it was
                        fork = jnp.logical_or(fork, m_skip)
                        fork_tgt = jnp.where(m_skip, a.pos + 1, fork_tgt)
                        fork_cnt = jnp.where(m_skip, 0, fork_cnt)
                else:
                    newc = st.count + 1
                    maxc = spec.count_cap if a.max_count < 0 else a.max_count
                    can_stay = jnp.logical_and(m_here, newc < maxc)
                    can_adv = jnp.logical_and(m_here, newc >= a.min_count)
                    mark(capture, atom.ckey, m)
                    mark(capture_here, atom.ckey, m_here)
                    if last:
                        complete = jnp.logical_or(complete, can_adv)
                        if a.min_count <= 1:
                            # a skip-collect satisfies min on its first
                            # event: emit, but keep the origin slot alive
                            complete = jnp.logical_or(complete, m_skip)
                        deactivate = jnp.logical_or(
                            deactivate,
                            jnp.logical_and(can_adv, jnp.logical_not(can_stay)))
                    else:
                        fk = jnp.logical_and(can_adv, can_stay)
                        fork = jnp.logical_or(fork, fk)
                        fork_tgt = jnp.where(fk, a.pos + 1, fork_tgt)
                        ai = jnp.logical_and(can_adv,
                                             jnp.logical_not(can_stay))
                        advance_inplace = jnp.logical_or(advance_inplace, ai)
                    # skip-collect into a count atom: fork a collector at
                    # the target position that already HOLDS this event
                    # (captures inherit; count starts at 1); the
                    # zero-collect origin survives.  Known limitation: a
                    # slot firing BOTH an own-position count fork and a
                    # skip fork on one event keeps only the skip fork
                    # (single fork candidate per slot)
                    fork = jnp.logical_or(fork, m_skip)
                    fork_tgt = jnp.where(m_skip, a.pos, fork_tgt)
                    fork_cnt = jnp.where(m_skip, 1, fork_cnt)

        # SEQUENCE: strict continuity
        if spec.state_type == "SEQUENCE":
            no_match = jnp.logical_and(
                st.active,
                jnp.logical_and(ev_ok[None, :], jnp.logical_not(matched_any)))
            kill = jnp.logical_or(kill, no_match)

        # ---- seed (virtual pending slot at position 0) ---------------------
        # an absent FIRST side (`not A and B` at position 0): A's arrival
        # disarms the virtual seed (non-every; `every` re-arms immediately,
        # so the arrival has no lasting effect there — reference:
        # AbsentLogicalPreStateProcessor restart semantics)
        if a0.partner is not None and a0.partner.absent and \
                a0.partner.stream_id == stream_id and not a0.every:
            patom = a0.partner
            pfilt = self._filters[patom.ckey]
            if pfilt is None:
                pc = jnp.ones((K,), jnp.bool_)
            else:
                env_p = dict(env)
                env_p[patom.ref] = tuple(
                    jnp.broadcast_to(cc[None, :], st.active.shape)
                    for cc in ev_cols)
                pc = _seed_eval(pfilt, env_p, K)
            disarm = jnp.logical_and(jnp.logical_and(st.seed_on, ev_ok), pc)
            st = st._replace(seed_on=jnp.logical_and(
                st.seed_on, jnp.logical_not(disarm)))
        seed_match = jnp.zeros((K,), jnp.bool_)
        seed_side = jnp.zeros((K,), jnp.int32)
        for atom, side in [(a0, 0)] + ([(a0.partner, 1)] if a0.partner else []):
            if atom is None or atom.stream_id != stream_id or a0.absent \
                    or atom.absent:
                continue
            filt = self._filters[atom.ckey]
            if filt is None:
                c = jnp.ones((K,), jnp.bool_)
            else:
                env_s = dict(env)
                env_s[atom.ref] = tuple(
                    jnp.broadcast_to(cc[None, :], st.active.shape)
                    for cc in ev_cols)
                c = _seed_eval(filt, env_s, K)
            sm = jnp.logical_and(jnp.logical_and(st.seed_on, ev_ok), c)
            seed_side = jnp.where(
                jnp.logical_and(sm, jnp.logical_not(seed_match)), side,
                seed_side)
            seed_match = jnp.logical_or(seed_match, sm)

        # a seed advances immediately iff the first atom completes with one
        # event: single non-count atom, count with min<=1, or logical OR
        if a0.logical is not None:
            seed_immediate = a0.logical == "OR" or (
                a0.partner is not None and a0.partner.absent)
        elif a0.is_count:
            seed_immediate = a0.min_count <= 1
        else:
            seed_immediate = True
        # ...and keeps a collecting continuation iff a count atom can take more
        seed_keeps = a0.is_count and (a0.max_count < 0 or a0.max_count > 1)

        seed_complete = jnp.logical_and(
            seed_match, jnp.asarray(seed_immediate and S == 1))
        # seed epsilon skip: when EVERY atom before the last is a plain
        # zero-min count, an event matching the last atom completes the
        # whole pattern from the virtual seed with all earlier captures
        # null (e.g. `e1=A?, e2=B` firing on a lone B)
        last_atom = spec.atoms[S - 1]
        seed_skip_possible = (
            S > 1 and len(skip_srcs.get(S - 1, ())) == S - 1 and
            last_atom.logical is None and not last_atom.absent and
            (not last_atom.is_count or last_atom.min_count <= 1))
        seed_skip_hit = jnp.zeros((K,), jnp.bool_)
        if seed_skip_possible and last_atom.stream_id == stream_id:
            lfilt = self._filters[last_atom.ckey]
            if lfilt is None:
                lc = jnp.ones((K,), jnp.bool_)
            else:
                env_l = dict(env)
                env_l[last_atom.ref] = tuple(
                    jnp.broadcast_to(cc[None, :], st.active.shape)
                    for cc in ev_cols)
                # the zero-occurrence interpretation carries NO captures:
                # references to the skipped atoms read null, so a filter
                # like `price > e1[0].price` correctly rejects it
                for aa in spec.all_atoms():
                    if aa.absent or aa is last_atom:
                        continue
                    a_sch = self.schemas[aa.stream_id]
                    nulls = tuple(
                        jnp.full((P, K), ev.null_value(t), d)
                        for t, d in zip(a_sch.types, a_sch.dtypes))
                    env_l[aa.ref] = nulls
                    for di in range(aa.capture_depth):
                        env_l[f"{aa.ref}@{di}"] = nulls
                    env_l[f"{aa.ref}@-1"] = nulls
                lc = _seed_eval(lfilt, env_l, K)
            seed_skip_hit = jnp.logical_and(
                jnp.logical_and(st.seed_on, ev_ok), lc)
            seed_complete = jnp.logical_or(seed_complete, seed_skip_hit)
        seed_spawn = jnp.logical_and(seed_match, jnp.asarray(
            (seed_immediate and S > 1) or not seed_immediate or seed_keeps))
        # spawned seed slot's position / count
        if seed_immediate and not seed_keeps:
            seed_pos, seed_count = 1, 0
        else:
            seed_pos, seed_count = 0, 1
        seed_fork_also = seed_immediate and seed_keeps and S > 1
        # (count atom with min<=1,max>1 at pos 0: one slot advances, one
        #  collects => spawn up to 2; handled by a second seed candidate)

        if not a0.every:
            st = st._replace(seed_on=jnp.logical_and(
                st.seed_on, jnp.logical_not(seed_match)))
            newly_done = jnp.logical_or(jnp.any(complete, axis=0),
                                        seed_complete)
            st = st._replace(done=jnp.logical_or(st.done, newly_done))

        st = st._replace(lmask=lmask_new)

        # ---- phase 4: in-place capture -------------------------------------
        newcaps = {}
        for a in spec.all_atoms():
            if a.absent:
                continue
            ck = a.ckey
            ts_c, cols_c = st.caps[ck]
            here = capture.get(ck)
            if here is None:
                newcaps[ck] = (ts_c, cols_c)
                continue
            D = ts_c.shape[1]
            # PART `count_capture` (observability/phases.py): a count
            # atom's write is one of its D capture rows, picked by the
            # slot's count — D selects over [P, D, K] a column a tick
            with jax.named_scope("count_capture") if a.is_count \
                    else contextlib.nullcontext():
                idx = jnp.clip(st.count, 0, D - 1)
                ncols = tuple(
                    _set_along(c, idx, jnp.broadcast_to(
                        ev_cols[j][None, :], idx.shape), here)
                    for j, c in enumerate(cols_c))
                nts = _set_along(ts_c, idx, jnp.broadcast_to(
                    ev_ts[None, :], idx.shape), here)
            newcaps[ck] = (nts, ncols)
        st = st._replace(caps=newcaps)

        # ---- phase 5: emission gather ([P+1, K]: slot axis + seed row) -----
        emit_mask = jnp.concatenate([complete, seed_complete[None, :]], axis=0)
        emit_ts = jnp.concatenate([
            jnp.where(absent_complete, absent_ts,
                      jnp.broadcast_to(ev_ts[None, :], (P, K))),
            ev_ts[None, :]], axis=0)                      # [P+1,K]
        emit_count = jnp.concatenate(
            [jnp.where(complete, st.count + jnp.where(
                capture_any(capture, F), 1, 0), 0),
             jnp.ones((1, K), jnp.int32)], axis=0)
        emit: Dict[str, Any] = {"mask": emit_mask, "ts": emit_ts,
                                "count": emit_count}
        for a in spec.all_atoms():
            if a.absent:
                continue
            if self.emit_refs is not None and a.ref not in self.emit_refs:
                continue
            ck = a.ckey
            ts_c, cols_c = st.caps[ck]
            D = ts_c.shape[1]
            # the seed emission row's captured atom: position 0 for a
            # single-atom pattern; the LAST atom for an epsilon-skip
            # completion (every earlier capture emits null)
            if S == 1:
                is_seed_cap = (a.pos == 0 and a.stream_id == stream_id)
            else:
                is_seed_cap = (seed_skip_possible and a.pos == S - 1 and
                               a.stream_id == stream_id)
            a_schema2 = self.schemas[a.stream_id]
            seed_cols = tuple(
                jnp.broadcast_to(ev_cols[j][None, None, :], (1, D, K))
                if is_seed_cap else
                jnp.full((1, D, K), ev.null_value(t), c.dtype)
                for j, (c, t) in enumerate(
                    zip(cols_c, a_schema2.types)))
            emit[ck] = (
                jnp.concatenate(
                    [ts_c, jnp.broadcast_to(ev_ts[None, None, :], (1, D, K))
                     if is_seed_cap else jnp.full((1, D, K), -1, jnp.int64)],
                    axis=0),
                tuple(jnp.concatenate([c, sc], axis=0)
                      for c, sc in zip(cols_c, seed_cols)))

        # ---- phase 6: spawn forks + seed -----------------------------------
        # PART `fork_spawn`: the masked ranking of candidates against free
        # slots and the pull of every leaf, the captures among them
        with jax.named_scope("fork_spawn"):
            st = self._spawn(st, fork, fork_tgt, fork_cnt, seed_spawn,
                             seed_pos, seed_count, seed_side, seed_fork_also,
                             stream_id, ev_cols, ev_ts, a0)

        # surviving zero-collect origins revert skip-written captures to
        # null AFTER emission (phase 5) and fork inheritance (phase 6)
        # consumed them: a later fork from the origin must not carry a
        # capture that belongs to the skipped interpretation only
        if skip_marks:
            newcaps2 = dict(st.caps)
            for a in spec.all_atoms():
                msk = skip_marks.get(a.ckey)
                if msk is None or a.absent:
                    continue
                ts_c, cols_c = st.caps[a.ckey]
                D2 = ts_c.shape[1]
                idx2 = jnp.clip(st.count, 0, D2 - 1)
                a_sch = self.schemas[a.stream_id]
                nts2 = _set_along(ts_c, idx2, jnp.full(idx2.shape, -1,
                                                       jnp.int64), msk)
                ncols2 = tuple(
                    _set_along(c, idx2,
                               jnp.full(idx2.shape, ev.null_value(t),
                                        c.dtype), msk)
                    for c, t in zip(cols_c, a_sch.types))
                newcaps2[a.ckey] = (nts2, ncols2)
            st = st._replace(caps=newcaps2)

        # ---- phase 7: in-place advance / kill / deactivate -----------------
        captured_now = capture_any(capture_here, F)
        st = st._replace(
            count=jnp.where(advance_inplace | deactivate, 0,
                            jnp.where(captured_now, st.count + 1,
                                      st.count)).astype(jnp.int32),
            pos=jnp.where(advance_inplace, st.pos + 1,
                          st.pos).astype(jnp.int32),
            lmask=jnp.where(advance_inplace, 0, st.lmask).astype(jnp.int32),
            entry_ts=jnp.where(advance_inplace, ev_ts[None, :], st.entry_ts),
            active=jnp.logical_and(
                st.active,
                jnp.logical_not(jnp.logical_or(kill, deactivate))),
        )
        return st, emit

    # -- spawn ----------------------------------------------------------------
    def _spawn(self, st: PatternState, fork, fork_tgt, fork_cnt, seed_spawn,
               seed_pos, seed_count, seed_side, seed_fork_also, stream_id,
               ev_cols, ev_ts, a0):
        """Allocate free slots for fork/seed candidates.

        Scatter-free formulation (TPU scatters serialize; gathers don't):
        instead of scattering candidates into target slots, each destination
        slot PULLS its candidate.  Slot j (if free) has free-rank r_j; the
        candidate with allocation-rank r_j lands there.  The rank->candidate
        inverse is a one-hot contraction over the tiny NC=P+2 axis, then all
        payload moves are take_along_axis gathers."""
        P, K = st.active.shape
        spec = self.spec

        # candidates: P slot-forks + seed (+ optional second seed continuation)
        extra = 2 if seed_fork_also else 1
        NC = P + extra
        seed2 = jnp.logical_and(seed_spawn, jnp.asarray(seed_fork_also))
        if seed_fork_also:
            cand_valid = jnp.concatenate(
                [fork, seed_spawn[None, :], seed2[None, :]], axis=0)
        else:
            cand_valid = jnp.concatenate([fork, seed_spawn[None, :]], axis=0)

        rank = jnp.cumsum(cand_valid.astype(jnp.int32), axis=0) - 1  # [NC,K]
        free = jnp.logical_not(st.active)                            # [P,K]
        free_rank = jnp.cumsum(free.astype(jnp.int32), axis=0) - 1   # [P,K]
        nfree = jnp.sum(free.astype(jnp.int32), axis=0)              # [K]
        ncand = jnp.sum(cand_valid.astype(jnp.int32), axis=0)

        # destination slot j takes candidate c iff free[j] and
        # rank[c] == free_rank[j] (and candidate exists)
        hot = jnp.logical_and(
            jnp.logical_and(cand_valid[None, :, :],
                            rank[None, :, :] == free_rank[:, None, :]),
            free[:, None, :])                                        # [P,NC,K]
        has_cand = jnp.any(hot, axis=1)                              # [P,K]

        st = st._replace(dropped=st.dropped + jnp.sum(
            jnp.maximum(ncand - nfree, 0).astype(jnp.int64)))
        if st.forked is not None:
            st = st._replace(forked=st.forked + jnp.sum(
                fork, dtype=jnp.int64))

        def pull(cand_field, old_field):
            # one-hot contraction over the tiny NC axis; a take_along_axis
            # here compiles to an element-serialized TPU gather (measured
            # 180ms/step at 131k keys — the whole step budget)
            got = oh_take(cand_field[None, :, :], hot, 1)
            return jnp.where(has_cand, got, old_field)

        # candidate payloads [NC,K]
        fork_pos = fork_tgt    # a.pos+1 of the matched atom (skip-aware)
        if seed_fork_also:
            # first seed candidate: advancing slot (pos 1); second: collector
            cpos = jnp.concatenate(
                [fork_pos,
                 jnp.full((1, K), 1, jnp.int32),
                 jnp.full((1, K), 0, jnp.int32)], axis=0)
            ccount = jnp.concatenate(
                [fork_cnt.astype(jnp.int32),
                 jnp.zeros((1, K), jnp.int32),
                 jnp.ones((1, K), jnp.int32)], axis=0)
        else:
            cpos = jnp.concatenate(
                [fork_pos, jnp.full((1, K), seed_pos, jnp.int32)], axis=0)
            ccount = jnp.concatenate(
                [fork_cnt.astype(jnp.int32),
                 jnp.full((1, K), seed_count, jnp.int32)], axis=0)
        # lmask only matters while the seed STAYS at position 0 collecting
        # the other logical side; an immediately-advancing seed (OR, or
        # AND-with-absent) must start its next position with a CLEAN mask —
        # residue bits corrupt the absent/logical logic of position 1
        seed_lmask = jnp.where(
            seed_spawn, jnp.left_shift(jnp.ones((K,), jnp.int32), seed_side),
            0)[None, :] if (a0.logical is not None and seed_pos == 0) \
            else jnp.zeros((1, K), jnp.int32)
        clmask = jnp.concatenate(
            [jnp.zeros((P, K), jnp.int32)] + [seed_lmask] * extra, axis=0)
        cstart = jnp.concatenate(
            [st.start_ts] + [ev_ts[None, :]] * extra, axis=0)
        centry = jnp.broadcast_to(ev_ts[None, :], (NC, K))

        st = st._replace(
            active=jnp.logical_or(st.active, has_cand),
            pos=pull(cpos, st.pos),
            count=pull(ccount, st.count),
            lmask=pull(clmask, st.lmask),
            start_ts=pull(cstart, st.start_ts),
            entry_ts=pull(centry, st.entry_ts),
        )

        # captures: forks inherit the source slot (post-capture state, which
        # already includes this event); seeds get the incoming event at atom0
        newcaps = {}
        # fork candidate c (< P) sources from slot c; seed candidates are the
        # trailing `extra` rows.  All moves are one-hot contractions over
        # the tiny candidate/slot axes (TPU-serialized gathers avoided).
        seed_taken = jnp.any(hot[:, P:, :], axis=1)              # [P,K]
        fork_hot = hot[:, :P, :]                                 # [P(dst),P(src),K]
        fork_taken = jnp.logical_and(has_cand, jnp.logical_not(seed_taken))
        for a in spec.all_atoms():
            if a.absent:
                continue
            ck = a.ckey
            ts_c, cols_c = st.caps[ck]
            D = ts_c.shape[1]
            seed_has = (a.pos == 0 and a.stream_id == stream_id)
            first_d = (jnp.arange(D) == 0)[None, :, None]
            seed_m = jnp.logical_and(seed_taken[:, None, :],
                                     jnp.ones((1, D, 1), jnp.bool_))

            def merge(c, incoming, nullv):
                # c [P,D,K]; inherited[p,d,k] = sum_src hot[p,src,k]*c[src,d,k]
                inherited = oh_take(c[None, :, :, :],
                                    fork_hot[:, :, None, :], 1)  # [P,D,K]
                out = jnp.where(fork_taken[:, None, :], inherited, c)
                # a recycled seed slot's stale captures clear to NULL (not
                # zero): unfilled branches must decode as null attributes
                clear = jnp.full_like(out, nullv) if nullv is not None \
                    else jnp.zeros_like(out)
                if seed_has:
                    iv = jnp.broadcast_to(incoming[None, None, :],
                                          (P, D, K)).astype(c.dtype)
                    out = jnp.where(
                        jnp.logical_and(seed_m, first_d), iv,
                        jnp.where(seed_m, clear, out))
                else:
                    out = jnp.where(seed_m, clear, out)
                return out

            a_schema = self.schemas[a.stream_id]
            newcaps[ck] = (merge(ts_c, ev_ts, -1),
                           tuple(merge(c, ev_cols[j], ev.null_value(t))
                                 for j, (c, t) in enumerate(
                                     zip(cols_c, a_schema.types))))
        return st._replace(caps=newcaps)

    # -- env ------------------------------------------------------------------
    def _build_env(self, st: PatternState, stream_id: str, ev_cols, ev_ts,
                   in_tabs=()):
        env: Dict[str, Any] = {"__ts__": ev_ts[None, :]}
        # `x in Table` probes: one dense compare against the table's first
        # column snapshot, broadcasting over whatever shape the filter's
        # operand carries ([P,K] slabs here, [B] in plain queries)
        for dep, (tcol0, tvalid) in zip(self.in_deps, in_tabs):
            def probe(vals, _tc=tcol0, _tv=tvalid):
                return jnp.any(
                    jnp.logical_and(vals[..., None] == _tc, _tv), axis=-1)
            env["__in__:" + dep] = probe
        for a in self.spec.all_atoms():
            if a.absent:
                continue
            ts_c, cols_c = st.caps[a.ckey]       # [P,D,K]
            D = ts_c.shape[1]
            env[a.ref] = tuple(c[:, 0, :] for c in cols_c)
            for i in range(D):
                env[f"{a.ref}@{i}"] = tuple(c[:, i, :] for c in cols_c)
            # e1[last]: the deepest FILLED capture row.  st.count is
            # position-local (resets when a fork advances past the count
            # atom), so the fill depth derives from the capture ts plane
            # itself (real event timestamps are > 0; unfilled rows keep
            # their zero init)
            nfill = jnp.sum((ts_c >= 0).astype(jnp.int32), axis=1)  # [P,K]
            last_i = jnp.clip(nfill - 1, 0, D - 1)
            last_oh = jnp.arange(D)[None, :, None] == last_i[:, None, :]
            env[f"{a.ref}@-1"] = tuple(oh_take(c, last_oh, 1)
                                       for c in cols_c)
        return env


def oh_take(c, oh, axis):
    """Gather along a tiny axis as a one-hot contraction (select + reduce).
    TPU-friendly replacement for take_along_axis, whose generic gather
    lowers to element-serialized DMA on TPU."""
    if c.dtype == jnp.bool_:
        return jnp.any(jnp.logical_and(oh, c), axis=axis)
    return jnp.sum(jnp.where(oh, c, jnp.zeros((), c.dtype)), axis=axis,
                   dtype=c.dtype)


def capture_any(capture: Dict[str, Any], F):
    out = F
    for m in capture.values():
        out = jnp.logical_or(out, m)
    return out


def _seed_eval(filt: CompiledExpr, env, K):
    v = filt.fn(env)
    v = jnp.broadcast_to(v, v.shape if v.ndim else (K,))
    if v.ndim == 2:     # [P,K] -> any slot row works; captures are zeroed
        return v[0, :]
    return v


def _set_along(arr, idx, vals, mask):
    """arr[p, idx[p,k], k] = vals[p,k] where mask[p,k]; arr is [P,D,K]."""
    hit = jnp.logical_and(
        jnp.arange(arr.shape[1])[None, :, None] == idx[:, None, :],
        mask[:, None, :])
    return jnp.where(hit, vals[:, None, :].astype(arr.dtype), arr)
