"""Block-parallel NFA advance for single-key (non-partitioned) patterns.

Reference behavior (what): StreamPreStateProcessor.java:363-403 — one event
at a time walks every pending state; a non-partitioned `from every e1=A ->
e2=B[...]` query is a single NFA consuming the stream sequentially.

TPU-native design (how): the scan path (pattern.py tick) is semantically
complete but sequential: K=1 batches degrade to E tiny [P,1] ticks per send
(what this step costs on the v5e, by section: PERF.md sections 5-7, the
cells `sequence_within.paced` / `.saturated`).  For the COMMON simple-chain
shape — every atom min=max=1, no logical pairs, no absent — the per-key
advance over a block of E events is computable in S-1 *parallel stages*
instead of E sequential ticks.  The two continuity rules want different
data layouts and share no stage logic, so there are TWO FORMS, chosen once
in `make_block_step` by `spec.state_type` — a fact of the plan, nothing
else:

  PATTERN (`->`), the GRID (`_grid_form`).  A thread waits for its FIRST
  matching event, however far: threads = P slab states + one candidate per
  in-chunk seed event; stage s evaluates filter_s over the [T, W] (thread x
  event) grid in one vectorized shot and a thread advances at its first
  match (cumsum first-true), resolved with one-hot contractions (oh_take)
  — no serialized gathers.  Events go in W-sized chunks under lax.scan so
  the grid stays bounded (quadratic in W, linear in E); pending threads at
  a chunk boundary re-enter the P-slot slab exactly like tick forks.
  Completions leave as [C, T] thread slots and are sorted back into
  arrival order.

  SEQUENCE (`,`), the LINEAR form (`_linear_form`).  Strict continuity: a
  thread only ever meets the NEXT valid event — it matches there or dies —
  so the thread seeded at the r-th valid event reads events r+1 .. r+S-1
  and nothing else.  With the valid events a PREFIX of the E slots (the
  host's selection lists them first: keyslots.valid_first_sel), "next valid
  event" is "next slot" and stage s is a compare of the columns against
  themselves shifted left by s: [E] vectors, static slices and pads, one
  pass — no grid, no lax.scan, no gather.  The slab's carried threads read
  only the block's first S-1 events, a [P] x (S-1) problem beside it.  A
  thread seeded at r completes at event r+S-1 and at no other, so indexed
  by the COMPLETING event the completions are in arrival order already:
  no sort; atom k's capture is the column shifted right by S-1-k.

Known benign divergences from the scan path, documented here because the
scan path is the semantic reference:

- Pendings INSIDE a chunk are unbounded (a burst of seeds that completes
  inside one chunk never touches the P-slot cap), so the block path drops
  strictly fewer states than per-event slot allocation.  The grid meets the
  P slots at every chunk boundary, as the scan path would; the linear form
  has ONE chunk, the block — only the <= S-1 threads still pending at the
  block's end enter the slab, so its `dropped` is never higher than the
  grid's was, and 0 wherever P >= S-1.
- After a non-every pattern completes (`done`), tick keeps advancing slab
  bookkeeping for the rest of the batch; the block path freezes at the
  completion index.  Unobservable through emissions (done gates all future
  matching for the key); resolves on @purge.
- A seed filter that reads ANOTHER atom's captures (pathological) sees
  fresh-slot zeros here, and so does any filter that reads a LATER atom's;
  tick aliases them to slot row 0's captures.
- Capture TIMESTAMP slabs (caps[ck][0]) go stale in the carried state:
  nothing reads them (emission env and filters bind capture COLUMNS only),
  they exist for layout parity with the scan path's packer.
- The linear form writes zeros into the capture columns a pending thread
  has not filled yet; the grid leaves what the slot held.  Nothing reads
  an unfilled capture.
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from . import event as ev
from .pattern import BIG, PatternExec, PatternSpec, oh_take
from .selector import SelectorExec
from .window import NO_WAKEUP, Rows

CHUNK = 128


def block_eligible(spec: PatternSpec) -> bool:
    """Simple chains only: single-count atoms, no logical pairs, no absent
    (timer machinery), PATTERN or SEQUENCE.  Everything else keeps the
    fully-general scan path."""
    for a in spec.atoms:
        if a.absent or a.partner is not None or a.is_count:
            return False
        if a.capture_depth != 1:
            return False
    return spec.state_type in ("PATTERN", "SEQUENCE")


def _chunking(E: int):
    """(W, C): the grid scans a block of E events as C chunks of W."""
    W = min(CHUNK, E)
    return W, (E + W - 1) // W


def block_layout(B: int, P: int, spec: PatternSpec) -> Dict[str, int]:
    """What a send of B events costs the block step, as the
    `siddhi:route_keys` span says a send's layout (`tiers`, `cells`,
    `ticks`, `max_e`) — and so which form ran.  A PATTERN: `ticks` the
    chunks its `lax.scan` walks, `cells` the `[T, W] = [P + W, W]` thread x
    event grid a stage evaluates, over all of them.  A SEQUENCE: `ticks` 1
    (one pass, no scan), `cells` the slots its S-1 stages read — the B
    events' and the P carried threads', once a stage."""
    if spec.state_type == "SEQUENCE":
        return {"tiers": 1, "cells": (spec.n_states - 1) * (P + B),
                "ticks": 1, "max_e": B}
    W, C = _chunking(B)
    return {"tiers": 1, "cells": C * (P + W) * W, "ticks": C, "max_e": B}


class _Chain:
    """What both forms read of one chain on one stream: its atoms, the
    environments their filters are called through, the seed rule."""

    def __init__(self, spec: PatternSpec, pexec: PatternExec, schemas,
                 stream_id: str):
        self.spec, self.pexec, self.schemas = spec, pexec, schemas
        self.atoms = spec.atoms
        self.S, self.P = spec.n_states, pexec.P
        # on[s]: atom s reads THIS stream's events
        self.on = [a.stream_id == stream_id for a in spec.atoms]

    def env(self, ts, in_tabs):
        env = {"__ts__": ts}
        for dep, (tcol0, tvalid) in zip(self.pexec.in_deps, in_tabs):
            def probe(vals, _tc=tcol0, _tv=tvalid):
                return jnp.any(jnp.logical_and(
                    vals[..., None] == _tc, _tv), axis=-1)
            env["__in__:" + dep] = probe
        return env

    @staticmethod
    def bind(env, ref, cols):
        env[ref] = cols
        env[f"{ref}@0"] = cols
        env[f"{ref}@-1"] = cols

    def zeros(self, atom, shape):
        """An atom's capture columns, unfilled."""
        return tuple(jnp.zeros(shape, d)
                     for d in self.schemas[atom.stream_id].dtypes)

    def emits(self, atom) -> bool:
        """Does the selector read this atom's captures (emission pruning)?"""
        refs = self.pexec.emit_refs
        return refs is None or atom.ref in refs

    def cond(self, atom, env, shape):
        filt = self.pexec._filters[atom.ckey]
        if filt is None:
            return jnp.ones(shape, jnp.bool_)
        return jnp.broadcast_to(filt.fn(env), shape)

    def seeds(self, ev_cols, ts, valid, seed_on, done, in_tabs):
        """(seed_fire [W], seed_on'): the events of `ev_cols` that start a
        thread — every one the first atom's filter takes, or without
        `every` the first such while `seed_on`."""
        a0 = self.atoms[0]
        W = ts.shape[0]
        if not self.on[0]:
            return jnp.zeros((W,), jnp.bool_), seed_on
        env0 = self.env(ts, in_tabs)
        for a in self.atoms:
            self.bind(env0, a.ref,
                      ev_cols if a.ref == a0.ref else self.zeros(a, (W,)))
        c0 = self.cond(a0, env0, (W,))
        c0 = jnp.logical_and(jnp.logical_and(c0, valid),
                             jnp.logical_not(done))
        if a0.every:
            return c0, seed_on
        cs0 = jnp.cumsum(c0.astype(jnp.int32))
        seed_fire = jnp.logical_and(jnp.logical_and(c0, cs0 == 1), seed_on)
        return seed_fire, jnp.logical_and(seed_on,
                                          jnp.logical_not(jnp.any(c0)))


def _refill(slab_alive, slab, pending, fresh):
    """Pending in-block threads (`pending` [W], their fields `fresh`) enter
    the slab's free slots by rank; the slab's own live threads keep theirs.
    `slab` / `fresh`: the same pytree of [P] / [W] fields.  Returns
    (active' [P], slab', threads over the P slots)."""
    free = jnp.logical_not(slab_alive)
    rank = jnp.cumsum(pending.astype(jnp.int32)) - 1              # [W]
    free_rank = jnp.cumsum(free.astype(jnp.int32)) - 1            # [P]
    hot = jnp.logical_and(
        jnp.logical_and(free[:, None], pending[None, :]),
        free_rank[:, None] == rank[None, :])                      # [P,W]
    has = jnp.any(hot, axis=1)
    over = jnp.maximum(jnp.sum(pending.astype(jnp.int64)) -
                       jnp.sum(free.astype(jnp.int64)), 0)

    def pull(old_field, new_field):
        got = oh_take(new_field[None, :], hot, 1)
        return jnp.where(has, got, old_field).astype(old_field.dtype)

    return (jnp.logical_or(slab_alive, has),
            jax.tree.map(pull, slab, fresh), over)


def _grid_form(chain: _Chain):
    """PATTERN chains: `advance` scans the block in chunks of a [T, W]
    thread x event grid, `candidates` sorts the [C, T] completion slots
    into arrival order."""
    spec, atoms, S, P = chain.spec, chain.atoms, chain.S, chain.P
    a0 = atoms[0]

    def chunk_advance(in_tabs, carry, xs):
        """One W-event chunk: seeds + S-1 vectorized stages + refill."""
        (active, pos, start_ts, entry_ts, slab_caps, seed_on, done,
         dropped) = carry
        ev_cols, ts, valid, base = xs
        W = ts.shape[0]
        T = P + W
        iota_w = jnp.arange(W, dtype=jnp.int32)

        seed_fire, seed_on = chain.seeds(ev_cols, ts, valid, seed_on, done,
                                         in_tabs)

        if S == 1:
            # single-atom pattern: every seed completes instantly
            comp_valid = jnp.concatenate(
                [jnp.zeros((P,), jnp.bool_), seed_fire])
            comp_idx = jnp.concatenate(
                [jnp.zeros((P,), jnp.int64),
                 base + iota_w.astype(jnp.int64)])
            comp_ts = jnp.concatenate([jnp.zeros((P,), jnp.int64), ts])
            caps_t = {
                a.ref: tuple(
                    jnp.concatenate([jnp.zeros((P,), c.dtype), c])
                    for c in (ev_cols if a.ref == a0.ref
                              else chain.zeros(a, (W,))))
                for a in atoms}
            if not a0.every:
                done = jnp.logical_or(done, jnp.any(comp_valid))
            ncarry = (active, pos, start_ts, entry_ts, slab_caps,
                      seed_on, done, dropped)
            return ncarry, (comp_valid, comp_idx, comp_ts, caps_t)

        # ---- thread arrays [T] ---------------------------------------------
        alive = jnp.concatenate([active, seed_fire])
        cur_pos = jnp.concatenate([pos, jnp.ones((W,), jnp.int32)])
        avail = jnp.concatenate(
            [jnp.zeros((P,), jnp.int32), iota_w + 1])
        start = jnp.concatenate([start_ts, ts])
        entry = jnp.concatenate([entry_ts, ts])
        caps_t = {}
        for a in atoms:
            seed_cols = ev_cols if (a.ref == a0.ref and chain.on[0]) \
                else chain.zeros(a, (W,))
            caps_t[a.ref] = tuple(
                jnp.concatenate([sc, tc.astype(sc.dtype)])
                for sc, tc in zip(slab_caps[a.ref], seed_cols))

        comp_valid = jnp.zeros((T,), jnp.bool_)
        comp_idx = jnp.zeros((T,), jnp.int64)
        comp_ts = jnp.zeros((T,), jnp.int64)

        gate = jnp.logical_not(done)
        # ---- stages (unrolled: S is small) ---------------------------------
        for s in range(1, S):
            a = atoms[s]
            if not chain.on[s]:
                continue
            eligible = jnp.logical_and(alive, cur_pos == s)
            env = chain.env(ts[None, :], in_tabs)
            for other in atoms:
                chain.bind(env, other.ref,
                           tuple(c[None, :] for c in ev_cols)
                           if other.ref == a.ref else
                           tuple(c[:, None] for c in caps_t[other.ref]))
            m = jnp.logical_and(chain.cond(a, env, (T, W)), valid[None, :])
            m = jnp.logical_and(m, iota_w[None, :] >= avail[:, None])
            m = jnp.logical_and(m, eligible[:, None])
            m = jnp.logical_and(m, gate)
            if spec.within is not None:
                m = jnp.logical_and(
                    m, ts[None, :] - start[:, None] <= spec.within)
            cs = jnp.cumsum(m.astype(jnp.int32), axis=1)
            first = jnp.logical_and(m, cs == 1)
            hit = jnp.any(first, axis=1)
            j_hit = oh_take(jnp.broadcast_to(
                iota_w[None, :].astype(jnp.int64), (T, W)), first, 1)
            ts_hit = oh_take(jnp.broadcast_to(ts[None, :], (T, W)),
                             first, 1)
            caps_t[a.ref] = tuple(
                jnp.where(hit,
                          oh_take(jnp.broadcast_to(c[None, :], (T, W)),
                                  first, 1), old)
                for c, old in zip(ev_cols, caps_t[a.ref]))
            avail = jnp.where(hit, (j_hit + 1).astype(jnp.int32), avail)
            entry = jnp.where(hit, ts_hit, entry)
            if s == S - 1:
                comp_valid = jnp.logical_or(comp_valid, hit)
                comp_idx = jnp.where(hit, base + j_hit, comp_idx)
                comp_ts = jnp.where(hit, ts_hit, comp_ts)
                alive = jnp.logical_and(alive, jnp.logical_not(hit))
            else:
                cur_pos = jnp.where(hit, s + 1, cur_pos).astype(jnp.int32)

        if not a0.every:
            # only the FIRST completion emits; it latches `done`
            cstar = jnp.min(jnp.where(comp_valid, comp_idx, BIG))
            comp_valid = jnp.logical_and(comp_valid, comp_idx == cstar)
            done = jnp.logical_or(done, jnp.any(comp_valid))

        # ---- slab refill: surviving seed threads -> free slots -------------
        nactive, (npos, nstart, nentry, ncaps), over = _refill(
            alive[:P],
            (cur_pos[:P], start[:P], entry[:P],
             {a.ref: tuple(tc[:P] for tc in caps_t[a.ref]) for a in atoms}),
            alive[P:],
            (cur_pos[P:], start[P:], entry[P:],
             {a.ref: tuple(tc[P:] for tc in caps_t[a.ref]) for a in atoms}))
        ncarry = (nactive, npos, nstart, nentry, ncaps, seed_on, done,
                  dropped + over)
        return ncarry, (comp_valid, comp_idx, comp_ts, caps_t)

    def advance(carry, cols, ts, valid, in_tabs):
        E = ts.shape[0]
        W, C = _chunking(E)
        pad = C * W - E
        with jax.named_scope("event_load"):
            if pad:
                cols = tuple(jnp.pad(c, (0, pad)) for c in cols)
                ts = jnp.pad(ts, (0, pad))
                valid = jnp.pad(valid, (0, pad))
            xs = (tuple(c.reshape(C, W) for c in cols), ts.reshape(C, W),
                  valid.reshape(C, W),
                  jnp.arange(C, dtype=jnp.int64) * W)
        with jax.named_scope("nfa_advance"):
            return lax.scan(functools.partial(chunk_advance, in_tabs),
                            carry, xs)

    def candidates(comps, cols, ts):
        """Order the [C, T] completion slots by arrival: (valid [CT], ts
        [CT], {ref: captures [CT]})."""
        comp_valid, comp_idx, comp_ts, caps_stack = comps
        C, T = comp_valid.shape
        CT = C * T
        thread_rank = jnp.broadcast_to(
            jnp.arange(T, dtype=jnp.int64)[None, :], (C, T))
        key = jnp.where(comp_valid,
                        comp_idx * (T + 1) + thread_rank,
                        jnp.asarray(BIG, jnp.int64)).reshape(CT)
        order = jnp.argsort(key)
        return (comp_valid.reshape(CT)[order], comp_ts.reshape(CT)[order],
                {a.ref: tuple(c.reshape(CT)[order]
                              for c in caps_stack[a.ref])
                 for a in atoms if chain.emits(a)})

    return advance, candidates


def _linear_form(chain: _Chain):
    """SEQUENCE chains, the events' valid slots a PREFIX of the block:
    `advance` is S-1 shifted compares over `[E]` for the threads seeded in
    the block, a `[P]` walk of the block's first S-1 events for the carried
    ones, and the <= S-1 threads still pending at its end into the slab;
    `candidates` lays the completions out by completing EVENT — arrival
    order as they stand."""
    spec, atoms, S, P, on = (chain.spec, chain.atoms, chain.S, chain.P,
                             chain.on)
    a0 = atoms[0]

    def shl(x, k):
        """x[i + k]: the event k slots on (zeros past the end)."""
        E = x.shape[0]
        if k == 0:
            return x
        return jnp.zeros_like(x) if k >= E else jnp.pad(x[k:], (0, k))

    def shr(x, k):
        """x[i - k]: the event k slots back (zeros before the start)."""
        E = x.shape[0]
        if k == 0:
            return x
        return jnp.zeros_like(x) if k >= E else jnp.pad(x[:E - k], (k, 0))

    def advance(carry, cols, ts, valid, in_tabs):
        with jax.named_scope("nfa_advance"):
            return _advance(carry, cols, ts, valid, in_tabs)

    def _advance(carry, cols, ts, valid, in_tabs):
        (active, pos, start_ts, entry_ts, slab_caps, seed_on, done,
         dropped) = carry
        E = ts.shape[0]
        # the head the carried threads read; the tail of seeds still pending
        H = min(S - 1, E)
        n = jnp.sum(valid.astype(jnp.int32))

        # ---- threads seeded in the block: adv[s][r], the thread seeded at
        # event r has taken events r .. r+s -----------------------------------
        seed_fire, seed_on = chain.seeds(cols, ts, valid, seed_on, done,
                                         in_tabs)
        adv = [seed_fire]
        for s in range(1, S):
            a = atoms[s]
            if not on[s] or s >= E:
                # another stream's atom: event r+s kills the thread if it
                # exists, and no event of this block advances it
                adv.append(jnp.zeros((E,), jnp.bool_))
                continue
            env = chain.env(shl(ts, s), in_tabs)
            for k, other in enumerate(atoms):
                chain.bind(env, other.ref,
                           tuple(shl(c, k) for c in cols)
                           if k <= s and on[k] else chain.zeros(other, (E,)))
            m = jnp.logical_and(adv[s - 1], chain.cond(a, env, (E,)))
            m = jnp.logical_and(m, shl(valid, s))
            if spec.within is not None:
                m = jnp.logical_and(m, shl(ts, s) - ts <= spec.within)
            adv.append(m)

        # ---- carried threads: slot p at pos s meets events 0 .. S-1-s -------
        gate = jnp.logical_not(done)
        alive, cur, start, entry = active, pos, start_ts, entry_ts
        caps_t = dict(slab_caps)
        comp_at = []                                   # per head event, [P]
        for e in range(H):
            ev_e = tuple(c[e] for c in cols)
            at, caps_b = cur, dict(caps_t)
            comp_e = jnp.zeros((P,), jnp.bool_)
            for s in range(1, S):
                a = atoms[s]
                # met: the thread at pos s meets event e and leaves pos s —
                # on to s+1, complete, or (no match, another stream's atom)
                # dead
                met = jnp.logical_and(jnp.logical_and(alive, at == s),
                                      e < n)
                alive = jnp.logical_and(alive, jnp.logical_not(met))
                if not on[s]:
                    continue
                env = chain.env(ts[e], in_tabs)
                for other in atoms:
                    chain.bind(env, other.ref,
                               ev_e if other.ref == a.ref
                               else caps_b[other.ref])
                hit = jnp.logical_and(jnp.logical_and(met, gate),
                                      chain.cond(a, env, (P,)))
                if spec.within is not None:
                    hit = jnp.logical_and(hit, ts[e] - start <= spec.within)
                caps_t[a.ref] = tuple(
                    jnp.where(hit, x, old)
                    for x, old in zip(ev_e, caps_t[a.ref]))
                entry = jnp.where(hit, ts[e], entry)
                if s == S - 1:
                    comp_e = hit
                else:
                    alive = jnp.logical_or(alive, hit)
                    cur = jnp.where(hit, s + 1, cur).astype(jnp.int32)
            comp_at.append(comp_e)

        # ---- carry out: the threads seeded in the last S-1 valid events and
        # still alive are pending, at pos = events they have taken ------------
        if H:
            t0 = jnp.maximum(n - H, 0)
            tail = lambda x: lax.dynamic_slice(x, (t0,), (H,))
            r = t0 + jnp.arange(H, dtype=jnp.int32)
            took = n - r                                # [H], 1 .. H
            taken = jnp.arange(1, S, dtype=jnp.int32)[:, None] == \
                took[None, :]                           # [S-1, H]
            pending = jnp.logical_and(r < n, oh_take(
                jnp.stack([tail(adv[k]) for k in range(S - 1)]), taken, 0))
            fresh_caps = {}
            for k, other in enumerate(atoms):
                fresh_caps[other.ref] = tuple(
                    jnp.where(k < took, tail(shl(c, k)),
                              jnp.zeros((), c.dtype))
                    for c in cols) if k < S - 1 and on[k] \
                    else chain.zeros(other, (H,))
            last_ts = lax.dynamic_slice(ts, (jnp.maximum(n - 1, 0),), (1,))
            active, (pos, start_ts, entry_ts, slab_caps), over = _refill(
                alive, (cur, start, entry, caps_t), pending,
                (took, tail(ts), jnp.broadcast_to(last_ts, (H,)),
                 fresh_caps))
            dropped = dropped + over

        comp_in = adv[S - 1]
        if not a0.every:
            # one seed ever, so one completion: it latches `done`
            done = jnp.logical_or(
                done, jnp.any(jnp.concatenate(comp_at + [comp_in])))
        ncarry = (active, pos, start_ts, entry_ts, slab_caps, seed_on, done,
                  dropped)
        return ncarry, (comp_at, caps_t, comp_in)

    def candidates(comps, cols, ts):
        """H x P slots for the carried threads — by completing event, then
        slab slot; all of them complete before event S-1 — then one slot an
        event for the thread that completes THERE, seeded S-1 events
        before: (valid [N], ts [N], {ref: captures [N]}), N = H P + E."""
        comp_at, caps_fin, comp_in = comps
        E = ts.shape[0]
        cat = jnp.concatenate
        cvalid = cat(comp_at + [shr(comp_in, S - 1)])
        cts = cat([jnp.broadcast_to(ts[e], (P,))
                   for e in range(len(comp_at))] + [ts])
        ccaps = {}
        for k, a in enumerate(atoms):
            if not chain.emits(a):
                continue
            own = tuple(shr(c, S - 1 - k) for c in cols) if on[k] \
                else chain.zeros(a, (E,))
            ccaps[a.ref] = tuple(
                cat([held] * len(comp_at) + [o.astype(held.dtype)])
                for held, o in zip(caps_fin[a.ref], own))
        return cvalid, cts, ccaps

    return advance, candidates


def make_block_step(spec: PatternSpec, pexec: PatternExec, sel: SelectorExec,
                    schemas, packer, stream_id: str, compact_rows: int):
    """Build the (packed, sel_state, raw_cols, raw_ts, sel_idx, key_ref,
    now, in_tabs) -> (packed', sel_state', out, wake) step — same signature
    as the scan step so the runtime drives either interchangeably.  A
    SEQUENCE takes the linear form, a PATTERN the grid (module docstring);
    `sel_idx` lists the valid rows first (keyslots.valid_first_sel)."""
    atoms = spec.atoms
    schema = schemas[stream_id]
    chain = _Chain(spec, pexec, schemas, stream_id)
    advance, candidates = (_linear_form if spec.state_type == "SEQUENCE"
                           else _grid_form)(chain)

    def step(packed, sel_state, raw_cols, raw_ts, sel_idx, key_ref, now,
             in_tabs=()):
        # Every op stands under a `jax.named_scope` SECTION, the names the
        # other pattern programs use (pattern_planner.make_step): op-name
        # metadata a device trace books time by, no equation moves for it
        b32, lo64, hi64, scalars = packed
        B = raw_ts.shape[0]
        with jax.named_scope("event_load"):
            csel = jnp.clip(sel_idx[0], 0, B - 1)                 # [E]
            cols = tuple(c[csel].astype(d)
                         for c, d in zip(raw_cols, schema.dtypes))
            ts = raw_ts[csel]
            valid = sel_idx[0] >= 0
        sq = lambda x: x[..., 0]                 # drop the K=1 axis
        with jax.named_scope("state_load"):
            st = packer.unpack(b32, lo64, hi64, scalars)
            carry = (
                sq(st.active), sq(st.pos), sq(st.start_ts), sq(st.entry_ts),
                {a.ref: tuple(sq(c[:, 0]) for c in st.caps[a.ckey][1])
                 for a in atoms},
                sq(st.seed_on), sq(st.done), st.dropped)
        carry, comps = advance(carry, cols, ts, valid, in_tabs)
        with jax.named_scope("nfa_advance"):
            (factive, fpos, fstart, fentry, fcaps, fseed_on, fdone,
             fdropped) = carry
            if spec.within is not None:
                factive = jnp.logical_and(factive,
                                          now - fstart <= spec.within)

        # ---- write the slab back in packed form ----------------------------
        uq = lambda x: x[..., None]
        with jax.named_scope("state_store"):
            ncapd = {}
            for a in atoms:
                old_ts, _old_cols = st.caps[a.ckey]
                ncapd[a.ckey] = (old_ts, tuple(
                    uq(uq(c)) for c in fcaps[a.ref]))
            nst = st._replace(
                active=uq(factive), pos=uq(fpos),
                count=jnp.zeros_like(st.count),
                lmask=jnp.zeros_like(st.lmask),
                start_ts=uq(fstart), entry_ts=uq(fentry),
                seed_on=uq(fseed_on), done=uq(fdone), dropped=fdropped,
                caps=ncapd)
            nb32, nlo, nhi, nscal = packer.pack(nst)

        # ---- emission: completions in arrival order, run the selector ------
        with jax.named_scope("match_rows"):
            o_valid, o_ts, o_caps = candidates(comps, cols, ts)
            N = o_valid.shape[0]
            env: Dict[str, Any] = {"__ts__": o_ts, "__now__": now}
            for ref, ocols in o_caps.items():
                chain.bind(env, ref, ocols)
            rows = Rows(
                ts=o_ts,
                kind=jnp.full((N,), ev.CURRENT, jnp.int32),
                valid=o_valid,
                seq=jnp.arange(N, dtype=jnp.int64),
                gslot=jnp.zeros((N,), jnp.int32),
                cols=(),
            )
        with jax.named_scope("selector"):
            sel_state, out = sel.process(sel_state, rows, env)
        ots, okind, ovalid, ocols2 = out
        R = min(compact_rows, N)
        with jax.named_scope("emission_compaction"):
            if R < N:
                # rows are arrival-ordered; valid rows beyond the @emit cap
                # drop
                rankv = jnp.cumsum(ovalid.astype(jnp.int32)) - 1
                keep = jnp.logical_and(ovalid, rankv < R)
                n_valid = jnp.sum(keep.astype(jnp.int64))
                n_dropped = jnp.sum(ovalid.astype(jnp.int64)) - n_valid
                out = (ots, okind, keep, ocols2)
            else:
                n_valid = jnp.sum(ovalid.astype(jnp.int64))
                n_dropped = jnp.zeros((), jnp.int64)
            out = (n_valid, n_dropped) + out
        with jax.named_scope("match_rows"):
            wake = jnp.asarray(NO_WAKEUP, jnp.int64)
        return (nb32, nlo, nhi, nscal), sel_state, out, wake

    return step
