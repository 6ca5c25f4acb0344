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
instead of E sequential ticks:

  threads = P slab states + one candidate per in-block seed event.
  stage s evaluates filter_s over the [T, W] (thread x event) grid in one
  vectorized shot; a PATTERN thread advances at its first matching event
  (cumsum first-true), a SEQUENCE thread must match the next valid event
  after its previous capture (strict continuity, next-valid gather) or die.
  Both resolve with one-hot contractions (oh_take) — no serialized gathers.

Events are processed in W-sized chunks under lax.scan so the [T, W] grid
stays bounded (quadratic in W, linear in E); pending threads at a chunk
boundary re-enter the P-slot slab exactly like tick forks (overflow counts
into `dropped`).  Known benign divergences from the scan path, documented
here because the scan path is the semantic reference:

- WITHIN-chunk pendings are unbounded (a burst of seeds that completes
  inside one chunk never touches the P-slot cap), so the block path drops
  strictly fewer states than per-event slot allocation.  Chunk-boundary
  pressure is identical (P slots).
- After a non-every pattern completes (`done`), tick keeps advancing slab
  bookkeeping for the rest of the batch; the block path freezes at the
  completion index.  Unobservable through emissions (done gates all future
  matching for the key); resolves on @purge.
- A seed filter that reads ANOTHER atom's captures (pathological) sees
  fresh-slot zeros here; tick aliases it to slot row 0's captures.
- Capture TIMESTAMP slabs (caps[ck][0]) go stale in the carried state:
  nothing reads them (emission env and filters bind capture COLUMNS only),
  they exist for layout parity with the scan path's packer.
"""
from __future__ import annotations

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
    """(W, C): a block of E events is scanned as C chunks of W."""
    W = min(CHUNK, E)
    return W, (E + W - 1) // W


def block_layout(B: int, P: int) -> Dict[str, int]:
    """What a send of B events costs the block step, as the
    `siddhi:route_keys` span says a send's layout (`tiers`, `cells`,
    `ticks`, `max_e`): `ticks` the chunks its `lax.scan` walks, `cells` the
    `[T, W] = [P + W, W]` thread x event grid a stage evaluates, over all
    of them."""
    W, C = _chunking(B)
    return {"tiers": 1, "cells": C * (P + W) * W, "ticks": C, "max_e": B}


def make_block_step(spec: PatternSpec, pexec: PatternExec, sel: SelectorExec,
                    schemas, packer, stream_id: str, compact_rows: int):
    """Build the (packed, sel_state, raw_cols, raw_ts, sel_idx, key_ref,
    now, in_tabs) -> (packed', sel_state', out, wake) step — same signature
    as the scan step so the runtime drives either interchangeably."""
    S = spec.n_states
    atoms = spec.atoms
    P = pexec.P
    schema = schemas[stream_id]
    a0 = atoms[0]
    emit_refs = pexec.emit_refs
    is_seq = spec.state_type == "SEQUENCE"

    def step(packed, sel_state, raw_cols, raw_ts, sel_idx, key_ref, now,
             in_tabs=()):
        def probe_env(env):
            for dep, (tcol0, tvalid) in zip(pexec.in_deps, in_tabs):
                def probe(vals, _tc=tcol0, _tv=tvalid):
                    return jnp.any(jnp.logical_and(
                        vals[..., None] == _tc, _tv), axis=-1)
                env["__in__:" + dep] = probe
            return env

        def bind(env, ref, cols):
            env[ref] = cols
            env[f"{ref}@0"] = cols
            env[f"{ref}@-1"] = cols

        def chunk_advance(carry, xs):
            """One W-event chunk: seeds + S-1 vectorized stages + refill."""
            (active, pos, start_ts, entry_ts, slab_caps, seed_on, done,
             dropped) = carry
            ev_cols, ts, valid, base = xs
            W = ts.shape[0]
            T = P + W
            iota_w = jnp.arange(W, dtype=jnp.int32)

            # ---- seeds -----------------------------------------------------
            if a0.stream_id == stream_id:
                filt0 = pexec._filters[a0.ckey]
                if filt0 is None:
                    c0 = jnp.ones((W,), jnp.bool_)
                else:
                    env0 = probe_env({"__ts__": ts})
                    for a in atoms:
                        bind(env0, a.ref,
                             ev_cols if a.ref == a0.ref else tuple(
                                 jnp.zeros((W,), d)
                                 for d in schemas[a.stream_id].dtypes))
                    c0 = jnp.broadcast_to(filt0.fn(env0), (W,))
                c0 = jnp.logical_and(jnp.logical_and(c0, valid),
                                     jnp.logical_not(done))
                if a0.every:
                    seed_fire = c0
                else:
                    cs0 = jnp.cumsum(c0.astype(jnp.int32))
                    seed_fire = jnp.logical_and(
                        jnp.logical_and(c0, cs0 == 1), seed_on)
                    seed_on = jnp.logical_and(
                        seed_on, jnp.logical_not(jnp.any(c0)))
            else:
                seed_fire = jnp.zeros((W,), jnp.bool_)

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
                        for c in (ev_cols if a.ref == a0.ref else tuple(
                            jnp.zeros((W,), d)
                            for d in schemas[a.stream_id].dtypes)))
                    for a in atoms}
                if not a0.every:
                    done = jnp.logical_or(done, jnp.any(comp_valid))
                ncarry = (active, pos, start_ts, entry_ts, slab_caps,
                          seed_on, done, dropped)
                return ncarry, (comp_valid, comp_idx, comp_ts, caps_t)

            # ---- thread arrays [T] -----------------------------------------
            T_ = T
            alive = jnp.concatenate([active, seed_fire])
            cur_pos = jnp.concatenate([pos, jnp.ones((W,), jnp.int32)])
            avail = jnp.concatenate(
                [jnp.zeros((P,), jnp.int32), iota_w + 1])
            start = jnp.concatenate([start_ts, ts])
            entry = jnp.concatenate([entry_ts, ts])
            caps_t = {}
            for a in atoms:
                seed_cols = ev_cols if (a.ref == a0.ref and
                                        a0.stream_id == stream_id) else \
                    tuple(jnp.zeros((W,), d)
                          for d in schemas[a.stream_id].dtypes)
                caps_t[a.ref] = tuple(
                    jnp.concatenate([sc, tc.astype(sc.dtype)])
                    for sc, tc in zip(slab_caps[a.ref], seed_cols))

            comp_valid = jnp.zeros((T_,), jnp.bool_)
            comp_idx = jnp.zeros((T_,), jnp.int64)
            comp_ts = jnp.zeros((T_,), jnp.int64)

            if is_seq:
                # next_valid[k] = first valid event index >= k (W if none)
                idxs = jnp.where(valid, iota_w, W)
                next_valid = lax.cummin(idxs, axis=0, reverse=True)

                def req_of(av):
                    oh_av = iota_w[None, :] == jnp.clip(av, 0, W - 1)[:, None]
                    nv = oh_take(jnp.broadcast_to(next_valid[None, :],
                                                  (T_, W)), oh_av, 1)
                    exists = jnp.logical_and(av < W, nv < W)
                    return nv, exists

            gate = jnp.logical_not(done)
            # ---- stages (unrolled: S is small) -----------------------------
            for s in range(1, S):
                a = atoms[s]
                eligible = jnp.logical_and(alive, cur_pos == s)
                if a.stream_id != stream_id:
                    if is_seq:
                        # strict continuity: any remaining valid event kills
                        # a thread waiting on another stream's atom
                        _nv, exists = req_of(avail)
                        alive = jnp.logical_and(
                            alive, jnp.logical_not(
                                jnp.logical_and(eligible, exists)))
                    continue
                filt = pexec._filters[a.ckey]
                env = probe_env({"__ts__": ts[None, :]})
                for other in atoms:
                    bind(env, other.ref,
                         tuple(c[None, :] for c in ev_cols)
                         if other.ref == a.ref else
                         tuple(c[:, None] for c in caps_t[other.ref]))
                if filt is None:
                    cond = jnp.ones((T_, W), jnp.bool_)
                else:
                    cond = jnp.broadcast_to(filt.fn(env), (T_, W))
                m = jnp.logical_and(cond, valid[None, :])
                m = jnp.logical_and(m, iota_w[None, :] >= avail[:, None])
                m = jnp.logical_and(m, eligible[:, None])
                m = jnp.logical_and(m, gate)
                if spec.within is not None:
                    m = jnp.logical_and(
                        m, ts[None, :] - start[:, None] <= spec.within)
                if is_seq:
                    nv, exists = req_of(avail)
                    first = jnp.logical_and(
                        m, jnp.logical_and(
                            iota_w[None, :] ==
                            jnp.clip(nv, 0, W - 1)[:, None],
                            exists[:, None]))
                    hit = jnp.any(first, axis=1)
                    # a next event exists but doesn't match: thread dies
                    alive = jnp.logical_and(alive, jnp.logical_not(
                        jnp.logical_and(
                            jnp.logical_and(eligible, exists),
                            jnp.logical_not(hit))))
                else:
                    cs = jnp.cumsum(m.astype(jnp.int32), axis=1)
                    first = jnp.logical_and(m, cs == 1)
                    hit = jnp.any(first, axis=1)
                j_hit = oh_take(jnp.broadcast_to(
                    iota_w[None, :].astype(jnp.int64), (T_, W)), first, 1)
                ts_hit = oh_take(jnp.broadcast_to(ts[None, :], (T_, W)),
                                 first, 1)
                caps_t[a.ref] = tuple(
                    jnp.where(hit,
                              oh_take(jnp.broadcast_to(c[None, :], (T_, W)),
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

            # ---- slab refill: surviving seed threads -> free slots ---------
            slab_alive = alive[:P]
            seed_pending = alive[P:]
            free = jnp.logical_not(slab_alive)
            rank = jnp.cumsum(seed_pending.astype(jnp.int32)) - 1     # [W]
            free_rank = jnp.cumsum(free.astype(jnp.int32)) - 1        # [P]
            hot = jnp.logical_and(
                jnp.logical_and(free[:, None], seed_pending[None, :]),
                free_rank[:, None] == rank[None, :])                  # [P,W]
            has = jnp.any(hot, axis=1)
            dropped = dropped + jnp.maximum(
                jnp.sum(seed_pending.astype(jnp.int64)) -
                jnp.sum(free.astype(jnp.int64)), 0)

            def pull(seed_field, old_field):
                got = oh_take(seed_field[None, :], hot, 1)
                return jnp.where(has, got, old_field)

            ncarry = (
                jnp.logical_or(slab_alive, has),
                pull(cur_pos[P:], cur_pos[:P]).astype(jnp.int32),
                pull(start[P:], start[:P]),
                pull(entry[P:], entry[:P]),
                {a.ref: tuple(pull(tc[P:], tc[:P]) for tc in caps_t[a.ref])
                 for a in atoms},
                seed_on, done, dropped)
            return ncarry, (comp_valid, comp_idx, comp_ts, caps_t)

        # ---- unpack state, chunk the block, scan ---------------------------
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
        with jax.named_scope("state_load"):
            st = packer.unpack(b32, lo64, hi64, scalars)
        E = ts.shape[0]
        W, C = _chunking(E)
        pad = C * W - E
        if pad:
            with jax.named_scope("event_load"):
                cols = tuple(jnp.pad(c, (0, pad)) for c in cols)
                ts = jnp.pad(ts, (0, pad))
                valid = jnp.pad(valid, (0, pad))
        T = P + W

        sq = lambda x: x[..., 0]                 # drop the K=1 axis
        with jax.named_scope("state_load"):
            carry = (
                sq(st.active), sq(st.pos), sq(st.start_ts), sq(st.entry_ts),
                {a.ref: tuple(sq(c[:, 0]) for c in st.caps[a.ckey][1])
                 for a in atoms},
                sq(st.seed_on), sq(st.done), st.dropped)
        with jax.named_scope("event_load"):
            xs = (tuple(c.reshape(C, W) for c in cols), ts.reshape(C, W),
                  valid.reshape(C, W),
                  jnp.arange(C, dtype=jnp.int64) * W)
        with jax.named_scope("nfa_advance"):
            carry, comps = lax.scan(chunk_advance, carry, xs)
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

        # ---- emission: order completions by arrival, run the selector ------
        comp_valid, comp_idx, comp_ts, caps_stack = comps    # [C,T] / nested
        CT = C * T
        with jax.named_scope("match_rows"):
            thread_rank = jnp.broadcast_to(
                jnp.arange(T, dtype=jnp.int64)[None, :], (C, T))
            key = jnp.where(comp_valid,
                            comp_idx * (T + 1) + thread_rank,
                            jnp.asarray(BIG, jnp.int64)).reshape(CT)
            order = jnp.argsort(key)
            o_valid = comp_valid.reshape(CT)[order]
            o_ts = comp_ts.reshape(CT)[order]

            env: Dict[str, Any] = {"__ts__": o_ts, "__now__": now}
            for a in atoms:
                if emit_refs is not None and a.ref not in emit_refs:
                    continue
                ocols = tuple(c.reshape(CT)[order]
                              for c in caps_stack[a.ref])
                bind(env, a.ref, ocols)
            rows = Rows(
                ts=o_ts,
                kind=jnp.full((CT,), ev.CURRENT, jnp.int32),
                valid=o_valid,
                seq=jnp.arange(CT, dtype=jnp.int64),
                gslot=jnp.zeros((CT,), jnp.int32),
                cols=(),
            )
        with jax.named_scope("selector"):
            sel_state, out = sel.process(sel_state, rows, env)
        ots, okind, ovalid, ocols2 = out
        R = min(compact_rows, CT)
        with jax.named_scope("emission_compaction"):
            if R < CT:
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
