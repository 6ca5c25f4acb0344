"""pattern_16m_zipf: traffic, plain reference and comparison.

The deployment is `app.siddhi` beside this file (pattern_1m's text): per
partition key,
    every e1[v==1] -> e2[v==2, p>=e1.p] -> e3[v==3] -> e4[v==4, p>=e3.p]
selecting (e1.key, e1.price, e2.price, e4.price).  What differs is who
sends: keys are drawn from Zipf(1.2) over the whole key space with a hot set
that moves, a key's events carry the key's NEXT stage of the cycle 1 -> 2 ->
3 -> 4 -> 1 (kept across sends), and a stage-2 / stage-4 event may miss its
condition — so partial matches live across sends, pile up in a key's slots
and are released several at once by one event.

Everything here is numpy and plain Python and imports nothing of siddhi_tpu:
it is the yardstick the program is held to, so it must not move when the
program does.  `Nfa` evaluates the query's own conditions on the values; the
generator runs one too, only to learn how many partials a key has alive
before it decides whether an event may miss.
"""
from __future__ import annotations

import time

import numpy as np

from benchmarks.harness.numeric import to_bf16

STAGES = 4
# bytes one event needs on the wire: long key, f32 price, i32 volume, long
# timestamp; and one result row: long key, 3 x f32 price, long timestamp
EVENT_BYTES = 8 + 4 + 4 + 8
ROW_BYTES = 8 + 3 * 4 + 8
# a key with at most this many events in a send is walked a position at a
# time, all such keys at once; a hotter key event by event in plain Python
ROUNDS = 16
# a send issued more than this after the one before is a slow one: a run
# is given up once slow sends in a row have used up the traffic's
# `drain_limit_s` at this much each (`Attribution.on_issue`)
SLOW_SEND_S = 1.0


def zipf_cdf(n: int, exponent: float) -> np.ndarray:
    """P(rank <= r) for r = 1 .. n of Zipf(exponent) truncated EXACTLY to
    1 .. n: the inverse CDF over the finite support (`rng.zipf` clipped
    would pile the whole tail on the last key)."""
    cdf = np.cumsum(np.arange(1, n + 1, dtype=np.float64) ** -exponent)
    cdf /= cdf[-1]
    return cdf


class Nfa:
    """The query per key, plainly: a key's events in arrival order, its
    partial matches (at most `slots`) kept between sends.  `wait[k, s]` is
    the stage partial s of key k waits for (0 = free slot); p1 / p2 / p3
    are what it captured."""

    def __init__(self, n_keys: int, slots: int):
        self.slots = slots
        self.wait = np.zeros((n_keys, slots), np.int8)
        self.p1 = np.zeros((n_keys, slots), np.float32)
        self.p2 = np.zeros((n_keys, slots), np.float32)
        self.p3 = np.zeros((n_keys, slots), np.float32)

    def feed(self, keys, vol, price, draw=None) -> dict:
        """One send.  Returns the rows it completes, {k, p1, p2, p4}.

        `draw` is the generator's: (want_pass, price_pass, price_miss), per
        event.  A stage-2 / stage-4 event then takes `price_pass` if it
        wants to pass or its key already has `slots - 1` partials alive,
        else `price_miss`, written into `price` at the event's turn."""
        order = np.argsort(keys, kind="stable")
        uniq, first, counts = np.unique(keys[order], return_index=True,
                                        return_counts=True)
        out = []
        few = np.nonzero(counts <= ROUNDS)[0]
        for j in range(int(counts[few].max(initial=0))):
            few = few[counts[few] > j]
            self._round(uniq[few], order[first[few] + j], vol, price, draw,
                        out)
        for u in np.nonzero(counts > ROUNDS)[0].tolist():
            self._one_key(int(uniq[u]),
                          order[first[u]:first[u] + counts[u]], vol, price,
                          draw, out)
        cols = [np.concatenate([part[c] for part in out]) if out else
                np.zeros(0, t) for c, t in
                enumerate((np.int64, np.float32, np.float32, np.float32))]
        return dict(zip(("k", "p1", "p2", "p4"), cols))

    def _round(self, k, e, vol, price, draw, out) -> None:
        """One event each of the distinct keys `k` (events `e`)."""
        v = vol[e]
        if draw is not None:
            want, p_pass, p_miss = draw
            gate = (v == 2) | (v == 4)
            alive = (self.wait[k] != 0).sum(1)
            ok = want[e] | (alive >= self.slots - 1)
            price[e] = np.where(gate, np.where(ok, p_pass[e], p_miss[e]),
                                price[e])
        p = price[e]
        m = v == 1
        if m.any():
            km = k[m]
            free = self.wait[km] == 0
            col = free.argmax(1)
            if not free[np.arange(km.size), col].all():
                raise ValueError("a key would need a fifth slot")
            self.wait[km, col] = 2
            self.p1[km, col] = p[m]
        m = v == 2
        if m.any():
            km, pm = k[m], p[m][:, None]
            r, c = np.nonzero((self.wait[km] == 2) & (pm >= self.p1[km]))
            self.wait[km[r], c] = 3
            self.p2[km[r], c] = pm[r, 0]
        m = v == 3
        if m.any():
            km, pm = k[m], p[m]
            r, c = np.nonzero(self.wait[km] == 3)
            self.wait[km[r], c] = 4
            self.p3[km[r], c] = pm[r]
        m = v == 4
        if m.any():
            km, pm = k[m], p[m][:, None]
            r, c = np.nonzero((self.wait[km] == 4) & (pm >= self.p3[km]))
            if r.size:
                kr = km[r]
                out.append((kr, self.p1[kr, c], self.p2[kr, c], pm[r, 0]))
                self.wait[kr, c] = 0

    def _one_key(self, key, e, vol, price, draw, out) -> None:
        """All of one (hot) key's events of a send, one by one."""
        S = self.slots
        wait = self.wait[key].tolist()
        p1, p2, p3 = (a[key].tolist() for a in (self.p1, self.p2, self.p3))
        vs, ps = vol[e].tolist(), price[e].tolist()
        if draw is not None:
            want, p_pass, p_miss = (a[e].tolist() for a in draw)
        rows = []
        for i, v in enumerate(vs):
            if draw is not None and (v == 2 or v == 4):
                ok = want[i] or S - wait.count(0) >= S - 1
                ps[i] = p_pass[i] if ok else p_miss[i]
            p = ps[i]
            if v == 1:
                if 0 not in wait:
                    raise ValueError("a key would need a fifth slot")
                s = wait.index(0)
                wait[s], p1[s] = 2, p
            elif v == 2:
                for s in range(S):
                    if wait[s] == 2 and p >= p1[s]:
                        wait[s], p2[s] = 3, p
            elif v == 3:
                for s in range(S):
                    if wait[s] == 3:
                        wait[s], p3[s] = 4, p
            elif v == 4:
                for s in range(S):
                    if wait[s] == 4 and p >= p3[s]:
                        rows.append((p1[s], p2[s], p))
                        wait[s] = 0
        self.wait[key] = wait
        self.p1[key], self.p2[key], self.p3[key] = p1, p2, p3
        if draw is not None:
            price[e] = ps
        if rows:
            r = np.array(rows, np.float32)
            out.append((np.full(len(rows), key, np.int64), r[:, 0], r[:, 1],
                        r[:, 2]))


def plan(seed: int, traffic: dict, sizes: dict) -> dict:
    """What is drawn once per run — the rank distribution and a seeded
    permutation of the key space, so that hot ranks are scattered over it —
    and what the generator keeps between sends: each key's next stage and
    an `Nfa` of its own."""
    n = int(sizes["n_keys"])
    return {
        "n_keys": n, "slots": int(sizes["slots"]),
        "cdf": zipf_cdf(n, float(traffic["zipf_exponent"])),
        "perm": np.random.default_rng([seed, 0x9e37]).permutation(
            n).astype(np.int64),
        "stage": np.zeros(n, np.int8),
        "nfa": Nfa(n, int(sizes["slots"])),
        "drain_limit_s": float(traffic["drain_limit_s"]),
    }


def send_keys(rng, i: int, traffic: dict, plan_: dict) -> np.ndarray:
    """The key of every event of the i-th send of this traffic, in arrival
    order.  `contiguous_sweep`: a block of keys, four events each, back to
    back (the prefill).  `zipf`: rank ~ Zipf over 1 .. n_keys, key =
    perm[(rank - 1 + shift) mod n_keys], shift = hot_set_shift x (i //
    hot_set_sends): every hot_set_sends sends the hot_set_shift hottest
    ranks pass to keys that were cold."""
    n_keys = plan_["n_keys"]
    if traffic["key_order"] == "contiguous_sweep":
        kb = int(traffic["keys_per_send"])
        lo = (i % (n_keys // kb)) * kb
        return np.repeat(np.arange(lo, lo + kb, dtype=np.int64), STAGES)
    if traffic["key_order"] == "zipf":
        rank0 = np.searchsorted(
            plan_["cdf"], rng.random(int(traffic["events_per_send"])),
            side="right")
        shift = int(traffic["hot_set_shift"]) * \
            (i // int(traffic["hot_set_sends"]))
        return plan_["perm"][(rank0 + shift) % n_keys]
    raise ValueError(f"unknown key_order {traffic['key_order']!r}")


def make_send(rng, i: int, traffic: dict, plan_: dict, clock_ms: int) -> dict:
    """One send.  Each event of a key carries the key's next stage; prices
    of stages 1 and 3 ~ U[0, 0.5); a stage-2 / stage-4 event passes (U[0.5,
    1), at or above every waiting partial's capture) with probability
    `pass_probability` and misses (U[-0.5, 0), below every one) otherwise,
    except that a miss becomes a pass while the key has `slots - 1`
    partials alive: no seed ever meets a full key."""
    keys = send_keys(rng, i, traffic, plan_)
    n = keys.shape[0]
    order = np.argsort(keys, kind="stable")
    uniq, first, counts = np.unique(keys[order], return_index=True,
                                    return_counts=True)
    within = np.arange(n) - np.repeat(first, counts)
    vol = np.empty(n, np.int32)
    vol[order] = (np.repeat(plan_["stage"][uniq], counts) + within) \
        % STAGES + 1
    plan_["stage"][uniq] = (plan_["stage"][uniq] + counts) % STAGES
    half = rng.random(n, np.float32) * np.float32(0.5)
    draw = (rng.random(n) < float(traffic["pass_probability"]),
            half + np.float32(0.5), half - np.float32(0.5))
    price = half.copy()
    rows = plan_["nfa"].feed(keys, vol, price, draw)
    return {
        "cols": [keys, price, vol],
        "ts": clock_ms + np.arange(n, dtype=np.int64) * 8 // n,
        "events": n,
        "rows": int(rows["k"].shape[0]),
    }


def events_per_send(traffic: dict) -> int:
    if traffic["key_order"] == "contiguous_sweep":
        return int(traffic["keys_per_send"]) * STAGES
    return int(traffic["events_per_send"])


def clock_step_ms(traffic: dict) -> int:
    return 10


def expected_rows(send: dict) -> int:
    """What the generator's own walk completed in this send."""
    return send["rows"]


def reference(sends: list, plan_: dict) -> list:
    """Every send, in order, through a fresh `Nfa`: the query's conditions
    on the values, per key in arrival order, partials carried from send to
    send; raises if a key would need a fifth slot.  About 25 s for the
    sends of a 30 s run at 16,777,216 keys (128 prefill sends of 524,288
    events, then ~1,200 of 8,192)."""
    n_keys = plan_.get("n_keys") or \
        1 + max(int(s["cols"][0].max()) for s in sends)
    nfa = Nfa(n_keys, plan_.get("slots", STAGES))
    out = []
    for s in sends:
        keys, price, vol = s["cols"]
        out.append(canonical(nfa.feed(keys, vol, price)))
    return out


def canonical(rows: dict) -> dict:
    """Rows of one send in the order the comparison uses: sorted on every
    column, the key first (a key has several rows a send, and the program
    emits them rank-major, not in arrival order)."""
    order = np.lexsort([rows[n] for n in ("p4", "p2", "p1", "k")])
    return {n: a[order] for n, a in rows.items()}


class Attribution:
    """Result row -> the send that completes it: the send in flight.  The
    schema carries nothing that names a send (a hot key has rows in every
    one), and delivery is blocking, one send outstanding — so a row
    delivered during another send's call counts against both sends.

    It is also what ends a run that cannot be the cell's, with the limit
    the traffic file gives a drain (`drain_limit_s`): an open loop's sends
    have no limit of their own in the harness (PERF.md section 7 asks a
    `benchmark` PR for one, and this goes with it), so a program that takes
    seconds a blocking send — 2.2 s on the chip where a Zipf send is laid
    out as one `distinct keys x hottest count` rectangle — would hold the
    machine for half an hour.  When sends in a row, each issued more than
    SLOW_SEND_S after the one before, have used up `drain_limit_s` at
    SLOW_SEND_S each, the run is given up with an error and exit code 1.
    One long hole — the traced run's planned one, a stall — is one send."""

    clock = staticmethod(time.perf_counter)

    def __init__(self, plan_: dict):
        self.sid = -1
        self.last_issue = None
        self.slow = 0
        self.give_up = plan_.get("drain_limit_s", float("inf")) / SLOW_SEND_S

    def on_issue(self, sid: int, send: dict) -> None:
        self.sid = sid
        now = self.clock()
        gap = 0.0 if self.last_issue is None else now - self.last_issue
        self.last_issue = now
        self.slow = self.slow + 1 if gap > SLOW_SEND_S else 0
        if self.slow >= self.give_up:
            raise RuntimeError(
                f"{self.slow} sends in a row were each issued more than "
                f"{SLOW_SEND_S} s after the one before (the last {gap:.2f} "
                f"s): the system takes seconds a send; giving the run up")

    def attribute(self, rows: dict) -> np.ndarray:
        return np.full(rows["k"].shape[0], self.sid, np.int64)


# each number compared, with its limit: all are exact comparisons (keys and
# prices are carried through the NFA captures, never computed), so 0
LIMITS = {"rows_missing": 0, "rows_unexpected": 0, "rows_differing": 0}


def compare(got: dict, want: dict) -> dict:
    """One send's delivered rows (canonical order) against the reference's:
    {number: value}, each held to LIMITS.  Missing and unexpected count
    rows per key (a key has several); with every key's count right, the
    rows are compared in canonical order, every column."""
    keys = np.union1d(got["k"], want["k"])
    n_got = np.bincount(np.searchsorted(keys, got["k"]),
                        minlength=keys.size)
    n_want = np.bincount(np.searchsorted(keys, want["k"]),
                         minlength=keys.size)
    missing = int(np.maximum(n_want - n_got, 0).sum())
    unexpected = int(np.maximum(n_got - n_want, 0).sum())
    differing = 0
    if not missing and not unexpected:
        bad = np.zeros(want["k"].shape[0], bool)
        for n in want:
            bad |= got[n] != want[n]
        differing = int(bad.sum())
    return {"rows_missing": missing, "rows_unexpected": unexpected,
            "rows_differing": differing}


def control_rows(want: dict) -> dict:
    """What the nearest lower precision would deliver: the reference's rows
    with the f32 payload carried as bfloat16."""
    return {n: (to_bf16(a) if a.dtype == np.float32 else a)
            for n, a in want.items()}


def least_bytes(traffic: dict, sizes: dict, config: dict) -> int:
    """Bytes the ALGORITHM needs to move through HBM for one send, from
    shapes: each touched key's NFA state row read and written (the
    expected number of distinct keys among the send's draws), the events
    in, the matched rows out (a row every four events).  Not what today's
    program moves."""
    n = events_per_send(traffic)
    if traffic["key_order"] == "zipf":
        cdf = zipf_cdf(int(sizes["n_keys"]), float(traffic["zipf_exponent"]))
        p = np.diff(cdf, prepend=0.0)
        touched = int(round(float((-np.expm1(n * np.log1p(-p))).sum())))
    else:
        touched = int(traffic["keys_per_send"])
    return (2 * touched * int(config["state_bytes_per_key"]) +
            n * EVENT_BYTES + (n // STAGES) * ROW_BYTES)
