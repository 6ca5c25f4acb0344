"""kleene_1m: traffic, plain reference and comparison.

The deployment is `app.siddhi` beside this file: per partition key,
    every e1[v==1] -> e2[v==2, p>=e1.p]<1:5> -> e3[v==3]
selecting (e1.key, e1.price, e2[0].price, e2[last].price, e3.price).

The count atom's semantics are THE PROGRAM'S, written down from its
behaviour and pinned in tests/test_kleene_1m_config.py (config.json `assumed`
has them beside the upstream reading they may differ from):

- an A opens a COLLECTOR holding its price;
- a B at or above a collector's A is collected by it (every such collector,
  each on its own).  While the collector then holds fewer than five, it stays
  AND a copy of what it holds so far walks on to wait for the C (an ADVANCED
  prefix: upstream's query guide reads as ONE accumulating StateEvent, so no
  copy — the departure); on its fifth it walks on itself;
- a C closes EVERY advanced prefix of the key — one row each: the A, the first
  and the last B the prefix holds, the C — and touches no collector: a
  collector outlives the C and goes on collecting later Bs for its old A until
  it holds five (upstream: the StateEvent is consumed by the C — the second
  departure);
- nothing else ends a thread (PATTERN, no `within`), so a sixth B is ignored
  by a prefix that walked on, and a B below every open A is dropped.

Everything here is numpy and imports nothing of siddhi_tpu: it is the
yardstick the program is held to, so it must not move when the program does.
`Threads` keeps the two kinds apart — collectors in a slab with free places,
advanced prefixes on a per-key stack a C empties — which is not how the
program lays its slots out; rows are compared as a multiset a key.
"""
from __future__ import annotations

import numpy as np

from benchmarks.harness.numeric import to_bf16

VISIT = 4                  # events a key hands over a visit
DEPTH = 5                  # B<1:5>
A, B, C = 1, 2, 3          # the stage tag in `volume`
# bytes one event needs on the wire: long key, f32 price, i32 volume, long
# timestamp; and one result row: long key, 4 x f32 price, long timestamp
EVENT_BYTES = 8 + 4 + 4 + 8
ROW_BYTES = 8 + 4 * 4 + 8
COLUMNS = ("k", "p1", "b0", "bl", "p3")
# bytes one emission SLOT takes on the u32 wire, a row or filler: the
# timestamp's two words and kind | valid, the long key's two, four prices
SLOT_BYTES = 4 * (3 + 2 + 4)
# how wide the reference's own slabs are when nothing sizes them (`peaks`)
UNSIZED = 64


class Threads:
    """The query per key, plainly: a key's events in arrival order, its
    threads kept between sends.  Collectors: `held[k, c]` Bs collected (-1 =
    a free place), `p1` / `b0` / `bl` the A, the first and the newest B.
    Advanced prefixes: `n_adv[k]` of them on a stack, `a1` / `a0` / `al`.
    `width`: the most LIVE THREADS (collectors + advanced) a key may have —
    the program's `slots`; `row_cap`: the most rows a key may be owed in one
    send — its `@emit(rows)`.  One more of either is an error, not a silent
    loss.  `peak_threads`, `peak_rows`: the most live threads any key had
    after any event, and the most rows one key was owed in one send;
    `shy_events`: how many Bs the generator's valve (`feed`, `shy`) drew
    below every A."""

    def __init__(self, n_keys: int, width: int, row_cap: int):
        self.width, self.row_cap = width, row_cap
        self.held = np.full((n_keys, width), -1, np.int8)
        self.p1 = np.zeros((n_keys, width), np.float32)
        self.b0 = np.zeros((n_keys, width), np.float32)
        self.bl = np.zeros((n_keys, width), np.float32)
        self.n_adv = np.zeros(n_keys, np.int16)
        self.a1 = np.zeros((n_keys, width), np.float32)
        self.a0 = np.zeros((n_keys, width), np.float32)
        self.al = np.zeros((n_keys, width), np.float32)
        self.peak_threads = 0
        self.peak_rows = 0
        self.shy_events = 0

    def feed(self, keys: np.ndarray, price: np.ndarray, vol: np.ndarray,
             shy: np.ndarray = None) -> dict:
        """One send: `keys` [Kb] distinct, `price` / `vol` [Kb, E] each
        key's events in arrival order.  One tick an event column, every key
        at once.  Returns the rows it completes.

        `shy` is the generator's: a price below every A, per event.  A B
        takes it, written into `price` at the event's turn, where the key is
        so full that a B collected by all of its c collectors could need a
        thread past `width` (live + c > width) or a row past `row_cap` (rows
        owed so far this send + advanced + c > row_cap): nobody collects it,
        and no fork and no row is ever lost."""
        names = ("held", "p1", "b0", "bl", "n_adv", "a1", "a0", "al")
        # a contiguous block (the sweep's) is worked on in place
        block = keys.size and int(keys[-1]) - int(keys[0]) + 1 == keys.size \
            and bool((np.diff(keys) == 1).all())
        at = slice(int(keys[0]), int(keys[-1]) + 1) if block else keys
        sub = {n: getattr(self, n)[at] for n in names}
        out, owed = [], np.zeros(keys.shape[0], np.int64)
        for j in range(price.shape[1]):
            if shy is not None:
                c = (sub["held"] >= 0).sum(1)
                tight = (vol[:, j] == B) & (
                    (sub["n_adv"] + 2 * c > self.width) |
                    (owed + sub["n_adv"] + c > self.row_cap))
                price[tight, j] = shy[tight, j]
                self.shy_events += int(tight.sum())
            rows = self._tick(sub, vol[:, j], price[:, j])
            if rows is not None:
                at, cols = rows
                owed += np.bincount(at, minlength=owed.shape[0])
                out.append((keys[at],) + cols)
        if not block:
            for n in names:
                getattr(self, n)[keys] = sub[n]
        self.peak_rows = max(self.peak_rows, int(owed.max(initial=0)))
        if self.peak_rows > self.row_cap:
            raise ValueError(
                f"a key is owed {self.peak_rows} rows in one send, more "
                f"than the deployment's `emit_rows` {self.row_cap}: the "
                f"program would drop a row")
        cols = [np.concatenate([part[c] for part in out]) if out else
                np.zeros(0, np.int64 if c == 0 else np.float32)
                for c in range(len(COLUMNS))]
        return dict(zip(COLUMNS, cols))

    def _tick(self, s: dict, v: np.ndarray, p: np.ndarray):
        """One event each of the keys of `s`.  Returns (key position of each
        row, (p1, b0, bl, p3)) or None.  The slabs are written through
        their flat views, place `k * W + c`."""
        held, n_adv, W = s["held"], s["n_adv"], self.width
        flat = {n: s[n].reshape(-1) for n in ("held", "p1", "b0", "bl",
                                              "a1", "a0", "al")}
        rows = None
        k = np.nonzero(v == A)[0]
        if k.size:
            free = held[k] < 0
            c = free.argmax(1)
            self._room(free[np.arange(k.size), c].all())
            flat["held"][k * W + c] = 0
            flat["p1"][k * W + c] = p[k]
        if (v == B).any():
            at = np.flatnonzero((held >= 0) & (p[:, None] >= s["p1"]) &
                                (v == B)[:, None])
            k = at // W
            first = at[flat["held"][at] == 0]
            flat["b0"][first] = p[first // W]
            flat["bl"][at] = p[k]
            flat["held"][at] += 1
            # the collector's prefix walks on: a copy while it holds fewer
            # than five, itself (its place freed) on the fifth.  `at` comes
            # key by key in place order: a key's r-th goes on its stack at
            # n_adv + r
            dst = n_adv[k] + (np.arange(k.size) - np.searchsorted(k, k))
            self._room(dst.max(initial=-1) < W)
            dst += k * W
            flat["a1"][dst] = flat["p1"][at]
            flat["a0"][dst] = flat["b0"][at]
            flat["al"][dst] = p[k]
            n_adv += np.bincount(k, minlength=n_adv.size).astype(n_adv.dtype)
            flat["held"][at[flat["held"][at] == DEPTH]] = -1
        k = np.nonzero((v == C) & (n_adv > 0))[0]
        if k.size:
            at = np.repeat(k, n_adv[k])
            i = np.arange(at.size) - np.repeat(
                np.cumsum(n_adv[k]) - n_adv[k], n_adv[k])
            src = at * W + i
            rows = (at, (flat["a1"][src], flat["a0"][src], flat["al"][src],
                         p[at]))
            n_adv[k] = 0
        live = (held >= 0).sum(1) + n_adv
        self._room(int(live.max(initial=0)) <= W)
        self.peak_threads = max(self.peak_threads, int(live.max(initial=0)))
        return rows

    def _room(self, ok) -> None:
        if not ok:
            raise ValueError(
                f"a key would need more than {self.width} live threads "
                f"(the deployment's `slots`): the program would lose a fork")


def plan(seed: int, traffic: dict, sizes: dict) -> dict:
    """What the generator keeps between sends, per key: where its episode
    stands (`left`: -1 the next event is an A, m > 0 Bs still to come, 0 the
    C), the episode's A, and a `Threads` of its own — the only way to know
    what a send is owed before it is sent."""
    n = int(sizes["n_keys"])
    slots = int(sizes.get("slots", UNSIZED))
    emit_rows = int(sizes.get("emit_rows", 4 * UNSIZED))
    return {
        "n_keys": n, "slots": slots, "emit_rows": emit_rows,
        "valve": "slots" in sizes,
        "left": np.full(n, -1, np.int8),
        "a_price": np.zeros(n, np.float32),
        "threads": Threads(n, slots, emit_rows),
    }


def send_keys(i: int, traffic: dict, plan_: dict) -> np.ndarray:
    """The distinct keys of the i-th send: a contiguous block, the blocks
    sweeping the key space round and round."""
    kb, n_keys = int(traffic["keys_per_send"]), plan_["n_keys"]
    lo = (i % (n_keys // kb)) * kb
    return np.arange(lo, lo + kb, dtype=np.int64)


def make_send(rng, i: int, traffic: dict, plan_: dict, clock_ms: int) -> dict:
    """One send: each key's NEXT four events of its endless run of episodes
    `A, n x B, C` — A = (1, u); n ~ U{1 .. b_max} Bs = (2, A's + u) with
    probability `pass_probability`, else (2, A's - u - 0.01), which fails its
    own A and may pass an older one; C = (3, u); u ~ U[0, 1) float32 — laid
    out key by key, a key's four in arrival order."""
    k = send_keys(i, traffic, plan_)
    kb = k.shape[0]
    left, a_price = plan_["left"][k], plan_["a_price"][k]
    b_max, p_pass = int(traffic["b_max"]), float(traffic["pass_probability"])
    price = np.empty((kb, VISIT), np.float32)
    vol = np.empty((kb, VISIT), np.int32)
    # drawn whether or not the valve is open: the same seed, the same events
    shy = -1 - rng.random((kb, VISIT), np.float32)
    for j in range(VISIT):
        u = rng.random(kb, np.float32)
        n_b = rng.integers(1, b_max + 1, kb).astype(np.int8)
        passes = rng.random(kb) < p_pass
        is_a, is_c = left < 0, left == 0
        b_price = np.where(passes, a_price + u,
                           a_price - u - np.float32(0.01))
        price[:, j] = np.where(is_a | is_c, u, b_price)
        vol[:, j] = np.where(is_a, A, np.where(is_c, C, B))
        a_price = np.where(is_a, u, a_price)
        left = np.where(is_a, n_b, np.where(is_c, -1, left - 1)) \
            .astype(np.int8)
    plan_["left"][k], plan_["a_price"][k] = left, a_price
    rows = plan_["threads"].feed(k, price, vol,
                                 shy if plan_["valve"] else None)
    return {
        "cols": [np.repeat(k, VISIT), np.ascontiguousarray(price.reshape(-1)),
                 np.ascontiguousarray(vol.reshape(-1))],
        "ts": clock_ms + np.tile(np.arange(VISIT, dtype=np.int64), kb),
        "events": kb * VISIT,
        "rows": int(rows["k"].shape[0]),
    }


def events_per_send(traffic: dict) -> int:
    return int(traffic["keys_per_send"]) * VISIT


def clock_step_ms(traffic: dict) -> int:
    return 10


def expected_rows(send: dict) -> int:
    """What the generator's own walk completed in this send."""
    return send["rows"]


def reference(sends: list, plan_: dict) -> list:
    """Every send since the app started, in order, through a fresh `Threads`
    as wide as the deployment's `slots` (it raises where a key would need
    one more).  Rows in the order they are made (the caller orders what it
    compares: `canonical`).  Says its own peaks: `plan_["peaks"]` and one
    printed line."""
    n_keys = plan_.get("n_keys") or \
        1 + max(int(s["cols"][0].max()) for s in sends)
    threads = Threads(n_keys, plan_.get("slots", UNSIZED),
                      plan_.get("emit_rows", 4 * UNSIZED))
    out = []
    for s in sends:
        keys, price, vol = s["cols"]
        k = keys.reshape(-1, VISIT)
        if not bool((k == k[:, :1]).all()):
            raise ValueError("reference expects 4 consecutive rows per key")
        out.append(threads.feed(
            k[:, 0], price.reshape(-1, VISIT), vol.reshape(-1, VISIT)))
    plan_["peaks"] = {"threads": threads.peak_threads,
                      "rows": threads.peak_rows}
    print(f"kleene_1m reference over {len(sends)} sends: peak live threads "
          f"a key {threads.peak_threads} (slots {threads.width}), peak rows "
          f"a key a send {threads.peak_rows} (emit_rows {threads.row_cap})",
          flush=True)
    return out


def canonical(rows: dict) -> dict:
    """Rows of one send in the order the comparison uses: sorted on every
    column, the key first (a key has several rows a send, and the program
    emits them rank-major, not in arrival order)."""
    order = np.lexsort([rows[n] for n in reversed(COLUMNS)])
    return {n: a[order] for n, a in rows.items()}


class Attribution:
    """Result row -> the send that completes it, by content: a `key -> send`
    array written when a send is issued.  Holds whichever thread delivers, as
    long as a key's rows are delivered before the key is sent again (a whole
    pass over the key space later)."""

    def __init__(self, plan_: dict):
        self.key2send = np.full(plan_["n_keys"], -1, np.int64)

    def on_issue(self, sid: int, send: dict) -> None:
        self.key2send[send["cols"][0][::VISIT]] = sid

    def attribute(self, rows: dict) -> np.ndarray:
        k = rows["k"]
        ok = (k >= 0) & (k < self.key2send.shape[0])
        sids = np.full(k.shape[0], -1, np.int64)
        sids[ok] = self.key2send[k[ok]]
        return sids


# each number compared, with its limit: all are exact comparisons (keys and
# prices are carried through the NFA captures, never computed), so 0
LIMITS = {"rows_missing": 0, "rows_unexpected": 0, "rows_differing": 0}


def compare(got: dict, want: dict) -> dict:
    """One send's delivered rows (canonical order) against the reference's:
    {number: value}, each held to LIMITS.  Missing and unexpected count rows
    per key (a key has several); with every key's count right, the rows are
    compared in canonical order, every column."""
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
    shapes: each touched key's NFA state row read and written once, the
    events in, the delivered rows out (`rows_per_event`, the reference's
    steady share, stated in config.json).  Not what today's program moves."""
    kb = int(traffic["keys_per_send"])
    rows = int(round(kb * VISIT * float(config["rows_per_event"])))
    return (2 * kb * state_bytes_per_key(int(sizes["slots"])) +
            kb * VISIT * EVENT_BYTES + rows * ROW_BYTES)


def state_bytes_per_key(slots: int) -> int:
    """A key's NFA state, from shapes: per slot six 4-byte words (active,
    pos, count, lmask as i32 rows; two i64 stamps) and seven capture rows
    (e1, 5 x e2, e3) of a long timestamp, a long key, a float price and an
    int volume; per key two flags."""
    return slots * (4 * 4 + 2 * 8 + (1 + DEPTH + 1) * (8 + 8 + 4 + 4)) + 8


def peaks(seed: int, traffic: dict, sizes: dict, n_sends: int) -> dict:
    """What `slots` and `emit_rows` are derived from: the reference's peaks
    over the first `n_sends` sends of this traffic, in slabs nothing sizes
    (so with the generator's valve shut)."""
    wide = {k: v for k, v in sizes.items()
            if k not in ("slots", "emit_rows")}
    plan_ = plan(seed, traffic, wide)
    rows = events = 0
    clock = 1000
    for i in range(n_sends):
        clock += clock_step_ms(traffic)
        s = make_send(np.random.default_rng([seed, i]), i, traffic, plan_,
                      clock)
        rows, events = rows + s["rows"], events + s["events"]
    t = plan_["threads"]
    return {"threads": t.peak_threads, "rows": t.peak_rows,
            "rows_per_event": rows / events}
