"""pattern_1m_served: traffic, plain reference and comparison.

The deployment is `app.siddhi` beside this file — `pattern_1m`'s app with
`@serve` on its query: per partition key,
    every e1[v==1] -> e2[v==2, p>=e1.p] -> e3[v==3] -> e4[v==4, p>=e3.p]
selecting (e1.key, e1.price, e2.price, e4.price), each send's emission
appended to a device ring and delivered by the drainer thread.  This is the
configuration's own copy of `pattern_1m`'s yardstick (generator, reference,
comparison, limits, `least_bytes`: the same traffic gives the same sends and
the same rows), with two things of its own: an `Attribution` that holds
delivery to SEND ORDER, which the deployment guarantees, and
`ring_least_bytes`.  Everything here is numpy and imports nothing of
siddhi_tpu: it is the yardstick the program is held to, so it must not move
when the program does.
"""
from __future__ import annotations

import numpy as np

from benchmarks.harness.numeric import to_bf16

STAGES = 4
# bytes one event needs on the wire: long key, f32 price, i32 volume, long
# timestamp; and one result row: long key, 3 x f32 price, long timestamp
EVENT_BYTES = 8 + 4 + 4 + 8
ROW_BYTES = 8 + 3 * 4 + 8
# one row slot of the emission as the ring holds it: the timestamp's two
# u32 planes and `kind | valid`, the long key's two planes, three f32 prices;
# and an emission's header: n_valid i64, n_dropped i64, ranks_used i32
SLOT_BYTES = 3 * 4 + 2 * 4 + 3 * 4
HEADER_BYTES = 8 + 8 + 4


def plan(seed: int, traffic: dict, sizes: dict) -> dict:
    """What is drawn once per run: for `key_order: permuted` a seeded
    permutation of the whole key space, so that a key comes round again
    only after every other key has."""
    out = {"n_keys": int(sizes["n_keys"])}
    if traffic["key_order"] == "permuted":
        out["perm"] = np.random.default_rng([seed, 0x9e37]).permutation(
            out["n_keys"]).astype(np.int64)
    return out


def send_keys(i: int, traffic: dict, plan_: dict) -> np.ndarray:
    """The distinct keys of the i-th send of this traffic."""
    kb, n_keys = int(traffic["keys_per_send"]), plan_["n_keys"]
    blocks = n_keys // kb
    lo = (i % blocks) * kb
    if traffic["key_order"] == "contiguous_sweep":
        return np.arange(lo, lo + kb, dtype=np.int64)
    if traffic["key_order"] == "permuted":
        return plan_["perm"][lo:lo + kb]
    raise ValueError(f"unknown key_order {traffic['key_order']!r}")


def make_send(rng, i: int, traffic: dict, plan_: dict, clock_ms: int) -> dict:
    """One send: each key gets its 4 stages in arrival order, prices seeded
    so that each key completes exactly one match (p2 >= p1, p4 >= p3) with a
    payload that differs per key and per send."""
    k = send_keys(i, traffic, plan_)
    kb = k.shape[0]
    r = rng.random((kb, STAGES), np.float32)
    price = np.stack([r[:, 0], r[:, 0] + r[:, 1],
                      r[:, 2], r[:, 2] + r[:, 3]], 1)
    return {
        "cols": [np.repeat(k, STAGES),
                 np.ascontiguousarray(price.reshape(-1)),
                 np.tile(np.arange(1, STAGES + 1, dtype=np.int32), kb)],
        "ts": clock_ms + np.tile(np.arange(STAGES, dtype=np.int64), kb),
        "events": kb * STAGES,
    }


def events_per_send(traffic: dict) -> int:
    return int(traffic["keys_per_send"]) * STAGES


def clock_step_ms(traffic: dict) -> int:
    return 10


def expected_rows(send: dict) -> int:
    """Every key of a send completes exactly one match."""
    return send["events"] // STAGES


def reference(sends: list, plan_: dict) -> list:
    """Plain per-key evaluation of the pattern over each send (4
    consecutive events per key; no partial match is alive at the start of a
    send, because every earlier visit of a key completed its match and
    `every` re-arms only e1).  Rows in key order."""
    out = []
    for s in sends:
        keys, price, vol = s["cols"]
        k = keys.reshape(-1, STAGES)
        p = price.reshape(-1, STAGES)
        v = vol.reshape(-1, STAGES)
        if not bool((k == k[:, :1]).all()):
            raise ValueError("reference expects 4 consecutive rows per key")
        hit = ((v == np.arange(1, STAGES + 1)).all(1) &
               (p[:, 1] >= p[:, 0]) & (p[:, 3] >= p[:, 2]))
        rows = {"k": k[hit, 0], "p1": p[hit, 0], "p2": p[hit, 1],
                "p4": p[hit, 3]}
        out.append(canonical(rows))
    return out


def canonical(rows: dict) -> dict:
    """Rows of one send in the order the comparison uses (by key: a key
    matches once per send, and the program emits rank-major, not in
    arrival order)."""
    order = np.argsort(rows["k"], kind="stable")
    return {n: a[order] for n, a in rows.items()}


class Attribution:
    """Result row -> the send that completes it, by content AND in send
    order: a `key -> send` array written when a send is issued, and the
    newest send any earlier delivery held a row of.  A row of an OLDER send
    than that arrives out of send order and is attributed to no send (-1):
    the harness counts it as a stray row (`failed` >= 1) and its own send
    stays short of it (`rows_missing`), so `correct` is false.  Holds
    whichever thread delivers, as long as a key's result is delivered
    before the key is sent again (a whole pass over the key space later).
    The rows of one delivery are one emission's, in no order among
    themselves: the order held to is that of deliveries."""

    def __init__(self, plan_: dict):
        self.key2send = np.full(plan_["n_keys"], -1, np.int64)
        self.newest = -1

    def on_issue(self, sid: int, send: dict) -> None:
        self.key2send[send["cols"][0][::STAGES]] = sid

    def attribute(self, rows: dict) -> np.ndarray:
        k = rows["k"]
        ok = (k >= 0) & (k < self.key2send.shape[0])
        sids = np.full(k.shape[0], -1, np.int64)
        sids[ok] = self.key2send[k[ok]]
        late = sids < self.newest
        if sids.size:
            self.newest = max(self.newest, int(sids.max()))
        sids[late] = -1
        return sids


# each number compared, with its limit: all are exact comparisons (keys and
# prices are carried through the NFA captures, never computed), so 0
LIMITS = {"rows_missing": 0, "rows_unexpected": 0, "rows_differing": 0}


def compare(got: dict, want: dict) -> dict:
    """One send's delivered rows (canonical order) against the reference's:
    {number: value}, each held to LIMITS."""
    n_got, n_want = got["k"].shape[0], want["k"].shape[0]
    missing = int(np.setdiff1d(want["k"], got["k"]).shape[0])
    unexpected = n_got - (n_want - missing)
    differing = 0
    if n_got == n_want and missing == 0:
        bad = np.zeros(n_want, bool)
        for n in want:
            bad |= got[n] != want[n]
        differing = int(bad.sum())
    return {"rows_missing": missing, "rows_unexpected": max(unexpected, 0),
            "rows_differing": differing}


def control_rows(want: dict) -> dict:
    """What the nearest lower precision would deliver: the reference's rows
    with the f32 payload carried as bfloat16."""
    return {n: (to_bf16(a) if a.dtype == np.float32 else a)
            for n, a in want.items()}


def least_bytes(traffic: dict, sizes: dict, config: dict) -> int:
    """Bytes the ALGORITHM needs to move through HBM for one send, from
    shapes: each touched key's NFA state row read and written, the batch
    columns in, the matched rows out.  Not what today's program moves."""
    kb = int(traffic["keys_per_send"])
    return (2 * kb * int(config["state_bytes_per_key"]) +
            kb * STAGES * EVENT_BYTES + kb * ROW_BYTES)


def ring_least_bytes(traffic: dict, sizes: dict, config: dict) -> int:
    """Bytes the ring's two programs must move through HBM for one send,
    from shapes: the send's emission — `emit_rows` row slots a key of the
    send (which of them hold a row is known only on the device) and its
    header — read and written once by the append (into the ring slot) and
    once by the read (out of it).  Not what today's programs move: they
    copy the emission at its key bucket's width."""
    emission = int(traffic["keys_per_send"]) * int(sizes["emit_rows"]) * \
        SLOT_BYTES + HEADER_BYTES
    return 4 * emission
