"""join_two_streams: traffic, plain reference and comparison of the test
fixture beside this file — the inner join of two `window.length` streams on
an id, sends alternating between the two streams.

    from L#window.length(n) join R#window.length(n) on L.id == R.id
    select L.id as s, L.price as p, R.qty as v

A row that arrives on one side is paired with every row of the OTHER side's
window that has its id, as that window stood before the send; then the
side's own window keeps its last n rows.  The first send of the app meets an
empty window and owes no rows, and so does every later send none of whose
ids the other window holds.

Numpy only, nothing of siddhi_tpu.  Keys and values are carried, never
computed: every comparison is exact.
"""
from __future__ import annotations

import numpy as np

from benchmarks.harness.numeric import to_bf16

SIDES = ("L", "R")
EVENT_BYTES = 8 + 4 + 8            # long id, a 4-byte value, long timestamp
ROW_BYTES = 8 + 4 + 4 + 8


def plan(seed: int, traffic: dict, sizes: dict) -> dict:
    """What the generator keeps between sends: the ids each side's window
    holds (sends are made in the order they are sent), so that a send knows
    how many rows it is owed."""
    return {"window_length": int(sizes["window_length"]),
            "ids": {side: np.zeros(0, np.int64) for side in SIDES}}


def events_per_send(traffic: dict) -> int:
    return int(traffic["events_per_send"])


def clock_step_ms(traffic: dict) -> int:
    return 1


def make_send(rng, i: int, traffic: dict, plan_: dict, clock_ms: int) -> dict:
    """The i-th send goes to L where i is even, to R where it is odd."""
    n = events_per_send(traffic)
    side = SIDES[i % 2]
    other = SIDES[1 - i % 2]
    ids = rng.integers(0, int(traffic["ids"]), n, np.int64)
    value = rng.random(n, np.float32) if side == "L" else \
        rng.integers(1, 9, n, np.int32)
    held = plan_["ids"]
    rows = int((ids[:, None] == held[other][None, :]).sum())
    held[side] = np.concatenate([held[side], ids])[-plan_["window_length"]:]
    return {"stream": side, "cols": [ids, value],
            "ts": np.full(n, clock_ms, np.int64), "events": n, "rows": rows}


def expected_rows(send: dict) -> int:
    return send["rows"]


def reference(sends: list, plan_: dict) -> list:
    """The plain thing: each side's last n rows, and for every send, in
    order, its rows paired with the other side's window as it stands."""
    w = plan_["window_length"]
    window = {side: (np.zeros(0, np.int64), np.zeros(0, dtype))
              for side, dtype in zip(SIDES, (np.float32, np.int32))}
    out = []
    for send in sends:
        side = send["stream"]
        ids, value = send["cols"]
        other_ids, other_value = window[SIDES[1 - SIDES.index(side)]]
        j, k = np.nonzero(ids[:, None] == other_ids[None, :])
        p, v = (value[j], other_value[k]) if side == "L" else \
            (other_value[k], value[j])
        out.append({"s": ids[j], "p": p, "v": v})
        window[side] = (np.concatenate([window[side][0], ids])[-w:],
                        np.concatenate([window[side][1], value])[-w:])
    return out


def canonical(rows: dict) -> dict:
    """By (s, p, v): the program may emit a send's pairs in any order."""
    order = np.lexsort((rows["v"], rows["p"], rows["s"]))
    return {n: a[order] for n, a in rows.items()}


class Attribution:
    """Result row -> the send in flight: delivery is blocking with one send
    outstanding, and a pair carries nothing that names the send that made
    it.  A row delivered while a send that owes none is in flight is
    unexpected there."""

    def __init__(self, plan_: dict):
        self.sid = -1

    def on_issue(self, sid: int, send: dict) -> None:
        self.sid = sid

    def attribute(self, rows: dict) -> np.ndarray:
        return np.full(rows["s"].shape[0], self.sid, np.int64)


LIMITS = {"rows_missing": 0, "rows_unexpected": 0, "rows_differing": 0}


def compare(got: dict, want: dict) -> dict:
    """One send's delivered rows against the reference's, both canonical:
    the counts' difference, and with the count right the rows that differ
    in any column."""
    n_got, n_want = got["s"].shape[0], want["s"].shape[0]
    differing = 0
    if n_got == n_want:
        bad = np.zeros(n_want, bool)
        for n in want:
            bad |= got[n] != want[n]
        differing = int(bad.sum())
    return {"rows_missing": max(n_want - n_got, 0),
            "rows_unexpected": max(n_got - n_want, 0),
            "rows_differing": differing}


def control_rows(want: dict) -> dict:
    """The reference's rows with the f32 payload carried as bfloat16."""
    return {n: (to_bf16(a) if a.dtype == np.float32 else a)
            for n, a in want.items()}


def least_bytes(traffic: dict, sizes: dict, config: dict) -> int:
    """From shapes: the events in, both windows read, one written."""
    return (events_per_send(traffic) + 3 * int(sizes["window_length"])) * \
        EVENT_BYTES
