"""sequence_within: traffic, plain reference and comparison — the
un-partitioned sequence

    from every e1=S[volume == 1], e2=S[volume == 2 and price > e1.price]
      within 1 sec
    select e1.price as p1, e2.price as p2

ONE NFA consumes the whole stream in arrival order, whatever send brought an
event.  `every` seeds a thread at each `volume == 1` event; a SEQUENCE is
strict, so the thread matches iff the NEXT event has `volume == 2`, a greater
price and an event time at most 1,000 ms after e1's — else it dies.  So the
matches are exactly the adjacent pairs (i, i + 1) of the concatenated stream
that satisfy all four, and the row `(p1, p2)` belongs to the send that holds
event i + 1: a thread pending at a send's end is carried into the next send,
and a send that starts more than 1,000 ms later finds it expired.

Numpy only, nothing of siddhi_tpu: it is the yardstick the program is held
to.  Prices are carried, never computed, and the one comparison (`>`) is
exact in float32: every number compared is exact, rows in delivery order.
"""
from __future__ import annotations

import numpy as np

from benchmarks.harness.numeric import to_bf16

# bytes one event needs on the wire: long symbol, f32 price, i32 volume,
# long timestamp; and one result row: f32 p1, f32 p2, long timestamp
EVENT_BYTES = 8 + 4 + 4 + 8
ROW_BYTES = 4 + 4 + 8
# one event-time millisecond per this many events of a send
EVENTS_PER_MS = 128
WITHIN_MS = 1000
# app.siddhi's `@capacity(slots='8')`: the pending threads the NFA keeps
# between sends, and what one costs — active, pos, start and entry time,
# and per atom (e1, e2) a capture's time and its three columns
SLOTS = 8
SLOT_BYTES = 1 + 4 + 8 + 8 + 2 * (8 + 8 + 4 + 4)


def plan(seed: int, traffic: dict, sizes: dict) -> dict:
    """What the generator keeps between sends (they are made in the order
    they are sent): the stream's last event, which the next send's first
    may complete, the event time the feed's pauses have added so far, and
    the event-time offsets within a send."""
    return {"last": None, "paused_ms": 0,
            "ts_offsets": np.arange(events_per_send(traffic),
                                    dtype=np.int64) // EVENTS_PER_MS}


def events_per_send(traffic: dict) -> int:
    return int(traffic["events_per_send"])


def clock_step_ms(traffic: dict) -> int:
    return max(1, events_per_send(traffic) // EVENTS_PER_MS)


def matches(v, p, ts) -> np.ndarray:
    """Which adjacent pairs (i, i + 1) of the events `v`, `p`, `ts` match:
    bool [n - 1]."""
    return (v[:-1] == 1) & (v[1:] == 2) & (p[1:] > p[:-1]) & \
        (ts[1:] - ts[:-1] <= WITHIN_MS)


def with_carried(last, send):
    """(v, p, ts) of the send with the stream's event before it in front,
    where there is one."""
    _symbol, p, v = send["cols"]
    ts = send["ts"]
    if last is None:
        return v, p, ts
    return (np.concatenate([last[:1], v]), np.concatenate([last[1:2], p]),
            np.concatenate([last[2:], ts]))


def last_of(send) -> tuple:
    _symbol, p, v = send["cols"]
    return v[-1], p[-1], send["ts"][-1]


def make_send(rng, i: int, traffic: dict, plan_: dict, clock_ms: int) -> dict:
    """One send, every column new: one rng call a column (a send made
    inside the closed loop has to cost well under a send's time).  Volumes
    are i.i.d. in {1, 2, 3} with `volume_mix`'s probabilities.  Every
    `pause_every_sends`-th send starts `pause_ms` after the last event of
    the send before it: the feed paused.  It records the event it may
    complete and the rows it is owed."""
    n = events_per_send(traffic)
    last = plan_["last"]
    start = clock_ms + plan_["paused_ms"]
    if last is not None and i % int(traffic["pause_every_sends"]) == 0:
        resumed = int(last[2]) + int(traffic["pause_ms"])
        plan_["paused_ms"] += resumed - start
        start = resumed
    one, two, _three = traffic["volume_mix"]
    u = rng.random(n, np.float32)
    volume = np.ones(n, np.int32)
    volume += u >= np.float32(one)
    volume += u >= np.float32(one + two)
    send = {
        "cols": [rng.integers(0, int(traffic["symbols"]), n, np.int64),
                 rng.random(n, np.float32), volume],
        "ts": start + plan_["ts_offsets"],
        "events": n,
        "carried": last,
    }
    send["rows"] = int(matches(*with_carried(last, send)).sum())
    plan_["last"] = last_of(send)
    return send


def expected_rows(send: dict) -> int:
    """The pairs whose second event is in this send — the carried event's
    pair among them."""
    return send["rows"]


def reference(sends: list, plan_: dict) -> list:
    """The plain thing: the stream is every send since the app started,
    end to end; a send's rows are the matching adjacent pairs whose second
    event it holds, in arrival order.  Send by send with the one event
    before it in front — the same pairs as over the concatenated stream,
    without holding a run's whole stream twice."""
    out, last = [], None
    for send in sends:
        v, p, ts = with_carried(last, send)
        first = np.nonzero(matches(v, p, ts))[0]
        out.append({"p1": p[first], "p2": p[first + 1]})
        last = last_of(send)
    return out


def canonical(rows: dict) -> dict:
    """Delivery order, untouched: the guarantee is arrival order of the
    completing event."""
    return rows


class Attribution:
    """Result row -> the send in flight: delivery is blocking with one call
    at a time, and a match carries nothing that names the send that made
    it.  A row delivered while a send that owes none is in flight is
    unexpected there."""

    def __init__(self, plan_: dict):
        self.sid = -1

    def on_issue(self, sid: int, send: dict) -> None:
        self.sid = sid

    def attribute(self, rows: dict) -> np.ndarray:
        return np.full(rows["p1"].shape[0], self.sid, np.int64)


LIMITS = {"rows_missing": 0, "rows_unexpected": 0, "rows_differing": 0}


def compare(got: dict, want: dict) -> dict:
    """One send's delivered rows against the reference's, both in delivery
    order: the counts' difference, and with the count right the rows that
    differ in either price (row j against row j)."""
    n_got, n_want = got["p1"].shape[0], want["p1"].shape[0]
    differing = 0
    if n_got == n_want:
        differing = int(((got["p1"] != want["p1"]) |
                         (got["p2"] != want["p2"])).sum())
    return {"rows_missing": max(n_want - n_got, 0),
            "rows_unexpected": max(n_got - n_want, 0),
            "rows_differing": differing}


def control_rows(want: dict) -> dict:
    """The reference's rows with both prices carried as bfloat16: the
    nearest precision below the configuration's, which no exact comparison
    passes."""
    return {"p1": to_bf16(want["p1"]), "p2": to_bf16(want["p2"])}


def least_bytes(traffic: dict, sizes: dict, config: dict) -> int:
    """From shapes: the send's events in, a row out for each match (the
    share of adjacent pairs that are a 1 then a 2, half of them rising),
    the `SLOTS`-slot slab of pending threads read and written."""
    n = events_per_send(traffic)
    one, two, _three = traffic["volume_mix"]
    rows = int(n * one * two / 2)
    return n * EVENT_BYTES + rows * ROW_BYTES + 2 * SLOTS * SLOT_BYTES
