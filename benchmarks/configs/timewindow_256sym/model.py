"""timewindow_256sym: traffic, plain reference and comparison — the sliding
time window with a grouped aggregate and a `having` that bites

    from StockStream#window.time(1 sec)
    select symbol, sum(price) as total, count() as n, avg(price) as ap
    group by symbol having total > 60000.0625
    insert into Out

(the threshold is `sizes["having_total"]`, formatted into `app.siddhi`)

ONE window holds the stream's last second of events, whatever send brought
them, on the events' own clock (`@app:playback`).  An event with timestamp t
first expires every held event whose timestamp + 1,000 <= t — each leaves its
symbol's sum and count and emits nothing under `insert into` — then joins
the window and its symbol's sum and count, and is emitted as ONE row
(symbol, total, n, ap) iff its symbol's `total` then passes `having`.  Rows
leave in arrival order.  A send that starts more than a second after the
stream's last event finds the whole window expired: nothing but the clock —
a timer — has removed those rows.

Numpy and the standard library only, nothing of siddhi_tpu: it is the
yardstick the program is held to.  The reference is the per-event loop the
paragraph above describes — a deque of (timestamp, symbol, price), a float64
sum and an integer count a symbol — carried ACROSS sends.
"""
from __future__ import annotations

import collections

import numpy as np

from benchmarks.harness.numeric import to_bf16

# bytes one event needs on the wire: long symbol, f32 price, long volume,
# long timestamp; one result row: long symbol, f32 total, long n, f32 ap,
# long timestamp; one row the window holds: the event and its expiry time
EVENT_BYTES = 8 + 4 + 8 + 8
ROW_BYTES = 8 + 4 + 8 + 4 + 8
HELD_BYTES = EVENT_BYTES + 8
# a symbol's two accumulators: f32 sum, long count
SLOT_BYTES = 4 + 8
WINDOW_MS = 1000
# prices are whole multiples of this (an eighth of a dollar, the tick US
# equities were quoted in) below `price_lo + price_ticks * TICK`
TICK = 0.125

# `total` and `ap` are held to the float64 values within these RELATIVE
# tolerances.  Every price is a multiple of 1/8 under 64, so a symbol's
# running total — and every partial sum an accumulation in another order
# makes on the way, each the difference of two running totals — is a
# multiple of 1/8 under 2**21 = 2,097,152 while the symbol has fewer than
# 32,768 rows in the window and the send together (`make_send` asserts it
# of every send), which float32 holds EXACTLY: adding a row and removing it
# again leaves no residue, so float32 accumulation of the window's terms
# passes with 0 error, and TOTAL_RTOL leaves room for one rounding of the
# result (u = 2**-24 = 5.96e-8) and no more.  `ap` is one float32 division
# of two exact numbers: 2 u with the reference's own cast; AP_RTOL stands
# eight times over that.
# bfloat16 anywhere — a price, the sum, the result — is off by up to 2**-8 =
# 3.9e-3: four thousand times AP_RTOL (`control_rows`; `--control 1`).
#
# `having` reads `total` alone.  The threshold lies BETWEEN two values of
# the grid (60000 + 1/16): no total is nearer to it than 1/16 = 1.04e-6 of
# it, five times TOTAL_RTOL, so no row's `having` can flip inside the
# tolerance (`plan` asserts it of the threshold, `SlidingWindow.feed` of
# every event).
TOTAL_RTOL = 2e-7
AP_RTOL = 1e-6


def events_per_send(traffic: dict) -> int:
    return int(traffic["events_per_send"])


def clock_step_ms(traffic: dict) -> int:
    """Event time advances with the schedule: a send's events span the
    interval the open loop gives it, so one second of window holds one
    second of offered events."""
    return max(1, round(1000.0 * events_per_send(traffic) /
                        float(traffic["rate_events_per_s"])))


class SlidingWindow:
    """The plain thing, event by event: the window as a deque in arrival
    order, a float64 sum and a count a symbol."""

    def __init__(self, symbols: int, having_total: float):
        self.having_total = having_total
        self.held = collections.deque()       # (timestamp, symbol, price)
        self.total = [0.0] * symbols
        self.n = [0] * symbols

    def feed(self, ts, symbol, price) -> dict:
        """The rows these events emit, in arrival order."""
        held, total, n = self.held, self.total, self.n
        having = self.having_total
        out_s, out_t, out_n = [], [], []
        for t, s, p in zip(ts.tolist(), symbol.tolist(),
                           price.astype(np.float64).tolist()):
            while held and held[0][0] + WINDOW_MS <= t:
                _, s0, p0 = held.popleft()
                total[s0] -= p0
                n[s0] -= 1
            held.append((t, s, p))
            total[s] += p
            n[s] += 1
            assert abs(total[s] - having) >= TICK / 2
            if total[s] > having:
                out_s.append(s)
                out_t.append(total[s])
                out_n.append(n[s])
        tot = np.asarray(out_t, np.float64)
        cnt = np.asarray(out_n, np.int64)
        return {"symbol": np.asarray(out_s, np.int64),
                "total": tot.astype(np.float32), "n": cnt,
                "ap": (tot / np.maximum(cnt, 1)).astype(np.float32)}


def plan(seed: int, traffic: dict, sizes: dict) -> dict:
    """What the generator keeps between sends (they are made in the order
    they are sent): the Zipf ranks' cumulative shares, the window as the
    reference carries it (for the rows a send is owed), the stream's last
    timestamp and the event time the feed's gaps have added so far."""
    k = int(traffic["symbols"])
    having = float(sizes["having_total"])
    assert TICK / 2 > TOTAL_RTOL * having and (having / TICK) % 1 == 0.5
    share = 1.0 / np.arange(1, k + 1, dtype=np.float64) ** \
        float(traffic["zipf_s"])
    return {"symbols": k, "having_total": having,
            "cdf": np.cumsum(share / share.sum()),
            "window": SlidingWindow(k, having), "last_ts": None, "gap_ms": 0}


def make_send(rng, i: int, traffic: dict, plan_: dict, clock_ms: int) -> dict:
    """One send, every column new: symbols by Zipf rank (rank r is symbol
    (r + i // hot_shift_every_sends) mod symbols: the hot set moves), prices
    on the 1/8 grid, timestamps spread evenly over the send's interval of
    the schedule.  Every `gap_every_sends`-th send starts `gap_ms` after
    the last event of the send before it — longer than the window, so all
    of it must expire by the clock alone.  It records the rows it is
    owed."""
    n = events_per_send(traffic)
    k = plan_["symbols"]
    step = clock_step_ms(traffic)
    start = clock_ms + plan_["gap_ms"]
    last = plan_["last_ts"]
    if last is not None and i % int(traffic["gap_every_sends"]) == 0:
        resumed = last + int(traffic["gap_ms"])
        plan_["gap_ms"] += resumed - start
        start = resumed
    rank = np.searchsorted(plan_["cdf"], rng.random(n), side="right")
    symbol = (np.minimum(rank, k - 1) +
              i // int(traffic["hot_shift_every_sends"])) % k
    ticks = rng.integers(0, int(traffic["price_ticks"]), n)
    price = (float(traffic["price_lo"]) + ticks * TICK).astype(np.float32)
    ts = start + np.arange(n, dtype=np.int64) * step // n
    send = {
        "cols": [symbol.astype(np.int64), price,
                 rng.integers(1, int(traffic["volume_hi"]) + 1, n, np.int64)],
        "ts": ts, "events": n,
    }
    window = plan_["window"]
    # the exactness the tolerances rest on: no symbol's rows, the window's
    # and the send's together and each at the highest price, reach 2**21
    price_hi = float(traffic["price_lo"]) + \
        int(traffic["price_ticks"]) * TICK
    most = int((np.asarray(window.n) + np.bincount(symbol, minlength=k)).max())
    assert most * price_hi < 2 ** 21 and price_hi <= 64, (most, price_hi)
    send["rows"] = int(window.feed(ts, send["cols"][0], price)["n"].shape[0])
    plan_["last_ts"] = int(ts[-1])
    return send


def expected_rows(send: dict) -> int:
    """The send's events whose symbol's total passes `having` as they
    arrive — none at all for a send that starts on an empty window."""
    return send["rows"]


def reference(sends: list, plan_: dict) -> list:
    """Every send since the app started, in order, through a window of its
    own: the rows each send's events emit."""
    window = SlidingWindow(plan_["symbols"], plan_["having_total"])
    return [window.feed(s["ts"], s["cols"][0], s["cols"][1]) for s in sends]


def canonical(rows: dict) -> dict:
    """Delivery order, untouched: the guarantee is arrival order."""
    return rows


class Attribution:
    """Result row -> the send in flight: delivery is blocking with one call
    at a time, and a row carries nothing that names the send that made it.
    A row delivered while a send that owes none is in flight — by a timer
    that fired ahead of its rows, say — is unexpected there."""

    def __init__(self, plan_: dict):
        self.sid = -1

    def on_issue(self, sid: int, send: dict) -> None:
        self.sid = sid

    def attribute(self, rows: dict) -> np.ndarray:
        return np.full(rows["symbol"].shape[0], self.sid, np.int64)


# each number compared, with its limit: the two counts, the rows whose
# `symbol` or `n` (exact) differ, and the rows whose `total` or `ap` is
# outside its tolerance — the one number the control fails
LIMITS = {"rows_missing": 0, "rows_unexpected": 0, "rows_differing": 0,
          "values_over_tolerance": 0}


def _over(got, want, rtol) -> np.ndarray:
    w = want.astype(np.float64)
    return ~(np.abs(got.astype(np.float64) - w) <= rtol * np.abs(w))


def compare(got: dict, want: dict) -> dict:
    """One send's delivered rows against the reference's, both in delivery
    order, row j against row j once the count is right; a NaN is over."""
    n_got, n_want = got["symbol"].shape[0], want["symbol"].shape[0]
    differing = over = 0
    if n_got == n_want:
        differing = int(((got["symbol"] != want["symbol"]) |
                         (got["n"] != want["n"])).sum())
        over = int((_over(got["total"], want["total"], TOTAL_RTOL) |
                    _over(got["ap"], want["ap"], AP_RTOL)).sum())
    return {"rows_missing": max(n_want - n_got, 0),
            "rows_unexpected": max(n_got - n_want, 0),
            "rows_differing": differing, "values_over_tolerance": over}


def control_rows(want: dict) -> dict:
    """What the nearest lower precision would deliver: the reference's
    `total` and `ap` carried as bfloat16, the exact columns as they are."""
    return dict(want, total=to_bf16(want["total"]), ap=to_bf16(want["ap"]))


def least_bytes(traffic: dict, sizes: dict, config: dict) -> int:
    """Bytes the ALGORITHM needs to move through HBM for one send, from
    shapes: the events in; each written into the window and read out of it
    once, a second later (a sliding window over a steady stream expires as
    many rows a send as it admits); a row out for the share that passes
    `having`; every symbol's sum and count read and written.  Not what
    today's program moves."""
    n = events_per_send(traffic)
    rows = int(n * float(traffic["having_share"]))
    return (n * EVENT_BYTES + 2 * n * HELD_BYTES + rows * ROW_BYTES +
            2 * int(traffic["symbols"]) * SLOT_BYTES)
