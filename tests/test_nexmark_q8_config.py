"""`nexmark_q8` — NEXmark query 8, Person join Auction on `id == seller` over
two sliding `window.time` — at CPU sizes (windows of a few thousand rows,
sends of 256): the deployed app against the cell's plain reference
(`benchmarks/configs/nexmark_q8/model.py`) BY VALUE, send by send, over
streams that wrap the rings several times.

What is held (ISSUE 57): a row is owed for a resident seller and none for an
expired or a never-seen one; the lead's rows come at the person send; a hot
seller's 100-and-more-deep key stands beside one-deep persons (the walk
depth is a side's own); a timer step between sends changes nothing; a send
may span more than the window; stamps out of order take the whole-slab
`process` and agree; the ring's rows after every send are the rows
`TimeWindow.process` keeps alive (the same sends through the whole-slab
form, window by window); a
bound too small is HEARD — an error at the listener, `window_dropped` > 0 —
by the ring and by the whole-slab form alike.  One parametrised test, each
case counted."""
import json
import os
import sys

import numpy as np
import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.core import event as ev

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import loader  # noqa: E402

CFG_DIR = os.path.join(ROOT, "benchmarks", "configs", "nexmark_q8")
COLUMNS = ("id", "name", "reserve")


@pytest.fixture(scope="module")
def q8():
    """The deployment's files at `rehearse_sizes`: (model, app text with
    `{...}` sizes left to fill, sizes, traffic)."""
    cell = loader.resolve("nexmark_q8.saturated", rehearse=True)
    with open(os.path.join(CFG_DIR, "app.siddhi")) as fh:
        text = fh.read()
    return cell.model, text, dict(cell.sizes), dict(cell.traffic)


def make_sends(model, traffic, sizes, n, seed=57, reorder=()):
    """`n` sends of the cell's generator; the sends in `reorder` get their
    rows' stamps in descending order (the same stamps, the rows' order
    kept)."""
    plan = model.plan(seed, traffic, sizes)
    sends = []
    for i in range(n):
        send = model.make_send(np.random.default_rng([seed, i]), i, traffic,
                               plan, 0)
        if i in reorder:
            send["ts"] = send["ts"][::-1].copy()
        sends.append(send)
    return sends, plan


def resident_rows(window, state, now):
    """The rows of a window that a reader at stamp `now` still sees, oldest
    first, whatever form its slab takes: (ts, columns...) as lists.  (A ring
    side moves its tail at its OWN next step; the compacting form under a
    timer at every send: what `now` no longer sees is in neither.)"""
    buf = window.current_buffer(state)
    seen = np.asarray(buf.alive) & (np.asarray(buf.expire_ts) > now)
    order = np.argsort(np.asarray(buf.add_seq)[seen], kind="stable")
    return [np.asarray(a)[seen][order].tolist()
            for a in (buf.ts,) + tuple(buf.cols[:-1])]


def drive(text, sends, between=None, expect_errors=False, watch=()):
    """Every send through the deployed app, in order: -> the rows each
    delivered, both windows' rows after the sends in `watch`, the errors
    heard, the runtime's last facts."""
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(text)
    got, errors = [], []

    def on_batch(_ts, b):
        sel = b["valid"] & (b["kind"] == ev.CURRENT)
        got.append({n: np.asarray(b["cols"][n])[sel] for n in COLUMNS})
    rt.add_batch_callback("q8", on_batch)
    rt.set_exception_listener(errors.append)
    rt.start()
    out = {"rows": [], "windows": {}}
    try:
        qr = rt.query_runtimes["q8"]
        handlers = {s: rt.get_input_handler(s) for s in ("Person", "Auction")}
        for i, send in enumerate(sends):
            got.clear()
            handlers[send["stream"]].send_columns(
                [c.copy() for c in send["cols"]],
                timestamps=send["ts"].copy())
            if between is not None:
                between(rt, qr, i, send)
            out["rows"].append(
                {n: np.concatenate([g[n] for g in got]) if got else
                 np.zeros(0, np.int64) for n in COLUMNS})
            if i in watch:
                out["windows"][i] = [
                    resident_rows(side.window, st, int(send["ts"].max()))
                    for side, st in ((qr.planned.left, qr.state[0]),
                                     (qr.planned.right, qr.state[1]))]
        rt.flush()
        out["plan"] = qr.planned.describe()
        out["schemas"] = {"Person": qr.planned.left.schema,
                          "Auction": qr.planned.right.schema}
        out["facts"] = qr.join_facts()
        out["explain"] = rt.explain("q8")["plan"]["equi_fastpath"]
        out["steps"] = {role: spec is not None
                        for role, _fn, spec in rt.compiled_steps("q8")}
        out["report"] = rt.state_report()["join"]
        from siddhi_tpu.observability.exposition import render_prometheus
        out["metrics"] = render_prometheus({rt.name: rt})
    finally:
        m.shutdown()
    assert expect_errors or not errors, errors[:1]
    out["errors"] = errors
    return out


def held_to(model, run, want, sends):
    """Every send's rows are the reference's, by value; -> rows in all."""
    total = 0
    for i, (got, ref, send) in enumerate(zip(run["rows"], want, sends)):
        nums = model.compare(model.canonical(got), model.canonical(ref))
        assert nums == dict.fromkeys(model.LIMITS, 0), (i, nums)
        assert ref["id"].shape[0] == model.expected_rows(send), i
        total += ref["id"].shape[0]
    return total


def process_windows(sends, schemas, window_ms, bounds, watch):
    """The same sends through `TimeWindow.process` — the whole-slab form, a
    step a send of its side with `now` the send's last stamp — and the rows
    each window shows a reader after the sends in `watch`."""
    import jax
    import jax.numpy as jnp
    from siddhi_tpu.core.window import Rows, TimeWindow
    from siddhi_tpu.query_api.expression import Constant
    wins = {s: TimeWindow(schemas[s], [Constant(window_ms, "LONG")], 256,
                          capacity_hint=bounds[s]) for s in schemas}
    steps = {s: jax.jit(w.process) for s, w in wins.items()}
    states = {s: w.init_state() for s, w in wins.items()}
    out = {}
    for i, send in enumerate(sends):
        s, n = send["stream"], send["ts"].shape[0]
        rows = Rows(ts=jnp.asarray(send["ts"]),
                    kind=jnp.zeros((n,), jnp.int32),
                    valid=jnp.ones((n,), bool),
                    seq=jnp.zeros((n,), jnp.int64),
                    gslot=jnp.zeros((n,), jnp.int32),
                    cols=tuple(jnp.asarray(c) for c in send["cols"]))
        states[s], _ = steps[s](states[s], rows, jnp.int64(send["ts"][-1]))
        if i in watch:
            now, got = int(send["ts"].max()), []
            for side in ("Person", "Auction"):
                buf = states[side][0]
                seen = np.asarray(buf.alive) & \
                    (np.asarray(buf.expire_ts) > now)
                order = np.argsort(np.asarray(buf.add_seq)[seen],
                                   kind="stable")
                got.append([np.asarray(a)[seen][order].tolist()
                            for a in (buf.ts,) + tuple(buf.cols)])
            out[i] = got
    return out


def case_replay(model, text, sizes, traffic):
    """44 sends (11 rounds; a window holds ~4): resident sellers owe a row,
    expired and never-seen ones none; after every send the rings hold what
    `process` keeps alive; a hot seller's 100-row key stands beside
    one-deep persons."""
    sends, plan = make_sends(model, traffic, sizes, 44)
    watch = range(3, 44, 5)         # person and auction sends among them
    run = drive(text.format(**sizes), sends, watch=watch)
    want = model.reference(sends, plan)
    total = held_to(model, run, want, sends)
    auctions = [s for s in sends if s["stream"] == "Auction"]
    owed = sum(s["rows"] for s in auctions[-9:]) / (9 * 256)
    assert total > 5000 and 0.85 < owed < 0.99, (total, owed)
    assert run["plan"]["left"]["window_processor"] == "TimeRingWindow"
    assert run["explain"]["index_kind"] == ["chain", "chain"]
    assert run["explain"]["window_bound_rows"] == [
        sizes["window_rows_person"], sizes["window_rows_auction"]]
    # a walk is as deep as the BATCH's keys reach: persons (unique ids) are
    # probed 1 deep, and the auctions' side — whose hot seller holds 100
    # rows and more — 4 deep by the persons that arrive, few of whom any
    # auction has named yet
    depth = run["explain"]["probe_depth"]
    assert depth[0] == 1 and depth[1] in (4, 16), depth
    assert run["facts"]["fullest_key"] >= 100, run["facts"]
    assert run["facts"]["window_dropped"] == 0
    # the rings wrapped: more rows went through than the bounds hold
    assert 11 * 256 > 2 * sizes["window_rows_person"]
    twin = process_windows(
        sends, run["schemas"], plan["window_ms"],
        {"Person": sizes["window_rows_person"],
         "Auction": sizes["window_rows_auction"]}, watch)
    for i in watch:
        assert run["windows"][i] == twin[i], i
    assert len(run["windows"][43][1][0]) > 2500
    # what the surfaces say of it: the auctions' ring moved its tail in its
    # own last step, send 43
    assert run["report"]["q8"]["window_rows_r"] == len(
        run["windows"][43][1][0])
    assert 'siddhi_join_window_rows{' in run["metrics"]
    assert 'siddhi_join_window_dropped_total{' in run["metrics"]


def case_lead(model, text, sizes, traffic):
    """Few active people, so ids named ahead of time are a real share: the
    person send delivers the lead's rows."""
    sizes = dict(sizes, active_people=40)
    traffic = dict(traffic, rows_per_send=16, first_event=40 * 50,
                   stamp_us=14_000_000)
    sends, plan = make_sends(model, traffic, sizes, 120)
    run = drive(text.format(**sizes), sends)
    want = model.reference(sends, plan)
    held_to(model, run, want, sends)
    at_person = sum(r["id"].shape[0] for s, r in zip(sends, want)
                    if s["stream"] == "Person")
    assert at_person >= 10, at_person


def case_timer(model, text, sizes, traffic):
    """A timer step on both sides between the sends (the scheduler arms
    none for a ring: expiry is the tail's place): same rows, same rings."""
    sends, plan = make_sends(model, traffic, sizes, 24)
    assert sends[23]["stream"] == "Auction"

    def tick(rt, qr, i, send):
        assert rt.timers_pending() == 0
        with qr._qlock:
            qr.on_timer(int(send["ts"][-1]) + 1)
    run = drive(text.format(**sizes), sends, between=tick, watch=(22, 23))
    held_to(model, run, model.reference(sends, plan), sends)
    plain = drive(text.format(**sizes), sends, watch=(22, 23))
    assert run["windows"] == plain["windows"]
    # the tick moved the persons' tail, which its own sends alone would not
    assert run["facts"]["window_rows_l"] < plain["facts"]["window_rows_l"]


def case_send_spans_more_than_the_window(model, text, sizes, traffic):
    """240 s a row, 16 rows a send: a send spans 3,840 s of a 3,600 s
    window, so its later rows no longer see what its first rows see, and
    only a seller fewer than 15 rows back owes a row."""
    sizes = dict(sizes, active_people=40)
    traffic = dict(traffic, rows_per_send=16, first_event=40 * 50,
                   stamp_us=240_000_000)
    sends, plan = make_sends(model, traffic, sizes, 160)
    assert sends[0]["ts"][-1] - sends[0]["ts"][0] >= plan["window_ms"]
    run = drive(text.format(**sizes), sends, watch=(159,))
    want = model.reference(sends, plan)
    total = held_to(model, run, want, sends)
    assert total > 20, total
    assert len(run["windows"][159][0][0]) <= 16


def case_stamps_out_of_order(model, text, sizes, traffic):
    """Two sends whose stamps run backwards take the whole-slab program
    (`slow_step[left]` / `slow_step[right]`, traced only then) and the rows
    are the nested comparison's."""
    sends, plan = make_sends(model, traffic, sizes, 28, reorder=(12, 14))
    run = drive(text.format(**sizes), sends)
    want = model.brute_force(sends, plan["window_ms"])
    for i, (got, ref) in enumerate(zip(run["rows"], want)):
        nums = model.compare(model.canonical(got), model.canonical(ref))
        assert nums == dict.fromkeys(model.LIMITS, 0), (i, nums)
    slow = [r for r, ran in run["steps"].items() if r.startswith("slow_")]
    assert len(slow) == 2 and all(run["steps"][r] for r in slow), run["steps"]
    in_order = drive(text.format(**sizes), make_sends(
        model, traffic, sizes, 8)[0])
    assert in_order["steps"]["step[right]"]
    assert not [r for r in in_order["steps"] if r.startswith("slow_")]


def case_bound_too_small(model, text, sizes, traffic):
    """Bounds a quarter of what the window's time keeps: the oldest rows
    go, each is COUNTED and the listener hears an error a send."""
    sizes = dict(sizes, window_rows_person=256, window_rows_auction=800)
    sends, _plan = make_sends(model, traffic, sizes, 24)
    run = drive(text.format(**sizes), sends, expect_errors=True)
    assert run["errors"] and all(
        "dropped" in str(e) and "@capacity(window" in str(e)
        for e in run["errors"])
    assert run["facts"]["window_dropped"] > 1000
    assert run["facts"]["window_rows_l"] <= 256
    assert run["facts"]["window_rows_r"] <= 800


def case_bound_too_small_whole_slab(model, text, sizes, traffic):
    """The same join keeping its EXPIRED rows (`insert all events`) takes
    `TimeWindow.process`, the whole-slab form: it honours the bound too (at
    least two batches), and what it drops is counted by the step and heard."""
    sizes = dict(sizes, window_rows_person=1024, window_rows_auction=1024)
    sends, _plan = make_sends(model, traffic, sizes, 12)
    assert "insert into NewUsers" in text
    run = drive(text.replace("insert into NewUsers",
                             "insert all events into NewUsers"
                             ).format(**sizes), sends, expect_errors=True)
    assert run["plan"]["right"]["window_processor"] == "TimeWindow"
    assert run["explain"]["window_bound_rows"] == [1024, 1024]
    assert run["errors"] and all("dropped" in str(e) for e in run["errors"])
    # rounds 2 and 3 each push 768 auctions into a slab that holds 1,024
    assert run["facts"]["window_dropped"] >= 1024


CASES = {f.__name__[5:]: f for f in (
    case_replay, case_lead, case_timer,
    case_send_spans_more_than_the_window, case_stamps_out_of_order,
    case_bound_too_small, case_bound_too_small_whole_slab)}


@pytest.mark.parametrize("case", sorted(CASES))
def test_nexmark_q8(case, q8):
    CASES[case](*q8)


def test_the_config_states_what_the_issue_asks():
    with open(os.path.join(CFG_DIR, "config.json")) as fh:
        cfg = json.load(fh)
    assert cfg["reduced"] in ([], ["window_hours"])
    said = " ".join(cfg["assumed"])
    for word in ("numActivePeople", "10,800,000", "PERSON_ID_LEAD",
                 "HOT_SELLER_RATIO", "extra", "first_event", "1.25 ms",
                 "32,768"):
        assert word in said, word
    assert len(cfg["source"]) <= 200 and "NEXmark" in cfg["source"]
    assert len(cfg["guarantees"]) >= 4
