"""`pattern_16m_zipf`: its plain reference against a brute-force per-key
Python NFA, the generator's guarantees (exact truncated Zipf, a hot set that
moves, never a fifth partial), the comparison against the faults it is
there to catch, `least_bytes`, and the whole of a run with a row delivered
one send late or a seed dropped underneath it."""
import json

import numpy as np
import pytest

import siddhi_tpu
from benchmarks.harness import loader
from test_bench_doctored import load_run_module

CELL = "pattern_16m_zipf.paced"


def small(events=256, n_keys=64, **over):
    cell = loader.resolve(CELL, rehearse=True)
    traffic = dict(cell.traffic, events_per_send=events, **over)
    sizes = dict(cell.sizes, n_keys=n_keys)
    return cell.model, traffic, sizes


def brute_force(sends, slots=4):
    """The query, key by key and event by event, in plain Python: a list of
    partials per key, each a dict of what it captured and the stage it
    waits for."""
    partials, out = {}, []
    for s in sends:
        keys, price, vol = (c.tolist() for c in s["cols"])
        rows = []
        for k, p, v in zip(keys, price, vol):
            mine = partials.setdefault(k, [])
            if v == 1:
                assert len(mine) < slots
                mine.append({"wait": 2, "p1": p})
            for part in list(mine):
                if v == 2 and part["wait"] == 2 and p >= part["p1"]:
                    part.update(wait=3, p2=p)
                elif v == 3 and part["wait"] == 3:
                    part.update(wait=4, p3=p)
                elif v == 4 and part["wait"] == 4 and p >= part["p3"]:
                    rows.append((k, part["p1"], part["p2"], p))
                    mine.remove(part)
        out.append(sorted(rows))
    return out


def as_tuples(rows):
    return sorted(zip(rows["k"].tolist(), rows["p1"].tolist(),
                      rows["p2"].tolist(), rows["p4"].tolist()))


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_reference_equals_a_brute_force_nfa(seed):
    m, traffic, sizes = small()
    plan = m.plan(seed, traffic, sizes)
    sends = [m.make_send(np.random.default_rng([seed, i]), i, traffic, plan,
                         1000 + 10 * i) for i in range(30)]
    refs = m.reference(sends, plan)
    want = brute_force(sends)
    assert [as_tuples(r) for r in refs] == want
    assert [len(w) for w in want] == [m.expected_rows(s) for s in sends]
    assert sum(len(w) for w in want) > 30 * 256 // 8
    # the hot key takes the plain-Python walk, the others the rounds
    assert max(int(np.unique(s["cols"][0], return_counts=True)[1].max())
               for s in sends) > m.ROUNDS


def test_reference_by_hand_and_the_fifth_slot():
    m, _, _ = small()
    # key 5: two seeds pile up behind a missed stage 2, one stage-2 event
    # advances both, one stage-4 event releases both; key 6 misses e4
    cols = [(5, 1, .1), (5, 2, -.2), (5, 3, .3), (5, 4, .9), (5, 1, .2),
            (5, 2, .6), (5, 3, .4), (6, 1, .1), (6, 2, .5), (6, 3, .3),
            (6, 4, -.1), (5, 4, .7)]
    send = {"cols": [np.array([c[0] for c in cols], np.int64),
                     np.array([c[2] for c in cols], np.float32),
                     np.array([c[1] for c in cols], np.int32)]}
    ref = m.reference([send], {})[0]
    assert as_tuples(ref) == [
        (5, np.float32(.1), np.float32(.6), np.float32(.7)),
        (5, np.float32(.2), np.float32(.6), np.float32(.7))]
    five = {"cols": [np.full(5, 9, np.int64), np.full(5, .1, np.float32),
                     np.ones(5, np.int32)]}
    with pytest.raises(ValueError, match="fifth slot"):
        m.reference([five], {})


def test_generator_zipf_is_exactly_truncated_and_the_hot_set_moves():
    m, traffic, sizes = small(events=8192, n_keys=4096, hot_set_shift=64,
                              hot_set_sends=4)
    plan = m.plan(7, traffic, sizes)
    cdf = plan["cdf"]
    assert cdf[-1] == 1.0 and cdf.shape == (4096,)
    share = np.diff(cdf, prepend=0.0)
    w = np.arange(1, 4097, dtype=np.float64) ** -1.2
    np.testing.assert_allclose(share, w / w.sum(), rtol=1e-9)
    sends = [m.make_send(np.random.default_rng([7, i]), i, traffic, plan,
                         1000 + 10 * i) for i in range(8)]
    rank_of = np.empty(4096, np.int64)
    for block in (0, 1):
        keys = np.concatenate([s["cols"][0]
                               for s in sends[4 * block:4 * block + 4]])
        rank_of[plan["perm"]] = (np.arange(4096) - 64 * block) % 4096
        ranks = rank_of[keys]
        n = keys.size
        for r in (0, 1, 2, 9):           # shares of the top ranks, 5 sigma
            got = (ranks == r).sum()
            assert abs(got - n * share[r]) < 5 * np.sqrt(n * share[r])
        # nothing piled on the last rank: its share is ~1e-5
        assert (ranks == 4095).sum() <= 3
    hot = [np.bincount(s["cols"][0], minlength=4096).argmax() for s in sends]
    assert len(set(hot[:4])) == 1 and len(set(hot[4:])) == 1
    assert hot[0] == plan["perm"][0] and hot[4] == plan["perm"][64]
    # stages cycle per key across sends; at most slots - 1 partials alive
    nxt = {}
    for s in sends:
        for k, v in zip(s["cols"][0].tolist(), s["cols"][2].tolist()):
            assert v == nxt.get(k, 1)
            nxt[k] = v % 4 + 1
    assert int((plan["nfa"].wait != 0).sum(1).max()) == 3
    # a stage-2 / stage-4 price is a pass or a miss, nothing between
    for s in sends:
        _, price, vol = s["cols"]
        gate = (vol == 2) | (vol == 4)
        assert ((price[gate] >= .5) | (price[gate] < 0)).all()
        assert 0.15 < (price[gate] < 0).mean() < 0.30
        assert ((price[~gate] >= 0) & (price[~gate] < .5)).all()
    assert all(m.expected_rows(s) > 0 for s in sends)


def test_the_same_seed_gives_the_same_sends():
    m, traffic, sizes = small()
    a, b = (m.plan(2 ** 31 + 9, traffic, sizes) for _ in range(2))
    for i in range(3):
        x = m.make_send(np.random.default_rng([5, i]), i, traffic, a, 1000)
        y = m.make_send(np.random.default_rng([5, i]), i, traffic, b, 1000)
        for c, d in zip(x["cols"] + [x["ts"]], y["cols"] + [y["ts"]]):
            np.testing.assert_array_equal(c, d)
        assert x["rows"] == y["rows"]


def test_compare_counts_rows_per_key_and_attributes_to_the_send_in_flight():
    m, _, _ = small()
    want = m.canonical({
        "k": np.array([4, 4, 4, 9], np.int64),
        "p1": np.array([.1, .2, .3, .1], np.float32),
        "p2": np.array([.6, .6, .7, .8], np.float32),
        "p4": np.array([.9, .9, .9, .6], np.float32)})
    zero = dict.fromkeys(m.LIMITS, 0)
    assert m.compare(want, want) == zero
    shuffled = {n: a[::-1] for n, a in want.items()}
    assert m.compare(m.canonical(shuffled), want) == zero
    short = {n: a[1:] for n, a in want.items()}       # one of key 4's rows
    assert m.compare(short, want) == dict(zero, rows_missing=1)
    dup = {n: np.concatenate([a, a[:1]]) for n, a in want.items()}
    assert m.compare(m.canonical(dup), want) == dict(zero, rows_unexpected=1)
    off = {n: a.copy() for n, a in want.items()}
    off["p2"][1] = np.nextafter(off["p2"][1], np.float32(2))
    assert m.compare(m.canonical(off), want)["rows_differing"] >= 1
    assert m.compare(m.canonical(m.control_rows(want)), want)[
        "rows_differing"] >= 3
    attr = m.Attribution({})
    attr.on_issue(7, {})
    assert attr.attribute(want).tolist() == [7] * 4
    attr.on_issue(8, {})
    assert attr.attribute(short).tolist() == [8] * 3


def test_sends_that_take_seconds_end_the_run():
    """As many sends in a row as the traffic's `drain_limit_s` has seconds,
    each issued over a second after the one before: the run is given up
    (exit code 1, through the harness).  One long hole — the traced run's
    planned one, a stall — is not."""
    m, _, _ = small()
    t = [100.0]
    assert m.plan(1, dict(loader.resolve(CELL).traffic, drain_limit_s=16),
                  {"n_keys": 64, "slots": 4})["drain_limit_s"] == 16.0
    attr = m.Attribution({"drain_limit_s": 16.0})
    attr.clock = lambda: t[0]
    for sid, gap in enumerate([.04] * 5 + [90.0] + [.04] * 5 + [2.2] * 15):
        t[0] += gap
        attr.on_issue(sid, {})
    t[0] += .04
    attr.on_issue(99, {})                 # a fast one: the count starts anew
    for sid in range(15):
        t[0] += 2.2
        attr.on_issue(sid, {})
    t[0] += 2.2
    with pytest.raises(RuntimeError, match="seconds a send"):
        attr.on_issue(16, {})


def test_least_bytes_and_config():
    cell = loader.resolve(CELL)
    m, cfg = cell.model, cell.config
    assert cell.sizes["n_keys"] == 16777216 and cell.chips == 1
    assert cell.sizes["n_keys"] * cfg["state_bytes_per_key"] == 8724152320
    got = m.least_bytes(cell.traffic, cell.sizes, cfg)
    touched = (got - 8192 * 24 - 2048 * 28) / (2 * 520)
    assert touched == int(touched) and 2150 < touched < 2280
    pre = cell.traffic["prefill"]
    assert pre["sends"] * pre["keys_per_send"] == cell.sizes["n_keys"]
    assert m.least_bytes(pre, cell.sizes, cfg) == \
        131072 * (2 * 520 + 4 * 24 + 28)
    one_m = loader.resolve("pattern_1m.paced")
    assert cell.app_text.replace("16777216", "N").replace("'512'", "R") == \
        one_m.app_text.replace("1048576", "N").replace("'2'", "R")
    for key in ("stream", "query", "columns", "state_bytes_per_key"):
        assert cfg[key] == one_m.config[key]
    assert set(one_m.config["guarantees"]) < set(cfg["guarantees"])
    assert cfg["reduced"] == [] and len(cfg["source"]) <= 200
    t = cell.traffic
    assert (t["events_per_send"], t["zipf_exponent"], t["hot_set_shift"],
            t["hot_set_sends"], t["warmup_sends"], t["trace_sends"],
            t["trace_gap_s"], t["drain_limit_s"]) == \
        (8192, 1.2, 4096, 128, 64, 20, 110.0, 20)
    with open(loader.BENCH_DIR + "/configs/pattern_16m_zipf/model.py") as fh:
        imports = [ln for ln in fh if ln.startswith(("import ", "from "))]
    assert imports and not any("siddhi_tpu" in ln for ln in imports)
    check_the_cells_own_quantities_keep_their_entries(loader.load_benchmark())


def check_the_cells_own_quantities_keep_their_entries(bench):
    """The cell's own quantities keep their `.zipf` entries, the cell first
    in their lists (a later cell may join behind it); the rest of the table
    is test_bench_per_layer_table.py's."""
    own = {e["name"] for e in bench["per_layer"]
           if e["workloads"][:1] == [CELL]}
    assert own >= {"layout_cells_per_event.zipf", "scan_ticks_per_send.zipf",
                   "hot_key_events_per_send.zipf", "step_roofline.zipf"}
    (w,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (w["config"], w["traffic"], w["chips"]) == \
        ("pattern_16m_zipf", "zipf_paced", 1)


class LateRows:
    """The real runtime, one fault between it and its user: from the 12th
    send on, the rows of a send are handed to the subscriber during the
    NEXT send's call (`late_row`), or one send's seeds never reach the
    runtime (`drop_seeds`: its stage-1 events are cut out of the batch)."""

    def __init__(self, rt, fault):
        self._rt, self._fault = rt, fault
        self._held, self._calls = None, 0

    def __getattr__(self, name):
        return getattr(self._rt, name)

    def get_input_handler(self, stream):
        real, outer = self._rt.get_input_handler(stream), self

        class Handler:
            def send_columns(self, cols, timestamps=None):
                outer._calls += 1
                if outer._fault == "drop_seeds" and outer._calls == 52:
                    keep = cols[2] != 1
                    cols = [c[keep] for c in cols]
                    timestamps = timestamps[keep]
                real.send_columns(cols, timestamps=timestamps)
        return Handler()

    def add_batch_callback(self, query, cb):
        def doctored(ts, b):
            if self._fault != "late_row" or self._calls < 52:
                return cb(ts, b)
            out = {k: b[k] for k in ("ts", "kind", "valid")}
            out["cols"] = {n: np.array(c) for n, c in b["cols"].items()}
            held, self._held = self._held, None
            if held is None and self._calls == 52:
                # hold one row of this delivery back for the next send
                first = int(np.nonzero(out["valid"] & (out["kind"] == 0))[0][0])
                late = {k: (v[first:first + 1] if k != "cols" else
                            {n: c[first:first + 1] for n, c in v.items()})
                        for k, v in out.items()}
                out["valid"] = out["valid"].copy()
                out["valid"][first] = False
                self._held = late
            cb(ts, out)
            if held is not None:
                cb(ts, held)
        self._rt.add_batch_callback(query, doctored)


@pytest.mark.parametrize("fault", ["late_row", "drop_seeds"])
def test_a_late_row_or_a_dropped_seed_is_not_correct(monkeypatch, capsys,
                                                     fault):
    real = siddhi_tpu.SiddhiManager.create_siddhi_app_runtime
    monkeypatch.setattr(
        siddhi_tpu.SiddhiManager, "create_siddhi_app_runtime",
        lambda self, *a, **kw: LateRows(real(self, *a, **kw), fault))
    rc = load_run_module().main(["--workload", CELL, "--seed", "11",
                                 "--seconds", "1.5", "--trace", "0",
                                 "--rehearse"])
    out = capsys.readouterr().out
    last = json.loads(out.strip().splitlines()[-1])
    assert rc == 0
    assert last["correct"] is False, out[-1500:]
    assert last["failed"] >= 1 and "OVER" in out
