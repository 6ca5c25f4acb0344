"""`pattern_16m_zipf` through its own app text at rehearsal sizes (4,096
keys, 1,024-event sends, the same exponent and generator): the deployed app
delivers the plain reference's rows, send by send, under sends that are not
a rectangle — one key with hundreds of events and hundreds of keys with one,
partial matches carried from send to send, one event releasing several
matches — laid out as tiers, with nothing dropped and nothing compiled after
the warm-up."""
import importlib.util
import json
import os

import numpy as np
import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.observability import RECOMPILES
from siddhi_tpu.observability import phases as ph

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG_DIR = os.path.join(ROOT, "benchmarks", "configs", "pattern_16m_zipf")
with open(os.path.join(ROOT, "benchmarks", "traffic",
                       "zipf_paced.json")) as _fh:
    TRAFFIC = json.load(_fh)
TRAFFIC.update(TRAFFIC["rehearse"])
N_SENDS, WARM = 40, 16


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(CFG_DIR, "config.json")) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def model():
    spec = importlib.util.spec_from_file_location(
        "bench_model_pattern_16m_zipf_t1", os.path.join(CFG_DIR, "model.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def run(config, model):
    """Prefill (every key bound, all passing), then N_SENDS Zipf sends;
    everything the tests below look at."""
    sizes = dict(config["sizes"], **config["rehearse_sizes"])
    with open(os.path.join(CFG_DIR, "app.siddhi")) as fh:
        text = "@app:statistics('BASIC')\n" + fh.read().format(**sizes)
    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime(text)
        errors, batches = [], []
        rt.set_exception_listener(errors.append)

        def on_batch(_ts, b):
            sel = b["valid"] & (b["kind"] == 0)
            batches.append({n: np.asarray(b["cols"][n])[sel]
                            for n in config["columns"]})

        rt.add_batch_callback(config["query"], on_batch)
        rt.start()
        h = rt.get_input_handler(config["stream"])
        plan = model.plan(11, TRAFFIC, sizes)
        pre = TRAFFIC["prefill"]
        mixes = [(pre, i) for i in range(pre["sends"])] + \
            [(TRAFFIC, i) for i in range(N_SENDS)]
        sends, rows, alive, compiles = [], [], [], []
        clock = 1000
        for sid, (mix, i) in enumerate(mixes):
            clock += model.clock_step_ms(mix)
            send = model.make_send(np.random.default_rng([11, sid]), i, mix,
                                   plan, clock)
            sends.append(send)
            alive.append(int((plan["nfa"].wait != 0).sum()))
            before = len(batches)
            h.send_columns([c.copy() for c in send["cols"]],
                           timestamps=send["ts"].copy())
            rt.flush()
            got = batches[before:]
            rows.append({n: np.concatenate([g[n] for g in got])
                         for n in config["columns"]} if got else
                        {n: np.zeros(0, t) for n, t in zip(
                            config["columns"], (np.int64,) + (np.float32,) * 3)})
            compiles.append(
                RECOMPILES.snapshot([config["query"]])[config["query"]]["count"])
        assert not errors, errors[:1]
        return {"sends": sends, "rows": rows, "plan": plan, "alive": alive,
                "compiles": compiles, "prefill": pre["sends"],
                "counters": rt.statistics().get("counters", {}),
                "phases": ph.phase_report(rt)["queries"][config["query"]],
                "refs": model.reference(sends, plan)}
    finally:
        m.shutdown()


def test_every_send_delivers_the_reference_rows_by_value(run, model):
    assert all(v == 0 for v in model.LIMITS.values())
    for i, (send, got, want) in enumerate(zip(run["sends"], run["rows"],
                                              run["refs"])):
        nums = model.compare(model.canonical(got), model.canonical(want))
        assert nums == dict.fromkeys(model.LIMITS, 0), (i, nums)
        assert want["k"].shape[0] == model.expected_rows(send) > 0, i
    # every prefill send binds its keys and completes one row a key
    assert all(r["k"].shape[0] == TRAFFIC["prefill"]["keys_per_send"]
               for r in run["rows"][:run["prefill"]])


def test_the_sends_are_not_a_rectangle(run):
    """One key at more than 64 events, hundreds of keys at one, in one
    send — and the hot key changes when the hot set moves."""
    zipf = run["sends"][run["prefill"]:]
    hottest = []
    for s in zipf:
        keys, counts = np.unique(s["cols"][0], return_counts=True)
        assert counts.max() > 64 and int((counts == 1).sum()) >= 100
        hottest.append(int(keys[counts.argmax()]))
    per = TRAFFIC["hot_set_sends"]
    assert len(set(hottest[:per])) == 1
    assert len({tuple(hottest[j:j + per]) for j in range(0, N_SENDS, per)}) \
        == N_SENDS // per


def test_partials_live_across_sends_and_one_event_releases_several(run):
    pre = run["prefill"]
    # the prefill leaves nothing alive; the Zipf sends leave partials behind
    assert run["alive"][pre - 1] == 0
    assert min(run["alive"][pre:]) > 0
    # a row whose e1 (p1) arrived in an EARLIER send than its e4 (p4)
    carried = 0
    for send, got in zip(run["sends"][pre:], run["rows"][pre:]):
        _, price, vol = send["cols"]
        here = set(price[vol == 1].tolist())
        carried += sum(p not in here for p in got["p1"].tolist())
    assert carried > 0
    # two rows of one key with one p4: one stage-4 event released both
    several = 0
    for got in run["rows"][pre:]:
        pairs = np.stack([got["k"].astype(np.float64), got["p4"]], 1)
        several += pairs.shape[0] - np.unique(pairs, axis=0).shape[0]
    assert several > 0


def test_nothing_is_dropped_and_nothing_compiles_after_the_warm_up(run,
                                                                   config):
    assert run["counters"].get(config["query"] + ".dropped", 0) == 0
    c = run["compiles"]
    assert c[run["prefill"] + WARM] == c[-1], c[run["prefill"]:]


def test_the_zipf_sends_are_laid_out_as_tiers(run):
    """`phase_report()` lists the layout under stage_host's parts: more
    than one tier a Zipf send, cells of the order of the events (the one
    rectangle would be 512 x 256 = 128 a event), ticks under twice the
    hottest key's count."""
    lay = run["phases"]["phases"]["stage_host"]["parts"]["route_keys"][
        "layout"]
    n_pre, per = run["prefill"], TRAFFIC["events_per_send"]
    zipf_cells = lay["cells"] - n_pre * 4 * TRAFFIC["prefill"]["keys_per_send"]
    assert lay["tiers"] - n_pre >= 2 * N_SENDS
    assert zipf_cells <= 32 * N_SENDS * per
    hot = sum(int(np.unique(s["cols"][0], return_counts=True)[1].max())
              for s in run["sends"][n_pre:])
    assert lay["max_e"] - 4 * n_pre == hot
    assert lay["ticks"] - 4 * n_pre <= 2 * hot
