"""`lengthbatch_1000` through its own app text: `lengthBatch(1000)` +
`avg(price)` fed by `send_columns`, read by a batch callback, against the
plain float64 reference of `benchmarks/configs/lengthbatch_1000/model.py`,
send by send in delivery order — sends that start inside a batch, a send
that completes no batch and one that completes several — with nothing
compiled after the warm-up; and the plain step's device-trace sections
(`jax.named_scope`: `plain_chain`, `window_fill`, `window_state`,
`window_order`, `agg_layout`, `agg_scan`, `project`), which leave the lowered
`jit_plain_step` as it was and name every op of the compiled one."""
import collections
import contextlib
import importlib.util
import json
import os

import jax
import numpy as np
import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.observability import RECOMPILES

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CFG_DIR = os.path.join(ROOT, "benchmarks", "configs", "lengthbatch_1000")
with open(os.path.join(ROOT, "benchmarks", "traffic",
                       "saturated_stream_131k.json")) as _fh:
    TRAFFIC = json.load(_fh)
with open(os.path.join(CFG_DIR, "config.json")) as _fh:
    CONFIG = json.load(_fh)
SECTIONS = ("plain_chain", "window_fill", "window_state", "window_order",
            "agg_layout", "agg_scan", "project")
N_SENDS = 12
# (window length, events a send): the source's window under sends smaller
# and larger than a batch, and the configuration's own rehearsal sizes
SHAPES = {"w1000_e384": (1000, 384), "w1000_e4096": (1000, 4096),
          "rehearse": (CONFIG["rehearse_sizes"]["window_length"],
                       TRAFFIC["rehearse"]["events_per_send"])}
SEEDS = (11, 2 ** 31 + 7)


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


MODEL = _load(os.path.join(CFG_DIR, "model.py"), "bench_model_lengthbatch_t1")
# `executed`: the instructions of a compiled text that run as device ops
_SS = _load(os.path.join(HERE, "test_step_sections.py"), "_step_sections_t1")


def app_text(window_length, filtered=False):
    """The configuration's app text; `filtered` puts a filter that passes
    every event in front of the window, so that the chain has ops."""
    with open(os.path.join(CFG_DIR, "app.siddhi")) as fh:
        text = fh.read().format(window_length=window_length)
    if filtered:
        head = "from StockStream#window"
        assert head in text
        text = text.replace(head, "from StockStream[volume > 0]#window")
    return text


def drive(shape, seed, n_sends=N_SENDS, keep_runtime=None, filtered=False):
    """Deploy, send `n_sends` sends, return what each delivered."""
    window_length, events = SHAPES[shape]
    traffic = dict(TRAFFIC, events_per_send=events)
    sizes = {"window_length": window_length}
    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime(app_text(window_length, filtered))
        errors, batches = [], []
        rt.set_exception_listener(errors.append)

        def on_batch(_ts, b):
            sel = b["valid"] & (b["kind"] == 0)
            batches.append(np.asarray(b["cols"]["ap"])[sel])

        rt.add_batch_callback(CONFIG["query"], on_batch)
        rt.start()
        h = rt.get_input_handler(CONFIG["stream"])
        plan = MODEL.plan(seed, traffic, sizes)
        sends, rows, compiles = [], [], []
        clock = 1000
        for sid in range(n_sends):
            clock += MODEL.clock_step_ms(traffic)
            send = MODEL.make_send(np.random.default_rng([seed, sid]), sid,
                                   traffic, plan, clock)
            sends.append(send)
            before = len(batches)
            h.send_columns([c.copy() for c in send["cols"]],
                           timestamps=send["ts"].copy())
            # blocking delivery: the rows are here when the call returns
            got = batches[before:]
            rows.append({"ap": np.concatenate(got) if got else
                         np.zeros(0, np.float32)})
            compiles.append(RECOMPILES.snapshot(
                [CONFIG["query"]])[CONFIG["query"]]["count"])
        assert not errors, errors[:1]
        out = {"sends": sends, "rows": rows, "compiles": compiles,
               "window_length": window_length,
               "refs": MODEL.reference(sends, plan)}
        if keep_runtime is not None:
            out["kept"] = keep_runtime(rt)
        return out
    finally:
        m.shutdown()


@pytest.fixture(scope="module")
def runs():
    cache = {}

    def get(shape, seed):
        if (shape, seed) not in cache:
            cache[shape, seed] = drive(shape, seed)
        return cache[shape, seed]
    return get


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_every_send_delivers_the_reference_rows_in_delivery_order(
        shape, seed, runs):
    run = runs(shape, seed)
    assert all(v == 0 for v in MODEL.LIMITS.values())
    for i, (send, got, want) in enumerate(zip(run["sends"], run["rows"],
                                              run["refs"])):
        nums = MODEL.compare(MODEL.canonical(got), MODEL.canonical(want))
        assert nums == dict.fromkeys(MODEL.LIMITS, 0), (i, nums)
        assert got["ap"].shape[0] == MODEL.expected_rows(send), i


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_sends_start_inside_a_batch_and_complete_none_or_several(
        shape, seed, runs):
    run = runs(shape, seed)
    w = run["window_length"]
    fills = [s["fill"] for s in run["sends"]]
    batches = [MODEL.expected_rows(s) // w for s in run["sends"]]
    assert sum(f != 0 for f in fills) >= len(fills) * 2 // 3, fills
    assert sum(batches) > 0
    if shape == "w1000_e384":
        # a send is less than half a batch: most complete none
        assert set(batches) == {0, 1} and batches.count(0) > batches.count(1)
    else:
        assert min(batches) >= 2 and len(set(batches)) == 2, batches


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_nothing_compiles_after_the_first_send(shape, seed, runs):
    compiles = runs(shape, seed)["compiles"]
    assert compiles[-1] == compiles[0], compiles


@pytest.mark.parametrize("seed", SEEDS)
def test_the_reference_at_bfloat16_fails_the_comparison(seed, runs):
    run = runs("w1000_e4096", seed)
    for want in run["refs"]:
        ctl = MODEL.compare(MODEL.control_rows(want), want)
        assert ctl["rows_missing"] == ctl["rows_unexpected"] == 0
        assert ctl["rows_differing"] > 0.5 * want["ap"].shape[0]
        assert ctl["rows_differing"] > MODEL.LIMITS["rows_differing"]


def test_a_dropped_batch_a_swapped_pair_and_a_nan_each_fail(runs):
    run = runs("w1000_e4096", SEEDS[0])
    got, want = run["rows"][2], run["refs"][2]
    w = run["window_length"]
    assert MODEL.compare({"ap": got["ap"][w:]}, want)["rows_missing"] == w
    twice = {"ap": np.concatenate([got["ap"], got["ap"][:w]])}
    assert MODEL.compare(twice, want)["rows_unexpected"] == w
    swapped = got["ap"].copy()
    swapped[[w, w + 1]] = swapped[[w + 1, w]]    # rows 1 and 2 of a batch
    assert MODEL.compare({"ap": swapped}, want)["rows_differing"] == 2
    holed = got["ap"].copy()
    holed[5] = np.nan
    assert MODEL.compare({"ap": holed}, want)["rows_differing"] == 1


# -- the plain step's sections ---------------------------------------------------

def plain_step_facts(rt):
    """(lowered text, lowered text with debug info, flops, bytes, compiled
    text) of the `jit_plain_step` the runtime ran."""
    found = [(fn, specs) for _role, fn, specs in
             rt.compiled_steps(CONFIG["query"])
             if specs is not None and fn._siddhi_role == "plain_step"]
    (fn, specs), = found
    lowered = fn.lower(*specs)
    compiled = lowered.compile()
    ca = compiled.cost_analysis()
    return (lowered.as_text(), lowered.as_text(debug_info=True),
            ca.get("flops"), ca.get("bytes accessed"), compiled.as_text())


@pytest.fixture(scope="module", params=["as_deployed", "filtered"])
def with_scopes(request):
    """The facts of the configuration's own plain step, and of the same
    query behind a filter (the deployed text has none, so its
    `plain_chain` holds nothing the program keeps)."""
    filtered = request.param == "filtered"
    run = drive("w1000_e4096", 3, n_sends=2, filtered=filtered,
                keep_runtime=plain_step_facts)
    for got, want in zip(run["rows"], run["refs"]):
        assert MODEL.compare(got, want) == dict.fromkeys(MODEL.LIMITS, 0)
    return filtered, run["kept"]


def sections_of(filtered):
    return SECTIONS if filtered else SECTIONS[1:]


def test_named_scopes_leave_the_lowered_plain_step_as_it_was(
        with_scopes, monkeypatch):
    """`jax.named_scope` is op-name metadata: the lowered program without
    its debug info, and XLA's cost analysis of the compiled one, are the
    same with the sections and with `jax.named_scope` patched out."""
    filtered, (text, named, flops, nbytes, _) = with_scopes
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    try:
        without = drive("w1000_e4096", 3, n_sends=2, filtered=filtered,
                        keep_runtime=plain_step_facts)["kept"]
    finally:
        monkeypatch.undo()
    assert all(s in named for s in sections_of(filtered))
    assert not any(s in without[1] for s in SECTIONS)
    assert text == without[0]
    assert (flops, nbytes) == without[2:4]


def test_every_op_of_the_plain_step_names_one_section(with_scopes):
    """Of the compiled text's instructions that run as ops, each that
    carries an `op_name` of the program names exactly one section; what
    the compiler puts in itself (copies, the loops it expands a cumsum or
    a sort's comparator into) carries none of the program's and is
    reported, not judged."""
    filtered, facts = with_scopes
    named, short, compilers = collections.Counter(), [], \
        collections.Counter()
    for opcode, op_name in _SS.executed(facts[4]):
        parts = op_name.split(";")[0].split("/")
        if parts[0] != "jit(plain_step)":
            compilers[opcode] += 1
            continue
        sections = [p for p in parts if p in SECTIONS]
        assert len(sections) <= 1, op_name
        if sections:
            named[sections[0]] += 1
        else:
            short.append((opcode, op_name))
    print(f"plain_step: instructions by section {dict(named)}; naming none "
          f"{short}; the compiler's own by opcode {dict(compilers)}")
    assert not short, short
    assert sum(named.values()) >= 30
    # the chain's compare is fused into the window's first fusion, which
    # takes its root's section: six sections show as ops of their own
    assert set(SECTIONS[1:]) <= set(named), named
