"""`join_len128` through its own app text: the two-stream `window.length`
join on symbol fed by `send_columns` on both handlers in turn, sends wider
than the window, read by a batch callback, against the plain reference of
`benchmarks/configs/join_len128/model.py` — exact, send by send, the first
send owing nothing, nothing dropped at the emission cap, nothing compiled
after each side's first send, symbols purged from the key allocator and bound
again on the way; the join step's device-trace sections (`jax.named_scope`:
`join_window`, `join_lanes`, `join_probe`, `join_pairs`, `join_select`,
`join_compact`), which are in both side programs and leave what they lower to
as it was; and the programs of the accepted benchmark cells, whose lowered
text WITH debug info is what it was before the sections went in."""
import contextlib
import hashlib
import importlib.util
import json
import os
import re
import subprocess
import sys

import jax
import numpy as np
import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.observability import RECOMPILES

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CONFIGS = os.path.join(ROOT, "benchmarks", "configs")
CFG_DIR = os.path.join(CONFIGS, "join_len128")
with open(os.path.join(ROOT, "benchmarks", "traffic",
                       "saturated_two_streams_32k.json")) as _fh:
    TRAFFIC = json.load(_fh)
with open(os.path.join(CFG_DIR, "config.json")) as _fh:
    CONFIG = json.load(_fh)
SECTIONS = ("join_window", "join_lanes", "join_probe", "join_pairs",
            "join_select", "join_compact")
N_SENDS = 10
# (sizes, events a send): the configuration's own rehearsal sizes, and the
# source's window under sends eight windows wide
SHAPES = {"rehearse": (CONFIG["rehearse_sizes"],
                       TRAFFIC["rehearse"]["events_per_send"]),
          "w128_e1024": ({"window_length": 128, "emit_rows": 8192}, 1024)}
SEEDS = (11, 2 ** 31 + 7)
DTYPES = {"s": np.int64, "p": np.float32, "v": np.int32}   # the columns read


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


MODEL = _load(os.path.join(CFG_DIR, "model.py"), "bench_model_join_len128_t1")


def app_text(name, sizes):
    with open(os.path.join(CONFIGS, name, "app.siddhi")) as fh:
        return fh.read().format(**sizes)


def drive(shape, seed, n_sends=N_SENDS, keep_runtime=None):
    """Deploy, send `n_sends` sends to L and R in turn, return what each
    delivered and what the key allocator purged and bound meanwhile."""
    sizes, events = SHAPES[shape]
    traffic = dict(TRAFFIC, events_per_send=events)
    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime(app_text("join_len128", sizes))
        errors, batches = [], []
        rt.set_exception_listener(errors.append)

        def on_batch(_ts, b):
            sel = b["valid"] & (b["kind"] == 0)
            batches.append(({n: np.asarray(b["cols"][n])[sel]
                             for n in CONFIG["columns"]}, b["n_dropped"]))

        rt.add_batch_callback(CONFIG["query"], on_batch)
        rt.start()
        handlers = {s: rt.get_input_handler(s) for s in CONFIG["streams"]}
        alloc = rt.query_runtimes[CONFIG["query"]].planned.join_key_allocator
        purged, real_purge = [], alloc.purge

        def symbols_bound():
            return {int(np.frombuffer(k, np.int64)[0]): slot
                    for k, slot in alloc.snapshot().items()}

        def purge(slots):
            by_slot = {slot: sym for sym, slot in symbols_bound().items()}
            purged.append({by_slot[int(s)] for s in slots})
            real_purge(slots)
        alloc.purge = purge

        plan = MODEL.plan(seed, traffic, sizes)
        out = {"sends": [], "rows": [], "dropped": [], "compiles": [],
               "purged": [], "bound": []}
        clock = 1000
        for sid in range(n_sends):
            clock += MODEL.clock_step_ms(traffic)
            send = MODEL.make_send(np.random.default_rng([seed, sid]), sid,
                                   traffic, plan, clock)
            out["sends"].append(send)
            before, purges = len(batches), len(purged)
            handlers[send["stream"]].send_columns(
                [c.copy() for c in send["cols"]],
                timestamps=send["ts"].copy())
            # blocking delivery: the pairs are here when the call returns
            got = batches[before:]
            out["rows"].append({
                n: np.concatenate([rows[n] for rows, _ in got]) if got else
                np.zeros(0, dtype) for n, dtype in DTYPES.items()})
            out["dropped"].append(sum(int(d) for _, d in got))
            out["compiles"].append(RECOMPILES.snapshot(
                [CONFIG["query"]])[CONFIG["query"]]["count"])
            out["purged"].append(set().union(*purged[purges:]))
            out["bound"].append(set(symbols_bound()))
        assert not errors, errors[:1]
        out["refs"] = MODEL.reference(out["sends"], plan)
        out["window_length"] = sizes["window_length"]
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


CASES = pytest.mark.parametrize(
    "shape,seed", [(shape, seed) for shape in sorted(SHAPES)
                   for seed in SEEDS])


@CASES
def test_every_send_delivers_the_reference_pairs_exactly(shape, seed, runs):
    run = runs(shape, seed)
    assert all(v == 0 for v in MODEL.LIMITS.values())
    for i, (send, got, want) in enumerate(zip(run["sends"], run["rows"],
                                              run["refs"])):
        assert send["stream"] == "LR"[i % 2]
        nums = MODEL.compare(MODEL.canonical(got), MODEL.canonical(want))
        assert nums == dict.fromkeys(MODEL.LIMITS, 0), (i, nums)
        assert got["s"].shape[0] == MODEL.expected_rows(send), i
        assert {n: got[n].dtype for n in CONFIG["columns"]} == DTYPES


@CASES
def test_the_first_send_owes_none_and_no_send_drops_a_row(shape, seed, runs):
    run = runs(shape, seed)
    owed = [MODEL.expected_rows(s) for s in run["sends"]]
    assert owed[0] == 0 and run["rows"][0]["s"].shape[0] == 0
    # every later send is wider than the window and meets a full one:
    # about events x window / symbols pairs
    events, w = run["sends"][0]["events"], run["window_length"]
    assert events > w
    mean = events * w / TRAFFIC["symbols"]
    assert all(0.6 * mean < n < 1.4 * mean for n in owed[1:]), owed
    assert run["dropped"] == [0] * len(owed)


@CASES
def test_nothing_compiles_after_each_sides_first_send(shape, seed, runs):
    compiles = runs(shape, seed)["compiles"]
    assert compiles[1] > compiles[0]          # the R side's own program
    assert compiles[-1] == compiles[1], compiles


@CASES
def test_symbols_are_purged_and_bound_again(shape, seed, runs):
    """A send wider than the window retains only its last `window` rows:
    a symbol neither window holds any more is purged from the allocator,
    and binds a slot again when it next arrives."""
    run = runs(shape, seed)
    w = run["window_length"]
    again = 0
    for i, gone in enumerate(run["purged"][:-1]):
        assert not gone & run["bound"][i]
        again += len(gone & run["bound"][i + 1])
        assert len(run["bound"][i]) <= 2 * w
    if w < TRAFFIC["symbols"]:
        assert again > 0 and all(run["purged"][1:])
    else:
        # two 128-row windows over 64 symbols: a symbol is out of both
        # about once a send
        assert sum(map(len, run["purged"])) >= 1


@pytest.mark.parametrize("seed", SEEDS)
def test_the_reference_with_p_at_bfloat16_fails_the_comparison(seed, runs):
    run = runs("rehearse", seed)
    for want in run["refs"][1:]:
        want = MODEL.canonical(want)
        ctl = MODEL.compare(MODEL.canonical(MODEL.control_rows(want)), want)
        assert ctl["rows_missing"] == ctl["rows_unexpected"] == 0
        assert ctl["rows_differing"] > 0.5 * want["s"].shape[0]
        assert ctl["rows_differing"] > MODEL.LIMITS["rows_differing"]


# -- the join step's sections ---------------------------------------------

def side_step_texts(rt):
    """{role: (lowered text, lowered text with debug info)} of the two side
    programs the runtime ran."""
    out = {}
    for role, fn, specs in rt.compiled_steps(CONFIG["query"]):
        if specs is not None:
            lowered = fn.lower(*specs)
            out[role] = (lowered.as_text(),
                         lowered.as_text(debug_info=True))
    return out


@pytest.fixture(scope="module")
def with_scopes():
    run = drive("rehearse", 3, n_sends=4, keep_runtime=side_step_texts)
    for got, want in zip(run["rows"], run["refs"]):
        assert MODEL.compare(MODEL.canonical(got), MODEL.canonical(want)) \
            == dict.fromkeys(MODEL.LIMITS, 0)
    return run["kept"]


def test_both_side_steps_name_the_six_sections(with_scopes):
    assert sorted(with_scopes) == ["step[left]", "step[right]"]
    for role, (_text, named) in with_scopes.items():
        for section in SECTIONS:
            assert f"/{section}/" in named, (role, section)
        # a CURRENT-only projection join takes its arrivals as trigger
        # rows: the window sorts no output (`window_order`, which stands
        # INSIDE `join_window` where EXPIRED rows join too —
        # tests/test_join_late_materialise.py holds both)
        assert "window_order" not in named, role


def test_named_scopes_leave_the_lowered_join_steps_as_they_were(
        with_scopes, monkeypatch):
    """`jax.named_scope` is op-name metadata: the lowered programs without
    their debug info are the same with `jax.named_scope` patched out."""
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    try:
        without = drive("rehearse", 3, n_sends=4,
                        keep_runtime=side_step_texts)["kept"]
    finally:
        monkeypatch.undo()
    assert sorted(without) == sorted(with_scopes)
    for role, (text, named) in without.items():
        assert not any(s in named for s in SECTIONS), role
        assert text == with_scopes[role][0], role


# -- the accepted cells' programs: no traced line moved -----------------

# sha256 of the lowered text WITH debug info (checkout path -> <ROOT>, frames
# outside siddhi_tpu/ dropped) of the programs the accepted benchmark cells
# run, at their configurations' rehearsal sizes, each configuration in a
# process of its own (an inner `jit` keeps the call stack of whoever traced
# it first, so a text depends on what the process traced before), taken on
# the PARENT of the PR that put the join sections in (06af8da).  Op source
# lines — the call stack's too, `runtime.py`'s junction and handler frames
# among them — are in the compile-cache key
# (`jax_compilation_cache_include_metadata_in_key`): a digest that moves is
# a one-time compile-cache miss in that configuration's cells.  A PR that
# moves a traced line on purpose pays it, says so, and re-pins: PR 44 did,
# for `lengthbatch_1000` (`LengthBatchWindow.process`' kept buffers); PR 45
# for all three (the runtimes' one base: `runtime.py`'s frames moved, one
# is now `_Subscription.process_staged`, the plans' and the window's new
# declarations shifted `pattern_planner.py` + 15 and `window.py` + 6 lines;
# the texts WITHOUT debug info are the parent's byte for byte); PR 46 for
# all three (the plan's `send_layout` and its two lines in
# `PatternQueryRuntime.process_staged`, for the block step's `route_keys`
# span: `runtime.py` + 2 from line 878, `pattern_planner.py` + 4 / + 6; the
# texts without debug info again the parent's byte for byte); PR 48 for
# `lengthbatch_1000` alone (`window.py` + 33 lines above `LengthBatchWindow`:
# the windows' `current_is_arrivals` / `admit`; the text without debug info
# the parent's byte for byte, `pattern_1m`'s two digests unmoved); PR 51 for
# all three (the scheduler's one wake-up a runtime and the playback clock:
# `runtime.py`'s lines move from `_QueryRuntimeBase.__init__` down, its
# junction and handler frames with them; `window.py` + 15 lines above
# `LengthBatchWindow`: `timer_coalesces`, `TimeWindow.process`' sections; the
# texts without debug info of all 60 programs of the nine accepted cells the
# parent's byte for byte); PR 52 for all three (`steputil.py` + 3 lines
# above `jit_step`, whose frame every program carries: the u32-plane helpers
# and their imports moved there from `pattern_planner.py`, whose lines move
# up by 13 below its imports and 23 more below `band_edges`; `window.py` + 1
# line from the top — that import — and + 5 above `LengthBatchWindow`:
# `TimeWindow.process` as one argsort and one packed gather a section; the
# texts without debug info of the nine other cells' programs are pinned
# since then in `tests/test_accepted_cells_text.py`); PR 53 for all three
# (the part scopes: `selector.py` + 20 lines inside `AggregatorBank.process`
# and so above `SelectorExec.process`, `window.py` + 3 inside `sort_rows`;
# `phases.tier_scope` gone and the parts listed in its docstring:
# `phases.py` + 4 lines above `dispatch`, whose frame every program
# carries, `runtime.py` - 1, and `PatternQueryRuntime.process_staged`'s
# upload and dispatch block one `with` shallower, so its frames' lines and
# columns moved; the texts without debug info of ALL TEN cells' programs
# the parent's byte for byte: `tests/test_accepted_cells_text.py`); PR 54 for
# all three (`selector.py` + 52 lines above `SelectorExec.process`, whose
# frame every selecting program carries: `_AggSpec.after`, `_Layout`, and
# `AggregatorBank.process` in waves with one packed gather each way; the
# texts without debug info of the nine cells that sort nothing in the
# selector the parent's byte for byte, `timewindow_256sym.paced` re-pinned
# there on purpose); PR 55 for all three (`pattern.py` + 20 lines and three
# PART scopes — `count_capture` names nothing where no atom counts —
# `pattern_planner.py` + 34 above `compact_emission` (`unpack_planes_at`),
# `phases.py` + 15 in its docstring above `dispatch`, `runtime.py` + 15
# above `PatternQueryRuntime.process_staged` (`_all_scalars`,
# `_nfa_facts`); the texts without debug info of ALL TEN cells' programs
# the parent's byte for byte).
ACCEPTED = {
    "lengthbatch_1000": {
        "step":
        "98d9c374d669b83c8e5b1366ca6f4bf64f808a511c633d8f561b0c4c96bde4c8"},
    "pattern_1m": {
        "dense_step[TradeStream]":
        "a9e682ffd839c858550febf0f810cb60c8d66a71bc4d401d3d0681ddb1e8d122",
        "step[TradeStream]":
        "7e39ae49b07fa770c8dda6673eaf4f4e794eff9ed04bbda5096e6a968e15379a"},
}
# what each is sent: two sends; the flagship's second revisits every other
# key of its first, so its slots are no contiguous run (the gather step)
_STAGES = np.tile(np.arange(1, 5, dtype=np.int32), 8)
_SENDS = {
    "lengthbatch_1000": [
        [np.arange(64, dtype=np.int64) % 7,
         np.linspace(10, 900, 64, dtype=np.float32),
         np.arange(1, 65, dtype=np.int32)]] * 2,
    "pattern_1m": [
        [np.repeat(np.arange(16, dtype=np.int64), 4)[:32],
         np.linspace(0, 1, 32, dtype=np.float32), _STAGES],
        [np.repeat(np.arange(0, 16, 2, dtype=np.int64), 4),
         np.linspace(0, 1, 32, dtype=np.float32), _STAGES]],
}


def lowered_digests(name):
    """{role: (sha256 of the normalised lowered text with debug info,
    whether it names anything of the join's)} of the programs configuration
    `name` runs over `_SENDS[name]`."""
    with open(os.path.join(CONFIGS, name, "config.json")) as fh:
        cfg = json.load(fh)
    sizes = dict(cfg["sizes"], **cfg.get("rehearse_sizes", {}))
    out = {}
    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime(app_text(name, sizes))
        errors = []
        rt.set_exception_listener(errors.append)
        rt.add_batch_callback(cfg["query"], lambda _ts, _b: None)
        rt.start()
        h = rt.get_input_handler(cfg["stream"])
        for i, cols in enumerate(_SENDS[name]):
            h.send_columns([c.copy() for c in cols], timestamps=np.full(
                cols[0].shape[0], 1000 + i, np.int64))
        assert not errors, errors[:1]
        for role, fn, specs in rt.compiled_steps(cfg["query"]):
            if specs is None:
                continue
            text = fn.lower(*specs).as_text(debug_info=True)
            text = "\n".join(
                line for line in text.replace(ROOT, "<ROOT>").splitlines()
                if ".py\"" not in line or "<ROOT>/siddhi_tpu/" in line)
            out[role] = (hashlib.sha256(text.encode()).hexdigest(),
                         "join.py" in text or
                         any(section in text for section in SECTIONS))
    finally:
        m.shutdown()
    return out


@pytest.fixture(scope="module")
def accepted():
    cache = {}

    def get(name):
        if name not in cache:
            done = subprocess.run(
                [sys.executable, os.path.abspath(__file__), name],
                capture_output=True, text=True, timeout=600,
                env=dict(os.environ, JAX_PLATFORMS="cpu",
                         PYTHONPATH=os.pathsep.join(
                             [ROOT, os.environ.get("PYTHONPATH", "")])))
            assert done.returncode == 0, done.stderr[-2000:]
            cache[name] = json.loads(done.stdout.strip().splitlines()[-1])
        return cache[name]
    return get


@pytest.mark.parametrize("name,role", [
    (name, role) for name in sorted(ACCEPTED) for role in ACCEPTED[name]])
def test_an_accepted_cells_program_lowers_to_the_parents_text(
        name, role, accepted):
    digest, names_the_join = accepted(name)[role]
    # it traces nothing of the join's: no line of join.py, no join section
    assert not names_the_join
    assert digest == ACCEPTED[name][role], (
        f"{name} {role}: the lowered text with debug info moved (a traced "
        f"line of siddhi_tpu moved): {digest}")


if __name__ == "__main__":
    print(json.dumps(lowered_digests(sys.argv[1])))
