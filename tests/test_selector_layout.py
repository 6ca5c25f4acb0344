"""The selector's two row layouts (`AggregatorBank.layout`): `in_order` — a
query whose plan allocates no group slot scans its rows where they stand —
against `sorted`, the argsort by (slot, reset epoch) and the permutation
back, on the same `Rows`: every emitted value and the new state bit for bit,
step after step with the state carried.  And what the lowered programs hold:
the deployed `lengthbatch_1000` step sorts once (the window's) and scatters
nothing in the selector; with `group by` the selector's sort is there."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.compiler import SiddhiCompiler
from siddhi_tpu.core import event as ev
from siddhi_tpu.core.executor import Scope
from siddhi_tpu.core.selector import SelectorExec
from siddhi_tpu.core.window import Rows

APP = """
define stream S (price double, qty long, flag bool);
@info(name='q') from S
select sum(qty) as sum_long, sum(price) as sum_double, avg(price) as avg,
       count() as count, min(price) as min, max(qty) as max,
       stdDev(price) as stdDev, and(flag) as and_, or(flag) as or_
insert into Out;
"""
AGGREGATORS = ("sum_long", "sum_double", "avg", "count", "min", "max",
               "stdDev", "and_", "or_")
B, STEPS, BATCH = 192, 4, 16
C, E, R = ev.CURRENT, ev.EXPIRED, ev.RESET


def _length_batch(step):
    """What `lengthBatch` hands over: per flush the EXPIRED replay of the
    batch before, one RESET, the batch's CURRENT rows — a step that
    completes no batch, one, many."""
    flushes = (0, 1, 5, 3)[step]
    kind = []
    for _ in range(flushes):
        kind += [E] * BATCH + [R] + [C] * BATCH
    return kind


def _sliding(step):
    """What a sliding `length` hands over once it is full: each arrival's
    EXPIRED row, then its CURRENT row (the first step fills the window)."""
    if step == 0:
        return [C] * BATCH
    return [E, C] * (B // 2)


def _no_window(step):
    """No window: CURRENT rows only, a running value carried across steps."""
    return [C] * (B - 7 * step)


def _holes(step):
    """Every kind, a TIMER row too, with invalid rows in the middle."""
    base = [C] * 5 + [E] * 3 + [ev.TIMER] + [R] + [C] * 9 + [E, C] * 4 + [R, R]
    return (base * 8)[step:B - step]


SHAPES = {"length_batch": _length_batch, "sliding_length": _sliding,
          "no_window": _no_window, "holes_and_nulls": _holes}


def rows_of(shape, step, rng):
    kind = np.full(B, C, np.int32)
    valid = np.zeros(B, bool)
    made = SHAPES[shape](step)
    kind[:len(made)] = made
    valid[:len(made)] = True
    price = (10 + 990 * rng.random(B)).astype(np.float32)
    qty = rng.integers(-1000, 1000, B).astype(np.int64)
    flag = rng.random(B) < 0.7
    if shape == "holes_and_nulls":
        valid &= rng.random(B) < 0.8
        price[rng.random(B) < 0.15] = np.nan
        qty[rng.random(B) < 0.15] = ev.null_value("LONG")
    return Rows(ts=jnp.arange(B, dtype=jnp.int64) + B * step,
                kind=jnp.asarray(kind), valid=jnp.asarray(valid),
                seq=jnp.arange(B, dtype=jnp.int64),
                gslot=jnp.zeros((B,), jnp.int32),
                cols=(jnp.asarray(price), jnp.asarray(qty),
                      jnp.asarray(flag)))


def selector(single_slot):
    app = SiddhiCompiler.parse(APP)
    interner = ev.StringInterner()
    schema = ev.Schema(app.stream_definition_map["S"], interner)
    scope = Scope()
    scope.interner = interner
    scope.add_source("S", schema)
    query = app.execution_element_list[0]
    return SelectorExec(query.selector, scope, schema, 64, "Out", interner,
                        single_slot=single_slot)


def run(sel, shape):
    """Per step: (the projected columns, the valid mask, the new state)."""
    @jax.jit
    def step(state, rows):
        env = {"S": rows.cols, "__ts__": rows.ts, "__kind__": rows.kind,
               "__now__": jnp.asarray(0, jnp.int64)}
        state, (_ts, _kind, valid, cols) = sel.process(state, rows, env)
        return state, valid, cols

    rng = np.random.default_rng([17, sorted(SHAPES).index(shape)])
    state, out = sel.init_state(), []
    for i in range(STEPS):
        state, valid, cols = step(state, rows_of(shape, i, rng))
        out.append(jax.device_get((cols, valid, state)))
    return out


def bits(a):
    """An array as its bytes' integers: NaN equals NaN, -0.0 is not 0.0."""
    a = np.ascontiguousarray(a)
    return a.view(f"u{a.dtype.itemsize}") if a.dtype != bool else a


@pytest.fixture(scope="module")
def both():
    sels = {"in_order": selector(True), "sorted": selector(False)}
    assert {k: s.bank.layout for k, s in sels.items()} == \
        {"in_order": "in_order", "sorted": "sorted"}
    assert sels["in_order"].out_names == list(AGGREGATORS)
    cache = {}

    def get(shape):
        if shape not in cache:
            cache[shape] = {k: run(s, shape) for k, s in sels.items()}
        return cache[shape]
    return get


def lowered_step(group_by):
    """(the plan's layout, the lowered `jit_plain_step`'s text, its traced
    equations as (primitive, scope path)) of the deployed
    `lengthbatch_1000` query, as it is or with `group by`."""
    from test_lengthbatch_config import CONFIG, app_text
    text = app_text(1000)
    if group_by:
        head = "select avg(price) as ap"
        assert head in text
        text = text.replace(head, head + " group by symbol")
    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime(text)
        rt.add_batch_callback(CONFIG["query"], lambda _ts, _b: None)
        rt.start()
        n = 2048
        rt.get_input_handler(CONFIG["stream"]).send_columns(
            [np.arange(n, dtype=np.int64) % 7, np.ones(n, np.float32),
             np.ones(n, np.int32)], timestamps=np.arange(n, dtype=np.int64))
        (fn, specs), = [(fn, specs) for _role, fn, specs in
                        rt.compiled_steps(CONFIG["query"])
                        if specs is not None and
                        fn._siddhi_role == "plain_step"]
        layout = rt.explain(CONFIG["query"])["plan"]["selector_layout"]
        traced = fn.trace(*specs)
        return layout, traced.lower().as_text(), list(equations(traced.jaxpr))
    finally:
        m.shutdown()


def equations(jaxpr, scope=""):
    """Every equation of a traced program, nested ones too, as (primitive
    name, the `jax.named_scope` path it stands under)."""
    for eqn in jaxpr.eqns:
        here = f"{scope}/{eqn.source_info.name_stack}"
        yield eqn.primitive.name, here
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from equations(sub, here)


def under(eqns, prefix, *sections):
    return [(p, path) for p, path in eqns if p.startswith(prefix) and
            any(s in path.split("/") for s in sections)]


CASES = [(shape, agg) for shape in SHAPES for agg in AGGREGATORS] + \
    [("lowered", "as_deployed"), ("lowered", "group_by_symbol")]


@pytest.mark.parametrize("shape,what", CASES,
                         ids=[f"{s}-{w}" for s, w in CASES])
def test_in_order_layout_is_the_sorted_layout_bit_for_bit(shape, what, both):
    if shape == "lowered":
        layout, text, eqns = lowered_step(what == "group_by_symbol")
        sorts = len([p for p, _path in eqns if p == "sort"])
        in_selector = ("agg_layout", "agg_scan")
        assert len(under(eqns, "sort", "window_order")) == 1
        if what == "as_deployed":
            # the one sort is the window's (`sort_rows`); the selector
            # neither sorts nor scatters
            assert layout == "in_order" and sorts == 1
            assert text.count("stablehlo.sort") == 1
            assert not under(eqns, "sort", *in_selector)
            assert not under(eqns, "scatter", *in_selector)
            # what remains of the layout stands under its section
            assert under(eqns, "cumsum", "agg_layout")
        else:
            assert layout == "sorted" and sorts == 2
            assert len(under(eqns, "sort", "agg_layout")) == 1
            assert under(eqns, "scatter", "agg_layout")
            assert under(eqns, "scatter", "agg_scan")
        return
    runs = both(shape)
    col = AGGREGATORS.index(what)
    emitted = 0
    for (cols_i, valid_i, state_i), (cols_s, valid_s, state_s) in zip(
            runs["in_order"], runs["sorted"]):
        assert cols_i[col].dtype == cols_s[col].dtype
        # every row slot, not the valid ones alone: the forms agree on
        # what they compute for EXPIRED, RESET and invalid rows too
        assert np.array_equal(bits(cols_i[col]), bits(cols_s[col]))
        assert np.array_equal(valid_i, valid_s)
        emitted += int(valid_i.sum())
        # the whole state, every accumulator of every aggregator
        assert len(state_i) == len(state_s)
        for a, b in zip(state_i, state_s):
            assert a.shape == b.shape and a.dtype == b.dtype
            assert np.array_equal(bits(a), bits(b))
    assert emitted > B
    # the carry is at work: the last step's rows depend on earlier steps
    last = runs["in_order"][-1]
    assert last[1].any() and any(
        not np.array_equal(bits(s), bits(z)) for s, z in
        zip(last[2], jax.device_get(selector(True).init_state())))
