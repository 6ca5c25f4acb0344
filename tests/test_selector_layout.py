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


# ---------------------------------------------------------------------------
# the `sorted` layout moves its rows packed (PR 54): ONE gather into
# (slot, epoch) order and one back a layout a wave — against the seven and
# three one-array gathers a step it made before, kept HERE as the reference

def reference_process(bank, state, rows, env):
    """`AggregatorBank.process` as it stood at 1bba841 (PR 53), less its
    scopes: every column gathered by `order` alone, `seg` among them, every
    spec's scan gathered back by `unorder` alone, the specs in list order."""
    from siddhi_tpu.core.selector import _segmented_scan
    B = rows.capacity
    in_order = bank.layout == "in_order"
    sign = jnp.where(
        jnp.logical_and(rows.valid, rows.kind == ev.CURRENT), 1,
        jnp.where(jnp.logical_and(rows.valid, rows.kind == ev.EXPIRED),
                  -1, 0))
    gslot = None if in_order else jnp.where(
        rows.gslot >= 0, rows.gslot, 0).astype(jnp.int32)
    is_reset = jnp.logical_and(rows.valid, rows.kind == ev.RESET)
    reset_epoch = jnp.cumsum(is_reset.astype(jnp.int64))
    epoch_before = reset_epoch - is_reset.astype(jnp.int64)
    total_resets = reset_epoch[-1]

    def heads(seg_s):
        return jnp.concatenate([
            jnp.ones((1,), jnp.bool_), seg_s[1:] != seg_s[:-1]])

    def layout(slot_vec):
        if slot_vec is None:
            return (None, None, epoch_before, heads(epoch_before), sign,
                    None, epoch_before)
        seg = slot_vec.astype(jnp.int64) * (B + 2) + epoch_before
        order = jnp.argsort(seg, stable=True)
        unorder = jnp.zeros((B,), jnp.int32).at[order].set(
            jnp.arange(B, dtype=jnp.int32))
        seg_s = seg[order]
        return (order, unorder, seg_s, heads(seg_s), sign[order],
                slot_vec[order], epoch_before[order])

    layouts = {None: layout(gslot)}
    for j in range(len(bank.pair_sources)):
        ps = env[f"__pslot__{j}"]
        layouts[j] = layout(jnp.where(ps >= 0, ps, 0).astype(jnp.int32))

    env = dict(env)
    env["__scanres__"] = results = []
    new_state = []
    for spec, st in zip(bank.specs, state):
        (order, unorder, seg_s, first, sign_s, slot_s,
         epoch_s) = layouts[spec.slot_src]
        K = st.shape[0]
        vals = spec.vals_fn(env, sign)
        vals = jnp.where(sign != 0, vals, jnp.asarray(spec.init, spec.dtype))
        v_s = vals if order is None else vals[order]
        carry = st[0] if slot_s is None else st[slot_s]
        v_s = jnp.where(jnp.logical_and(first, epoch_s == 0),
                        spec.op(carry, v_s), v_s)
        scanned = _segmented_scan(v_s, seg_s, spec.op)
        results.append(scanned if unorder is None else scanned[unorder])
        contrib = jnp.logical_and(sign_s != 0, epoch_s == total_resets)
        idx = jnp.arange(B)
        if slot_s is None:
            last = jnp.max(jnp.where(contrib, idx, -1))
            last_idx = jnp.where(
                jnp.arange(K) == 0, last, -1).astype(jnp.int32)
        else:
            last_idx = jnp.full((K,), -1, jnp.int32).at[
                jnp.where(contrib, slot_s, K).astype(jnp.int32)
            ].max(jnp.where(contrib, idx, -1).astype(jnp.int32), mode="drop")
        gathered = scanned[jnp.clip(last_idx, 0, B - 1)]
        base = jnp.where(total_resets > 0,
                         jnp.full((K,), spec.init, spec.dtype), st)
        new_state.append(jnp.where(last_idx >= 0, gathered, base))
    return tuple(new_state), tuple(results)


GROUPS, VALUES = 11, 8      # group slots in use; distinct values a group
# case -> (select list, row kinds a step, lowest gslot, keys under vmap,
#          the (wave, layout) pairs that cross a permutation)
PACKED = {
    "sum_count_avg_current_expired": (
        "sum(price) as s, count() as c, avg(price) as a", _sliding, 0, 0, 1),
    "reset_rows_of_a_batch_window": (
        "sum(qty) as s, count() as c, avg(price) as a",
        _length_batch, 0, 0, 1),
    "min_max_and_seen": (
        "min(price) as lo, max(qty) as hi", _holes, 0, 0, 1),
    "and_or": ("and(flag) as a, or(flag) as o", _holes, 0, 0, 1),
    # two layouts, two waves: `sum:` / `cnt:` by group slot and `ref:` by
    # (group, value) pair slot, then `dc:`, which reads `ref:`, by group slot
    "distinct_count_beside_a_sum": (
        "sum(qty) as s, distinctCount(val) as d", _sliding, 0, 0, 3),
    "distinct_count_alone": ("distinctCount(val) as d", _holes, 0, 0, 2),
    "gslot_minus_one": (
        "sum(price) as s, count() as c, avg(price) as a", _holes, -1, 0, 1),
    "under_vmap_a_keyed_window": (
        "sum(price) as s, count() as c, max(price) as hi", _sliding, 0, 3, 1),
}


def grouped_bank(select):
    app = SiddhiCompiler.parse(f"""
        define stream S (price float, qty long, flag bool, val int);
        @info(name='q') from S select {select} group by val insert into Out;
        """)
    interner = ev.StringInterner()
    schema = ev.Schema(app.stream_definition_map["S"], interner)
    scope = Scope()
    scope.interner = interner
    scope.add_source("S", schema)
    sel = SelectorExec(app.execution_element_list[0].selector, scope, schema,
                       16, "Out", interner)
    assert sel.bank.layout == "sorted"
    return sel.bank


def grouped_rows(kinds, step, lowest, rng):
    """`rows_of`'s shapes with a group slot a row (`lowest` = -1: some rows
    name none) and the pair slot distinctCount's host side would hand in."""
    shape = {v: k for k, v in SHAPES.items()}[kinds]
    rows = rows_of(shape, step, rng)
    gslot = rng.integers(lowest, GROUPS, B).astype(np.int32)
    val = rng.integers(0, VALUES, B).astype(np.int32)
    pslot = np.where(gslot >= 0, gslot * VALUES + val, -1).astype(np.int32)
    return rows._replace(gslot=jnp.asarray(gslot),
                         cols=rows.cols + (jnp.asarray(val),)), \
        jnp.asarray(pslot)


def stacked(trees):
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


@pytest.mark.parametrize("case", sorted(PACKED))
def test_packed_layout_is_the_per_array_layout_bit_for_bit(case):
    select, kinds, lowest, keys, crossings = PACKED[case]
    bank = grouped_bank(select)

    def step(process):
        def one(state, rows, pslot):
            env = {"S": rows.cols, "__ts__": rows.ts, "__kind__": rows.kind,
                   "__now__": jnp.asarray(0, jnp.int64), "__pslot__0": pslot}
            return process(state, rows, env)
        return jax.jit(jax.vmap(one) if keys else one)

    packed = step(bank.process)
    reference = step(lambda *a: reference_process(bank, *a))
    rng = np.random.default_rng([54, sorted(PACKED).index(case)])
    state = bank.init_state()
    if keys:
        state = stacked([state] * keys)
    state_p = state_r = state
    moved = 0
    for i in range(STEPS):
        made = [grouped_rows(kinds, i, lowest, rng)
                for _ in range(keys or 1)]
        rows, pslot = stacked(made) if keys else made[0]
        state_p, scans_p = jax.device_get(packed(state_p, rows, pslot))
        state_r, scans_r = jax.device_get(reference(state_r, rows, pslot))
        assert len(scans_p) == len(scans_r) == len(bank.specs)
        for got, want in zip(scans_p + state_p, scans_r + state_r):
            assert got.shape == want.shape and got.dtype == want.dtype
            assert np.array_equal(bits(got), bits(want))
        moved += sum(int((a != b).any()) for a, b in zip(
            state_p, jax.device_get(state)))
    assert moved >= len(bank.specs)       # every accumulator was at work

    # what the traced program holds: one gather into a layout's order and
    # one back for each (wave, layout), whatever the number of specs
    waves = {(s.after is not None, s.slot_src) for s in bank.specs}
    assert len(waves) == crossings
    made = grouped_rows(kinds, 0, lowest, rng)
    args = (bank.init_state(),) + made
    if keys:
        args = stacked([args] * keys)
    eqns = list(equations(packed.trace(*args).jaxpr))
    assert len(under(eqns, "gather", "to_sorted")) == crossings
    assert len(under(eqns, "gather", "from_sorted")) == crossings
    assert len(under(eqns, "sort", "order")) == \
        len(under(eqns, "scatter", "invert")) == 1 + len(bank.pair_sources)
