"""Each configuration's plain reference against a hand-worked case, and its
comparison against the faults it is there to catch."""
import numpy as np

from benchmarks.harness import loader


def model_of(cell_name):
    cell = loader.resolve(cell_name, rehearse=True)
    return cell, cell.model


def test_pattern_reference_by_hand():
    _, m = model_of("pattern_1m.saturated")
    # key 5 matches (2>=1, 9>=3); key 6 fails e2 (0.5 < 1); key 7 fails e4
    keys = np.repeat(np.array([5, 6, 7], np.int64), 4)
    price = np.array([1, 2, 3, 9, 1, .5, 3, 9, 1, 2, 3, 2], np.float32)
    vol = np.tile(np.array([1, 2, 3, 4], np.int32), 3)
    ref = m.reference([{"cols": [keys, price, vol]}], {})[0]
    assert ref["k"].tolist() == [5]
    assert (ref["p1"][0], ref["p2"][0], ref["p4"][0]) == (1.0, 2.0, 9.0)


def test_pattern_send_matches_once_per_key_and_attributes_by_key():
    cell, m = model_of("pattern_1m.paced")
    plan = m.plan(3, cell.traffic, cell.sizes)
    rng = np.random.default_rng(0)
    sends = [m.make_send(rng, i, cell.traffic, plan, 1000 + 10 * i)
             for i in range(3)]
    refs = m.reference(sends, plan)
    kb = cell.traffic["keys_per_send"]
    assert all(r["k"].shape[0] == kb == m.expected_rows(s)
               for r, s in zip(refs, sends))
    # without replacement: three sends, no key twice
    assert np.unique(np.concatenate([r["k"] for r in refs])).size == 3 * kb
    attr = m.Attribution(plan)
    for sid, s in enumerate(sends):
        attr.on_issue(sid, s)
    mixed = {"k": np.concatenate([refs[2]["k"][:2], refs[0]["k"][:1],
                                  np.array([-4, 10 ** 9])])}
    assert attr.attribute(mixed).tolist() == [2, 2, 0, -1, -1]


def test_pattern_compare_catches_each_fault():
    _, m = model_of("pattern_1m.saturated")
    want = {"k": np.arange(8, dtype=np.int64),
            "p1": np.linspace(.1, .8, 8).astype(np.float32),
            "p2": np.linspace(.2, .9, 8).astype(np.float32),
            "p4": np.linspace(.3, 1., 8).astype(np.float32)}
    assert m.compare(want, want) == {"rows_missing": 0,
                                     "rows_unexpected": 0,
                                     "rows_differing": 0}
    short = {n: a[:-1] for n, a in want.items()}
    assert m.compare(short, want)["rows_missing"] == 1
    dup = {n: np.concatenate([a, a[:1]]) for n, a in want.items()}
    assert m.compare(m.canonical(dup), want)["rows_unexpected"] == 1
    off = {n: a.copy() for n, a in want.items()}
    off["p2"][3] = np.nextafter(off["p2"][3], np.float32(2))
    assert m.compare(off, want)["rows_differing"] == 1
    # the control: the same rows with a bfloat16 payload fail the exact
    # comparison on (nearly) every row
    assert m.compare(m.control_rows(want), want)["rows_differing"] >= 6


def test_least_bytes_from_shapes():
    cell = loader.resolve("pattern_1m.saturated")
    # 131,072 keys: 2 x 520 B of state, 4 events of 24 B in, one row of 28 B
    assert cell.model.least_bytes(cell.traffic, cell.sizes, cell.config) \
        == 131072 * (2 * 520 + 4 * 24 + 28)
    cell = loader.resolve("pattern_1m.paced")
    assert cell.model.least_bytes(cell.traffic, cell.sizes, cell.config) \
        == 2048 * (2 * 520 + 4 * 24 + 28)
