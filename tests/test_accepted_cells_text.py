"""The programs of the nine cells that do not run a `window.time` lower to the
text they lowered to at b993d59 (PR 51), and the time window's cell to
ca1471e's (PR 52), byte for byte: sha256 of
`fn.lower(*specs).as_text()` — WITHOUT debug info, so a line that moves
does not trip it; an op that changes does — of every program each cell runs
through its prefill and warm-up at rehearsal sizes (`benchmarks/harness`'
own `Deployment`, `--rehearse`'s sizes), keyed by query, role and a digest
of the argument shapes.  PR 52 changed `TimeWindow.process` and moved the
u32-plane helpers from `pattern_planner` to `steputil`; no op these cells
trace.  `timewindow_256sym.paced`, the tenth, is pinned from ca1471e (PR 52)
by PR 53, whose part scopes (`tests/test_section_parts.py`) move no op of
any cell, and AGAIN by PR 54 from its own tree: the selector's `sorted`
layout there moves its rows in one packed gather each way (eleven and seven
gathers before; `tests/test_selector_layout.py` holds the two forms to each
other bit for bit), and the other nine — `in_order`, a projection, or no
selector scan at all — are still the parent's.  A PR that changes one
of these programs ON PURPOSE re-pins its cell from its own parent:
`python tests/test_accepted_cells_text.py <cell>...` prints the digests."""
import hashlib
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PARENTS = {
    "pattern_1m.saturated": {
        "flagship:step[TradeStream]:5926f472": "33fc9fb98376c345b04f077b1576744d451cb439c91c23dcd1a4c14a63f4e235",
        "flagship:dense_step[TradeStream]:ad915821": "d870775c9013cc298b883be8c834682462ba77caeff962690635e383fc900014"
    },
    "pattern_1m.paced": {
        "flagship:step[TradeStream]:d05aa89c": "26fcb84ae1d807e183413841b0b2d006f8a5639f4f08f8655acbdd148b549ca9",
        "flagship:dense_step[TradeStream]:ad915821": "d870775c9013cc298b883be8c834682462ba77caeff962690635e383fc900014"
    },
    "pattern_32m.mesh4_saturated": {
        "flagship:dense_step[TradeStream]:1313e60d": "a23e504e404303854762dd9cbee9ef071e1edf3f56aa227372dd127fe8dc3d96"
    },
    "pattern_16m_zipf.paced": {
        "flagship:step[TradeStream]:936150dd": "ccb818f81f09c2308d8edbb329c0e386b609722b5f076db1208654d752a7b387",
        "flagship:dense_step[TradeStream]:1313e60d": "ed73dc0bf0d4c9d5afbaf67c293161e0a70a4d329ceb8becb4e02eb50cc5d841"
    },
    "pattern_1m.served_paced": {
        "flagship:step[TradeStream]:d05aa89c": "26fcb84ae1d807e183413841b0b2d006f8a5639f4f08f8655acbdd148b549ca9",
        "flagship:dense_step[TradeStream]:ad915821": "d870775c9013cc298b883be8c834682462ba77caeff962690635e383fc900014",
        "flagship:ring_append[0]:ca14a660": "9a5d36d9c0c8232c65393db629de70ee1242e110419ae05af0d5345722d568df",
        "flagship:ring_read[0]:9a393ed9": "b27b8f6bce814fe62fc6514cd3846ffa12812ee44e12c6e4d0f033f466444ba3"
    },
    "lengthbatch_1000.saturated": {
        "q:step:7e87e514": "518420a5259cdd6e478add68b57af5c1cd1209bb8d2fa4d492f31b33c7d3ca99"
    },
    "join_len128.saturated": {
        "q:step[left]:9a43abd4": "57f0e85d104bef00d3ce1b3de16620b71c69e211ad8444d649b96d66ead4ddf9",
        "q:step[right]:9a43abd4": "96c7e92dd661a8b5f13fd3d6e474672b7d77b08d3bc96ce70b95fffef9ebfb4f"
    },
    "sequence_within.paced": {
        "q:step[S]:f2232b4e": "cce5b8c313a3f769f8bb7e7b95b15dc3740d9389563b50ed19996899ed75cf9d"
    },
    "sequence_within.saturated": {
        "q:step[S]:f2232b4e": "cce5b8c313a3f769f8bb7e7b95b15dc3740d9389563b50ed19996899ed75cf9d"
    },
    "timewindow_256sym.paced": {
        "q:step:2e2fd6ee": "55776da986784ffb0119a926bda462f56f7b11206efaedf02b900977e1699dd9"
    }
}


def digests(cell_name):
    """{`query:role:shapes`: sha256 of the lowered text without debug info}
    of the programs `cell_name` runs at rehearsal sizes."""
    import jax
    sys.path.insert(0, ROOT)
    from benchmarks.harness import loader, runner
    cell = loader.resolve(cell_name, rehearse=True)
    dep = runner.Deployment(cell, 7, annotate=False)
    out = {}
    try:
        pre = cell.traffic.get("prefill")
        if pre:
            dep.run_untimed(pre, int(pre["sends"]), "prefill")
        dep.run_untimed(cell.traffic, int(cell.traffic["warmup_sends"]),
                        "warm-up")
        dep.flush()
        for q in dep.rt.query_runtimes:
            for role, fn, specs in dep.rt.compiled_steps(q):
                if specs is None:
                    continue
                shapes = ",".join(str(getattr(s, "shape", ""))
                                  for s in jax.tree.leaves(specs))
                key = f"{q}:{role}:" + \
                    hashlib.sha256(shapes.encode()).hexdigest()[:8]
                out[key] = hashlib.sha256(
                    fn.lower(*specs).as_text().encode()).hexdigest()
    finally:
        dep.close()
    return out


@pytest.fixture(scope="module")
def lowered():
    """Every cell's digests, from ONE child process (the cells deploy one
    after another; the mesh cell takes 4 of the 8 virtual CPU devices)."""
    done = subprocess.run(
        [sys.executable, os.path.abspath(__file__)] + sorted(PARENTS),
        capture_output=True, text=True, timeout=900, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 XLA_FLAGS="--xla_force_host_platform_device_count=8",
                 PYTHONPATH=os.pathsep.join(
                     [ROOT, os.environ.get("PYTHONPATH", "")])))
    assert done.returncode == 0, done.stderr[-2000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", sorted(PARENTS))
def test_a_cell_without_a_time_window_lowers_to_the_parents_text(
        cell, lowered):
    assert lowered[cell] == PARENTS[cell], (
        f"{cell}: a program's lowered text (no debug info) is not the "
        f"parent's: {json.dumps(lowered[cell])}")


if __name__ == "__main__":
    print(json.dumps({name: digests(name) for name in sys.argv[1:]}))
