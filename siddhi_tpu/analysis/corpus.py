"""The audit corpus: every app whose compiled plan is fingerprinted.

Two sources:

- `samples/apps/*.siddhi` — the shipped sample corpus (`make lint-apps`
  already keeps it zero-ERROR; the auditor additionally pins each app's
  cost fingerprint).
- The three bench serving shapes ROADMAP gates perf PRs on — flagship
  (partitioned 4-state pattern), windowed_join (the 100× outlier item 2
  names), and the block-NFA sequence — defined HERE and imported by
  `bench.py`, so the shapes the benchmark drives and the shapes the
  audit gate pins are one set of strings that cannot drift.

Templates keep bench's historical placeholder names ({async_ann},
{pipe_ann}, {n_keys}, {slots}, {ann}, {keys}) so bench call sites
format them unchanged.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional, Tuple

# ---------------------------------------------------------------------------
# bench shape templates (single source — bench.py imports these)
# ---------------------------------------------------------------------------

# the flagship serving shape: partitioned 4-stage NFA over a key space
# (bench.py run_tpu / --mode multichip drive it at different capacities)
FLAGSHIP_QL_TEMPLATE = """
@app:playback
{async_ann}
define stream TradeStream (key long, price float, volume int);
partition with (key of TradeStream)
begin
  @capacity(keys='{n_keys}', slots='{slots}')
  @emit(rows='2')
  {pipe_ann}
  @info(name='flagship')
  from every e1=TradeStream[volume == 1]
       -> e2=TradeStream[volume == 2 and price >= e1.price]
       -> e3=TradeStream[volume == 3]
       -> e4=TradeStream[volume == 4 and price >= e3.price]
  select e1.key as k, e1.price as p1, e2.price as p2, e4.price as p4
  insert into Matches;
end;
"""

# multichip variant: same NFA with @fuse riding the mesh (bench
# _mc_flagship); kept as its own template because the @fuse annotation
# changes the compiled artifact set (shard_fused_steps)
MC_FLAGSHIP_QL = """
@app:playback
define stream TradeStream (key long, price float, volume int);
partition with (key of TradeStream)
begin
  @capacity(keys='{keys}', slots='4')
  @emit(rows='2')
  @fuse(batches='4')
  @info(name='flagship')
  from every e1=TradeStream[volume == 1]
       -> e2=TradeStream[volume == 2 and price >= e1.price]
       -> e3=TradeStream[volume == 3]
       -> e4=TradeStream[volume == 4 and price >= e3.price]
  select e1.key as k, e1.price as p1, e2.price as p2, e4.price as p4
  insert into Matches;
end;
"""

# the 100x outlier: two-stream windowed join evaluated as a full [R,C]
# grid today (ROADMAP item 2 / lint JOIN002 cite this shape)
WINDOWED_JOIN_QL = """
@app:playback
define stream L (symbol long, price float);
define stream R (symbol long, qty int);
@emit(rows='65536')
@info(name='q')
from L#window.length(128) join R#window.length(128)
  on L.symbol == R.symbol
select L.symbol as s, L.price as p, R.qty as v
insert into Out;
"""

# multichip join variant (bench _mc_windowed_join — GSPMD placement)
MC_JOIN_QL = """
@app:playback
define stream JL (sym long, price float);
define stream JR (sym long, qty int);
@emit(rows='65536')
@info(name='wjoin')
from JL#window.length(64) join JR#window.length(64)
  on JL.sym == JR.sym
select JL.sym as s, JL.price as p, JR.qty as q
insert into JOut;
"""

# single-key block-NFA sequence (bench sequence_within /
# _mc_block_nfa)
SEQUENCE_QL = """
@app:playback
define stream S (symbol long, price float, volume int);
@capacity(keys='1', slots='8')
@emit(rows='4096')
{ann}
@info(name='q')
from every e1=S[volume == 1], e2=S[volume == 2 and price > e1.price]
  within 1 sec
select e1.price as p1, e2.price as p2
insert into M;
"""


# ---------------------------------------------------------------------------
# the audited corpus
# ---------------------------------------------------------------------------

def bench_shapes() -> List[Tuple[str, str, int]]:
    """(corpus key, SiddhiQL, mesh devices) for the bench shapes the
    audit baseline pins.  `mesh devices` 1 = single device; the sharded
    flagship entry is what surfaces collectives in the step HLO (skipped
    with a note when the environment has fewer devices)."""
    return [
        ("bench/flagship",
         FLAGSHIP_QL_TEMPLATE.format(async_ann="", pipe_ann="",
                                     n_keys=512, slots=4), 1),
        ("bench/windowed_join", WINDOWED_JOIN_QL, 1),
        ("bench/block_nfa", SEQUENCE_QL.format(ann=""), 1),
        # the served variant bench --mode serve_compare drives: same NFA
        # with emissions routed through the device ring (its fingerprint
        # pins the serve_ring state component plan_facts adds)
        ("bench/block_nfa_served",
         SEQUENCE_QL.format(ann="@serve\n@fuse(batches='8')"), 1),
        ("bench/flagship_sharded", MC_FLAGSHIP_QL.format(keys=512), 4),
    ]


def sample_apps(samples_dir: Optional[str] = None) -> Dict[str, str]:
    """{corpus key: SiddhiQL} for every shipped sample app."""
    if samples_dir is None:
        samples_dir = os.path.join(repo_root(), "samples", "apps")
    out: Dict[str, str] = {}
    for path in sorted(glob.glob(os.path.join(samples_dir,
                                              "*.siddhi"))):
        name = os.path.splitext(os.path.basename(path))[0]
        with open(path, "r") as fh:
            out[f"samples/{name}"] = fh.read()
    return out


def repo_root() -> str:
    """The repository root (two levels above this package)."""
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def corpus(samples_dir: Optional[str] = None,
           include_bench: bool = True) -> List[Tuple[str, str, int]]:
    """Ordered (key, ql, mesh devices) over the full audited corpus."""
    out = [(k, ql, 1) for k, ql in sample_apps(samples_dir).items()]
    if include_bench:
        out += bench_shapes()
    return out
