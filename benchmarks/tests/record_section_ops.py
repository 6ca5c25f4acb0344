#!/usr/bin/env python3
"""Record the small TPU trace `data/tiny_timewindow.xplane.pb.gz` that
`test_bench_section_ops.py` reads (kept gzipped; the test unpacks it).
Run once on the chip:

    python benchmarks/tests/record_section_ops.py chiprun_out/recorded_ops

The cell `timewindow_256sym.paced` at its rehearsal sizes (an 8,192-row
window, 1,024-event sends, a feed gap every 8th send) through the harness's
own `Deployment`: 14 warm sends outside the capture — past the gap at send 8
and the five sends that fill the window after it — then sends 14 and 15
inside it, each waited for.  Each of the two expires rows by the clock before
its first arrival, so the capture holds what a traced run of the cell holds,
in small: two `bench:send_columns` spans and on the device plane TWO programs
of the one module `jit_plain_step` — the send's step at 1,024 rows and the
timer's at its few — every op of `agg_layout`, `agg_scan` naming its section
and its part in its event metadata's `tf_op`.
"""
import gzip
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

WARM_SENDS = 14


def main() -> int:
    out = sys.argv[1] if len(sys.argv) > 1 else "chiprun_out/recorded_ops"
    from benchmarks.harness import (loader, plain_sections, platform, runner,
                                    section_ops, trace_reduce)
    cell = loader.resolve("timewindow_256sym.paced", rehearse=True)
    if platform.start_jax(False, cell.chips, "record_section_ops") is None:
        return 1
    import jax
    dep = runner.Deployment(cell, 11, annotate=True)
    try:
        dep.run_untimed(cell.traffic, WARM_SENDS, "warm-up")
        dep.flush()
        sids = [dep.make(cell.traffic) for _ in range(2)]
        shutil.rmtree(out, ignore_errors=True)
        os.makedirs(out, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(out, profiler_options=opts)
        for sid in sids:
            dep.issue(sid, runner.now())
            if dep.tracker.wait(sid, 10.0) is None:
                raise RuntimeError(f"send {sid} was not delivered")
        dep.flush()
        jax.profiler.stop_trace()
        facts = dep.rt.timer_facts()
    finally:
        dep.close()
    if dep.errors:
        raise RuntimeError(f"the runtime reported {dep.errors[:1]}")
    keep = os.path.join(out, "tiny_timewindow.xplane.pb")
    shutil.copy(trace_reduce.newest_xplane(out), keep)
    with open(keep, "rb") as src, \
            gzip.open(keep + ".gz", "wb", compresslevel=9) as dst:
        shutil.copyfileobj(src, dst)
    print(keep, os.path.getsize(keep), "bytes;", keep + ".gz",
          os.path.getsize(keep + ".gz"), "bytes; timers", facts)
    red = trace_reduce.reduce_trace(keep)
    print(red)
    print(plain_sections.reduce_sections(keep, red["skew_s"]))
    ops = section_ops.reduce_ops(keep, red["skew_s"])
    print({k: v for k, v in ops.items() if k != "rows"})
    for row in ops["rows"][:40]:
        print(row)
    return 0


if __name__ == "__main__":
    sys.exit(main())
