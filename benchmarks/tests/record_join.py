#!/usr/bin/env python3
"""Record the small TPU trace `data/tiny_join.xplane.pb.gz` that
`test_bench_join_len128.py` reads (kept gzipped; the test unpacks it).
Run once on the chip:

    python benchmarks/tests/record_join.py chiprun_out/recorded_join

The cell `join_len128.saturated` at its rehearsal sizes (windows of 8,
512-event sends, L and R in turn) through the harness's own `Deployment`: the
warm sends outside the capture, then four sends inside it, two a side, each
waited for.  The capture holds what a traced run of the cell holds, in small:
four `bench:send_columns` spans, the runtime's `siddhi:route_keys` spans with
their `lane_k` / `lane_need`, and on the device plane two executions each of
`jit_join_left` and `jit_join_right`, every op of which names its section in
its event metadata's `tf_op`.
"""
import gzip
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main() -> int:
    out = sys.argv[1] if len(sys.argv) > 1 else "chiprun_out/recorded_join"
    from benchmarks.harness import (loader, join_sections, platform, runner,
                                    trace_reduce)
    cell = loader.resolve("join_len128.saturated", rehearse=True)
    if platform.start_jax(False, cell.chips, "record_join") is None:
        return 1
    import jax
    dep = runner.Deployment(cell, 11, annotate=True)
    try:
        dep.run_untimed(cell.traffic, int(cell.traffic["warmup_sends"]),
                        "warm-up")
        dep.flush()
        sids = [dep.make(cell.traffic) for _ in range(4)]
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
    finally:
        dep.close()
    if dep.errors:
        raise RuntimeError(f"the runtime reported {dep.errors[:1]}")
    keep = os.path.join(out, "tiny_join.xplane.pb")
    shutil.copy(trace_reduce.newest_xplane(out), keep)
    with open(keep, "rb") as src, \
            gzip.open(keep + ".gz", "wb", compresslevel=9) as dst:
        shutil.copyfileobj(src, dst)
    print(keep, os.path.getsize(keep), "bytes;", keep + ".gz",
          os.path.getsize(keep + ".gz"), "bytes")
    red = trace_reduce.reduce_trace(keep)
    print(red)
    print(join_sections.reduce_sections(keep, red["skew_s"]))
    print(join_sections.read_lanes(keep))
    return 0


if __name__ == "__main__":
    sys.exit(main())
