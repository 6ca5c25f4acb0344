#!/usr/bin/env python3
"""Record the small TPU trace `data/tiny_sections.xplane.pb.gz` that
`test_bench_step_sections.py` reads (kept gzipped; the test unpacks it).
Run once on the chip:

    python benchmarks/tests/record_sections.py chiprun_out/recorded

The cell `pattern_16m_zipf.paced` at its rehearsal sizes (4,096 keys,
1,024-event sends, Zipf keys) through the harness's own `Deployment`:
prefill and the warm sends outside the capture, then two sends inside it,
5 ms of `bench:wait_due` before each and each waited for.  A send there is
three `[Kb, E]` tiers, so the capture holds what a traced run of the cell
holds, in small: two `bench:send_columns` spans, under each a `siddhi:send`
that says its `minflt`, and on the device plane six executions of
`jit_pattern_step` — three programs, one a rectangle — every op of which
names its section and its `rect_<Kb>x<E>` in its event metadata's `tf_op`.
"""
import gzip
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main() -> int:
    out = sys.argv[1] if len(sys.argv) > 1 else "chiprun_out/recorded"
    from benchmarks.harness import (loader, platform, runner, send_stats,
                                    step_sections, trace_reduce)
    cell = loader.resolve("pattern_16m_zipf.paced", rehearse=True)
    if platform.start_jax(False, cell.chips, "record_sections") is None:
        return 1
    import jax
    dep = runner.Deployment(cell, 11, annotate=True)
    try:
        pre = cell.traffic["prefill"]
        dep.run_untimed(pre, int(pre["sends"]), "prefill")
        dep.run_untimed(cell.traffic, int(cell.traffic["warmup_sends"]),
                        "warm-up")
        dep.flush()
        sids = [dep.make(cell.traffic) for _ in range(2)]
        shutil.rmtree(out, ignore_errors=True)
        os.makedirs(out, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(out, profiler_options=opts)
        for sid in sids:
            with dep.span("wait_due"):
                time.sleep(0.005)
            dep.issue(sid, runner.now())
            if dep.tracker.wait(sid, 10.0) is None:
                raise RuntimeError(f"send {sid} was not delivered")
        dep.flush()
        jax.profiler.stop_trace()
    finally:
        dep.close()
    if dep.errors:
        raise RuntimeError(f"the runtime reported {dep.errors[:1]}")
    keep = os.path.join(out, "tiny_sections.xplane.pb")
    shutil.copy(trace_reduce.newest_xplane(out), keep)
    with open(keep, "rb") as src, \
            gzip.open(keep + ".gz", "wb", compresslevel=9) as dst:
        shutil.copyfileobj(src, dst)
    print(keep, os.path.getsize(keep), "bytes;", keep + ".gz",
          os.path.getsize(keep + ".gz"), "bytes")
    red = trace_reduce.reduce_trace(keep)
    print(red)
    print(step_sections.reduce_sections(keep, red["skew_s"]))
    print(send_stats.read_sends(keep))
    return 0


if __name__ == "__main__":
    sys.exit(main())
