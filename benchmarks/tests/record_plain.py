#!/usr/bin/env python3
"""Record the small TPU trace `data/tiny_plain.xplane.pb.gz` that
`test_bench_lengthbatch_1000.py` reads (kept gzipped; the test unpacks it).
Run once on the chip:

    python benchmarks/tests/record_plain.py chiprun_out/recorded_plain

The cell `lengthbatch_1000.saturated` at its rehearsal sizes (window 10,
1,024-event sends) through the harness's own `Deployment`: the warm sends
outside the capture, then two sends inside it, each waited for.  The capture
holds what a traced run of the cell holds, in small: two
`bench:send_columns` spans and on the device plane two executions of
`jit_plain_step`, every op of which names its section in its event
metadata's `tf_op`.
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
    out = sys.argv[1] if len(sys.argv) > 1 else "chiprun_out/recorded_plain"
    from benchmarks.harness import (loader, plain_sections, platform, runner,
                                    trace_reduce)
    cell = loader.resolve("lengthbatch_1000.saturated", rehearse=True)
    if platform.start_jax(False, cell.chips, "record_plain") is None:
        return 1
    import jax
    dep = runner.Deployment(cell, 11, annotate=True)
    try:
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
            dep.issue(sid, runner.now())
            if dep.tracker.wait(sid, 10.0) is None:
                raise RuntimeError(f"send {sid} was not delivered")
        dep.flush()
        jax.profiler.stop_trace()
    finally:
        dep.close()
    if dep.errors:
        raise RuntimeError(f"the runtime reported {dep.errors[:1]}")
    keep = os.path.join(out, "tiny_plain.xplane.pb")
    shutil.copy(trace_reduce.newest_xplane(out), keep)
    with open(keep, "rb") as src, \
            gzip.open(keep + ".gz", "wb", compresslevel=9) as dst:
        shutil.copyfileobj(src, dst)
    print(keep, os.path.getsize(keep), "bytes;", keep + ".gz",
          os.path.getsize(keep + ".gz"), "bytes")
    red = trace_reduce.reduce_trace(keep)
    print(red)
    print(plain_sections.reduce_sections(keep, red["skew_s"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
