#!/usr/bin/env python3
"""Record the small TPU trace `data/tiny_served.xplane.pb.gz` that
`test_bench_pattern_1m_served.py` reads (a real run's trace is ~1 MB, so it
is kept gzipped and the test unpacks it).  Run once on the chip:

    python benchmarks/tests/record_served.py chiprun_out/recorded

The cell `pattern_1m.served_paced` at its rehearsal sizes (1,024 keys,
16-key sends) through the harness's own `Deployment`: prefill and four warm
sends outside the capture, then four sends inside it, 5 ms of
`bench:wait_due` before each and each waited for, then the flush.  So the
capture holds what a traced run of the cell holds, in small: four
`bench:send_columns` spans with the runtime's `siddhi:*` spans of a served
send under them (two `h2d` uploads that say their bytes, the step's dispatch,
`dispatch step=ring_append` with its `occupancy`), the drainer thread's
`dispatch step=ring_read`, `fetch what=ring` (`items`, `ring_wait_us`), row
fetches, `demux`, `sink` with `bench:subscriber` inside — and on the device
plane the modules `jit_pattern_step`, `jit_ring_append` and `jit_ring_read`.
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
    from benchmarks.harness import (loader, platform, runner, served_spans,
                                    trace_reduce)
    cell = loader.resolve("pattern_1m.served_paced", rehearse=True)
    if platform.start_jax(False, cell.chips, "record_served") is None:
        return 1
    import jax
    dep = runner.Deployment(cell, 7, annotate=True)
    try:
        pre = cell.traffic["prefill"]
        dep.run_untimed(pre, int(pre["sends"]), "prefill")
        dep.run_untimed(cell.traffic, 4, "warm-up")
        dep.flush()
        sids = [dep.make(cell.traffic) for _ in range(4)]
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
    keep = os.path.join(out, "tiny_served.xplane.pb")
    shutil.copy(trace_reduce.newest_xplane(out), keep)
    with open(keep, "rb") as src, \
            gzip.open(keep + ".gz", "wb", compresslevel=9) as dst:
        shutil.copyfileobj(src, dst)
    print(keep, os.path.getsize(keep), "bytes;", keep + ".gz",
          os.path.getsize(keep + ".gz"), "bytes")
    print(trace_reduce.reduce_trace(keep))
    print(served_spans.read_served(keep))
    return 0


if __name__ == "__main__":
    sys.exit(main())
