#!/usr/bin/env python3
"""Record the small TPU trace `data/tiny_spans.xplane.pb` that
`test_bench_program_spans.py` reduces.  Run once on the chip:

    python benchmarks/tests/record_spans.py chiprun_out/recorded

Three `bench:send_columns` spans, each holding one `siddhi:send` shaped like
a send of the runtime's blocking path, with sleeps standing in for host work
so the numbers can be checked by hand: `siddhi:stage` (2 ms), `siddhi:h2d`
(1 ms), `siddhi:dispatch` (the jitted call, returns at submit),
`siddhi:fetch` (block_until_ready: holds the device step), `siddhi:demux`
(1 ms of its own) with a `siddhi:sink` (3 ms, `bench:subscriber` inside it)
nested in it.  The second send's delivery — fetch, demux, sink — runs on
another thread, as a drainer's does, while the sender's `siddhi:send` waits;
5 ms of `bench:wait_due` before the second and third send.  So the reduction
has to find 3 sends, 3 dispatches and 3 fetches, ~2 ms of stage and ~3 ms of
sink a send, an idle device under every host span, and the second thread's
spans counted like the first's.
"""
import os
import shutil
import sys
import threading
import time


def main() -> int:
    out = sys.argv[1] if len(sys.argv) > 1 else "chiprun_out/recorded"
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation as span
    if jax.devices()[0].platform != "tpu":
        print("record_spans: no TPU", file=sys.stderr)
        return 1
    step = jax.jit(lambda x: x * 2.0 + 1.0)
    x = jnp.ones((16, 1024, 1024), jnp.float32)
    step(x).block_until_ready()
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out, exist_ok=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(out, profiler_options=opts)

    def deliver(y, batch):
        with span("siddhi:fetch", q="q", batch=batch, what="header"):
            y.block_until_ready()
        with span("siddhi:demux", q="q", batch=batch):
            time.sleep(0.001)
            with span("siddhi:sink", q="q", batch=batch):
                with span("bench:subscriber"):
                    time.sleep(0.003)

    for i in range(3):
        if i:
            with span("bench:wait_due"):
                time.sleep(0.005)
        with span("bench:send_columns", sid=i):
            with span("siddhi:send", stream="S", batch=i + 1):
                with span("siddhi:stage", q="q", batch=i + 1):
                    time.sleep(0.002)
                with span("siddhi:h2d", q="q", batch=i + 1):
                    time.sleep(0.001)
                with span("siddhi:dispatch", q="q", batch=i + 1,
                          step="plain_step"):
                    y = step(x)
                if i == 1:
                    t = threading.Thread(target=deliver, args=(y, i + 1))
                    t.start()
                    t.join()
                else:
                    deliver(y, i + 1)
    jax.profiler.stop_trace()
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    from benchmarks.harness import program_spans, trace_reduce
    path = trace_reduce.newest_xplane(out)
    keep = os.path.join(out, "tiny_spans.xplane.pb")
    shutil.copy(path, keep)
    print(keep, os.path.getsize(keep), "bytes")
    red = trace_reduce.reduce_trace(keep)
    print(red)
    print(program_spans.reduce_spans(keep, red["skew_s"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
