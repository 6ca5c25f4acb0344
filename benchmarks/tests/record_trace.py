#!/usr/bin/env python3
"""Record the small TPU trace `data/tiny_tpu.xplane.pb` that
`test_trace_reduce.py` reduces.  Run once on the chip:

    python benchmarks/tests/record_trace.py chiprun_out/recorded

Three `bench:send_columns` spans, each running one jitted program to the end (an
elementwise pass over 64 MiB, so an op takes a fraction of a millisecond), with a
`bench:subscriber` span nested in the second; 20 ms of `bench:wait_due` before
the second and third.  So the reduction has to find three sends, a device that
is idle for more than 40 ms of a ~45 ms slice, and `bench:wait_due` as the
owner of the longest gaps.
"""
import os
import shutil
import sys
import time


def main() -> int:
    out = sys.argv[1] if len(sys.argv) > 1 else "chiprun_out/recorded"
    import jax
    import jax.numpy as jnp
    if jax.devices()[0].platform != "tpu":
        print("record_trace: no TPU", file=sys.stderr)
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
    for i in range(3):
        if i:
            with jax.profiler.TraceAnnotation("bench:wait_due"):
                time.sleep(0.02)
        with jax.profiler.TraceAnnotation("bench:send_columns", sid=i):
            y = step(x)
            if i == 1:
                with jax.profiler.TraceAnnotation("bench:subscriber"):
                    y.block_until_ready()
            else:
                y.block_until_ready()
    jax.profiler.stop_trace()
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    from benchmarks.harness import trace_reduce
    path = trace_reduce.newest_xplane(out)
    keep = os.path.join(out, "tiny_tpu.xplane.pb")
    shutil.copy(path, keep)
    print(keep, os.path.getsize(keep), "bytes")
    print(trace_reduce.reduce_trace(keep))
    return 0


if __name__ == "__main__":
    sys.exit(main())
