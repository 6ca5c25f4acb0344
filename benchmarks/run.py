#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once, in this process, on this machine.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Deploys the cell's configuration, warms the shapes its traffic uses, measures
for `--seconds`, drains, compares every timed send's rows with the
configuration's plain reference, prints what it saw and then — as the last
line of stdout — one JSON object: `correct`, `attempted`, `failed`, `metrics`,
`device` (and `breakdown` with `--trace 1`).  `--trace 0` reports the cell's
end-to-end metrics with statistics OFF and no profiler; `--trace 1` its
per-layer metrics from the same run with the harness's spans on and a
jax.profiler trace of its first few sends.

Any platform but `tpu`, fewer chips than the cell asks for, or a device that
`harness/peaks.json` does not list is an error: exit 1, no result line.
`--rehearse` walks the same code at tiny sizes on whatever jax has (the CPU
here); its output is labelled and carries no metric.  `--control 1` also
prints what the comparison makes of the reference's rows at the nearest lower
precision (the driver never passes it).
"""
import time
T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def say(msg: str) -> None:
    print(msg, flush=True)


def end_to_end(run: dict) -> dict:
    """Every end-to-end number this harness takes, by name; a cell reports
    the ones BENCHMARK.json lists for it."""
    from benchmarks.harness import numeric
    out = {"setup_s": run["setup_s"]}
    if run["window_s"] > 0:
        out["events_per_s"] = run["events"] / run["window_s"]
    lat = run["latency_ms"]
    if lat:
        out["latency_p50_ms"] = numeric.median(lat)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmarks.harness import (loader, numeric, platform, runner,
                                    trace_reduce)
    cell = loader.resolve(args.workload, rehearse=args.rehearse)
    seconds = args.seconds if args.seconds is not None else \
        float(loader.load_benchmark()["run_seconds"])

    started = platform.start_jax(args.rehearse, cell.chips, "benchmark")
    if started is None:
        return 1
    cache_dir, devs, device, runtime_start_s = started
    meters = runner.CompileMeters()
    meters.register()
    if args.rehearse:
        say("REHEARSAL: tiny sizes on whatever jax has — not a chip run; "
            "nothing below is a measurement and the result line carries "
            "no metric")
    else:
        cell.peaks = loader.load_peaks(device["kind"])
    say(f"cell {cell.name} seed {args.seed} seconds {seconds} trace "
        f"{args.trace}; device {json.dumps(device)}; compile cache "
        f"{cache_dir}; {time.perf_counter() - T_START:.2f} s since process "
        f"start, {runtime_start_s:.2f} s of it the accelerator runtime's "
        f"start")

    run = runner.run_cell(cell, args.seed, seconds, bool(args.trace),
                          bool(args.control), T_START, meters, devs, say)

    lat, late = run["latency_ms"], run["gen_late_ms"]
    say(f"window {run['window_s']:.3f} s: {run['attempted']} sends issued, "
        f"{run['completed']} completed, {run['events']} events, "
        f"{run['failed']} failed, {run['n_errors']} listener errors, "
        f"compiles in window {run['compiles_in_window']}")
    if lat:
        med = numeric.median(lat)
        say(f"latency over {len(lat)} sends (ms): p50 {med:.3f}  p95 "
            f"{numeric.percentile(lat, 0.95):.3f}  p99 "
            f"{numeric.percentile(lat, 0.99):.3f}  max {max(lat):.3f} at "
            f"send {lat.index(max(lat))}  "
            f"({sum(v > 2 * med for v in lat)} sends over twice the "
            f"median); generator lateness p50 {numeric.median(late):.3f}  "
            f"max {max(late):.3f}")
        if len(lat) <= 64:
            say("  each send's latency (ms): "
                + " ".join(f"{v:.0f}" for v in lat))

    device_out = dict(device, memory_peak_bytes=run["peak_hbm_bytes"])
    result = {"correct": run["failed"] == 0 and run["attempted"] > 0 and
              run["completed"] == run["attempted"],
              "attempted": run["attempted"], "failed": run["failed"]}
    metrics = {}
    if not args.trace:
        values = end_to_end(run)
        for entry in cell.end_to_end:
            if entry["name"] in values:
                metrics[entry["name"]] = {"value": values[entry["name"]],
                                          "unit": entry["unit"]}
            else:
                say(f"NOT REPORTED: {entry['name']} — this harness takes "
                    f"no end-to-end metric of that name")
    else:
        reduced = trace_reduce.reduce_trace(
            trace_reduce.newest_xplane(run["trace_dir"]))
        run["trace_reduced"] = reduced
        say(f"trace slice: {json.dumps(reduced)}")
        for entry, read in cell.per_layer:
            value = read(run)
            if value is not None:
                metrics[entry["name"]] = {"value": value,
                                          "unit": entry["unit"]}
        device_out["busy_s"] = reduced["busy_s"]
        device_out["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["by_module"],
                               "idle_gaps": reduced["idle_gaps"]}
    if args.rehearse:
        say("REHEARSAL metrics computed and withheld: "
            + ", ".join(sorted(metrics)))
        metrics = {}
        result.pop("breakdown", None)
        device_out.pop("busy_s", None)
        device_out.pop("window_s", None)
    else:
        for name, m in metrics.items():
            say(f"  {name} = {m['value']!r} {m['unit']}")
    result["metrics"] = metrics
    result["device"] = device_out
    say(f"total {time.perf_counter() - T_START:.1f} s")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
