#!/usr/bin/env python3
"""Find the highest rate an open-loop cell sustains, once, on the chip.

    python benchmarks/sweep.py --workload pattern_1m.paced --seed 1 \
        --rates 100000,150000,... [--step-seconds 10]

One deployment, set up as `run.py` sets it up; then one open-loop step of
`--step-seconds` at each rate in turn, rising, with the runtime flushed between
steps.  A rate is SUSTAINED when the last send of its step was issued less than
one send interval after it was due: the generator, which a blocking send holds
up, had not fallen behind by the end.  The knee is the highest sustained rate
below the first that is not.  The table goes to stdout and to
`chiprun_out/sweep/<workload>.json`; the cell's rate (0.8 x knee) is then
written into its traffic file by hand, with the table in PERF.md.

Every send goes through `runner.Deployment.issue`, as `run.py`'s do: to the
stream it names, and done at its call's return where it owes no rows — so a
two-stream cell's knee is swept by this file as it stands
(`tests/test_bench_two_streams.py` rehearses it on one).
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", required=True,
                    help="comma-separated events/s, rising")
    ap.add_argument("--step-seconds", type=float, default=10.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    from benchmarks.harness import loader, numeric, platform, runner
    cell = loader.resolve(args.workload, rehearse=args.rehearse)
    if cell.traffic["loop"] != "open":
        raise SystemExit(f"{cell.name} is not an open-loop cell")
    started = platform.start_jax(args.rehearse, cell.chips, "sweep")
    if started is None:
        return 1
    device = started[2]
    if args.rehearse:
        print("REHEARSAL: tiny sizes, not a chip run — no rate below is a "
              "measurement", flush=True)

    traffic = cell.traffic
    dep = runner.Deployment(cell, args.seed, annotate=False)
    rows = []
    try:
        pre = traffic.get("prefill")
        if pre:
            dep.run_untimed(pre, int(pre["sends"]), "prefill")
        dep.run_untimed(traffic, int(traffic["warmup_sends"]), "warm-up")
        dep.flush()
        print(f"set-up {time.perf_counter() - T_START:.1f} s", flush=True)
        per_send = cell.model.events_per_send(traffic)
        for rate in [float(r) for r in args.rates.split(",")]:
            traffic["rate_events_per_s"] = rate
            interval = per_send / rate
            sids = [dep.make(traffic) for _ in range(
                runner.planned_sends(cell, args.step_seconds))]
            win = runner.open_loop(dep, sids, args.step_seconds, None)
            dep.flush()
            for sid in sids:
                dep.tracker.wait(sid, float(traffic["drain_limit_s"]))
            done = dep.tracker.done_t
            lat = [(done[s] - dep.stamps[s]["due"]) * 1e3
                   for s in sids if s in done]
            late = [(dep.stamps[s]["issued"] - dep.stamps[s]["due"]) * 1e3
                    for s in sids]
            row = {
                "rate_events_per_s": rate, "sends": len(sids),
                "completed": len(lat), "interval_ms": interval * 1e3,
                "last_send_late_ms": late[-1],
                "late_ms_p50": numeric.median(late),
                "late_ms_max": max(late),
                "latency_ms_p50": numeric.median(lat),
                "latency_ms_p99": numeric.percentile(lat, 0.99),
                "achieved_events_per_s":
                    len(lat) * per_send / (max(done[s] for s in sids
                                               if s in done) - win["t0"]),
                "sustained": bool(late[-1] < interval * 1e3 and
                                  len(lat) == len(sids) and not dep.errors),
            }
            rows.append(row)
            print(json.dumps(row), flush=True)
    finally:
        dep.close()
    knee = None
    for r in rows:
        if not r["sustained"]:
            break
        knee = r["rate_events_per_s"]
    out = {"workload": cell.name, "seed": args.seed, "device": device,
           "step_seconds": args.step_seconds, "rehearsal": args.rehearse,
           "errors": len(dep.errors), "knee_events_per_s": knee,
           "cell_rate_events_per_s": None if knee is None else 0.8 * knee,
           "steps": rows}
    out_dir = os.path.join(ROOT, "chiprun_out", "sweep")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, cell.name + ".json"), "w") as fh:
        json.dump(out, fh, indent=1)
        fh.write("\n")
    print(f"knee {knee} events/s; 0.8 x knee = "
          f"{out['cell_rate_events_per_s']}", flush=True)
    return 0 if knee is not None and not dep.errors else 1


if __name__ == "__main__":
    sys.exit(main())
