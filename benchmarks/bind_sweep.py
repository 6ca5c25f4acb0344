#!/usr/bin/env python3
"""Bind a cell's WHOLE key space once, on the chip, and say what every chip
holds afterwards.

    python benchmarks/bind_sweep.py --workload pattern_32m.mesh4_saturated --seed 1

A cell whose traffic visits only an `active_keys` range (config.json `reduced`)
leaves most of the deployment's keys unbound in every run.  This runs what the
cut left out, once: one deployment, set up as `run.py` deploys it, then ONE
contiguous sweep of `n_keys / keys_per_send` sends over the whole key space,
each waited for and timed, every send's rows compared with the configuration's
plain reference; then one more pass over the first `--again` blocks under the
final bindings.  It prints the sweep's wall, the per-send times, and
`memory_stats()` of EVERY chip the cell holds (`run.py` reports the fullest
one), and writes them to `chiprun_out/bind_sweep/<workload>.json`.  Not a
metric of BENCHMARK.json: PERF.md section 4 quotes it.  Its sends go through
`runner.Deployment.issue`, to the stream each names
(`tests/test_bench_two_streams.py` rehearses it on a two-stream app).
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def chip_memory(devs) -> list:
    out = []
    for d in devs:
        st = d.memory_stats() or {}
        out.append({"id": d.id,
                    "bytes_in_use": int(st.get("bytes_in_use", 0)),
                    "peak_bytes_in_use": int(st.get("peak_bytes_in_use", 0)),
                    "bytes_limit": int(st.get("bytes_limit", 0))})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--again", type=int, default=8,
                    help="blocks sent once more after the sweep")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    from benchmarks.harness import loader, numeric, platform, runner
    cell = loader.resolve(args.workload, rehearse=args.rehearse)
    started = platform.start_jax(args.rehearse, cell.chips, "bind_sweep")
    if started is None:
        return 1
    _cache, devs, device, _ = started
    devs = devs[:cell.chips]
    if args.rehearse:
        print("REHEARSAL: tiny sizes, not a chip run — no time below is a "
              "measurement", flush=True)
    # the cell's own traffic with the cut undone: every key is visited
    whole = dict(cell.traffic, active_keys=int(cell.sizes["n_keys"]))
    n_sends = int(cell.sizes["n_keys"]) // int(whole["keys_per_send"])

    t = time.perf_counter()
    dep = runner.Deployment(cell, args.seed, annotate=False)
    deploy_s = time.perf_counter() - t
    after_deploy = chip_memory(devs)
    print(f"deploy {deploy_s:.2f} s; per chip after deploy: "
          f"{json.dumps(after_deploy)}", flush=True)
    send_ms, sids = [], []
    try:
        t0 = time.perf_counter()
        for j in range(n_sends + args.again):
            if j == n_sends:
                sweep_s = time.perf_counter() - t0
            t = time.perf_counter()
            # one send through the window's own call and subscriber,
            # waited for; raises if it is not delivered or the runtime errs
            dep.run_untimed(whole, 1, "bind sweep")
            send_ms.append((time.perf_counter() - t) * 1e3)
            sids.append(len(dep.sends) - 1)
        if args.again == 0:
            sweep_s = time.perf_counter() - t0
        state_memory = dep.rt.state_memory()
        chips = chip_memory(devs)
    finally:
        dep.close()
    checked = runner.check(dep, sids, False, print)
    bind, again = send_ms[:n_sends], send_ms[n_sends:]
    out = {
        "workload": cell.name, "seed": args.seed, "device": device,
        "rehearsal": args.rehearse, "n_keys": int(cell.sizes["n_keys"]),
        "sends": n_sends, "events": sum(dep.sends[s]["events"]
                                        for s in sids[:n_sends]),
        "deploy_s": deploy_s, "sweep_s": sweep_s,
        "send_ms_first": bind[0], "send_ms_p50": numeric.median(bind),
        "send_ms_max": max(bind),
        "again_send_ms_p50": numeric.median(again) if again else None,
        "state_bytes": sum(v for c in state_memory.values()
                           for v in c.values()),
        "chips_after_deploy": after_deploy, "chips": chips,
        "failed_sends": len(checked["failed_sends"]),
        "errors": len(dep.errors),
    }
    out_dir = os.path.join(ROOT, "chiprun_out", "bind_sweep")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, cell.name + ".json"), "w") as fh:
        json.dump(out, fh, indent=1)
        fh.write("\n")
    print(json.dumps(out), flush=True)
    return 0 if not checked["failed_sends"] and not dep.errors else 1


if __name__ == "__main__":
    sys.exit(main())
