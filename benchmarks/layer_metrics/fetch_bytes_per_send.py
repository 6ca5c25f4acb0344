"""emission: bytes the delivery paths pulled off the device per send — the
`bytes` of every `siddhi:fetch` span (header, rows, ring) that starts in the
traced slice, divided by the `siddhi:send` spans that start in it.  Beside
`fetch_ms_per_send`: what the fetches moved, not how long they took.  A
banded pattern emission's `rows` fetches also carry `ranks` / `ranks_cap`
(ranks fetched of the ranks the emission could hold), printed here where
the program has them.  Reads the run's own xplane over the slice
`trace_reduce` / `program_spans` reduce; None on a run without a trace or
on a program whose fetch spans carry no `bytes`."""
from benchmarks.harness import trace_reduce as tr

FETCH, SEND = "siddhi:fetch", "siddhi:send"


def read_fetches(path: str):
    """{sends, fetches, bytes, ranks, ranks_cap} over the slice (start of
    the first `bench:send_columns` span -> end of the last `bench:*`
    span), or None where it holds no send or no fetch that says its
    bytes."""
    import jax
    first_send, last_end, seen = None, 0.0, []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for ev in line.events:
                s = float(ev.start_ns)
                if ev.name.startswith(tr.SPAN_PREFIX):
                    last_end = max(last_end, s + float(ev.duration_ns))
                    if ev.name == tr.SEND_SPAN and \
                            (first_send is None or s < first_send):
                        first_send = s
                elif ev.name == SEND:
                    seen.append((s, None))
                elif ev.name == FETCH:
                    seen.append((s, dict(ev.stats)))
    if first_send is None:
        return None
    out = {"sends": 0, "fetches": 0, "bytes": 0, "ranks": 0, "ranks_cap": 0}
    for s, stats in seen:
        if not first_send <= s < last_end:
            continue
        if stats is None:
            out["sends"] += 1
        elif "bytes" in stats:
            out["fetches"] += 1
            for key in ("bytes", "ranks", "ranks_cap"):
                out[key] += int(stats.get(key, 0))
    return out if out["sends"] and out["fetches"] else None


def read(run):
    if "fetch_bytes" not in run:
        red = run.get("trace_reduced")
        out = None
        if run.get("trace_dir") and red and red.get("sends_in_slice"):
            out = read_fetches(tr.newest_xplane(run["trace_dir"]))
        run["fetch_bytes"] = out
        if out is not None:
            print(f"fetches over the slice: {out}", flush=True)
    out = run["fetch_bytes"]
    return None if out is None else out["bytes"] / out["sends"]
