"""What the per-layer readers share: the run record's stamps and trace
slice.  A reader returns None where it finds nothing to read."""
from __future__ import annotations


def served_path_ms(run: dict, part: str):
    """Mean milliseconds per send of one part of the blocking send call, by
    the harness's own clock, over the window's sends:
    `subscriber` (inside the batch callback, payload reads included), `post`
    (callback's end to the call's return) or `pre` (the rest: staging,
    upload, dispatch, device step, fetch, demux).

    None when results are delivered on another thread than the sender's:
    some timed send OWED rows (the stamp's `owed`, what `Deployment.issue`
    told the tracker) and its subscriber did not run inside its call.  A
    send that owed none and whose subscriber never ran (rule 2 of
    `runner.py`) is no sign of that: it counts with `subscriber` 0 and
    `post` 0, `pre` the whole call — so the three parts still add up to the
    mean of `returned - issued` over every timed send."""
    parts = []
    for st in run["stamps"]:
        if "returned" not in st:
            continue
        whole = st["returned"] - st["issued"]
        if st["subscriber_end"] is None:
            if st["owed"]:
                return None
            split = {"subscriber": 0.0, "post": 0.0, "pre": whole}
        else:
            post = st["returned"] - st["subscriber_end"]
            split = {"subscriber": st["subscriber_s"], "post": post,
                     "pre": whole - st["subscriber_s"] - post}
        parts.append(split[part] * 1e3)
    if not parts:
        return None
    return sum(parts) / len(parts)


def trace_slice(run: dict):
    """The reduced trace, or None when no send fell inside it or nothing
    ran on the device."""
    red = run.get("trace_reduced")
    if not red or not red["sends_in_slice"] or red["window_s"] <= 0:
        return None
    return red
