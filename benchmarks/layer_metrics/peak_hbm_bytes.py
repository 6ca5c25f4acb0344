"""state: `memory_stats()["peak_bytes_in_use"]` of the fullest chip after
the window."""


def read(run):
    return run["peak_hbm_bytes"] or None
