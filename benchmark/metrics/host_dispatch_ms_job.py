"""Host milliseconds a job in the span `engine.dispatch` (the one
`run_loop` call: argument handling and the asynchronous enqueue): the
mean over the window's jobs, from the program's job samples
(`slot_active_pct.py`). On the host's clock."""


def read(run, trace):
    import cells

    t = cells._load("metrics", "slot_active_pct", cells.ROOT, "window_totals")(run)
    if t is None or "dispatch" not in t["phases"]:
        return None
    return 1e3 * t["phases"]["dispatch"] / t["jobs"]
