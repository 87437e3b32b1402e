"""Host milliseconds a job in the span `engine.readback` (the
synchronizing transfer of the counters, the clock base and the chunk
count after the one dispatch): the mean over the window's jobs of the
seconds the program's own span helper read, from its job samples
(`slot_active_pct.py`). On the host's clock; the device trace names the
same span in `breakdown.idle_gaps`."""


def read(run, trace):
    import cells

    t = cells._load("metrics", "slot_active_pct", cells.ROOT, "window_totals")(run)
    if t is None or "readback" not in t["phases"]:
        return None
    return 1e3 * t["phases"]["readback"] / t["jobs"]
