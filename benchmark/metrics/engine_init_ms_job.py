"""Host milliseconds a job in the span `engine.init` (`Engine.__init__`:
the trace laid out and uploaded, `build_state`), or `fleet.init` where the
job is a fleet, the mean over the window's jobs, from the program's job
samples (`slot_active_pct.py`): `phases.init`. A job of the benchmark is a
new engine, so the build runs once a job: outside the job's clock, inside
the window, and in the set-up twice (the warm-up's engine and the parity
job's). The runners build the engine before the profiler starts, so the
span reaches no `breakdown.idle_gaps`; this is its one reading. On the
host's clock. Listed for the cells whose job is one machine; a fleet's
build is `fleet.init`, which `fleet_build_ms_job` reads where it is listed.
A program that commits no sample gives nothing to read."""


def read(run, trace):
    import cells

    t = cells._load("metrics", "slot_active_pct", cells.ROOT, "window_totals")(run)
    if t is None or "init" not in t["phases"]:
        return None
    return 1e3 * t["phases"]["init"] / t["jobs"]
