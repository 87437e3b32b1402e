"""Host milliseconds a job in the span `fleet.init` (`FleetEngine.__init__`:
the machines' states and the trace made and laid on their chips), the mean
over the window's jobs, from the program's job samples
(`slot_active_pct.py`). A job of the benchmark is a new fleet, so the build
runs once a job: outside the job's clock, inside the window, and in the
set-up twice (the warm-up's fleet and the parity job's). On the host's
clock. A program whose fleet commits no sample and a job of one machine
give nothing to read."""


def read(run, trace):
    import cells

    t = cells._load("metrics", "slot_active_pct", cells.ROOT, "window_totals")(run)
    if t is None or not t["caps"].get("elements") or "init" not in t["phases"]:
        return None
    return 1e3 * t["phases"]["init"] / t["jobs"]
