"""Share of the window's core-steps in which the core stood at END: 100
less the shares of `slot_active`, `slot_quantum` and `slot_frozen`, the
three stat rows that account for every core-step of a core with events
left (`slot_active_pct.py::window_totals`, over the window's whole jobs:
a count, exact for a seed). Where a few hot records' chains of retried
requests set the job's length, this is what the cores that finished
their own work wait for while the chains drain."""


def read(run, trace):
    import cells

    t = cells._load("metrics", "slot_active_pct", cells.ROOT, "window_totals")(run)
    rows = ("slot_active", "slot_quantum", "slot_frozen")
    if t is None or not all(r in t["deltas"] for r in rows):
        return None
    slots = t["caps"]["n_cores"] * t["steps"]
    return 100.0 - 100.0 * sum(t["deltas"][r] for r in rows) / slots if slots else None
