"""Share of the window's core-steps in which the core was frozen at a
barrier it had arrived at: 100 * `slot_frozen` / (`n_cores` x steps), from
the program's stat rows over the window's whole jobs
(`slot_active_pct.py`). Zero on a trace without barriers."""


def read(run, trace):
    import cells

    return cells._load("metrics", "slot_active_pct", cells.ROOT, "slot_pct")(run, "slot_frozen")
