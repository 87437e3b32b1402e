"""Share of the window's core-steps in which the core had work but stood
ahead of the quantum window, waiting for the laggards: 100 *
`slot_quantum` / (`n_cores` x steps), from the program's stat rows over
the window's whole jobs (`slot_active_pct.py`)."""


def read(run, trace):
    import cells

    return cells._load("metrics", "slot_active_pct", cells.ROOT, "slot_pct")(run, "slot_quantum")
