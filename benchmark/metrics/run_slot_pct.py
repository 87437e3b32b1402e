"""Share of the local run's candidate slots that retired an event: 100 *
`run_events` / (`n_cores` x steps x `local_run_len`), from the program's
stat rows over the window's whole jobs (`slot_active_pct.py`). Phase 0.5
gathers `local_run_len` + 1 event records and directory rows a core a
step whatever this reads."""


def read(run, trace):
    import cells

    return cells._load("metrics", "slot_active_pct", cells.ROOT, "slot_pct")(
        run, "run_events", "local_run_len")
