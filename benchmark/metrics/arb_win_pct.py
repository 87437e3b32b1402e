"""Share of the uncore requests presented in a step that were served in
it: 100 * served / (served + `retries`), where served is `l1_read_misses`
+ `l1_write_misses` + `upgrades` (every winner of a (bank, set) slot and
every coalesced read join counts in exactly one of them) and a retry is a
request that lost its slot's arbitration and presents the same event again
next step. The modelled counters of the window's whole jobs, from the
program's job samples (`slot_active_pct.py`): a count, exact for a seed,
and no stat row of its own."""


def read(run, trace):
    import cells

    t = cells._load("metrics", "slot_active_pct", cells.ROOT, "window_totals")(run)
    if t is None:
        return None
    d = t["deltas"]
    served = d["l1_read_misses"] + d["l1_write_misses"] + d["upgrades"]
    return 100.0 * served / (served + d["retries"]) if served + d["retries"] else None
