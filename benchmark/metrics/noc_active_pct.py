"""Share of the router walk's sorted slots that held a real entry: 100 *
`noc_entries` / (steps x sorted entries a step), the stat row over the
window's whole jobs (`slot_active_pct.py`) and the static `C x legs x H`
of the machine. Only a machine with the router model sorts any."""


def read(run, trace):
    import cells

    t = cells._load("metrics", "slot_active_pct", cells.ROOT, "window_totals")(run)
    if t is None or "noc_entries" not in t["deltas"] or not t["caps"].get("sort_entries"):
        return None
    return 100.0 * t["deltas"]["noc_entries"] / (t["steps"] * t["caps"]["sort_entries"])
