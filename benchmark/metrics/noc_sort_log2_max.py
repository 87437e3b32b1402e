"""log2 of the sort a compacted router walk would have needed at its
fullest step: the highest non-zero lane of the stat row `noc_sort_log2`,
whose lane b counts the steps with (2^(b-1), 2^b] real entries, over the
window's whole jobs (`slot_active_pct.py`). Beside it: log2 of the slots
sorted now, `C x legs x H` rounded up to a power of two."""


def read(run, trace):
    import cells

    t = cells._load("metrics", "slot_active_pct", cells.ROOT, "window_totals")(run)
    if t is None or not t["caps"].get("sort_entries"):
        return None
    lanes = [b for b, n in enumerate(t["deltas"].get("noc_sort_log2", ())) if n]
    return float(max(lanes)) if lanes else None
