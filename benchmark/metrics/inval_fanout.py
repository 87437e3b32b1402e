"""Invalidation messages per write that reached the directory
(`l1_write_misses` + `upgrades`) in the checked job (the window's first,
whose counters the run keeps): what a write pays for the readers its line
has gathered. A count, exact for a trace; nothing to read where no write
left its core."""


def read(run, trace):
    job = run["checked"]
    if job is None:
        return None
    c = job["counters"]
    if not all(k in c for k in ("invalidations", "l1_write_misses", "upgrades")):
        return None
    writes = int(c["l1_write_misses"].sum()) + int(c["upgrades"].sum())
    return int(c["invalidations"].sum()) / writes if writes else None
