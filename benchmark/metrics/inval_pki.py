"""Invalidation messages per thousand target instructions of the checked
job (the window's first, whose counters the run keeps): a count, exact for
a trace. Under a coarse sharer vector an invalidation goes to every core
of every flagged group, so this is what the directory's coarseness costs
the machine it simulates."""


def read(run, trace):
    job = run["checked"]
    if job is None:
        return None
    instructions = int(job["counters"]["instructions"].sum())
    if not instructions:
        return None
    return 1e3 * int(job["counters"]["invalidations"].sum()) / instructions
