"""Barrier arrivals per thousand target instructions of the checked job
(the window's first, whose counters the run keeps): a count, exact for a
trace. Each arrival is a one-way message to the barrier's home tile, a
frozen core and, under the router model, a third leg in the step's link
walk; a program that does not count `barrier_waits` gives nothing to read."""


def read(run, trace):
    job = run["checked"]
    if job is None or "barrier_waits" not in job["counters"]:
        return None
    instructions = int(job["counters"]["instructions"].sum())
    if not instructions:
        return None
    return 1e3 * int(job["counters"]["barrier_waits"].sum()) / instructions
