"""Cycles that LLC misses waited in their home memory controller's queue
(`dram_queue_cycles`: from a miss's arrival at the controller to the start
of its service, behind the misses that arrived before it) per thousand
target instructions of the checked job (the window's first, whose
counters the run keeps): a count, exact for a trace. What the cores
behind one controller cost each other where every reference of a stream
is a cold miss; a machine without `dram_queue` counts none, and a program
without the counter gives nothing to read."""


def read(run, trace):
    job = run["checked"]
    if job is None or "dram_queue_cycles" not in job["counters"]:
        return None
    instructions = int(job["counters"]["instructions"].sum())
    if not instructions:
        return None
    return 1e3 * int(job["counters"]["dram_queue_cycles"].sum()) / instructions
