"""The fullest core's target instructions over the mean core's, from the
checked job's per-core `instructions` (the window's first job, whose
counters the run keeps): a count, exact for a trace. Where routing is
uneven the job is as long as its fullest expert's cores, and every other
lane of every step is padding: 1 is a machine whose cores all hold the
same work."""


def read(run, trace):
    job = run["checked"]
    if job is None or "instructions" not in job["counters"]:
        return None
    per_core = job["counters"]["instructions"]
    total = int(per_core.sum())
    return int(per_core.max()) * per_core.size / total if total else None
