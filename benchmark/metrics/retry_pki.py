"""Requests that lost their (bank, set)'s arbitration and were presented
again, per thousand target instructions of the checked job (the window's
first, whose counters the run keeps): a count, exact for a trace. A lost
request costs its core a whole step, so under skewed keys this is what
the hot records' home banks cost the machine; a program that does not
count `retries` gives nothing to read."""


def read(run, trace):
    job = run["checked"]
    if job is None or "retries" not in job["counters"]:
        return None
    instructions = int(job["counters"]["instructions"].sum())
    if not instructions:
        return None
    return 1e3 * int(job["counters"]["retries"].sum()) / instructions
