"""Device milliseconds per step in the FIFO ranking of `ops/ranking.py`
(`sort` and `searchsorted`, the latter a binary search of gathers), from
the traced job: the leaf ops whose `op_name` in the compiled program holds
`sort`. Found by name until the step has named scopes."""


def read(run, trace):
    from xplane import op_seconds

    job = next((j for j in run["jobs"] if j.get("traced")), None)
    if trace is None or job is None:
        return None
    s = op_seconds(trace, ("sort",))
    return None if s is None else 1e3 * s / job["steps"]
