"""Device milliseconds per step in the step's own gathers, from the traced
job: the leaf ops whose `op_name` in the compiled program holds `gather`
or `take_along_axis` and not `sort` (a `searchsorted`'s gathers are the
ranking's, `sort_ms_step`). Found by name until the step has named
scopes."""


def read(run, trace):
    from xplane import op_seconds

    job = next((j for j in run["jobs"] if j.get("traced")), None)
    if trace is None or job is None:
        return None
    s = op_seconds(trace, ("gather", "take_along_axis"), without=("sort",))
    return None if s is None else 1e3 * s / job["steps"]
