"""Target instructions retired per core per executed step: a count, exact
for a seed. `sim_mips` is this over `step_ms`, times the cores."""


def read(run, trace):
    steps = sum(j["steps"] for j in run["jobs"])
    if not steps:
        return None
    return sum(j["instructions"] for j in run["jobs"]) / steps / run["n_cores"]
