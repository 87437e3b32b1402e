"""Host milliseconds per executed engine step, over every job of the
window: sum of job seconds / sum of `Engine.steps_run`."""


def read(run, trace):
    steps = sum(j["steps"] for j in run["jobs"])
    return 1e3 * sum(j["seconds"] for j in run["jobs"]) / steps if steps else None
