"""Host seconds of the slowest whole job of the window (host driver)."""


def read(run, trace):
    return max((j["seconds"] for j in run["jobs"]), default=None)
