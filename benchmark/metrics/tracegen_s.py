"""Host seconds the generator took to make the run's traces (entry layer)."""


def read(run, trace):
    return run["tracegen_s"]
