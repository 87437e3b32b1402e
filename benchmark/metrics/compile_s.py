"""Host seconds around the first `run_loop` call: the compile, or the load
from the persistent cache, plus one chunk of steps (entry layer)."""


def read(run, trace):
    return run["compile_s"]
