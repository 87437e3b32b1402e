"""Share of the traced job's window in which no op ran on the device:
100 * (1 - union of device-op intervals / window), from the xplane."""


def read(run, trace):
    if trace is None or not trace["window_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
