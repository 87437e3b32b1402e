"""Share of the HBM-bandwidth roofline the step reaches, in percent:
least time / device time per step, from the traced job.

It is a bandwidth roofline. Least time = bytes the step has to touch /
peak HBM bytes per second, and those bytes are, for every core, one event
record, one L1 set and one directory row (which in this state layout
holds the LLC set's tags, owners, LRU stamps and sharer words), each read
once and written once. Not the whole state: the step does not need it.
Sizes come from the arrays the run really had."""


def step_bytes(run: dict) -> int:
    shapes = run["jobs"][0]["state_shapes"]
    (ev_shape, ev_item) = run["jobs"][0]["events_shape"]
    l1 = run["machine"]["l1"]
    l1_sets = l1["size"] // (l1["ways"] * l1["line"])
    (l1_shape, l1_item), (dir_shape, dir_item) = shapes["l1"], shapes["dirm"]
    per_core = (
        ev_shape[2] * ev_item  # one event record
        + l1_shape[1] // l1_sets * l1_item  # one set of every L1 plane
        + dir_shape[1] * dir_item  # one directory row
    )
    return 2 * per_core * run["n_cores"]


def read(run, trace):
    from cells import peak_for

    job = next((j for j in run["jobs"] if j.get("traced")), None)
    if trace is None or job is None or not trace["busy_s"]:
        return None
    peak = peak_for(run["peaks"], job["device_kind"])["hbm_bytes_per_s"]
    least_s = step_bytes(run) / peak
    return 100.0 * least_s / (trace["busy_s"] / job["steps"])
