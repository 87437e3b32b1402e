"""Device milliseconds per step of the ops under a `stat` sub-scope
(`s.probe/stat`, `s.noc/stat`): what the step's stat rows cost that did
not fuse into work the step does anyway, from the traced job. 0.0 where
the compiled program names the scope and no op of its own ran under it;
a program without the scope gives nothing to read."""


def read(run, trace):
    from phase_ops import traced_job
    from xplane import op_names, op_seconds

    job = traced_job(run, trace)
    if job is None or not any("/stat/" in p for p in op_names(run["hlo_text"]).values()):
        return None
    return 1e3 * (op_seconds(trace, ("/stat/",)) or 0.0) / job["steps"]
