"""Device milliseconds per step under the scope `s.dir` (the directory
transition of phase 3: grants, victim and back-invalidation, the
invalidation-target reductions, the prefetcher), from the traced job."""


def read(run, trace):
    from phase_ops import phase_ms_step

    return phase_ms_step(run, trace, "/s.dir/")
