"""Device milliseconds per step under the scope `s.commit` (latency
composition, granted L1 state, counters, phase 4.A's fused L1 scatter and
directory row scatter-add, the end-of-step commit; `commit_step` under
`step_impl=pallas`), from the traced job."""


def read(run, trace):
    from phase_ops import phase_ms_step

    return phase_ms_step(run, trace, "/s.commit/")
