"""Device milliseconds per step under the scope `s.probe` (the arbitration
event, its L1 probe and pull validation, the home-row parse and the hit
classification: phases 0.9 and 1 of `step`; `probe_classify` under
`step_impl=pallas`), from the traced job."""


def read(run, trace):
    from phase_ops import phase_ms_step

    return phase_ms_step(run, trace, "/s.probe/")
