"""Device milliseconds per step under the scope `s.arb` (read-join
coalescing and the per-(bank,set) scatter-min arbitration, phase 2 of
`step`), from the traced job."""


def read(run, trace):
    from phase_ops import phase_ms_step

    return phase_ms_step(run, trace, "/s.arb/")
