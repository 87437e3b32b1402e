"""Device milliseconds per step under the scope `s.local` (the quantum
barrier and the closed-form local runs, phases 0 and 0.5 of `step`), from
the traced job: the leaf ops whose `op_name` holds the scope."""


def read(run, trace):
    from phase_ops import phase_ms_step

    return phase_ms_step(run, trace, "/s.local/")
