"""Device milliseconds per step under the scope `s.sync/barrier`: the
barrier arrivals (the arrival message's charge, the freeze, the slot's
count and latest arrival) and the releases, from the traced job. It is
inside `ph_sync_ms_step`; a program from before the sub-scope came gives
nothing to read."""


def read(run, trace):
    from phase_ops import phase_ms_step

    return phase_ms_step(run, trace, "/s.sync/barrier/")
