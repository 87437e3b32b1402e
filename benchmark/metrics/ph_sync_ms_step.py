"""Device milliseconds per step under the scope `s.sync` (phase 2.7: the
lock table's unlocks and grants, the barrier slots' arrivals and
releases), from the traced job. Only a program compiled for a trace with
LOCK, UNLOCK or BARRIER events (`has_sync`) has the scope. The barrier
arrivals' leg of the router walk is under `s.noc`, not here."""


def read(run, trace):
    from phase_ops import phase_ms_step

    return phase_ms_step(run, trace, "/s.sync/")
