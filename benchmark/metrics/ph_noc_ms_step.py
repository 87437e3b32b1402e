"""Device milliseconds per step under the scope `s.noc` (NoC contention:
the hop-by-hop router walk, its FIFO rank `s.noc/rank` included), from the
traced job. Only a machine with a contention model has the scope."""


def read(run, trace):
    from phase_ops import phase_ms_step

    return phase_ms_step(run, trace, "/s.noc/")
