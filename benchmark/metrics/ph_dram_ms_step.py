"""Device milliseconds per step under the scope `s.dram` (the
memory-controller queue, its FIFO rank `s.dram/rank` and the lane order
both ranks share included), from the traced job. Only a machine with
`dram_queue` has the scope."""


def read(run, trace):
    from phase_ops import phase_ms_step

    return phase_ms_step(run, trace, "/s.dram/")
