"""Device milliseconds per step of the router's FIFO rank alone: the ops
under `s.noc/rank`, the call into `ops/ranking.py::segmented_rank` over
the router's (lane, hop) entries, apart from the DRAM queue's rank
(`s.dram/rank`). `sort_ms_step` finds both callers by the word `sort`."""


def read(run, trace):
    from phase_ops import phase_ms_step

    return phase_ms_step(run, trace, "/s.noc/rank/")
