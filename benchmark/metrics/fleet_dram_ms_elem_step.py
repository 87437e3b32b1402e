"""Device milliseconds per step per machine under the scope `s.dram` (the
memory-controller queue, its FIFO rank `s.dram/rank` and the lane order
both ranks share included) of a traced job of many machines:
`ph_dram_ms_step` / `caps.elements`, to be read against `ph_dram_ms_step`
of a solo run of the same machine and trace. Only a machine with
`dram_queue` has the scope. What gives nothing to read:
`fleet_noc_ms_elem_step.py`."""


def read(run, trace):
    import cells

    return cells._load("metrics", "fleet_noc_ms_elem_step", cells.ROOT, "elem_ms_step")(
        run, trace, "/s.dram/")
