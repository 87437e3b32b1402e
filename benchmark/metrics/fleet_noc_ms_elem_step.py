"""Device milliseconds per step per machine under the scope `s.noc` (the
hop-by-hop router walk whole, its FIFO rank `s.noc/rank` included) of a
traced job of many machines (`runners/fleet.py`): `ph_noc_ms_step` /
`caps.elements`, the number to read against `ph_noc_ms_step` of a solo
run of the same machine and trace. Only a machine with a contention model
has the scope.

`elem_ms_step` is what the three readers of a fleet's scopes an element
share (`fleet_rank_noc_ms_elem_step.py`, `fleet_dram_ms_elem_step.py`):
the element count is the program's own, from the `caps` of its job samples
(`slot_active_pct.py::window_totals`); a program whose fleet commits no
sample, a program without the scope, a run without a trace and a job of
one machine give nothing to read."""


def elem_ms_step(run, trace, needle: str):
    import cells
    from phase_ops import phase_ms_step

    ms = phase_ms_step(run, trace, needle)
    if ms is None:
        return None
    t = cells._load("metrics", "slot_active_pct", cells.ROOT, "window_totals")(run)
    if t is None or not t["caps"].get("elements"):
        return None
    return ms / t["caps"]["elements"]


def read(run, trace):
    return elem_ms_step(run, trace, "/s.noc/")
