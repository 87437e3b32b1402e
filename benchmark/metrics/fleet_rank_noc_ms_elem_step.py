"""Device milliseconds per step per machine of the router's per-link work
alone, the ops under `s.noc/rank` (`ops/ranking.py` under `_router_walk`:
the three sorts of E + NL entries, the start maxima, the segmented scans)
of a traced job of many machines: `rank_noc_ms_step` / `caps.elements`,
to be read against `rank_noc_ms_step` of a solo run of the same machine
and trace. What gives nothing to read: `fleet_noc_ms_elem_step.py`."""


def read(run, trace):
    import cells

    return cells._load("metrics", "fleet_noc_ms_elem_step", cells.ROOT, "elem_ms_step")(
        run, trace, "/s.noc/rank/")
