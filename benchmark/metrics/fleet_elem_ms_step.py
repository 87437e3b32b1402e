"""Host milliseconds per executed step per machine of a job of many
(`runners/fleet.py`): `step_ms` / `caps.elements`, the number to read
against `step_ms` of a solo run of the same machine and trace. The
element count is the program's own, from the `caps` of its job samples
(`slot_active_pct.py`); a program whose fleet commits no sample, and a job
of one machine, give nothing to read."""


def read(run, trace):
    import cells

    t = cells._load("metrics", "slot_active_pct", cells.ROOT, "window_totals")(run)
    if t is None or not t["caps"].get("elements"):
        return None
    steps = sum(j["steps"] for j in run["jobs"])
    seconds = sum(j["seconds"] for j in run["jobs"])
    return 1e3 * seconds / steps / t["caps"]["elements"] if steps else None
