"""Host milliseconds per executed step per machine of ONE CHIP of a fleet
on several chips: `step_ms` x `caps.chips` / `caps.elements`, a chip's
step over the machines that chip holds. The number to read against
`fleet_elem_ms_step` of the same machines as a one-chip fleet
(`rung3.nocsweep-b4`) and `step_ms` of a solo run: with every machine
whole on its chip and no collective in the step the three are one
program's. Both counts are the program's own, from the `caps` of its job
samples (`slot_active_pct.py`); a program whose samples say nothing of
chips (every one before PR 51), a fleet on one chip and a job of one
machine give nothing to read."""


def read(run, trace):
    import cells

    t = cells._load("metrics", "slot_active_pct", cells.ROOT, "window_totals")(run)
    if t is None or t["caps"].get("chips", 1) < 2 or not t["caps"].get("elements"):
        return None
    steps = sum(j["steps"] for j in run["jobs"])
    seconds = sum(j["seconds"] for j in run["jobs"])
    return 1e3 * seconds / steps * t["caps"]["chips"] / t["caps"]["elements"] if steps else None
