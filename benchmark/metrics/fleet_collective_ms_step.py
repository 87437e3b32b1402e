"""Device milliseconds per step in collectives, per chip, from the traced
job of a fleet on several chips: `collective_ms_step.py`'s count (the leaf
ops whose instruction in the compiled program is one of its `COLLECTIVES`,
`-start` and `-done` halves included) on a job of many machines. A fleet
on a mesh lies with every machine whole on one chip and its loop is the
one-chip program a chip, so this is to read 0, and says so: anything else
is a collective that got into the step. That the fleet lies on several
chips is the program's own word (`caps.chips` of its job samples,
`slot_active_pct.py`); a program whose samples say nothing of chips (every
one before PR 51), a fleet on one chip, a job of one machine and a run
without a trace give nothing to read."""


def read(run, trace):
    import cells
    from phase_ops import instruction_seconds, opcodes, traced_job

    job = traced_job(run, trace)
    if job is None or not job.get("elements") or not job["steps"]:
        return None
    t = cells._load("metrics", "slot_active_pct", cells.ROOT, "window_totals")(run)
    if t is None or t["caps"].get("chips", 1) < 2:
        return None
    collectives = cells._module("metrics", "collective_ms_step", cells.ROOT).COLLECTIVES
    ops = opcodes(run["hlo_text"])
    hit = [s for name, s in instruction_seconds(trace).items()
           if ops.get(name, name.split(".", 1)[0]).removesuffix("-start")
           .removesuffix("-done") in collectives]
    return 1e3 * sum(hit) / job["steps"]
