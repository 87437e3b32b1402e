"""Share of the traced job's busy device time spent in fusions that span
two phases: 100 * device seconds of the fusions whose fused computation
(in the compiled program's text) holds instructions of more than one
phase scope / `busy_s`. A fusion carries one `op_name`, its root's, so
the phase metrics bill all of such a fusion to one phase: this is how
much of the phase table is blurred that way."""


def read(run, trace):
    from phase_ops import fusion_phases, instruction_seconds

    if trace is None or not trace["busy_s"]:
        return None
    phases = fusion_phases(run["hlo_text"])
    if not any(phases.values()):
        return None  # a program without the scopes
    seconds = instruction_seconds(trace)
    mixed = sum(seconds.get(name, 0.0) for name, held in phases.items() if len(held) > 1)
    return 100.0 * mixed / trace["busy_s"]
