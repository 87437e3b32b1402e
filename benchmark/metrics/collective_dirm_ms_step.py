"""Device milliseconds per step, per chip, in the collectives that carry
directory rows between chips, from the traced job of a sharded run:
`collective_ms_step`'s instructions (all-reduce, all-gather,
reduce-scatter, all-to-all, collective-permute, `-start` and `-done`
halves by their own durations), but only those whose label names one of
the three phases that read or write `dirm`: `s.local` (the local run's row
gather: on four chips every chip gathers from its own quarter and the rows
are all-reduced), `s.dir` and `s.commit` (the winners' full-row deltas and
the scatter's indices, gathered before the row scatter-add). What is left
of `collective_ms_step` is the arbitration table, the probes' element
gathers and the chunk's housekeeping.

A one-chip program has no such instruction, and a program without the
phase scopes names no phase: the reader returns None."""

# `collective_ms_step.COLLECTIVES`, spelled again: a reader is a file loaded
# by its path and imports no other reader (benchmark/tests/test_rung4_cell.py
# holds the two equal)
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")
DIRM_PHASES = ("s.local", "s.dir", "s.commit")


def read(run, trace):
    from phase_ops import opcodes, phase_of, traced_job

    job = traced_job(run, trace)
    if job is None:
        return None
    ops = opcodes(run["hlo_text"])
    hit = []
    for label, (seconds, _count) in trace["ops"].items():
        name = label.split(" ", 1)[0]
        opcode = ops.get(name, name.split(".", 1)[0])
        if (opcode.removesuffix("-start").removesuffix("-done") in COLLECTIVES
                and phase_of(label) in DIRM_PHASES):
            hit.append(seconds)
    return 1e3 * sum(hit) / job["steps"] if hit else None
