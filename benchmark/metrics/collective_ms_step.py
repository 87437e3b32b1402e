"""Device milliseconds per step in collectives, per chip, from the traced
job of a sharded run: the leaf ops whose instruction in the compiled
program is an `all-reduce`, `all-gather`, `reduce-scatter`, `all-to-all`
or `collective-permute`, their asynchronous `-start` and `-done` halves
included.

A `-start`/`-done` pair is counted by the two ops' own durations: issuing
the transfer, and waiting for it at the end. The time between the two, in
which the transfer overlaps other ops of the step, is not collective time
here (it costs the step nothing), and a collective that the compiler
folded into a fusion is billed to that fusion. A one-chip program has no
such instruction and the reader returns None."""

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")


def read(run, trace):
    from phase_ops import instruction_seconds, opcodes, traced_job

    job = traced_job(run, trace)
    if job is None:
        return None
    ops = opcodes(run["hlo_text"])
    hit = [s for name, s in instruction_seconds(trace).items()
           if ops.get(name, name.split(".", 1)[0]).removesuffix("-start")
           .removesuffix("-done") in COLLECTIVES]
    return 1e3 * sum(hit) / job["steps"] if hit else None
