"""Share of the traced job's busy device time that a phase of `step`
accounts for: 100 * device seconds of the leaf ops whose label holds a
phase scope / `busy_s`. What is left is `run_loop`'s own scope (`s.chunk`:
drain, rebase, termination test) and whatever XLA left without a name."""


def read(run, trace):
    from phase_ops import OUTSIDE, phase_of

    if trace is None or not trace["busy_s"]:
        return None
    named = [v[0] for k, v in trace["ops"].items() if phase_of(k) not in (None, OUTSIDE)]
    return 100.0 * sum(named) / trace["busy_s"] if named else None
