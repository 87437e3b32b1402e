"""Share of the window's core-steps in which the core presented an event
to the step's phases 1-4: 100 * `slot_active` / (`n_cores` x steps), from
the stat rows the step keeps beside its counters (the program's
`STAT_NAMES`). A count over the window's whole jobs, exact for a seed.
What is left of 100 % waited ahead of the quantum window
(`slot_quantum_pct`), was frozen at a barrier (`slot_frozen_pct`) or
stood at END.

`window_totals` is what every reader of the job samples shares: the
program's fused `Engine.run` commits one sample a job to its process
store (label, steps, the counters' and stat rows' totals, its host spans'
seconds, the static sizes the ratios divide by), and the window's jobs
are the store's last `len(run["jobs"])` such samples; of them the whole
passes count, so a number repeats to the digit for a seed. A program without
the store or without the stat rows gives every reader nothing to read.
"""


def window_totals(run):
    """{jobs, steps, deltas, phases, caps} summed over the samples of the
    window's jobs (the histogram row lane by lane; `caps` are static),
    or None."""
    try:
        from primesim_tpu.obs import process_store
    except ImportError:
        return None
    n = len(run["jobs"])
    if not n:
        return None
    samples = [s for s in process_store().samples()
               if s["label"] == "engine" and "caps" in s][-n:]
    # sample for job, in order: the steps agree, or the store holds others'
    if [s["steps"] for s in samples] != [j["steps"] for j in run["jobs"]]:
        return None
    # a traced window ends with the job in flight, not with the pass, so how
    # many jobs it holds is the clock's: keep its whole passes over the
    # panel (the same work in every run of a cell), or the first job of a
    # window shorter than a pass (the seed draws which trace that is)
    if run.get("passes") is not None:
        n = run["passes"] * len({j.get("trace") for j in run["jobs"]}) or 1
        samples = samples[:n]
    deltas: dict = {}
    phases: dict = {}
    for s in samples:
        for k, v in s["deltas"].items():
            if isinstance(v, list):
                have = deltas.setdefault(k, [0] * len(v))
                deltas[k] = [a + b for a, b in zip(have, v)]
            else:
                deltas[k] = deltas.get(k, 0) + v
        for k, v in s.get("phases", {}).items():
            phases[k] = phases.get(k, 0.0) + v
    return {"jobs": n, "steps": sum(s["steps"] for s in samples), "deltas": deltas,
            "phases": phases, "caps": samples[-1]["caps"]}


def slot_pct(run, row: str, per_core_step: str | None = None):
    """100 * the stat row's total / (`n_cores` x steps [x a further static
    size a core-step]), or None."""
    t = window_totals(run)
    if t is None or row not in t["deltas"]:
        return None
    slots = t["caps"]["n_cores"] * t["steps"] * (t["caps"][per_core_step] if per_core_step else 1)
    return 100.0 * t["deltas"][row] / slots if slots else None


def read(run, trace):
    return slot_pct(run, "slot_active")
