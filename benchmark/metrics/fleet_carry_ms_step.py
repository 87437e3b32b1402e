"""Device milliseconds per step (the longest element's steps) of the
traced job's ops that belong to no phase of the step and not to `s.chunk`
(`phase_ops.phase_of` finds no `s.` scope in their label): on a job of
many machines (`runners/fleet.py`) what `jax.vmap` makes of `run_loop`'s
`lax.while_loop`, the carry select-masked so that finished elements
freeze, and the copies of the whole carry round it. Needs no scope of its
own in the program. A job of one machine gives nothing to read: what lies
outside its cover is `ph_cover_pct`'s."""


def read(run, trace):
    from phase_ops import phase_of, traced_job

    job = traced_job(run, trace)
    if job is None or not job.get("elements") or not job["steps"]:
        return None
    outside = [v[0] for k, v in trace["ops"].items() if phase_of(k) is None]
    return 1e3 * sum(outside) / job["steps"]
