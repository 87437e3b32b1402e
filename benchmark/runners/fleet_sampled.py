"""`runners/fleet.py`'s job on a program whose fused fleet run accounts for
itself: every call of `FleetEngine.run` is held to having committed its
one job sample (label `fleet`, the longest element's steps, `caps` that
say how many machines), which is what the readers of the job samples
(`metrics/slot_active_pct.py::window_totals`: `arb_win_pct`, the host
spans, `fleet_elem_ms_step`, `fleet_frozen_pct`) read in a cell of many
machines.

A program whose `FleetEngine.run` commits no sample (every one before
PR 43) cannot run a configuration that names this runner, and is told so
before anything compiles: `warm_up` raises `NoJobSample` where
`sim/fleet.py` has not the builder of job samples (`engine.commit_job`,
DESIGN.md section 15) in hand, and the run ends with a traceback and exit
code 1, having measured nothing. That is meant: such a program would
print a result line without the metrics that list no cells, and a line
that lacks a metric it owes is no result. The name is only the early
answer; what holds is counted: the parity job, the first after the
warm-up and before the window, raises the same where the run it drove
left no sample that says what it did, and inside the window a job without
its sample is a failed job, as any job that raises.

Everything else (the warm-up, the job record, `element_machines`) is
`runners/fleet.py`'s, unchanged."""

from __future__ import annotations

import cells

_fleet = cells.load_runner("fleet", cells.ROOT)

KNOB_PATHS = _fleet.KNOB_PATHS
element_machine = _fleet.element_machine
element_machines = _fleet.element_machines


class NoJobSample(RuntimeError):
    """The program runs a fleet and commits no sample of it."""


def warm_up(cfg, run: dict, parity_trace, mesh, want_hlo: bool) -> tuple:
    """`runners/fleet.py::warm_up`, refused before it compiles anything on a
    program whose fleet has no job sample to commit."""
    from primesim_tpu.sim import fleet

    if not hasattr(fleet, "commit_job"):
        raise NoJobSample(
            f"{fleet.__file__} builds no job sample (no `commit_job`): this program's "
            f"FleetEngine.run commits none, and this configuration's sample readers "
            f"would have nothing to read")
    return _fleet.warm_up(cfg, run, parity_trace, mesh, want_hlo)


def run_job(cfg, run: dict, trace, events, mesh=None, profile_dir: str | None = None) -> dict:
    """`runners/fleet.py::run_job`, held to the sample the run committed for it."""
    from primesim_tpu.obs import process_store

    store = process_store()
    before = store.seq
    job = _fleet.run_job(cfg, run, trace, events, mesh, profile_dir)
    last = store.samples()[-1] if store.seq == before + 1 else {}
    caps = last.get("caps") or {}
    if (last.get("label"), last.get("steps"), caps.get("elements")) != (
            "fleet", job["steps"], len(job["elements"])):
        raise NoJobSample(
            f"FleetEngine.run retired {len(job['elements'])} machines in {job['steps']} steps "
            f"and did not commit the one job sample that says so ({store.seq - before} "
            f"committed; label {last.get('label')!r}, steps {last.get('steps')!r}, "
            f"elements {caps.get('elements')!r})")
    return job
