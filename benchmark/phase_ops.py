"""What the per-phase metric readers (`metrics/ph_*.py`,
`rank_noc_ms_step.py`, `collective_ms_step.py`) share.

The program wraps each phase of its step in a `jax.named_scope`
(`primesim_tpu/sim/engine.py::PHASES`), so the `op_name` of every
instruction it compiles holds the phase as one component of its path:
`jit(run_loop)/s.noc/rank/jit(searchsorted)/vmap()/gather`. All phases
share the prefix `s.`, which is how a reader finds one without a list of
them. `xplane.reduce` has already put that path into each op's label. A
program without the scopes (any commit before they came) gives every
reader here nothing to read, and it returns None.
"""

from __future__ import annotations

import re

_SCOPE = re.compile(r"/(s\.\w+)(?=/)")  # a phase scope, as a whole path component
OUTSIDE = "s.chunk"  # `run_loop`'s per-chunk housekeeping: no phase of the step
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+) \(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*? ([a-z][\w\-]*)\(")
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")


def traced_job(run: dict, trace: dict | None) -> dict | None:
    """The job that ran under the profiler, where there is a trace of it."""
    if trace is None:
        return None
    return next((j for j in run["jobs"] if j.get("traced")), None)


def phase_of(path: str) -> str | None:
    """The phase scope in an op's label or `op_name`, `s.chunk` too."""
    m = _SCOPE.search(path)
    return m.group(1) if m else None


def phase_ms_step(run: dict, trace: dict | None, needle: str) -> float | None:
    """Device milliseconds per step of the traced job's leaf ops whose
    label holds `needle`, a scope path such as `/s.noc/` or `/s.noc/rank/`."""
    from xplane import op_seconds

    job = traced_job(run, trace)
    if job is None:
        return None
    s = op_seconds(trace, (needle,))
    return None if s is None else 1e3 * s / job["steps"]


def instruction_seconds(trace: dict) -> dict:
    """{instruction name: device seconds} of the trace's leaf ops (a
    label is the instruction name, then its `op_name` path)."""
    out: dict = {}
    for label, (seconds, _count) in trace["ops"].items():
        name = label.split(" ", 1)[0]
        out[name] = out.get(name, 0.0) + seconds
    return out


def opcodes(hlo_text: str | None) -> dict:
    """{instruction name: opcode} of a compiled module's text."""
    out = {}
    for line in (hlo_text or "").splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def fusion_phases(hlo_text: str | None) -> dict:
    """{fusion instruction name: the set of phases among the instructions
    of the computation it calls} of a compiled module's text. A fusion
    carries one `op_name`, its root's; this is what else it holds."""
    from xplane import op_names

    paths = op_names(hlo_text)
    inside: dict = {}  # computation name -> phases of its instructions
    calls: dict = {}  # fusion instruction name -> computation name
    current = None
    for line in (hlo_text or "").splitlines():
        head = _COMPUTATION.match(line)
        if head:
            current = inside.setdefault(head.group(1), set())
            continue
        m = _INSTRUCTION.match(line)
        if m is None or current is None:
            continue
        if m.group(2) == "fusion":
            called = _CALLS.search(line)
            if called:
                calls[m.group(1)] = called.group(1)
        phase = phase_of(paths.get(m.group(1), ""))
        if phase:
            current.add(phase)
    return {name: inside.get(comp, set()) for name, comp in calls.items()}
