"""Reduction of a profiler trace (`*.xplane.pb`) to what the per-layer
metrics read. Needs nothing but JAX's own `ProfileData`.

Of each device plane it reads the line of XLA ops. Ops nest there: a
`while` spans its body's ops. Busy time is the union of the intervals of
every op but those control-flow containers, so a `while` counts only
through what runs inside it, and a pause between two ops of its body is
idle time that the container's name explains. The window is the host's `benchmark_job`
annotation (`measure.run_job`), which is on the trace's own clock; without
one it is the span from the first device op to the last.

The trace names an op by its HLO text (`%fusion.615 = s32[...] fusion(...)`)
and says nothing of where it came from. The compiled program's own text
does: `op_names` reads each instruction's `op_name` from it
(`jit(run_loop)/.../jit(searchsorted)/.../gather`), and an op's label is
its instruction name followed by the tail of that path. That is what
tells the ranking's `searchsorted` from the step's own gathers.
"""

from __future__ import annotations

import glob
import os
import re

OPS_LINE = "XLA Ops"
ANNOTATION = "benchmark_job"
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?metadata=\{[^}]*?op_name=\"([^\"]*)\"")


def find_xplane(profile_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"), recursive=True))
    return found[-1] if found else None


def op_names(hlo_text: str | None) -> dict:
    """{instruction name: op_name} of a compiled module's text."""
    out = {}
    for line in (hlo_text or "").splitlines():
        m = _INSTR.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def _label(name: str, names: dict) -> str:
    instr = name.split(" = ", 1)[0].lstrip("%")
    path = names.get(instr)
    if path is None:
        return instr
    return instr + " " + "/".join(p for p in path.split("/")
                                  if p not in ("while", "body", "closed_call", "cond"))[-200:]


CONTROL_FLOW = ("while", "conditional", "call")


def _is_control_flow(label: str) -> bool:
    return label.split(" ", 1)[0].split(".", 1)[0] in CONTROL_FLOW


def _leaves(events: list) -> tuple[list, list]:
    """(leaves, containers) of events (start, end, label). A container is
    a control-flow op that spans other ops; every other op is a leaf and
    its whole duration is busy time, also where the next op starts before
    it has ended."""
    events = sorted(events, key=lambda e: (e[0], -e[1]))
    leaves, containers, stack = [], [], []  # stack of [event, has_child]

    def close(entry):
        ev, has_child = entry
        (containers if has_child and _is_control_flow(ev[2]) else leaves).append(ev)

    for e in events:
        while stack and stack[-1][0][1] <= e[0]:
            close(stack.pop())
        if stack:
            stack[-1][1] = True
        stack.append([e, False])
    while stack:
        close(stack.pop())
    return leaves, containers


def _union(intervals: list, lo: float, hi: float) -> list:
    """Merged intervals clipped to [lo, hi]."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _explain_all(gaps: list, containers: list, host: list) -> dict:
    """Seconds of idle gaps by what each lay inside: the innermost device
    container over its middle (a pause between two ops of a `while` body),
    else the shortest host span over it (what the host was doing)."""
    out: dict = {}
    containers = sorted(containers, key=lambda c: (c[0], -c[1]))
    stack, ci = [], 0
    for gs, ge in gaps:  # gaps are in time order
        mid = (gs + ge) / 2
        while ci < len(containers) and containers[ci][0] <= mid:
            stack.append(containers[ci])
            ci += 1
        while stack and stack[-1][1] < mid:
            stack.pop()
        inside = [c for c in stack if c[0] <= mid <= c[1]]
        if inside:
            name = "device: inside " + min(inside, key=lambda c: c[1] - c[0])[2]
        else:
            over = [h for h in host if h[0] <= mid <= h[1] and h[2] != ANNOTATION]
            name = ("host: " + min(over, key=lambda h: h[1] - h[0])[2]) if over \
                else "unattributed"
        out[name] = out.get(name, 0.0) + (ge - gs) / 1e9
    return out


def reduce(path: str, hlo_text: str | None = None) -> dict | None:
    """{window_s, busy_s, n_devices, ops: {label: [seconds, count]},
    device_ops, idle_gaps} of one trace, or None where the trace holds no
    device op (a rehearsal on the CPU)."""
    from jax.profiler import ProfileData

    names = op_names(hlo_text)
    labels: dict = {}
    data = ProfileData.from_file(path)
    device, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                evs = []
                for e in line.events:
                    name = e.name
                    label = labels.get(name)
                    if label is None:
                        label = labels[name] = _label(name, names)
                    evs.append((e.start_ns, e.start_ns + e.duration_ns, label))
                if evs:
                    device.append(evs)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.start_ns, e.start_ns + e.duration_ns, e.name)
                            for e in line.events)
    if not device:
        return None
    spans = [(s, e) for s, e, name in host if name == ANNOTATION]
    if spans:
        lo, hi = min(s for s, _ in spans), max(e for _, e in spans)
    else:
        lo = min(e[0] for evs in device for e in evs)
        hi = max(e[1] for evs in device for e in evs)

    busy_ns, ops, gaps = 0.0, {}, {}
    for evs in device:
        leaves, containers = _leaves(evs)
        merged = _union([(s, e) for s, e, _ in leaves], lo, hi)
        busy_ns += sum(e - s for s, e in merged)
        for s, e, label in leaves:
            s, e = max(s, lo), min(e, hi)
            if e > s:
                rec = ops.setdefault(label, [0.0, 0])
                rec[0] += (e - s) / 1e9
                rec[1] += 1
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        idle = [(gs, ge) for gs, ge in zip(edges[0::2], edges[1::2]) if ge > gs]
        for name, s in _explain_all(idle, containers, host).items():
            gaps[name] = gaps.get(name, 0.0) + s
    n = len(device)

    def top(d: dict) -> list:
        return [[k[:64], v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:10]]

    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_ns / n / 1e9,
        "n_devices": n,
        "ops": {k: [v[0] / n, v[1]] for k, v in ops.items()},
        "device_ops": top({k: v[0] / n for k, v in ops.items()}),
        "idle_gaps": top({k: v / n for k, v in gaps.items()}),
    }


def op_seconds(trace: dict, needles: tuple, without: tuple = ()) -> float | None:
    """Device seconds of the leaf ops whose label holds any of `needles`
    and none of `without`; None where no such op ran."""
    hit = [v[0] for k, v in trace["ops"].items()
           if any(n in k for n in needles) and not any(w in k for w in without)]
    return sum(hit) if hit else None
