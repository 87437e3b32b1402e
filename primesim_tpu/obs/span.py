"""One host span on both clocks (DESIGN.md §15).

`with span("engine.dispatch") as s:` opens a `jax.profiler.TraceAnnotation`
of that name, which puts the interval into a profiler trace on the device
ops' own clock (with no profiler attached it costs tens of nanoseconds),
and leaves the same interval's seconds by `time.perf_counter` in
`s.seconds`: one name, one interval, read once. The engine's host spans
all open here. Apart from the rest of `obs`, which imports no JAX.
"""

from __future__ import annotations

import time

import jax


class span:
    __slots__ = ("_annotation", "_t0", "seconds")

    def __init__(self, name: str):
        self._annotation = jax.profiler.TraceAnnotation(name)
        self.seconds = 0.0

    def __enter__(self) -> "span":
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.seconds = time.perf_counter() - self._t0
        self._annotation.__exit__(*exc)
        return False
