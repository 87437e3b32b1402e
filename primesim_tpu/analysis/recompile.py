"""Runtime recompile sentinel: one compilation per geometry, enforced.

The fleet contract (DESIGN.md §7) is that a knob sweep is ONE
compilation — the jit key is `cfg.timing_normalized()` and every
timing/fault knob rides traced state. A regression (a knob leaking
into the static key) doesn't fail any functional test; it just
silently recompiles per element and the sweep gets slow. This
contextmanager makes that failure loud:

    with recompile_sentinel(allowed=1, watch=("fleet",)):
        FleetEngine(cfg, traces, overrides).run()

It snapshots the jit compile-cache entry count (`fn._cache_size()`,
present on jax's jitted callables) of the watched entry points on
entry and asserts on exit that no watched function grew by more than
`allowed` entries. `allowed=1` permits the first compile of a fresh
geometry; `allowed=0` guards an already-warm measurement loop. If the running jax build doesn't expose
`_cache_size` the sentinel degrades to a no-op rather than failing.
"""

from __future__ import annotations

import importlib
from contextlib import contextmanager

from .errors import RecompileError

# preset name -> (module, jitted entry point attribute names)
_PRESETS = {
    "engine": ("primesim_tpu.sim.engine", ("run_loop", "run_chunk")),
    "fleet": ("primesim_tpu.sim.fleet",
              ("fleet_run_loop", "fleet_run_chunk")),
}


def _resolve(watch) -> dict:
    """Map display name -> jitted callable exposing `_cache_size`."""
    fns: dict = {}
    for w in watch if watch is not None else tuple(_PRESETS):
        if isinstance(w, str):
            if w not in _PRESETS:
                raise RecompileError(
                    f"unknown watch preset '{w}' "
                    f"(have: {', '.join(sorted(_PRESETS))})"
                )
            modname, names = _PRESETS[w]
            mod = importlib.import_module(modname)
            for name in names:
                fns[f"{w}:{name}"] = getattr(mod, name)
        else:
            fns[getattr(w, "__name__", repr(w))] = w
    return {k: f for k, f in fns.items() if hasattr(f, "_cache_size")}


class Sentinel:
    """Live view inside the guarded region (mostly for tests)."""

    def __init__(self, fns: dict):
        self._fns = fns
        self._before = {k: f._cache_size() for k, f in fns.items()}

    @property
    def active(self) -> bool:
        return bool(self._fns)

    def growth(self) -> dict:
        return {
            k: f._cache_size() - self._before[k]
            for k, f in self._fns.items()
        }


@contextmanager
def recompile_sentinel(allowed: int = 1, watch=None, label: str = ""):
    """Assert no watched jit entry point compiles more than `allowed`
    times inside the block. `watch` takes preset names ("engine",
    "fleet") and/or jitted callables; default watches both presets.
    Raises RecompileError (exit 2 via the CLI contract) on breach."""
    sentinel = Sentinel(_resolve(watch))
    yield sentinel
    growth = sentinel.growth()
    over = {k: g for k, g in growth.items() if g > allowed}
    if over:
        what = ", ".join(f"{k} compiled {g}x" for k, g in over.items())
        raise RecompileError(
            f"recompile sentinel{f' [{label}]' if label else ''}: "
            f"{what} (allowed {allowed} per geometry) — a knob likely "
            "leaked into the static jit key",
            growth=growth,
        )
