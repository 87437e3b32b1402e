"""The benchmark's own modules (`benchmark/*.py`) and its own tests
(`benchmark/tests/`), for the tier-1 tests that hold the benchmark's forks
and references to the program. Importing this puts `benchmark/` on the
path, as `benchmark/run.py` does for itself."""

import importlib.util
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)


def _load(name: str, path: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_benchmark_tests(name: str = "test_benchmark"):
    """`benchmark/tests/<name>.py` as a module. It says `from conftest
    import BENCH, ROOT` and means the benchmark's conftest, while under
    `pytest tests/` that name is this directory's: the benchmark's stands
    in under it for as long as the module loads."""
    tests = os.path.join(BENCH, "tests")
    mine = sys.modules.get("conftest")
    sys.modules["conftest"] = _load("benchmark_tests_conftest",
                                    os.path.join(tests, "conftest.py"))
    sys.path.insert(0, tests)  # its helpers `tinycell` and `scratchroot`
    try:
        return _load(f"benchmark_tests_{name}", os.path.join(tests, f"{name}.py"))
    finally:
        sys.path.remove(tests)
        if mine is None:
            del sys.modules["conftest"]
        else:
            sys.modules["conftest"] = mine


class LiveBytes:
    """An allocator that counts, which the CPU's does not, to stand behind
    `sim.engine.alloc_now` in a test of lifetimes: the bytes of the
    process's live arrays at every reading, and the most it has read."""

    peak = 0

    def __call__(self):
        import jax

        held = sum(a.nbytes for a in jax.live_arrays())
        self.peak = max(self.peak, held)
        return {0: {"bytes_in_use": held, "largest_free_block_bytes": 0,
                    "peak_bytes_in_use": self.peak}}


def assert_reference_equals_golden(reference, machine: dict, ev, gold=None):
    """A plain reference (a module with `RefSim` and `COUNTERS`) against
    the golden model on one machine and one folded trace: the step count,
    every core's cycles, every counter it models, and zero in every
    counter it does not. `gold`: the golden model already run on them.
    Returns the reference's finished simulation."""
    import numpy as np

    import trafficgen
    from primesim_tpu.config.machine import MachineConfig
    from primesim_tpu.golden.sim import GoldenSim
    from primesim_tpu.trace.format import Trace

    if gold is None:
        lengths = (ev[:, :, 0] != trafficgen.EV_END).sum(1) + 1
        gold = GoldenSim(MachineConfig.from_dict(machine), Trace(ev, lengths))
        gold.run()
    ref = reference.RefSim(machine, ev)
    ref.run()
    assert ref.step_count == gold.step_count
    assert np.array_equal(np.asarray(ref.cycles), gold.cycles)
    for k, v in gold.counters.items():
        if k in reference.COUNTERS:
            assert np.array_equal(np.asarray(ref.counters[k]), v), k
        else:
            assert not v.any(), k
    return ref


def vary_string(overrides: dict) -> str:
    """One element's overrides as `primetpu sweep --vary` spells them."""
    return ",".join(f"{k}={v}" for k, v in overrides.items())


def handed_to_fleet_by_sweep(monkeypatch, machine_file: str, spec: dict,
                             extra: tuple = (), first: str | None = None) -> tuple:
    """(cfg, traces, overrides, keywords) that `primetpu sweep
    configs/<machine_file> --synth <the cell's parity trace> --vary ...`,
    one `--vary` for each of the cell's elements after the first (and one,
    `first`, for the first where a caller spells `{}` as a knob at its own
    value), then `extra`, hands `FleetEngine`; nothing is built or run."""
    import pytest

    import primesim_tpu.sim.fleet as fleet_module
    from primesim_tpu.cli import main

    class Handed(Exception):
        pass

    def capture(cfg, traces, overrides=None, **kw):
        raise Handed(cfg, traces, overrides, kw)

    monkeypatch.setattr(fleet_module, "FleetEngine", capture)
    args = spec["traffic"]["args"] | spec["traffic"]["parity_args"]
    argv = ["sweep", os.path.join(ROOT, "configs", machine_file),
            "--synth", "fft_like:" + ",".join(f"{k}={v}" for k, v in args.items()), "--fold",
            "--chunk-steps", "8", "--strict"]
    if first is not None:
        argv += ["--vary", first]
    for ov in spec["config"]["run"]["fleet"]["overrides"][1:]:
        argv += ["--vary", vary_string(ov)]
    argv += list(extra)
    with pytest.raises(Handed) as handed:
        main(argv)
    return handed.value.args
