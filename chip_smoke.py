"""chip_smoke.py — the quickest proof that `primetpu run` starts on the chip.

One process that owns the chip and spawns nothing that needs it. It
drives the product through `primesim_tpu.cli.main([...])` in-process and
fails — non-zero exit, reason on stderr — at the FIRST phase that does
not hold; no phase failure is turned into a printed note. Phases run in
the order bring-up proceeds, so the output shows how far it got:

1. device    — `jax.devices()[0].platform` is the expected one ("tpu")
               or exit, naming what was found.
2. parity    — Engine vs the scalar oracle `golden.sim.GoldenSim`,
               per-core cycles and every counter equal, on the 64-core
               rung-1 machine and the 8-core full-timing-stack machine.
3. main path — `run configs/rung3_1024core_o3.json` on the 21.16 M-
               instruction bench trace (1024 cores, 32x32 router NoC,
               DRAM queue, O3; ~0.85 GB of state in HBM), the five
               backend-invariant counts pinned below.
4. four chips — with >= 4 devices, phase 3 again with `--devices 4`.

The last stdout line is exactly `{"ok": true, "device": {"platform",
"kind", "count"}}`, the device as JAX reports it; nothing of the kind is
printed unless every phase held. The line before it, `[summary] {...}`,
carries versions, per-phase wall/compile/run seconds and counts. Its MIPS
figures are smoke observations, nobody's benchmark. Compile seconds per
phase are JAX's own backend-compile events, so two runs against one
`JAX_COMPILATION_CACHE_DIR` show the second one hitting.
"""

from __future__ import annotations

import contextlib
import io
import json
import os
import re
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))

RUNG1_SYNTH = "fft_like:n_phases=4,points_per_core=64,ins_per_mem=4"
BENCH_SYNTH = "fft_like:n_phases=4,points_per_core=256,ins_per_mem=8,seed=42"
BENCH_CHUNK = 512

# Backend-invariant ground truth (counts, not speeds): the same values
# come out of the CPU container and of the golden oracle.
RUNG1_TRUTH = {"instructions": 191173, "max_core_cycles": 10693,
               "noc_msgs": 17408}
RUNG3_TRUTH = {"instructions": 21163720, "max_core_cycles": 824798,
               "noc_msgs": 1077012, "noc_contention_cycles": 1454895220,
               "dram_queue_cycles": 100895397}


def fail(phase: str, reason: str):
    raise SystemExit(f"chip_smoke: {phase} FAILED: {reason}")


class CompileMeter:
    """Seconds JAX spent in the backend compiler — or loading the
    executable from the persistent cache instead — plus cache hits,
    accumulated from jax.monitoring events; `lap()` returns the delta
    since the last lap. (Tracing and lowering are not counted: their
    events nest, and a cache cannot save them.)"""

    _BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring as mon

        self.compile_s = 0.0
        self.cache_hits = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event == self._BACKEND_COMPILE:
            self.compile_s += secs

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def lap(self) -> dict:
        out = {"compile_s": round(self.compile_s, 2),
               "cache_hits": self.cache_hits}
        self.compile_s, self.cache_hits = 0.0, 0
        return out


def phase_device(platform: str) -> dict:
    import importlib.metadata as md

    import jax
    import jaxlib

    devs = jax.devices()
    found = devs[0].platform
    if found != platform:
        fail("device", f"expected platform {platform!r}, JAX found "
             f"{found!r} ({devs[0].device_kind}, {len(devs)} device(s))")
    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = None
    device = {"platform": found, "kind": devs[0].device_kind,
              "count": len(devs)}
    versions = {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                "libtpu": libtpu}
    print(f"[device] {json.dumps(device)} {json.dumps(versions)}", flush=True)
    return {"device": device, "versions": versions}


def check_parity(cfg, trace, chunk_steps: int, platform: str) -> dict:
    """Engine vs GoldenSim on one (machine, trace): per-core cycles and
    every counter equal, with the engine's state on `platform`. Returns
    the three headline counts."""
    import numpy as np

    from primesim_tpu.golden.sim import GoldenSim
    from primesim_tpu.sim.engine import Engine
    from primesim_tpu.util.device import device_fields

    g = GoldenSim(cfg, trace)
    g.run()
    e = Engine(cfg, trace, chunk_steps=chunk_steps)
    e.run()
    where = device_fields(e.state.cycles)["platform"]
    if where != platform:
        fail("parity", f"engine state lives on {where!r}, not {platform!r}")
    if not np.array_equal(e.cycles, g.cycles):
        bad = np.flatnonzero(np.asarray(e.cycles) != np.asarray(g.cycles))
        fail("parity", f"per-core cycles differ from the oracle on "
             f"{bad.size} core(s), first core {int(bad[0])}")
    ec = e.counters
    for name, gv in g.counters.items():
        if not np.array_equal(ec[name], gv):
            fail("parity", f"counter {name!r} differs from the oracle: "
                 f"engine {int(ec[name].sum())} vs golden {int(gv.sum())}")
    return {"instructions": int(ec["instructions"].sum()),
            "max_core_cycles": int(np.max(e.cycles)),
            "noc_msgs": int(ec["noc_msgs"].sum())}


def full_stack_8core():
    """The verify skill's 8-core machine with every timing mechanism on
    (router contention + DRAM queue + coarse sharers + O3) and the
    false-sharing trace that exercises them."""
    from primesim_tpu.config.machine import (
        CoreConfig,
        NocConfig,
        small_test_config,
    )
    from primesim_tpu.trace import synth

    cfg = small_test_config(
        8, n_banks=8, local_run_len=4, dram_queue=True, dram_service=8,
        sharer_group=4, core=CoreConfig(o3_overlap_256=64),
        noc=NocConfig(mesh_x=2, mesh_y=2, contention=True,
                      contention_model="router", contention_lat=2),
    )
    return cfg, synth.false_sharing(8, n_mem_ops=40, seed=77), 32


def phase_parity(platform: str, meter: CompileMeter) -> dict:
    from primesim_tpu.config.machine import MachineConfig
    from primesim_tpu.serve.scheduler import parse_synth_spec

    with open(os.path.join(HERE, "configs", "rung1_64core_fft.json")) as f:
        cfg1 = MachineConfig.from_json(f.read())
    cases = {
        "rung1_64core": (cfg1, parse_synth_spec(RUNG1_SYNTH, 64, True), 16),
        "full_stack_8core": full_stack_8core(),
    }
    out = {}
    for name, (cfg, trace, chunk) in cases.items():
        t0 = time.perf_counter()
        counts = check_parity(cfg, trace, chunk, platform)
        if name == "rung1_64core" and counts != RUNG1_TRUTH:
            fail("parity", f"rung-1 counts {counts} != {RUNG1_TRUTH}")
        out[name] = {
            **counts, "wall_s": round(time.perf_counter() - t0, 2),
            **meter.lap(),
        }
        print(f"[parity] {name} == oracle {json.dumps(out[name])}",
              flush=True)
    return out


def run_cli(argv: list[str]) -> dict:
    """`primetpu <argv>` in-process through the product's entry point;
    returns the run summary (metric line) it printed."""
    from primesim_tpu.cli import main as cli_main

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = cli_main(argv)
    if rc != 0:
        fail("cli", f"`primetpu {' '.join(argv)}` exited {rc}")
    for line in buf.getvalue().splitlines():
        if line.startswith("{"):
            rec = json.loads(line)
            if rec.get("metric") == "simulated_MIPS":
                return rec
    fail("cli", f"`primetpu {' '.join(argv)}` printed no run summary")


def run_and_check(phase: str, config: str, truth: dict, platform: str,
                  meter: CompileMeter, extra: tuple = (),
                  n_devices: int = 1) -> dict:
    """One full-size `primetpu run` on the bench trace: every count in
    `truth` exact, and the summary says the arrays lived on `platform`."""
    with tempfile.TemporaryDirectory() as td:
        report = os.path.join(td, "report.txt")
        t0 = time.perf_counter()
        rec = run_cli([
            "run", config, "--synth", BENCH_SYNTH, "--fold",
            "--chunk-steps", str(BENCH_CHUNK), "--report", report, *extra,
        ])
        wall = time.perf_counter() - t0
        with open(report) as f:
            text = f.read()
    d = rec["detail"]
    counts = {k: d[k] for k in ("instructions", "max_core_cycles",
                                "noc_msgs")}
    for key, label in (("noc_contention_cycles", "NoC contention cyc"),
                       ("dram_queue_cycles", "DRAM queue cycles")):
        m = re.search(rf"{label}\s+([\d,]+)", text)
        if m is None:
            fail(phase, f"report has no {label!r} line")
        counts[key] = int(m.group(1).replace(",", ""))
    wrong = {k: (counts[k], v) for k, v in truth.items() if counts[k] != v}
    if wrong:
        fail(phase, f"counts differ from ground truth (got, want): {wrong}")
    if (d["platform"], d["n_devices"]) != (platform, n_devices):
        fail(phase, f"summary says the run's arrays lived on "
             f"{d['platform']!r} x{d['n_devices']}, expected "
             f"{platform!r} x{n_devices}")
    out = {
        **{k: counts[k] for k in truth},
        "platform": d["platform"], "device_kind": d["device_kind"],
        "n_devices": d["n_devices"], "wall_s": round(wall, 2),
        **meter.lap(), "run_s": d["wall_s"], "smoke_mips": rec["value"],
    }
    print(f"[{phase}] {json.dumps(out)}", flush=True)
    return out


def phase_four_chips(platform: str, meter: CompileMeter, rung3: str) -> dict:
    """With >= 4 devices: the main path sharded over 4 of them, same
    counts, directory and L1 state spanning all 4. On a one-chip machine
    `sharded` is null — a fact about the machine, not a skipped check."""
    import jax

    n = jax.device_count()
    if n < 4:
        print(f'[four_chips] {{"sharded": null, "n_devices": {n}}}',
              flush=True)
        return {"sharded": None, "n_devices": n}
    out = run_and_check(
        "four_chips", rung3, RUNG3_TRUTH, platform, meter,
        extra=("--devices", "4"), n_devices=4,
    )
    from primesim_tpu.config.machine import MachineConfig
    from primesim_tpu.parallel.sharding import tile_mesh
    from primesim_tpu.serve.scheduler import parse_synth_spec
    from primesim_tpu.sim.engine import Engine

    with open(rung3) as f:
        cfg = MachineConfig.from_json(f.read())
    eng = Engine(cfg, parse_synth_spec(BENCH_SYNTH, cfg.n_cores, True),
                 chunk_steps=BENCH_CHUNK, mesh=tile_mesh(4))
    for name in ("dirm", "l1"):
        span = len(getattr(eng.state, name).sharding.device_set)
        if span != 4:
            fail("four_chips", f"state.{name} spans {span} device(s), not 4")
    return {**out, "sharded": True}


def main(platform: str = "tpu") -> int:
    # imported before anything is printed: beside no repo, no output
    from primesim_tpu.util.device import configure_compile_cache

    t_start = time.perf_counter()
    head = phase_device(platform)
    cache_dir = configure_compile_cache()
    print(f"[cache] jax compile cache at {cache_dir}", flush=True)
    meter = CompileMeter()
    rung3 = os.path.join(HERE, "configs", "rung3_1024core_o3.json")

    phases = {"parity": phase_parity(platform, meter)}
    phases["main_path"] = run_and_check(
        "main_path", rung3, RUNG3_TRUTH, platform, meter
    )
    phases["four_chips"] = phase_four_chips(platform, meter, rung3)

    import jax

    found = jax.devices()[0].platform
    if found != platform:
        fail("device", f"platform changed under the run: now {found!r}")
    summary = {
        **head, "compile_cache_dir": cache_dir,
        "wall_s": round(time.perf_counter() - t_start, 1), "phases": phases,
    }
    print(f"[summary] {json.dumps(summary)}")
    print(json.dumps({"ok": True, "device": head["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
