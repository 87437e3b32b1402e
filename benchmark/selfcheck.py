"""The runs that `correct` has to fail, beside sound runs that it has to pass.

    python3 benchmark/selfcheck.py --workload W --seeds 11,12,13 [--seconds S]

All in one process, which holds the chip throughout. For every seed: a
sound run (correct has to be true), then the control: the program
simulates a machine whose `dram_lat` is one cycle off while the reference
keeps the machine the configuration file states, so both the whole timed
job and the parity job that the reference checks disagree with it
(correct has to be false). Last, one run with a program compiled inside the measured window
(correct has to be false). Exits 0 only if every run came out as it has
to. The benchmark's own runs never come here.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse
import json
import sys

import run as harness


def _compile_something() -> None:
    import jax
    import jax.numpy as jnp

    jax.jit(lambda x: (x * 3 + 1).sum())(jnp.arange(12345)).block_until_ready()


def _nonzero(notes: list) -> list:
    return [n for n in notes if n.startswith("[check]") and " = 0 (" not in n]


def selfcheck(spec: dict, seeds: list, seconds: float, rehearsal: bool,
              device: dict) -> dict:
    off_by_one = {"dram_lat": spec["config"]["machine"]["dram_lat"] + 1}
    runs = []

    def one(kind: str, seed: int, expect: bool, **broken) -> None:
        result, notes = harness.execute(spec, seed, seconds, False, rehearsal,
                                        device, time.perf_counter(), **broken)
        runs.append({"kind": kind, "seed": seed, "correct": result["correct"],
                     "as_expected": result["correct"] is expect,
                     "failed_numbers": _nonzero(notes), "metrics": result["metrics"]})
        print(f"[selfcheck] {kind} seed={seed} correct={result['correct']} "
              f"(has to be {expect}) {_nonzero(notes)}", flush=True)

    for seed in seeds:
        one("sound", seed, True)
        one("control_dram_lat_plus_1", seed, False, program_machine_patch=off_by_one)
    one("compile_inside_window", seeds[0], False, in_window=_compile_something)
    return {"ok": all(r["as_expected"] for r in runs), "workload": spec["name"],
            "device": device, "runs": runs}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated whole numbers")
    ap.add_argument("--seconds", type=float, default=0.0,
                    help="window length; 0 is one whole pass over the panel")
    ns = ap.parse_args(argv)

    import cells

    spec = cells.load_cell(ns.workload)
    rehearsal, device = harness._device_or_die(spec["cell"]["chips"])
    summary = selfcheck(spec, [int(s) for s in ns.seeds.split(",")], ns.seconds,
                        rehearsal, device)
    print(json.dumps(summary))
    return 0 if summary["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
