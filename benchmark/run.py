"""The benchmark's one command:

    python3 benchmark/run.py --workload W --seed N --seconds S --trace 0|1

Looks W up in BENCHMARK.json, generates the traffic mix's traces in the
order the seed draws, drives the program through set-up and the measured
window, decides `correct`, and prints one JSON object as the last line
of stdout. Off the
chip it fails, unless JAX_PLATFORMS=cpu asks for a rehearsal, whose
numbers are printed under `cpu_rehearsal.` names and never as a device's.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (ROOT, HERE):  # the checkout's program, the benchmark's own modules
    if p not in sys.path:
        sys.path.insert(0, p)


def _die(msg: str, code: int = 3):
    print(f"benchmark/run.py: {msg}", file=sys.stderr)
    raise SystemExit(code)


def _device_or_die(chips: int) -> tuple[bool, dict]:
    """(rehearsal, device fields). The measuring process is the only one
    that touches JAX, so it is the only one that ever holds the chip."""
    import primesim_tpu  # before JAX: a checkout without the program fails here
    from primesim_tpu.util.device import configure_compile_cache

    import jax

    if not os.path.abspath(primesim_tpu.__file__).startswith(ROOT + os.sep):
        _die(f"primesim_tpu was imported from {primesim_tpu.__file__}, not from this checkout")
    configure_compile_cache()
    # every program of a run, the small ones too, has to be in the cache
    # after the cell's first run in a checkout
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devs = jax.devices()
    rehearsal = os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
    if devs[0].platform != "tpu" and not rehearsal:
        _die(f"no accelerator: JAX found {devs[0].platform!r} ({devs[0].device_kind}); "
             "set JAX_PLATFORMS=cpu for a rehearsal")
    if len(devs) < chips:
        _die(f"the cell asks for {chips} chip(s), JAX found {len(devs)}")
    return rehearsal, {"platform": devs[0].platform, "kind": devs[0].device_kind}


def execute(spec: dict, seed: int, seconds: float, trace: bool, rehearsal: bool,
            device: dict, t_start: float, **broken) -> tuple[dict, list]:
    """One run, from set-up to the result object and the lines that show
    every number compared. `broken` is passed to `measure.run_cell` by
    `selfcheck.py` and the tests."""
    import cells
    import check
    import measure
    import xplane

    record = measure.run_cell(spec, seed, seconds, trace, t_start, **broken)
    verdict = check.decide(record, expect_platform=device["platform"])

    reduced = None
    if record["profile_dir"]:
        path = xplane.find_xplane(record["profile_dir"])
        reduced = xplane.reduce(path, record["hlo_text"]) if path else None
        shutil.rmtree(record["profile_dir"], ignore_errors=True)

    jobs = record["jobs"]
    values: dict = {}
    if not trace:
        job_s = sum(j["seconds"] for j in jobs)
        values["sim_mips"] = sum(j["instructions"] for j in jobs) / job_s / 1e6 if jobs else None
        peak = record["memory_peak_bytes"]
        values["hbm_peak_gb"] = peak / 1e9 if peak else None
        values["setup_s"] = record["setup_s"]
        listed = spec["end_to_end"]
    else:
        listed = spec["per_layer"]
        for m in listed:
            values[m["name"]] = cells.load_metric(m["name"], spec["root"])(record, reduced)
    prefix = "cpu_rehearsal." if rehearsal else ""
    metrics = {
        prefix + m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
        for m in listed if values.get(m["name"]) is not None
    }
    result = {
        "correct": verdict["correct"],
        "attempted": verdict["attempted"],
        "failed": verdict["failed"],
        "metrics": metrics,
        "device": {
            "platform": device["platform"],
            "kind": device["kind"],
            "count": record["parity"]["n_devices"],
            "memory_peak_bytes": record["memory_peak_bytes"],
        },
    }
    if reduced is not None:
        result["device"]["busy_s"] = reduced["busy_s"]
        result["device"]["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    notes = verdict["lines"] + [
        f"[run] workload={spec['name']} seed={seed} passes={record['passes']} "
        f"jobs={len(jobs)} window_s={record['window_s']:.3f} setup_s={record['setup_s']:.3f} "
        f"setup_programs={record['setup_compiles']} reference_s={verdict['reference_s']:.3f} "
        f"checked_trace={verdict['checked_trace']}",
        "[run] set-up seconds by phase: " + " ".join(
            f"{k}={v:.3f}" for k, v in record["phases_s"].items()),
        "[run] job seconds, first 16: " + " ".join(f"{j['seconds']:.4f}" for j in jobs[:16]),
        "[run] trace:steps in the run's order: " + " ".join(
            f"{j['trace']}:{j['steps']}" for j in jobs[: len({j["trace"] for j in jobs})]),
    ] + [f"[run] raised: {r}" for r in record["raised"]]
    return result, notes


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ns = ap.parse_args(argv)
    if not 0 <= ns.seed < 2**40:
        _die("--seed must be a whole number in [0, 2^40)", 2)

    import cells

    try:
        spec = cells.load_cell(ns.workload)
    except cells.CellError as e:
        _die(str(e), 2)
    try:
        rehearsal, device = _device_or_die(spec["cell"]["chips"])
    except ImportError as e:
        _die(f"the program is not in this checkout: {e}")
    result, notes = execute(spec, ns.seed, ns.seconds, bool(ns.trace), rehearsal,
                            device, T_START)
    for line in notes:
        print(line)
    print(json.dumps(result))
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
