"""A benchmark run's job samples, not only its line (PR 53).

The result line of `benchmark/run.py --trace 1` carries one number a metric;
the job samples behind them (`obs.process_store()`: `phases`, `caps`, and
`place`, the chips and what their allocators held and had free as each job's
arrays were laid, and since PR 54 once they were (`alloc_built`) and as the
job's wait ended (`alloc_run`), each with the process's peak: DESIGN.md
section 15) stay in the process. This runs the benchmark of a checkout and dumps them after it, and
prints a directory of such runs as one row a run:

    python scripts/prof/job_samples.py run ROOT OUT.jsonl --workload W --seed N --seconds 10 --trace 1
    python scripts/prof/job_samples.py table DIR PREFIX [metric ...]

`run` executes ROOT/benchmark/run.py with the arguments given (its stdout is
the benchmark's: redirect it to DIR/<name>.out beside OUT = DIR/<name>.jsonl);
ROOT may be another checkout (a parent under `.chipwork/`), whose own program
then runs. `table` reads DIR/PREFIX*.out and the `.jsonl` beside each: the
metrics named (a default set where none is), then of the parity job's, the
window's first job's and its last job's sample `place` and `phases.init`.
"""

from __future__ import annotations

import atexit
import glob
import json
import os
import runpy
import sys

METRICS = ("ph_probe_ms_step", "ph_local_ms_step", "ph_commit_ms_step", "job_s_max",
           "engine_init_ms_job", "fleet_build_ms_job", "host_readback_ms_job",
           "sim_mips", "hbm_peak_gb", "setup_s")


def run(root: str, out: str, argv: list) -> None:
    root, out = os.path.abspath(root), os.path.abspath(out)
    os.chdir(root)
    sys.path.insert(0, root)

    def dump():
        from primesim_tpu.obs import process_store

        process_store().dump_jsonl(out)

    atexit.register(dump)
    sys.argv = [os.path.join(root, "benchmark", "run.py"), *argv]
    runpy.run_path(sys.argv[0], run_name="__main__")


def table(directory: str, prefix: str, metrics: list) -> None:
    for path in sorted(glob.glob(os.path.join(directory, prefix + "*.out"))):
        name = os.path.basename(path)[:-4]
        with open(path) as f:
            lines = [line for line in f.read().splitlines() if line.startswith("{")]
        if not lines:
            print(name, "no result line")
            continue
        result = json.loads(lines[-1])
        values = {k: v["value"] for k, v in result["metrics"].items()}
        print(name, "correct", result["correct"],
              json.dumps({k: values[k] for k in metrics or METRICS if k in values}))
        dumped = path[:-4] + ".jsonl"
        if not os.path.exists(dumped):
            continue
        with open(dumped) as f:
            jobs = [s for s in map(json.loads, f) if "place" in s]
        if not jobs:
            continue
        # the warm-up commits no sample: the parity job's is the first
        for tag, s in (("parity", jobs[0]), ("first", jobs[min(1, len(jobs) - 1)]),
                       ("last", jobs[-1])):
            print("   ", tag, "place", json.dumps(s["place"]),
                  "init_ms", round(1e3 * s["phases"]["init"], 2))


if __name__ == "__main__":
    if len(sys.argv) >= 4 and sys.argv[1] == "run":
        run(sys.argv[2], sys.argv[3], sys.argv[4:])
    elif len(sys.argv) >= 4 and sys.argv[1] == "table":
        table(sys.argv[2], sys.argv[3], sys.argv[4:])
    else:
        raise SystemExit(__doc__)
