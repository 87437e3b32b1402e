"""The comparison that decides `correct`.

Every number compared is a count of disagreements and its limit is 0: the
configurations guarantee per-core cycles and every counter bit-exact with
the plain reference. Three parts:

- the checked job: the first job of the window, a whole timed job at the
  timed size on the trace the run's seed put first; once the window has
  closed the reference simulates that same trace on the machine the
  configuration file states, and every core's cycle count, every counter
  and the step count have to agree;
- the parity job: the run's own short trace, drawn from its seed, went
  through the same `Engine` calls and the same compiled program during
  set-up, and is held to the reference in the same way;
- every timed job: each finished with every core at END, retired exactly
  the instructions its trace holds (counted by the benchmark's own
  generator), gave the same counts in every pass, ended on the platform
  and the number of devices the cell asks for, and nothing was compiled
  or loaded inside the window.
"""

from __future__ import annotations

import time

import numpy as np

from reference import COUNTERS, RefSim


def reference_numbers(prefix: str, record: dict, events: np.ndarray, got: dict) -> tuple[list, float]:
    """[(name, value)] of one job of the program (`got`: cycles, counters,
    steps) against the reference on the same trace, and the reference's
    seconds."""
    t0 = time.perf_counter()
    ref = RefSim(record["machine"], events)
    ref.run()
    ref_s = time.perf_counter() - t0
    out = [(f"{prefix}.cycles.cores_differing",
            int((np.asarray(ref.cycles, np.int64) != got["cycles"]).sum()))]
    for k in COUNTERS:
        out.append((f"{prefix}.{k}.cores_differing",
                    int((np.asarray(ref.counters[k], np.int64) != got["counters"][k]).sum())))
    unmodelled = sum(int(np.count_nonzero(v)) for k, v in got["counters"].items()
                     if k not in COUNTERS)
    out.append((f"{prefix}.unmodelled_counters.nonzero", unmodelled))
    chunk = record["chunk_steps"]
    out.append((f"{prefix}.steps.differing", int(-(-ref.step_count // chunk) * chunk != got["steps"])))
    return out, ref_s


def job_numbers(record: dict, expect_platform: str) -> tuple[list, list]:
    """[(name, value)] of the timed jobs and the window, and for each job
    whether it came out wrong itself."""
    jobs = record["jobs"]
    first: dict = {}
    changed = [first.setdefault(j["trace"], j["digest"]) != j["digest"] for j in jobs]
    unfinished = [bool(j["not_at_end"]) for j in jobs]
    miscounted = [j["instructions"] != j["expect_instructions"] for j in jobs]
    every = jobs + [record["parity"]]
    numbers = [
        ("jobs.raised", len(record["raised"])),
        ("jobs.not_at_end.cores", sum(j["not_at_end"] for j in jobs)),
        ("jobs.instructions.differing", sum(miscounted)),
        ("jobs.counts_changed_between_passes", sum(changed)),
        ("jobs.wrong_platform", sum(j["platform"] != expect_platform for j in every)),
        ("jobs.wrong_device_count", sum(j["n_devices"] != record["chips"] for j in every)),
        ("window.programs_compiled_or_loaded", record["window_compiles"]),
        ("window.jobs_missing", int(not jobs)),
        ("window.whole_passes_missing", int(record["passes"] < 1 and not record["traced"])),
    ]
    return numbers, list(map(any, zip(changed, unfinished, miscounted)))


def decide(record: dict, expect_platform: str = "tpu") -> dict:
    """`correct`, `attempted`, `failed`, and every number beside its limit."""
    timed, bad_jobs = job_numbers(record, expect_platform)
    job = record["checked"]
    checked, checked_s = [("checked.job_missing", int(job is None))], 0.0
    if job is not None:
        numbers, checked_s = reference_numbers("checked", record, job["events"], job)
        checked += numbers
        bad_jobs[0] = bad_jobs[0] or any(v for _, v in numbers)  # it is the first timed job
    parity, parity_s = reference_numbers(
        "parity", record, record["parity_events"], record["parity"])
    parity.append(("parity.not_at_end.cores", record["parity"]["not_at_end"]))
    numbers = checked + parity + timed
    return {
        "correct": not any(v for _, v in numbers),
        "attempted": len(record["jobs"]) + len(record["raised"]) + 1,
        "failed": len(record["raised"]) + sum(bad_jobs) + int(any(v for _, v in parity)),
        "lines": [f"[check] {name} = {value} (limit 0)" for name, value in numbers],
        "reference_s": checked_s + parity_s,
        "checked_trace": None if job is None else job["trace"],
    }
