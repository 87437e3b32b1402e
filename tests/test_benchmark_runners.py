"""The benchmark's tests of its runners (`benchmark/tests/test_runners.py`:
the seam in `measure.run_cell`, `runners/solo.py` against the record the
harness gave before the move, `runners/fleet.py` element by element
against a solo `Engine`, `check.decide` on a job of many machines), held
in tier-1 as `tests/test_benchmark_forks.py` holds its two: a change to
`sim/fleet.py` or `sim/engine.py` that leaves a runner behind fails
`pytest tests/`, not only `pytest benchmark/tests`.

Twelve of its thirteen are imported as they stand. The thirteenth says by
its name what it waited for (`..._until_its_engine_commits_samples`):
since PR 43 `FleetEngine.run` commits one sample a job, so its first
assertion is false, and its twin below expects what is now true. The file
is the benchmark's and stays as it is until a `benchmark` PR (PERF.md
section 7).

Last, the runner the committed fleet cell names, `runners/fleet_sampled.py`:
`runners/fleet.py`'s job, each held to the one sample its run committed."""

import numpy as np
import pytest

from benchmark_modules import load_benchmark_tests

_theirs = load_benchmark_tests("test_runners")
tiny_root, ticking = _theirs.tiny_root, _theirs.ticking  # their fixtures

test_solo_gives_the_record_the_harness_gave_before_the_move = \
    _theirs.test_solo_gives_the_record_the_harness_gave_before_the_move
test_the_harness_imports_no_engine = _theirs.test_the_harness_imports_no_engine
test_a_runner_is_found_by_name_or_refused_before_anything_compiles = \
    _theirs.test_a_runner_is_found_by_name_or_refused_before_anything_compiles
test_every_element_of_a_fleet_job_equals_a_solo_engine = \
    _theirs.test_every_element_of_a_fleet_job_equals_a_solo_engine
test_the_dict_space_table_equals_apply_overrides_for_every_knob = \
    _theirs.test_the_dict_space_table_equals_apply_overrides_for_every_knob
test_a_fleet_run_is_correct_as_built = _theirs.test_a_fleet_run_is_correct_as_built
test_the_drawn_elements_differ_with_the_seed = _theirs.test_the_drawn_elements_differ_with_the_seed
test_one_element_patched_is_not_correct_where_it_is_drawn = \
    _theirs.test_one_element_patched_is_not_correct_where_it_is_drawn
test_selfchecks_control_reaches_a_fleet = _theirs.test_selfchecks_control_reaches_a_fleet
test_a_broken_fleet_is_not_correct = _theirs.test_a_broken_fleet_is_not_correct


def test_sample_readers_find_a_fleets_own_samples(tiny_root, ticking):
    """The tier-1 twin of the benchmark's
    `test_sample_readers_find_nothing_on_a_fleet_until_its_engine_commits_samples`."""
    import cells
    import measure
    from primesim_tpu.obs import process_store
    from primesim_tpu.sim.fleet import FleetEngine
    from primesim_tpu.stats.counters import stat_totals
    from primesim_tpu.trace.format import Trace

    before = process_store().seq
    record, verdict = _theirs._decide(tiny_root, _theirs.CELL_FLEET, 5)
    assert verdict["correct"] is True
    jobs = record["jobs"]
    # one sample a job, the warm-up's dispatch none: the parity job's, then the window's
    assert process_store().seq == before + 1 + len(jobs)
    samples = process_store().samples()[-len(jobs):]
    assert [s["label"] for s in samples] == ["fleet"] * len(jobs)
    assert [s["steps"] for s in samples] == [j["steps"] for j in jobs]
    assert samples[0]["caps"]["element_steps"] == [el["steps"] for el in jobs[0]["elements"]]
    assert samples[0]["caps"]["n_cores"] == 4 * 16 and samples[0]["caps"]["elements"] == 4
    assert samples[0]["deltas"]["instructions"] == jobs[0]["instructions"]

    readers = {n: cells.load_metric(n) for n in
               ("host_dispatch_ms_job", "host_readback_ms_job", "arb_win_pct", "slot_active_pct",
                "fleet_elem_ms_step", "fleet_frozen_pct")}
    got = {n: read(record, None) for n, read in readers.items()}
    assert all(v is not None for v in got.values()), got
    assert got["host_dispatch_ms_job"] > 0 and got["host_readback_ms_job"] > 0
    # the window is whole passes over the panel's two traces: a fleet on each, recounted
    _, run, cfg, _, _ = _theirs._tiny(tiny_root)
    spec = cells.load_cell(_theirs.CELL_FLEET, root=tiny_root)
    ovs = run["fleet"]["overrides"]
    served = retries = active = lanes = live = ran = 0
    for seed in spec["traffic"]["panel_seeds"]:
        ev = cells.load_generator("fft_like", tiny_root)(16, seed, **spec["traffic"]["args"])
        fleet = FleetEngine(cfg, [Trace(ev, measure._lengths(ev))] * len(ovs),
                            ovs, chunk_steps=run["chunk_steps"])
        fleet.run()
        c = {k: int(v.sum()) for k, v in fleet.counters.items()}
        served += c["l1_read_misses"] + c["l1_write_misses"] + c["upgrades"]
        retries += c["retries"]
        active += stat_totals(fleet.step_stats)["slot_active"]
        lanes += len(ovs) * 16 * int(fleet.steps_run.max())
        live += int(fleet.steps_run.sum())
        ran += len(ovs) * int(fleet.steps_run.max())
    assert got["arb_win_pct"] == pytest.approx(100 * served / (served + retries))
    assert got["slot_active_pct"] == pytest.approx(100 * active / lanes)
    assert got["fleet_frozen_pct"] == pytest.approx(100 * (1 - live / ran)) and \
        0 < got["fleet_frozen_pct"] < 100  # the `quantum` 100 element ends at another step
    steps = sum(j["steps"] for j in jobs)
    assert got["fleet_elem_ms_step"] == pytest.approx(
        1e3 * sum(j["seconds"] for j in jobs) / steps / 4)
    assert np.isfinite(list(got.values())).all()


def test_the_sampled_runner_is_the_fleets_job_held_to_its_one_sample(tiny_root, monkeypatch):
    """`runners/fleet_sampled.py` (the committed cell `rung2.sweep-b16`'s): the
    record `runners/fleet.py` gives, and no record where the run committed no
    sample, though an equal job's sample is the store's last."""
    import cells
    import primesim_tpu.sim.fleet as program_fleet

    _, run, cfg, trace, ev = _theirs._tiny(tiny_root)
    run = {**run, "fleet": {"overrides": _theirs.FLEET_OVERRIDES[:2]}}
    sampled, plain = cells.load_runner("fleet_sampled"), cells.load_runner("fleet")
    job = sampled.run_job(cfg, run, trace, ev, None, None)
    theirs = plain.run_job(cfg, run, trace, ev, None, None)
    assert set(job) == set(theirs) and job["digest"] == theirs["digest"]
    assert [el["digest"] for el in job["elements"]] == [el["digest"] for el in theirs["elements"]]
    # the parent of PR 43: a fleet that runs, and commits nothing
    monkeypatch.setattr(program_fleet, "commit_job", lambda *a, **kw: None)
    with pytest.raises(sampled.NoJobSample, match="0 committed"):
        sampled.run_job(cfg, run, trace, ev, None, None)
    assert plain.run_job(cfg, run, trace, ev, None, None)["digest"] == job["digest"]


def test_the_sampled_runner_refuses_before_it_compiles(monkeypatch):
    """A program whose `sim/fleet.py` has no builder of job samples in hand
    is refused by the warm-up, before a `FleetEngine` is built."""
    import cells
    import primesim_tpu.sim.fleet as program_fleet

    sampled = cells.load_runner("fleet_sampled")

    def built(*a, **kw):
        raise AssertionError("the warm-up went on to build a fleet")

    monkeypatch.setattr(program_fleet, "FleetEngine", built)
    monkeypatch.delattr(program_fleet, "commit_job")
    with pytest.raises(sampled.NoJobSample, match="commit_job"):
        sampled.warm_up(None, {"chunk_steps": 8, "fleet": {"overrides": [{}]}}, None, None, False)
