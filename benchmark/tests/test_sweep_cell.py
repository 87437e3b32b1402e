"""The committed cell `rung2.sweep-b16`: its configuration `rung2-sweep-b16`
is BASELINE rung 2's machine letter for letter with sixteen overrides
written out (the grid regenerated here), its traffic is SPLASH-2 FFT
`-m16` over the 256 cores, it runs on one chip through
`runners/fleet_sampled.py` (`runners/fleet.py`'s job, held to the job
sample the program commits for it), and the three metrics it brings list
it and nothing else. Entries are found by name: what a later PR appends
moves none of this."""

import json
import os

import numpy as np
import pytest

import cells
import reference
import trafficgen
from conftest import ROOT

CELL = "rung2.sweep-b16"
CONFIG = "rung2-sweep-b16"
NEW = ("fleet_carry_ms_step", "fleet_elem_ms_step", "fleet_frozen_pct")


@pytest.fixture(scope="module")
def spec():
    return cells.load_cell(CELL)


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def grid() -> list:
    """Tentpole 3 of ISSUE 43: quantum {1000, 500} x llc_lat {10, 12, 14, 16}
    x dram_lat {80, 100} in the order i = 1..15 gives, element 0 the machine
    as it stands in the corner (1000, 10, 80)'s place."""
    return [{}] + [{"quantum": 500 if i % 2 else 1000, "llc_lat": 10 + 2 * (i // 2 % 4),
                    "dram_lat": 80 + 20 * (i // 8 % 4)} for i in range(1, 16)]


def test_the_machine_is_rung_2s_letter_for_letter(spec):
    with open(os.path.join(ROOT, "configs", "rung2_256core_parsec.json")) as f:
        rung2 = json.load(f)
    assert spec["config"]["machine"] == {**rung2, "dram_queue": False, "dram_service": 0}
    assert spec["reference"] is None and cells.load_reference(None) is reference
    # the stock reference takes the machine: `run.py` builds one before anything compiles
    reference.RefSim(rung2 | {"dram_queue": False, "dram_service": 0},
                     np.full((256, 1, 4), trafficgen.EV_END, np.int32))
    assert spec["config"]["run"] == {
        "chunk_steps": 8, "step_impl": "xla", "devices": 1, "runner": "fleet_sampled",
        "fleet": {"checked_elements": 2, "overrides": grid()}}
    assert sorted(spec["config"]["reduced"]) == ["checked_elements", "chunk_steps", "elements",
                                                 "workload"]
    assert "guarantee" in spec["config"] and "grid" in spec["config"]["assumed"]


def test_the_overrides_are_the_grid_and_one_twin(spec):
    ovs = spec["config"]["run"]["fleet"]["overrides"]
    assert ovs == grid() and len(ovs) == 16
    written = {(o["quantum"], o["llc_lat"], o["dram_lat"]) for o in ovs[1:]}
    whole = {(q, l, d) for q in (1000, 500) for l in (10, 12, 14, 16) for d in (80, 100)}
    assert whole - written == {(1000, 10, 80)} and len(written) == 15
    runner = cells.load_runner(spec["runner"])
    machines = runner.element_machines(spec["config"]["machine"], spec["config"]["run"])
    texts = [json.dumps(m, sort_keys=True) for m in machines]
    assert len(set(texts)) == 15  # fifteen distinct machines and one twin:
    assert texts[0] == texts[10] and ovs[10] == {"quantum": 1000, "llc_lat": 12, "dram_lat": 100}
    assert machines[0] == spec["config"]["machine"]
    m = machines[7]
    assert (m["quantum"], m["llc"]["latency"], m["dram_lat"]) == (500, 16, 80)
    for got, ov in zip(machines, ovs):  # no knob but the three, no width of the machine
        assert {k: v for k, v in got.items() if k not in ("quantum", "llc", "dram_lat")} == \
            {k: v for k, v in machines[0].items() if k not in ("quantum", "llc", "dram_lat")}
        assert {**got["llc"], "latency": 12} == machines[0]["llc"]


def test_traffic_is_fft_m16_over_256_cores(spec):
    t = spec["traffic"]
    assert (t["generator"], t["panel_seeds"], t["fold"]) == ("fft_like", [404], True)
    assert t["args"] == {"n_phases": 4, "points_per_core": 256, "ins_per_mem": 8}
    assert t["parity_args"] == {"n_phases": 2, "points_per_core": 8}
    assert t["args"]["points_per_core"] * spec["config"]["machine"]["n_cores"] == 2 ** 16
    assert "panel_seeds" in t["assumed"]


def test_the_cells_entries(spec, bench):
    assert spec["cell"] == {"name": CELL, "config": CONFIG, "traffic": "fft-m16-256", "chips": 1,
                            "why": spec["cell"]["why"]}
    assert spec["runner"] == "fleet_sampled"
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert entry["file"] == "benchmark/configs/rung2-sweep-b16.json"
    assert entry["source"] == spec["config"]["source"] and len(entry["source"]) <= 200
    for word in ("PriME", "quantum", "primetpu sweep --vary", "rung 2"):
        assert word in entry["source"]
    assert entry["reduced"] == ["chunk_steps", "workload", "elements", "checked_elements"]
    # the one cell of its configuration, and the one cell on a runner of fleets
    assert [w["name"] for w in bench["workloads"] if w["config"] == CONFIG] == [CELL]
    assert {m["name"] for m in spec["end_to_end"]} == {"sim_mips", "hbm_peak_gb", "setup_s"}


def test_the_runner_is_the_fleets_and_refuses_a_program_without_job_samples(spec, monkeypatch):
    """`runners/fleet_sampled.py` hands `runners/fleet.py` through, and its
    warm-up refuses, before anything compiles, a program whose `sim/fleet.py`
    has no builder of job samples (every one before PR 43)."""
    import primesim_tpu.sim.fleet as program_fleet

    runner, fleet = cells.load_runner(spec["runner"]), cells.load_runner("fleet")
    run, machine = spec["config"]["run"], spec["config"]["machine"]
    assert runner.element_machines(machine, run) == fleet.element_machines(machine, run)
    assert runner.KNOB_PATHS == fleet.KNOB_PATHS

    def no_compile(*a, **k):
        raise AssertionError("the warm-up went on to compile")

    monkeypatch.setattr(program_fleet, "fleet_run_loop", no_compile)
    monkeypatch.setattr(program_fleet, "FleetEngine", no_compile)
    monkeypatch.delattr(program_fleet, "commit_job")
    with pytest.raises(runner.NoJobSample, match="commit_job"):
        runner.warm_up(None, run, None, None, False)


def test_the_new_metrics_list_this_cell_and_only_it(spec, bench):
    mine = [m for m in bench["per_layer"] if m["name"] in NEW]
    assert [m["name"] for m in mine] == list(NEW)
    sources = {"fleet_carry_ms_step": "device_trace", "fleet_elem_ms_step": "host_clock",
               "fleet_frozen_pct": "program_counter"}
    for m in mine:
        assert (m["workloads"], m["moves"], m["better"]) == ([CELL], "sim_mips", "lower")
        assert m["source"] == sources[m["name"]]
        assert callable(cells.load_metric(m["name"]))
    assert {m["unit"] for m in mine} == {"ms", "%"}
    names = {m["name"] for m in spec["per_layer"]}
    # every reader without a list reports here too, the sample readers among them
    assert set(NEW) | {"arb_win_pct", "host_dispatch_ms_job", "host_readback_ms_job",
                       "step_roofline", "step_ms", "ins_per_step", "device_idle_pct",
                       "ph_local_ms_step", "ph_probe_ms_step", "ph_arb_ms_step", "ph_dir_ms_step",
                       "ph_commit_ms_step", "ph_cover_pct", "ph_mixed_pct", "job_s_max",
                       "tracegen_s", "compile_s"} == names
    for other in bench["workloads"]:
        if other["name"] != CELL:
            assert not set(NEW) & {m["name"] for m in cells.load_cell(other["name"])["per_layer"]}
