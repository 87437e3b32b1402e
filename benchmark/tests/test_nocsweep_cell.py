"""The committed cell `rung3.nocsweep-b4`: its configuration
`rung3-nocsweep-b4` is `rung3`'s machine letter for letter with four
overrides written out (`link_lat` {1, 2} x `dram_service` {0, 25}), its
traffic is the first trace of `fft-m16`'s panel alone, it runs on one chip
through `runners/fleet_sampled.py`, and the three metrics it brings list it
and nothing else and read a fleet's scopes an element. Entries are found by
name: what a later PR appends moves none of this."""

import json
import os

import numpy as np
import pytest

import cells
import reference
import trafficgen
from conftest import ROOT

CELL = "rung3.nocsweep-b4"
CONFIG = "rung3-nocsweep-b4"
NEW = ("fleet_rank_noc_ms_elem_step", "fleet_noc_ms_elem_step", "fleet_dram_ms_elem_step")
GRID = [{}, {"link_lat": 2}, {"dram_service": 25}, {"link_lat": 2, "dram_service": 25}]


@pytest.fixture(scope="module")
def spec():
    return cells.load_cell(CELL)


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_machine_is_rung_3s_letter_for_letter(spec):
    rung3 = cells.load_cell("rung3.fft-m16")["config"]
    assert spec["config"]["machine"] == rung3["machine"]
    with open(os.path.join(ROOT, "configs", "rung3_1024core_o3.json")) as f:
        assert spec["config"]["machine"] == {**json.load(f), "dram_service": 0}
    assert spec["reference"] is None and cells.load_reference(None) is reference
    # the stock reference takes every element's machine: `run.py` builds one before anything compiles
    runner = cells.load_runner(spec["runner"])
    for machine in runner.element_machines(spec["config"]["machine"], spec["config"]["run"]):
        reference.RefSim(machine, np.full((1024, 1, 4), trafficgen.EV_END, np.int32))
    assert spec["config"]["run"] == {
        **rung3["run"], "runner": "fleet_sampled",
        "fleet": {"checked_elements": 1, "overrides": GRID}}
    assert sorted(spec["config"]["reduced"]) == ["checked_elements", "chunk_steps", "elements",
                                                 "trace_points"]
    assert "guarantee" in spec["config"] and "grid" in spec["config"]["assumed"]
    assert "lax.while_loop" in spec["config"]["what"]  # the loop as it is since PR 45


def test_the_overrides_are_the_grid_and_no_twin(spec):
    ovs = spec["config"]["run"]["fleet"]["overrides"]
    assert ovs == GRID
    runner = cells.load_runner(spec["runner"])
    machines = runner.element_machines(spec["config"]["machine"], spec["config"]["run"])
    assert machines[0] == spec["config"]["machine"]
    knobs = [(m["noc"]["link_lat"], m["dram_service"]) for m in machines]
    assert knobs == [(1, 0), (2, 0), (1, 25), (2, 25)]  # link_lat {1, 2} x dram_service {0, 25}
    assert len({json.dumps(m, sort_keys=True) for m in machines}) == 4
    for got in machines:  # no knob but the two, no width of the machine
        assert {k: v for k, v in got.items() if k not in ("noc", "dram_service")} == \
            {k: v for k, v in machines[0].items() if k not in ("noc", "dram_service")}
        assert {**got["noc"], "link_lat": 1} == machines[0]["noc"]


def test_traffic_is_the_first_trace_of_fft_m16s_panel(spec):
    t, panel = spec["traffic"], cells.load_cell("rung3.fft-m16")["traffic"]
    for key in ("generator", "args", "parity_args", "fold"):
        assert t[key] == panel[key], key
    assert t["panel_seeds"] == panel["panel_seeds"][:1] == [404]
    assert t["args"]["points_per_core"] * spec["config"]["machine"]["n_cores"] == 2 ** 16
    assert "panel_seeds" in t["assumed"]
    # one trace: every seed runs it first, so the checked job is the same work in every run
    assert trafficgen.panel_order(t, 4700000001) == [0]


def test_the_cells_entries(spec, bench):
    assert spec["cell"] == {"name": CELL, "config": CONFIG, "traffic": "fft-m16-s404", "chips": 1,
                            "why": spec["cell"]["why"]}
    assert spec["runner"] == "fleet_sampled"
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert entry["file"] == "benchmark/configs/rung3-nocsweep-b4.json"
    assert entry["source"] == spec["config"]["source"] and len(entry["source"]) <= 200
    for word in ("PriME", "router", "memory-controller", "primetpu sweep --vary",
                 "configs/rung3_1024core_o3.json"):
        assert word in entry["source"]
    assert entry["reduced"] == ["chunk_steps", "trace_points", "elements", "checked_elements"]
    assert [w["name"] for w in bench["workloads"] if w["config"] == CONFIG] == [CELL]
    assert {m["name"] for m in spec["end_to_end"]} == {"sim_mips", "hbm_peak_gb", "setup_s"}
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 2


def test_the_new_metrics_list_this_cell_and_only_it(spec, bench):
    mine = [m for m in bench["per_layer"] if m["name"] in NEW]
    assert [m["name"] for m in mine] == list(NEW)
    layers = {"fleet_rank_noc_ms_elem_step": "ranking", "fleet_noc_ms_elem_step": "step",
              "fleet_dram_ms_elem_step": "step"}
    for m in mine:
        assert (m["workloads"], m["moves"], m["better"], m["unit"], m["source"]) == \
            ([CELL], "sim_mips", "lower", "ms", "device_trace")
        assert m["layer"] == layers[m["name"]]
        assert callable(cells.load_metric(m["name"]))
    names = {m["name"] for m in spec["per_layer"]}
    # every reader without a list reports here too; the closed lists stay closed
    assert set(NEW) | {"arb_win_pct", "host_dispatch_ms_job", "host_readback_ms_job",
                       "step_roofline", "step_ms", "ins_per_step", "device_idle_pct",
                       "ph_local_ms_step", "ph_probe_ms_step", "ph_arb_ms_step", "ph_dir_ms_step",
                       "ph_commit_ms_step", "ph_cover_pct", "ph_mixed_pct", "job_s_max",
                       "tracegen_s", "compile_s"} == names
    for other in bench["workloads"]:
        if other["name"] != CELL:
            assert not set(NEW) & {m["name"] for m in cells.load_cell(other["name"])["per_layer"]}


# ---- the three readers the cell brings --------------------------------------

def _sample(steps, elements=0):
    """A job sample as the program's `engine.commit_job` records one: a
    fleet's (`caps.elements`), or a solo engine's."""
    from primesim_tpu.obs import process_store

    caps = {"n_cores": 64 * max(elements, 1), "local_run_len": 8, "sort_entries": 0}
    if elements:
        caps.update(elements=elements, element_steps=[steps] * elements)
    process_store().record(0.0, "fleet" if elements else "engine", steps, 1.0,
                           {"instructions": 1}, caps=caps,
                           phases={"init": 0.0, "dispatch": 0.001, "wait": 1.0, "readback": 0.001})


_IN = "jit(fleet_run_loop)/while/body/vmap()/while/body/closed_call"
OPS = {f"sort.1 {_IN}/s.noc/rank/sort": [0.30, 200],
       f"fusion.2 {_IN}/s.noc/rank/reduce_window_max": [0.10, 200],
       f"fusion.3 {_IN}/s.noc/concatenate": [0.06, 200],       # the walk outside its rank
       "fusion.4 (unnamed)/s.noc/(held)": [0.02, 200],          # billed by what it holds
       f"fusion.5 {_IN}/s.noc/stat/add": [0.02, 200],
       f"sort.6 {_IN}/s.dram/rank/sort": [0.04, 200],
       f"fusion.7 {_IN}/s.dram/gather": [0.01, 200],
       f"fusion.8 {_IN}/s.local/gather": [0.40, 200],
       "copy-done": [0.05, 1]}


def test_the_three_readers_read_a_fleets_scopes_an_element():
    rank, noc, dram = (cells.load_metric(n) for n in NEW)
    _sample(200, elements=4)
    run = {"jobs": [{"steps": 200, "seconds": 1.0, "trace": 0, "traced": True,
                     "elements": [{}] * 4}]}
    trace = {"ops": OPS}
    assert rank(run, trace) == pytest.approx(1e3 * 0.40 / 200 / 4)
    assert noc(run, trace) == pytest.approx(1e3 * 0.50 / 200 / 4)
    assert dram(run, trace) == pytest.approx(1e3 * 0.05 / 200 / 4)
    # against the solo readers of the same scopes: theirs, over the machines
    for fleets, solo in zip((rank, noc, dram),
                            ("rank_noc_ms_step", "ph_noc_ms_step", "ph_dram_ms_step")):
        assert fleets(run, trace) == pytest.approx(cells.load_metric(solo)(run, trace) / 4)
    # no trace, or no job under the profiler: nothing to read, no raise
    for read in (rank, noc, dram):
        assert read(run, None) is None
        assert read({"jobs": [dict(run["jobs"][0], traced=False)]}, trace) is None
    # a machine without the scopes (rung 2's fleet has neither walk nor queue)
    plain = {"ops": {k: v for k, v in OPS.items() if "s.noc" not in k and "s.dram" not in k}}
    assert rank(run, plain) is None and noc(run, plain) is None and dram(run, plain) is None


def test_the_three_readers_find_nothing_on_a_solo_run_or_without_a_sample():
    rank, noc, dram = (cells.load_metric(n) for n in NEW)
    trace = {"ops": OPS}
    # a program whose fleet commits no sample of this job (the steps differ from the store's last)
    _sample(200, elements=4)
    other = {"jobs": [{"steps": 201, "seconds": 1.0, "traced": True, "elements": [{}] * 4}]}
    assert rank(other, trace) is None and noc(other, trace) is None and dram(other, trace) is None
    # a solo engine's sample names no elements: `ph_noc_ms_step`'s to say
    _sample(40)
    solo = {"jobs": [{"steps": 40, "seconds": 0.5, "traced": True}]}
    assert cells.load_metric("ph_noc_ms_step")(solo, trace) is not None
    assert rank(solo, trace) is None and noc(solo, trace) is None and dram(solo, trace) is None
    assert rank({"jobs": []}, trace) is None
