"""The committed cell `rung3.nocsweep-b16.x4`: its configuration
`rung3-nocsweep-b16-x4` is `rung3-nocsweep-b4`'s machine key for key with
sixteen overrides written out (`llc_lat` {12, 16} x `dram_lat` {100, 80}
outside, that cell's `link_lat` {1, 2} x `dram_service` {0, 25} inside, so
elements 0-3 are its four machines), on that cell's traffic, on four chips
through `runners/fleet_sampled.py`; and the four metrics it brings list it
and nothing else and read the `caps` a fleet on several chips commits.
Entries are found by name: what a later PR appends moves none of this."""

import json
import os

import numpy as np
import pytest

import cells
import reference
import trafficgen
from conftest import ROOT

CELL = "rung3.nocsweep-b16.x4"
CONFIG = "rung3-nocsweep-b16-x4"
CONTROL = "rung3.nocsweep-b4"
NEW = ("fleet_chip_wait_pct", "fleet_chip_elem_ms_step", "fleet_collective_ms_step",
       "fleet_build_ms_job")
INNER = [{}, {"link_lat": 2}, {"dram_service": 25}, {"link_lat": 2, "dram_service": 25}]
OUTER = [{}, {"dram_lat": 80}, {"llc_lat": 16}, {"llc_lat": 16, "dram_lat": 80}]  # a chip each
GRID = [{**outer, **inner} for outer in OUTER for inner in INNER]


@pytest.fixture(scope="module")
def spec():
    return cells.load_cell(CELL)


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_machine_is_the_one_chip_cells_key_for_key(spec):
    control = cells.load_cell(CONTROL)["config"]
    assert spec["config"]["machine"] == control["machine"]
    assert list(spec["config"]["machine"]) == list(control["machine"])
    assert spec["reference"] is None and cells.load_reference(None) is reference
    # the stock reference takes every element's machine: `run.py` builds one before anything compiles
    runner = cells.load_runner(spec["runner"])
    for machine in runner.element_machines(spec["config"]["machine"], spec["config"]["run"]):
        reference.RefSim(machine, np.full((1024, 1, 4), trafficgen.EV_END, np.int32))
    assert spec["config"]["run"] == {
        **control["run"], "devices": 4,
        "fleet": {"checked_elements": 1, "overrides": GRID}}
    assert sorted(spec["config"]["reduced"]) == ["checked_elements", "chunk_steps", "elements",
                                                 "trace_points"]
    assert spec["config"]["deployment"] == \
        "one four-chip v5e host, four whole machines a chip, no machine divided"
    assert spec["config"]["guarantee"] == control["guarantee"]
    for key in ("grid", "placement", "twin", "router_lat, contention_lat"):
        assert key in spec["config"]["assumed"], key
    assert "shard_map" in spec["config"]["what"]  # the loop as it is on a mesh since PR 51


def test_the_overrides_are_the_grid_in_blocks_of_the_one_chip_cells_four(spec):
    ovs = spec["config"]["run"]["fleet"]["overrides"]
    assert ovs == GRID and len(ovs) == 16
    assert ovs[:4] == cells.load_cell(CONTROL)["config"]["run"]["fleet"]["overrides"] == INNER
    runner = cells.load_runner(spec["runner"])
    machines = runner.element_machines(spec["config"]["machine"], spec["config"]["run"])
    assert machines[0] == spec["config"]["machine"]
    knobs = [(m["llc"]["latency"], m["dram_lat"], m["noc"]["link_lat"], m["dram_service"])
             for m in machines]
    assert knobs == [(llc, dram, link, svc) for llc in (12, 16) for dram in (100, 80)
                     for link, svc in ((1, 0), (2, 0), (1, 25), (2, 25))]
    assert len({json.dumps(m, sort_keys=True) for m in machines}) == 16  # no twin
    chips = spec["cell"]["chips"]
    for chip in range(chips):  # a chip's block: one outer point, the inner grid in its order
        block = knobs[chip * 4:(chip + 1) * 4]
        assert len({k[:2] for k in block}) == 1
        assert [k[2:] for k in block] == [(1, 0), (2, 0), (1, 25), (2, 25)]
    for got in machines:  # no knob but the four, no width of the machine
        rest = lambda m: {k: v for k, v in m.items()  # noqa: E731
                          if k not in ("noc", "llc", "dram_lat", "dram_service")}
        assert rest(got) == rest(machines[0])
        assert {**got["noc"], "link_lat": 1} == machines[0]["noc"]
        assert {**got["llc"], "latency": 12} == machines[0]["llc"]


def test_traffic_is_the_one_chip_cells_file(spec):
    control = cells.load_cell(CONTROL)
    assert spec["cell"]["traffic"] == control["cell"]["traffic"] == "fft-m16-s404"
    assert spec["traffic"] == control["traffic"]
    assert trafficgen.panel_order(spec["traffic"], 4700000001) == [0]


def test_the_cells_entries(spec, bench):
    assert spec["cell"] == {"name": CELL, "config": CONFIG, "traffic": "fft-m16-s404", "chips": 4,
                            "why": spec["cell"]["why"]}
    assert spec["cell"]["chips"] == spec["config"]["run"]["devices"] == 4
    assert spec["runner"] == "fleet_sampled"
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert entry["file"] == "benchmark/configs/rung3-nocsweep-b16-x4.json"
    assert entry["source"] == spec["config"]["source"] and len(entry["source"]) <= 200
    for word in ("PriME", "router", "memory-controller", "primetpu sweep --vary", "--devices 4",
                 "configs/rung3_1024core_o3.json"):
        assert word in entry["source"]
    control = next(c for c in bench["configs"] if c["name"] == "rung3-nocsweep-b4")
    assert entry["source"] != control["source"] and entry["file"] != control["file"]
    assert entry["reduced"] == ["chunk_steps", "trace_points", "elements", "checked_elements"]
    assert [w["name"] for w in bench["workloads"] if w["config"] == CONFIG] == [CELL]
    assert {m["name"] for m in spec["end_to_end"]} == {"sim_mips", "hbm_peak_gb", "setup_s"}
    four = [w["name"] for w in bench["workloads"] if w["chips"] == 4]
    assert CELL in four and len(four) <= len(bench["workloads"]) // 2
    assert len(spec["cell"]["why"]) <= 200 and len(entry["why"]) <= 200


def test_the_new_metrics_list_this_cell_and_only_it(spec, bench):
    mine = [m for m in bench["per_layer"] if m["name"] in NEW]
    assert [m["name"] for m in mine] == list(NEW)
    want = {"fleet_chip_wait_pct": ("sharding", "program_counter", "%", "sim_mips"),
            "fleet_chip_elem_ms_step": ("step", "host_clock", "ms", "sim_mips"),
            "fleet_collective_ms_step": ("sharding", "device_trace", "ms", "sim_mips"),
            "fleet_build_ms_job": ("entry", "host_clock", "ms", "setup_s")}
    for m in mine:
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert (m["layer"], m["source"], m["unit"], m["moves"]) == want[m["name"]]
        assert (m["workloads"], m["better"]) == ([CELL], "lower")
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


# ---- the four readers the cell brings ---------------------------------------

def _sample(steps, elements=0, chip_steps=None, init=0.25):
    """A job sample as the program's `engine.commit_job` records one: a
    fleet's on `len(chip_steps)` chips, a fleet's of a program before PR 51
    (`caps` without chips), or a solo engine's."""
    from primesim_tpu.obs import process_store

    caps = {"n_cores": 64 * max(elements, 1), "local_run_len": 8, "sort_entries": 0}
    if elements:
        caps.update(elements=elements, element_steps=[steps] * elements)
    if chip_steps:
        caps.update(chips=len(chip_steps), chip_steps=list(chip_steps))
    process_store().record(0.0, "fleet" if elements else "engine", steps, 1.0,
                           {"instructions": 1}, caps=caps,
                           phases={"init": init, "dispatch": 0.001, "wait": 1.0, "readback": 0.001})


_IN = "jit(fleet_run_loop)/shard_map/while/body/vmap()/while/body/closed_call"
OPS = {f"sort.1 {_IN}/s.noc/rank/sort": [0.30, 200],
       f"fusion.8 {_IN}/s.local/gather": [0.40, 200],
       "all-reduce.3 (unnamed)": [0.02, 200],
       "all-gather-start.4 (unnamed)": [0.01, 200],
       "all-gather-done.4 (unnamed)": [0.03, 200],
       "copy-done": [0.05, 1]}
HLO = ("%all-reduce.3 = s32[8]{0} all-reduce(s32[8]{0} %p), replica_groups={}\n"
       "%all-gather-start.4 = (s32[2]{0}, s32[8]{0}) all-gather-start(s32[2]{0} %q)\n"
       "%all-gather-done.4 = s32[8]{0} all-gather-done((s32[2]{0}, s32[8]{0}) %all-gather-start.4)\n"
       "%sort.1 = s32[8]{0} sort(s32[8]{0} %r), dimensions={0}\n")


def _run(steps, elements=16, **job):
    return {"jobs": [{"steps": steps, "seconds": 2.0, "trace": 0, "traced": True,
                      "elements": [{}] * elements, **job}], "hlo_text": HLO}


def test_the_four_readers_read_a_fleet_on_several_chips():
    wait, elem, collective, build = (cells.load_metric(n) for n in NEW)
    _sample(200, elements=16, chip_steps=[200, 176, 200, 184], init=0.5)
    run = _run(200)
    # chip-steps the short chips stood done: 1 - 760 / (4 x 200)
    assert wait(run, None) == pytest.approx(100.0 * (1 - 760 / 800))
    # a chip's step over its own four machines: step_ms x chips / elements
    assert elem(run, None) == pytest.approx(1e3 * 2.0 / 200 * 4 / 16)
    assert elem(run, None) == pytest.approx(
        cells.load_metric("fleet_elem_ms_step")(run, None) * 4)
    assert build(run, None) == pytest.approx(500.0)
    # the collectives of the compiled text, their start and done halves, a step
    assert collective(run, {"ops": OPS}) == pytest.approx(1e3 * 0.06 / 200)
    assert collective(run, {"ops": OPS}) == pytest.approx(
        cells.load_metric("collective_ms_step")(run, {"ops": OPS}))
    # a text without one reads 0, and says so: `collective_ms_step` would say nothing
    clean = {"ops": {k: v for k, v in OPS.items() if "all-" not in k}}
    assert collective(run, clean) == 0.0
    assert cells.load_metric("collective_ms_step")(run, clean) is None
    # every chip as long as the longest: nothing waited
    _sample(528, elements=16, chip_steps=[528] * 4)
    assert wait(_run(528), None) == 0.0


def test_the_four_readers_find_nothing_on_one_chip_a_solo_run_or_without_a_sample():
    wait, elem, collective, build = (cells.load_metric(n) for n in NEW)
    trace = {"ops": OPS}
    # without a trace, or with no job under the profiler: the device reader has nothing
    _sample(200, elements=16, chip_steps=[200] * 4)
    assert collective(_run(200), None) is None
    assert collective(_run(200, traced=False), trace) is None
    # a program whose fleet commits no sample of this job (the steps differ from the store's last)
    for read in (wait, elem, collective, build):
        assert read(_run(201), trace) is None
        assert read({"jobs": []}, trace) is None
    # a fleet on one chip (the one-chip cells): the three that read chips say nothing
    _sample(200, elements=4, chip_steps=[200])
    one = _run(200, elements=4)
    assert wait(one, trace) is None and elem(one, trace) is None and collective(one, trace) is None
    assert build(one, trace) == pytest.approx(250.0)
    # a program before PR 51: its fleet's `caps` say nothing of chips
    _sample(200, elements=4)
    assert wait(one, trace) is None and elem(one, trace) is None and collective(one, trace) is None
    # a solo engine's sample names no elements
    _sample(40)
    solo = {"jobs": [{"steps": 40, "seconds": 0.5, "traced": True}], "hlo_text": HLO}
    for read in (wait, elem, collective, build):
        assert read(solo, trace) is None
