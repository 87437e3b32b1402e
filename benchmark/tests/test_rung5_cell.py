"""The committed cell `rung5.fft-m18-16k`: it loads, it is held to a plain
reference of its own, its machine is rung 5's file letter for letter, and
its two readers read what they say (on the CPU, nothing simulated at the
cell's size but the reference's first step of the parity trace)."""

import json
import os

import numpy as np
import pytest

import cells
import reference
import trafficgen
from conftest import ROOT

CELL = "rung5.fft-m18-16k"


@pytest.fixture(scope="module")
def spec():
    return cells.load_cell(CELL)


def test_cell_loads_and_states_rung_5(spec):
    assert spec["cell"]["chips"] == 1 and spec["config"]["run"] == {
        "chunk_steps": 8, "step_impl": "xla", "devices": 1}
    with open(os.path.join(ROOT, "configs", "rung5_16384core_wafer.json")) as f:
        rung5 = json.load(f)
    # the ladder's file, plus the two keys the reference contract makes a file state
    assert spec["config"]["machine"] == {**rung5, "dram_queue": False, "dram_service": 0}
    assert spec["config"]["machine"]["sharer_group"] == 64
    t = spec["traffic"]
    assert (t["generator"], t["args"], t["parity_args"], t["panel_seeds"]) == (
        "fft_like", {"n_phases": 4, "points_per_core": 16, "ins_per_mem": 8},
        {"n_phases": 2, "points_per_core": 4}, [404])
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"] if c["name"] == "rung5")
    assert sorted(entry["reduced"]) == sorted(spec["config"]["reduced"])
    names = [m["name"] for m in spec["per_layer"]]
    assert {"ph_dirgrp_ms_step", "inval_pki", "ph_dir_ms_step", "step_roofline"} <= set(names)
    assert not {"ph_noc_ms_step", "ph_dram_ms_step", "rank_noc_ms_step",
                "collective_ms_step"} & set(names)
    for name in names:
        assert callable(cells.load_metric(name))


def test_cell_is_held_to_the_coarse_directory_reference(spec):
    assert spec["reference"] == "coarse_dir"
    own = cells.load_reference(spec["reference"])
    assert issubclass(own.RefSim, reference.RefSim) and own.RefSim is not reference.RefSim
    machine = spec["config"]["machine"]
    with pytest.raises(reference.UnsupportedMachine):  # the stock one refuses the key
        reference.RefSim(machine, np.full((16384, 1, 4), trafficgen.EV_END, np.int32))
    ev = trafficgen.make_trace(spec["traffic"], machine["n_cores"], 2**31 + 7, parity=True)
    assert ev.shape == (16384, 19, 4)
    ref = own.RefSim(machine, ev)
    assert (ref.C, ref.B, ref.G, ref.n_tiles) == (16384, 4096, 64, 16384)
    ref.step()  # every core misses on its first line
    assert ref.step_count == 1 and sum(ref.counters["llc_misses"]) > 0


def test_inval_pki_is_a_count_of_the_checked_job():
    read = cells.load_metric("inval_pki")
    counters = {"instructions": np.array([600, 400]), "invalidations": np.array([200, 62])}
    assert read({"checked": {"counters": counters}}, None) == 262.0
    assert read({"checked": None}, None) is None
    assert read({"checked": {"counters": {k: v * 0 for k, v in counters.items()}}}, None) is None


def test_ph_dirgrp_reads_the_group_scope_and_nothing_off_the_chip():
    read, whole = cells.load_metric("ph_dirgrp_ms_step"), cells.load_metric("ph_dir_ms_step")
    run = {"jobs": [{"traced": True, "steps": 4}]}
    assert read(run, None) is None
    trace = {"ops": {
        "fusion.1 jit(run_loop)/s.dir/grp/reduce_max": [0.002, 4],
        "fusion.2 jit(run_loop)/s.dir/select_n": [0.001, 4],
        "fusion.3 jit(run_loop)/s.local/gather": [0.008, 4],
    }}
    assert read(run, trace) == pytest.approx(0.5)
    assert whole(run, trace) == pytest.approx(0.75)  # the group work is inside `s.dir`
    # a program without the scope (the parent, a machine with G = 1): nothing to read
    del trace["ops"]["fusion.1 jit(run_loop)/s.dir/grp/reduce_max"]
    assert read(run, trace) is None
