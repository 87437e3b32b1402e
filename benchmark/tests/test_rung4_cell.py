"""The committed cell `rung4.fft-m18-4k.x4`: it loads, it is held to a plain
reference of its own, its machine is rung 4's file letter for letter, and
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

CELL = "rung4.fft-m18-4k.x4"


@pytest.fixture(scope="module")
def spec():
    return cells.load_cell(CELL)


def test_cell_loads_and_states_rung_4(spec):
    assert spec["cell"]["chips"] == 4 and spec["config"]["run"] == {
        "chunk_steps": 8, "step_impl": "xla", "devices": 4}
    with open(os.path.join(ROOT, "configs", "rung4_4096core_biglittle.json")) as f:
        rung4 = json.load(f)
    # the ladder's file, plus the two keys the reference contract makes a file state
    assert spec["config"]["machine"] == {**rung4, "dram_queue": False, "dram_service": 0}
    assert spec["config"]["machine"]["sharer_chunk_words"] == 8
    assert spec["config"]["machine"]["core"]["cpi_pattern"] == [1, 1, 1, 1, 3, 3, 3, 3]
    t = spec["traffic"]
    assert (t["generator"], t["args"], t["parity_args"], t["panel_seeds"]) == (
        "fft_like", {"n_phases": 4, "points_per_core": 16, "ins_per_mem": 8},
        {"n_phases": 2, "points_per_core": 4}, [404])
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == "rung4-x4")
    assert sorted(entry["reduced"]) == sorted(spec["config"]["reduced"])
    assert entry["source"] == spec["config"]["source"]
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= len(bench["workloads"]) // 2
    names = [m["name"] for m in spec["per_layer"]]
    assert {"ph_dirchunk_ms_step", "collective_dirm_ms_step", "ph_dir_ms_step",
            "step_roofline", "device_idle_pct", "ph_cover_pct"} <= set(names)
    assert not {"ph_noc_ms_step", "ph_dram_ms_step", "rank_noc_ms_step", "ph_dirgrp_ms_step",
                "inval_pki", "collective_ms_step"} & set(names)
    for name in names:
        assert callable(cells.load_metric(name))


def test_cell_is_held_to_the_biglittle_reference(spec):
    assert spec["reference"] == "biglittle"
    own = cells.load_reference(spec["reference"])
    assert issubclass(own.RefSim, reference.RefSim) and own.RefSim is not reference.RefSim
    machine = spec["config"]["machine"]
    with pytest.raises(reference.UnsupportedMachine):  # the stock one refuses both keys
        reference.RefSim(machine, np.full((4096, 1, 4), trafficgen.EV_END, np.int32))
    ev = trafficgen.make_trace(spec["traffic"], machine["n_cores"], 2**31 + 7, parity=True)
    assert ev.shape == (4096, 19, 4)
    ref = own.RefSim(machine, ev)
    assert (ref.C, ref.B, ref.n_tiles) == (4096, 4096, 4096)
    assert ref.cpi[:9] == [1, 1, 1, 1, 3, 3, 3, 3, 1] and len(ref.cpi) == 4096
    ref.step()  # every core misses on its first line
    assert ref.step_count == 1 and sum(ref.counters["llc_misses"]) > 0
    full = trafficgen.make_trace(spec["traffic"], machine["n_cores"], 404)
    assert full.shape == (4096, 137, 4) and trafficgen.total_instructions(full) == 5288167


RUN = {"jobs": [{"traced": True, "steps": 4}], "hlo_text": "\n".join([
    "  %all-reduce.38 = s32[4096,9,1152]{2,1,0} all-reduce(%fusion.1), replica_groups={}",
    "  %all-gather.89 = s32[4096,1152]{1,0} all-gather(%fusion.2), dimensions={0}",
    "  %all-reduce.43 = s32[2097152]{0} all-reduce(%fusion.3), replica_groups={}",
    "  %all-gather-start.7 = s32[4096,1]{0,1} all-gather-start(%fusion.4), dimensions={0}",
    "  %fusion.9 = s32[4096]{0} fusion(%x), kind=kLoop, calls=%fc",
])}
# labels as `xplane.reduce` writes them (the instruction, then its `op_name` without the
# loops' frames); `all-reduce.38` and `all-reduce.43` as the cell's traced run on the chip had them
OPS = {
    "all-reduce.38 jit(run_loop)/s.local/gather": [0.020, 4],
    "all-gather.89 jit(run_loop)/s.commit/scatter-add": [0.004, 4],
    "all-reduce.43 jit(run_loop)/s.arb/scatter-min": [0.002, 4],
    "all-gather-start.7 jit(run_loop)/s.probe/gather": [0.001, 4],
    "all-reduce.35 jit(run_loop)/s.chunk/gather": [0.001, 1],
    "fusion.9 jit(run_loop)/s.dir/chunk/reduce_max": [0.002, 64],
    "fusion.10 jit(run_loop)/s.dir/select_n": [0.001, 4],
    "fusion.11 jit(run_loop)/s.local/gather": [0.008, 4],
}


def test_ph_dirchunk_reads_the_chunk_scope_and_nothing_off_the_chip():
    read, whole = cells.load_metric("ph_dirchunk_ms_step"), cells.load_metric("ph_dir_ms_step")
    assert read(RUN, None) is None
    trace = {"ops": dict(OPS)}
    assert read(RUN, trace) == pytest.approx(0.5)
    assert whole(RUN, trace) == pytest.approx(0.75)  # the blockwise work is inside `s.dir`
    # `s.chunk`, run_loop's own scope, is no part of it
    assert not any("/s.dir/chunk/" in k for k in OPS if "/s.chunk/" in k)
    # a program without the scope (the parent, a machine without the field): nothing to read
    del trace["ops"]["fusion.9 jit(run_loop)/s.dir/chunk/reduce_max"]
    assert read(RUN, trace) is None


def test_collective_dirm_reads_the_collectives_of_the_phases_that_touch_the_directory():
    read, every = (cells.load_metric("collective_dirm_ms_step"),
                   cells.load_metric("collective_ms_step"))
    assert read(RUN, None) is None
    trace = {"ops": dict(OPS)}
    assert read(RUN, trace) == pytest.approx(6.0)  # s.local and s.commit: 24 ms over 4 steps
    assert every(RUN, trace) == pytest.approx(7.0)  # + s.arb, s.probe and s.chunk
    # a one-chip program has no collective; one without the scopes names no phase
    none = {"ops": {k: v for k, v in OPS.items() if k.startswith("fusion")}}
    assert read(RUN, none) is None
    bare = {"ops": {k.replace("/s.local/", "/").replace("/s.commit/", "/"): v
                    for k, v in OPS.items()}}
    assert read(RUN, bare) is None
    # the two readers count the same opcodes
    mine, stock = (cells._module("metrics", name, ROOT)
                   for name in ("collective_dirm_ms_step", "collective_ms_step"))
    assert mine.COLLECTIVES == stock.COLLECTIVES
