"""The committed cell `rung4.fft-m18-4k` (ISSUE 55), on the CPU: rung 4's
4096-core big.LITTLE full-map machine WHOLE on one chip. The cell loads; its
machine is `rung4-x4.json`'s and the ladder's file field for field; it runs
on one device through the `solo` runner against the `biglittle` reference;
its one new metric, `state_copies_peak`, lists this cell and only it and
reads a number off a rehearsal's record; and `init_state` on the full
machine (shapes only, nothing allocated) gives the one leaf the cell exists
for: `dirm` `[2097152, 1152]` int32, over 2^31 elements and over 2^32 bytes.
The parity of the program with the reference at a small size is
`tests/test_reference_biglittle.py`'s; that a job's engine lets its machine
go is `tests/test_job_holds_machine_once.py`'s."""

import itertools
import json
import os
import types

import jax
import numpy as np
import pytest

from benchmark_modules import ROOT, LiveBytes, load_benchmark_tests

import cells  # noqa: E402  (benchmark/ is on the path now)
import check  # noqa: E402
import measure  # noqa: E402
import trafficgen  # noqa: E402

from primesim_tpu.config.machine import MachineConfig  # noqa: E402
from primesim_tpu.sim.state import dirm_width, init_state  # noqa: E402

CELL, TWIN = "rung4.fft-m18-4k", "rung4.fft-m18-4k.x4"
METRIC = "state_copies_peak"

_scratch = load_benchmark_tests("scratchroot")  # a checkout that cells can be added to


def _json(*path):
    with open(os.path.join(ROOT, *path)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def spec():
    return cells.load_cell(CELL)


@pytest.fixture(scope="module")
def bench():
    return _json("BENCHMARK.json")


# ---- the files and the entries ---------------------------------------------

def test_the_cell_loads_on_one_chip_solo_against_biglittle(spec):
    assert spec["cell"] == {**spec["cell"], "config": "rung4", "traffic": "fft-m18-4k", "chips": 1}
    assert spec["config"]["run"] == {"chunk_steps": 8, "step_impl": "xla", "devices": 1}
    assert spec["runner"] == "solo" and "runner" not in spec["config"]["run"]
    assert spec["reference"] == "biglittle"
    assert spec["config"]["guarantee"] == _json("benchmark", "configs", "rung4-x4.json")["guarantee"]
    for m in spec["per_layer"]:
        assert callable(cells.load_metric(m["name"])), m["name"]


LADDER = {**_json("configs", "rung4_4096core_biglittle.json"), "dram_queue": False, "dram_service": 0}


@pytest.mark.parametrize("field", sorted(LADDER))
def test_the_machine_is_the_four_chip_cells_and_the_ladders_field_for_field(spec, field):
    """Nothing cut: every width as BASELINE's fourth rung publishes it (the
    two DRAM keys are the defaults a configuration file has to state)."""
    machine = spec["config"]["machine"]
    assert sorted(machine) == sorted(LADDER)
    assert machine[field] == _json("benchmark", "configs", "rung4-x4.json")["machine"][field]
    assert machine[field] == LADDER[field]


def test_the_traffic_and_the_reference_are_the_four_chip_cells_files(spec):
    twin = cells.load_cell(TWIN)
    assert spec["traffic"] == twin["traffic"] and spec["reference"] == twin["reference"]
    assert spec["runner"] == twin["runner"] == "solo"
    assert spec["config"]["machine"] == twin["config"]["machine"]
    assert {**twin["config"]["run"], "devices": 1} == spec["config"]["run"]
    # one trace of the panel, whole jobs: the run's seed draws the parity job's alone
    full = trafficgen.make_trace(spec["traffic"], 4096, spec["traffic"]["panel_seeds"][0])
    assert full.shape == (4096, 137, 4) and trafficgen.total_instructions(full) == 5288167


def test_reduced_lists_chunk_steps_and_workload_only(spec, bench):
    entry = next(c for c in bench["configs"] if c["name"] == "rung4")
    assert entry["file"] == "benchmark/configs/rung4.json"
    assert sorted(entry["reduced"]) == sorted(spec["config"]["reduced"]) == ["chunk_steps", "workload"]
    assert spec["config"]["reduced"] == _json("benchmark", "configs", "rung4-x4.json")["reduced"]
    assert entry["source"] == spec["config"]["source"]
    assert spec["config"]["assumed"] == {}


def test_at_most_half_the_cells_ask_for_four_chips(bench):
    cells_ = bench["workloads"]
    assert len(cells_) == len({w["name"] for w in cells_}) >= 12
    assert sum(w["chips"] == 4 for w in cells_) <= len(cells_) // 2
    assert [w["chips"] for w in cells_ if w["config"] in ("rung4", "rung4-x4")] == [4, 1]


def test_the_new_metric_lists_this_cell_and_only_it(spec, bench):
    entry = next(m for m in bench["per_layer"] if m["name"] == METRIC)
    assert entry == {"name": METRIC, "unit": "x", "better": "lower", "source": "program_counter",
                     "layer": "host driver", "moves": "hbm_peak_gb", "workloads": [CELL]}
    assert METRIC in [m["name"] for m in spec["per_layer"]]
    assert "hbm_peak_gb" in [m["name"] for m in spec["end_to_end"]]
    for w in bench["workloads"]:
        if w["name"] != CELL:
            assert METRIC not in [m["name"] for m in cells.load_cell(w["name"])["per_layer"]]


def test_the_traced_line_carries_the_roofline_and_the_five_phases(spec):
    names = {m["name"] for m in spec["per_layer"]}
    assert {"step_roofline", "step_ms", "device_idle_pct", "ph_local_ms_step", "ph_probe_ms_step",
            "ph_arb_ms_step", "ph_dir_ms_step", "ph_commit_ms_step", METRIC} <= names
    # closed lists of other cells, left as they are (PERF.md section 7)
    assert not {"collective_ms_step", "ph_noc_ms_step", "ph_dirgrp_ms_step"} & names


# ---- the one leaf the cell exists for ---------------------------------------

def test_the_full_machines_directory_is_one_leaf_over_2_31_elements(spec):
    """`jax.eval_shape`: shapes alone, nothing allocated. The two thresholds
    the leaf passes, which no other cell's does (rung 5's `dirm` is 0.8 G
    elements and 3.2 GB, a shard of `rung4-x4` 0.6 G and 2.42 GB): a signed
    32-bit ELEMENT offset ends at 2^31, a 32-bit BYTE offset at 2^32."""
    cfg = MachineConfig.from_dict({**spec["config"]["machine"], "step_impl": "xla"})
    state = jax.eval_shape(lambda: init_state(cfg))
    assert (state.dirm.shape, state.dirm.dtype) == ((2097152, 1152), np.int32)
    assert state.dirm.shape == (cfg.n_banks * cfg.llc.sets, dirm_width(cfg))
    elements = state.dirm.shape[0] * state.dirm.shape[1]
    assert elements == 2_415_919_104 > 2**31
    assert elements * 4 == 9_663_676_416 > 2**32
    # rows from here on lie wholly past element 2^31: banks 3641 to 4095
    first_past = -(-2**31 // state.dirm.shape[1])
    assert first_past == 1_864_136 and -(-first_past // cfg.llc.sets) == 3641
    # and the checked trace reaches them: its lines' home rows, as `step` maps them
    ev = trafficgen.make_trace(spec["traffic"], cfg.n_cores, spec["traffic"]["panel_seeds"][0])
    line = ev[:, :, 2].astype(np.int64)[ev[:, :, 0] != trafficgen.EV_END] >> 6
    row = (line & (cfg.n_banks - 1)) * cfg.llc.sets + (
        (line >> (cfg.n_banks.bit_length() - 1)) & (cfg.llc.sets - 1))
    assert (len(np.unique(row)), len(np.unique(row[row >= first_past]))) == (8192, 896)
    assert int((row >= first_past).sum()) == 60928
    # every other leaf is small beside it: a job holds 9.7 GB, two do not fit 16
    total = sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree.leaves(state))
    assert 9.70e9 < total < 9.72e9 and 2 * total > 15.75e9


def test_step_roofline_counts_one_row_a_core_not_the_leaf(spec):
    """The roofline's bytes come from `state_shapes`: 4096 cores x (an event
    record, an L1 set, a directory row of 4608 bytes), read and written."""
    roofline = cells._module("metrics", "step_roofline", ROOT)
    cfg = MachineConfig.from_dict({**spec["config"]["machine"], "step_impl": "xla"})
    state = jax.eval_shape(lambda: init_state(cfg))
    run = {"n_cores": 4096, "machine": spec["config"]["machine"], "jobs": [{
        "state_shapes": {k: [list(v.shape), v.dtype.itemsize]
                         for k, v in state._asdict().items() if hasattr(v, "shape")},
        "events_shape": [[4096, 137, 4], 4]}]}
    assert roofline.step_bytes(run) == 2 * 4096 * (16 + 5 * 4 * 4 + 4608)


# ---- the reader, on a rehearsal's record ------------------------------------

SMALL = "rung4s"


@pytest.fixture(scope="module")
def small_root(tmp_path_factory, spec):
    """A checkout whose one added cell is this cell's files with the machine
    at 64 cores (the pattern, the full map in two words a way, in blocks of
    one), and the metric's list opened to it."""
    dst = str(tmp_path_factory.mktemp("checkout"))
    bench = _scratch.copy_checkout(dst)
    config = json.loads(json.dumps(spec["config"]))
    config["name"] = SMALL
    config["machine"].update(n_cores=64, n_banks=64, sharer_chunk_words=1)
    config["machine"]["noc"].update(mesh_x=8, mesh_y=8)
    _scratch.add_cell(bench, f"{SMALL}.fft-m18-4k")
    next(m for m in bench["per_layer"] if m["name"] == METRIC)["workloads"].append(
        f"{SMALL}.fft-m18-4k")
    _scratch.write(dst, bench, {f"benchmark/configs/{SMALL}.json": config})
    return dst


def test_the_reader_reads_a_number_off_a_rehearsals_record(small_root, monkeypatch):
    from primesim_tpu.obs import process_store
    from primesim_tpu.sim import engine

    read = cells.load_metric(METRIC, small_root)
    small = cells.load_cell(f"{SMALL}.fft-m18-4k", root=small_root)
    assert METRIC in [m["name"] for m in small["per_layer"]]
    # the CPU's allocator counts nothing: nothing to read, and no raise
    plain = measure.run_cell(small, 55, 0.05, False, 0.0)
    assert plain["jobs"] and process_store().samples()[-1]["place"]["alloc"] == {}
    # `state_bytes` is counted from the shapes, so here too: every leaf, once
    cfg = MachineConfig.from_dict({**small["config"]["machine"], "step_impl": "xla"})
    leaves = jax.tree.leaves(jax.eval_shape(lambda: init_state(cfg)))
    assert process_store().samples()[-1]["place"]["state_bytes"] == [
        sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in leaves)]
    assert read(plain, None) is None
    # one that counts: every job of the window held its machine once, and
    # nothing of an earlier job lay under a later one's
    monkeypatch.setattr(engine, "alloc_now", LiveBytes())
    ticks = itertools.count()  # a clock that ticks once a reading: the same jobs on any host
    monkeypatch.setattr(measure, "time", types.SimpleNamespace(
        perf_counter=lambda: 0.004 * next(ticks)))
    record = measure.run_cell(small, 55, 0.1, False, 0.0)
    verdict = check.decide(record, cells.load_reference("biglittle", small_root),
                           expect_platform="cpu")
    assert verdict["correct"] is True and len(record["jobs"]) >= 2
    samples = process_store().samples()[-len(record["jobs"]):]
    places = [s["place"] for s in samples]
    assert len({p["alloc"]["bytes_in_use"][0] for p in places}) == 1
    state = places[0]["state_bytes"][0]
    got = read(record, None)
    assert got == max((p["alloc_run"]["peak_bytes_in_use"][0] - p["alloc"]["bytes_in_use"][0])
                      / state for p in places)
    # the state once, the trace and the loop's small results beside it; never twice
    assert 1.0 <= got < 1.5
    # a program whose samples say nothing of `state_bytes` (the parent's): nothing to read
    assert read({"jobs": []}, None) is None
    for p in places:  # (the store's own samples: put back what is taken out)
        p["was"] = p.pop("state_bytes")
    try:
        assert read(record, None) is None
    finally:
        for p in places:
            p["state_bytes"] = p.pop("was")

