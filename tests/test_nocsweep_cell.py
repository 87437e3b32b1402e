"""The committed cell `rung3.nocsweep-b4` (ISSUE 47), on the CPU: the cell
loads; its four overrides are what `primetpu sweep --vary` parses and what
`apply_overrides` builds; and at a small size (64 O3 cores, 8x8 mesh under
the router model, the DRAM queue) a fleet over the same four overrides
gives, element by element, the cycles and every counter of a solo `Engine`,
of the benchmark's stock reference and of the golden model: the router
walk's sorts, the DRAM queue's rank and the `link_free` / `dram_free`
clocks under a batch axis. The benchmark's own tests of the cell
(`benchmark/tests/test_nocsweep_cell.py`: the files, the entries, the three
readers on a hand-made record) are held here as they stand."""

import json

import numpy as np
import pytest

from benchmark_modules import (assert_reference_equals_golden, handed_to_fleet_by_sweep,
                               load_benchmark_tests, vary_string)

import cells  # noqa: E402  (benchmark/ is on the path now)
import measure  # noqa: E402
import reference  # noqa: E402
import trafficgen  # noqa: E402

from primesim_tpu.config.machine import MachineConfig  # noqa: E402
from primesim_tpu.sim.engine import Engine  # noqa: E402
from primesim_tpu.sim.fleet import FleetEngine, apply_overrides  # noqa: E402
from primesim_tpu.trace.format import Trace  # noqa: E402

_theirs = load_benchmark_tests("test_nocsweep_cell")
spec, bench = _theirs.spec, _theirs.bench  # their fixtures
CELL = _theirs.CELL

test_the_machine_is_rung_3s_letter_for_letter = _theirs.test_the_machine_is_rung_3s_letter_for_letter
test_the_overrides_are_the_grid_and_no_twin = _theirs.test_the_overrides_are_the_grid_and_no_twin
test_traffic_is_the_first_trace_of_fft_m16s_panel = \
    _theirs.test_traffic_is_the_first_trace_of_fft_m16s_panel


def test_the_cells_entries(spec, bench):
    """`benchmark/tests/test_nocsweep_cell.py::test_the_cells_entries` but
    for its last line, which counts the benchmark's four-chip cells as they
    stood at PR 47 (two; `rung3.nocsweep-b16.x4` is the third since PR 51,
    and the benchmark's own file is a `benchmark` PR's to edit): this cell
    is on one chip, and at most half of the cells ask for four."""
    assert spec["cell"] == {"name": CELL, "config": _theirs.CONFIG, "traffic": "fft-m16-s404",
                            "chips": 1, "why": spec["cell"]["why"]}
    assert spec["runner"] == "fleet_sampled"
    entry = next(c for c in bench["configs"] if c["name"] == _theirs.CONFIG)
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert entry["file"] == "benchmark/configs/rung3-nocsweep-b4.json"
    assert entry["source"] == spec["config"]["source"] and len(entry["source"]) <= 200
    for word in ("PriME", "router", "memory-controller", "primetpu sweep --vary",
                 "configs/rung3_1024core_o3.json"):
        assert word in entry["source"]
    assert entry["reduced"] == ["chunk_steps", "trace_points", "elements", "checked_elements"]
    assert [w["name"] for w in bench["workloads"] if w["config"] == _theirs.CONFIG] == [CELL]
    assert {m["name"] for m in spec["end_to_end"]} == {"sim_mips", "hbm_peak_gb", "setup_s"}
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= len(bench["workloads"]) // 2


test_the_new_metrics_list_this_cell_and_only_it = \
    _theirs.test_the_new_metrics_list_this_cell_and_only_it
test_the_three_readers_read_a_fleets_scopes_an_element = \
    _theirs.test_the_three_readers_read_a_fleets_scopes_an_element
test_the_three_readers_find_nothing_on_a_solo_run_or_without_a_sample = \
    _theirs.test_the_three_readers_find_nothing_on_a_solo_run_or_without_a_sample


def small_machine(machine: dict) -> dict:
    """The cell's machine at 64 cores on an 8x8 mesh: every selector (the
    router model, the DRAM queue, O3) and every latency as the file has them."""
    return {**machine, "n_cores": 64, "n_banks": 64,
            "noc": {**machine["noc"], "mesh_x": 8, "mesh_y": 8}}


@pytest.fixture(scope="module")
def ran(spec):
    """One fleet over the file's four overrides on the small machine and a
    short `fft_like` trace (the traffic file's generator, its parity size)."""
    run, machine = spec["config"]["run"], small_machine(spec["config"]["machine"])
    ev = trafficgen.make_trace(spec["traffic"], machine["n_cores"], 47, parity=True)
    cfg = MachineConfig.from_dict(machine)
    trace = Trace(ev, measure._lengths(ev))
    ovs = run["fleet"]["overrides"]
    fleet = FleetEngine(cfg, [trace] * len(ovs), ovs, chunk_steps=run["chunk_steps"])
    fleet.run()
    return machine, cfg, trace, ev, ovs, fleet


def test_every_element_equals_a_solo_engine_on_its_own_machine(ran):
    _, cfg, trace, ev, ovs, fleet = ran
    assert fleet.n_elements == 4 and fleet.done()
    assert cfg.noc.contention_model == "router" and cfg.dram_queue
    expect = trafficgen.total_instructions(ev)
    for e, ov in enumerate(ovs):
        solo = Engine(apply_overrides(cfg, ov), trace, chunk_steps=fleet.chunk_steps)
        solo.run()
        np.testing.assert_array_equal(fleet.cycles[e], solo.cycles, err_msg=f"element {e}")
        for k, v in solo.counters.items():
            np.testing.assert_array_equal(fleet.counters[k][e], v, err_msg=f"element {e} {k}")
        for k, v in solo.step_stats.items():
            np.testing.assert_array_equal(fleet.step_stats[k][e], v, err_msg=f"element {e} {k}")
        assert int(fleet.steps_run[e]) == solo.steps_run
        assert int(fleet.counters["instructions"][e].sum()) == expect
    # the two knobs are felt, each in its own counter's cycles, and no element repeats another
    noc = fleet.counters["noc_contention_cycles"].sum(1)
    dram = fleet.counters["dram_queue_cycles"].sum(1)
    assert noc.all() and dram.all() and dram[2] < dram[0] and dram[3] < dram[1]
    digests = [measure.digest(fleet.cycles[e], {k: v[e] for k, v in fleet.counters.items()})
               for e in range(4)]
    assert len(set(digests)) == 4
    assert fleet.cycles[1].max() > fleet.cycles[0].max()  # link_lat 2 stretches the clocks


@pytest.mark.parametrize("e", range(4))
def test_an_element_whole_against_the_reference_and_golden(spec, ran, e):
    machine, _, _, ev, ovs, fleet = ran
    runner = cells.load_runner(spec["runner"])
    mine = runner.element_machine(machine, ovs[e])
    ref = assert_reference_equals_golden(reference, mine, ev)  # the two agree, then:
    np.testing.assert_array_equal(fleet.cycles[e], np.asarray(ref.cycles, np.int64))
    for k, v in fleet.counters.items():
        if k in reference.COUNTERS:
            np.testing.assert_array_equal(v[e], np.asarray(ref.counters[k], np.int64), err_msg=k)
        else:
            assert not v[e].any(), k
    chunk = fleet.chunk_steps
    assert int(fleet.steps_run[e]) == -(-ref.step_count // chunk) * chunk


# ---- the cell is what `primetpu sweep` runs ----------------------------------

def test_each_override_is_what_its_vary_string_parses_to(spec):
    from primesim_tpu.cli import _parse_vary

    ovs = spec["config"]["run"]["fleet"]["overrides"]
    assert [vary_string(ov) for ov in ovs[1:]] == [
        "link_lat=2", "dram_service=25", "link_lat=2,dram_service=25"]
    for ov in ovs[1:]:  # element 0 is the machine as it stands: no string spells `{}`
        assert _parse_vary(vary_string(ov)) == ov


def test_the_dict_space_machines_are_apply_overrides_machines(spec):
    """`runners/fleet.py::element_machine` (what the reference is given) and
    `sim/fleet.py::apply_overrides` (what the program runs) build the same
    four machines from the file's."""
    runner = cells.load_runner(spec["runner"])
    machine = spec["config"]["machine"]
    cfg = MachineConfig.from_dict(machine)
    for ov in spec["config"]["run"]["fleet"]["overrides"]:
        assert MachineConfig.from_dict(runner.element_machine(machine, ov)) == \
            apply_overrides(cfg, ov)
    both = apply_overrides(cfg, {"link_lat": 2, "dram_service": 25})
    assert (both.noc.link_lat, both.noc.router_lat, both.dram_service, both.dram_lat) == \
        (2, 1, 25, 100)


def test_cmd_sweeps_fan_builds_the_files_machines(spec, monkeypatch):
    """`primetpu sweep configs/rung3_1024core_o3.json --synth ... --vary ...`
    with the three strings hands `FleetEngine` the configurations
    `apply_overrides` builds from the cell's file."""
    ovs = spec["config"]["run"]["fleet"]["overrides"]
    cfg, traces, overrides, kw = handed_to_fleet_by_sweep(
        monkeypatch, "rung3_1024core_o3.json", spec)
    mine = MachineConfig.from_dict(spec["config"]["machine"])
    assert cfg == mine and kw["chunk_steps"] == 8 and kw["mesh"] is None
    assert overrides == ovs[1:] and len(traces) == 3  # the one trace fanned over the varies
    assert all(t is traces[0] for t in traces)
    assert len({json.dumps(ov, sort_keys=True) for ov in overrides}) == 3
