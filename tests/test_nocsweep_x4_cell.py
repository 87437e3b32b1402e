"""The committed cell `rung3.nocsweep-b16.x4` (ISSUE 51), on the CPU: the
cell loads; its sixteen overrides are what `primetpu sweep --vary` parses
and what `apply_overrides` builds; `primetpu sweep --devices 4` hands
`FleetEngine` those machines and a mesh of four; and at a small size (16 O3
cores, 4x4 mesh under the router model, the DRAM queue) the fleet over the
same sixteen overrides on four devices, four whole machines a device, gives
element by element what the fleet on one device gives, what the one-chip
cell's four give on device 0, and the cycles and every counter of the
benchmark's stock reference and of the golden model. The benchmark's own
tests of the cell (`benchmark/tests/test_nocsweep_x4_cell.py`: the files,
the entries, the four readers on hand-made records) are held here as they
stand; `tests/test_fleet_on_chips.py` holds the layout, the build and the
loop."""

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
from primesim_tpu.parallel.sharding import AXIS, tile_mesh  # noqa: E402
from primesim_tpu.sim.fleet import FleetEngine, apply_overrides  # noqa: E402
from primesim_tpu.trace.format import Trace  # noqa: E402

_theirs = load_benchmark_tests("test_nocsweep_x4_cell")
spec, bench = _theirs.spec, _theirs.bench  # their fixtures
CELL, CONTROL = _theirs.CELL, _theirs.CONTROL

test_the_machine_is_the_one_chip_cells_key_for_key = \
    _theirs.test_the_machine_is_the_one_chip_cells_key_for_key
test_the_overrides_are_the_grid_in_blocks_of_the_one_chip_cells_four = \
    _theirs.test_the_overrides_are_the_grid_in_blocks_of_the_one_chip_cells_four
test_traffic_is_the_one_chip_cells_file = _theirs.test_traffic_is_the_one_chip_cells_file
test_the_cells_entries = _theirs.test_the_cells_entries
test_the_new_metrics_list_this_cell_and_only_it = \
    _theirs.test_the_new_metrics_list_this_cell_and_only_it
test_the_four_readers_read_a_fleet_on_several_chips = \
    _theirs.test_the_four_readers_read_a_fleet_on_several_chips
test_the_four_readers_find_nothing_on_one_chip_a_solo_run_or_without_a_sample = \
    _theirs.test_the_four_readers_find_nothing_on_one_chip_a_solo_run_or_without_a_sample


def small_machine(machine: dict) -> dict:
    """The cell's machine at 16 cores on a 4x4 mesh: every selector (the
    router model, the DRAM queue, O3) and every latency as the file has them."""
    return {**machine, "n_cores": 16, "n_banks": 16,
            "noc": {**machine["noc"], "mesh_x": 4, "mesh_y": 4}}


@pytest.fixture(scope="module")
def ran(spec):
    """The file's sixteen overrides on the small machine and a short
    `fft_like` trace (the traffic file's generator, its parity size): one
    fleet on four devices, one on none."""
    run, machine = spec["config"]["run"], small_machine(spec["config"]["machine"])
    ev = trafficgen.make_trace(spec["traffic"], machine["n_cores"], 51, parity=True)
    cfg = MachineConfig.from_dict(machine)
    trace = Trace(ev, measure._lengths(ev))
    ovs = run["fleet"]["overrides"]
    fleets = []
    for mesh in (tile_mesh(run["devices"]), None):
        fleet = FleetEngine(cfg, [trace] * len(ovs), ovs, chunk_steps=run["chunk_steps"],
                            mesh=mesh)
        fleet.run()
        fleets.append(fleet)
    return machine, cfg, trace, ev, ovs, fleets


def test_four_whole_machines_a_device_in_the_order_written(spec, ran):
    _, _, _, _, ovs, (on_chips, _) = ran
    assert on_chips.n_elements == 16 and on_chips.done()
    assert on_chips.mesh.shape[AXIS] == spec["cell"]["chips"] == 4
    for shard in on_chips.state.knobs.llc_lat.addressable_shards:
        chip = shard.device.id
        assert shard.index == (slice(4 * chip, 4 * chip + 4),)
        want = [apply_overrides(on_chips.cfg, ov).llc.latency for ov in ovs[4 * chip:4 * chip + 4]]
        assert np.asarray(shard.data).tolist() == want == [12 if chip < 2 else 16] * 4
    assert measure.engine_fields(on_chips)["n_devices"] == 4  # `check.py`'s wrong_device_count


def test_the_fleet_on_four_devices_equals_the_fleet_on_one(ran):
    _, _, _, ev, _, (on_chips, whole) = ran
    np.testing.assert_array_equal(on_chips.cycles, whole.cycles)
    np.testing.assert_array_equal(on_chips.steps_run, whole.steps_run)
    for k, v in whole.counters.items():
        np.testing.assert_array_equal(on_chips.counters[k], v, err_msg=k)
    for k, v in whole.step_stats.items():
        np.testing.assert_array_equal(on_chips.step_stats[k], v, err_msg=k)
    expect = trafficgen.total_instructions(ev)
    assert (on_chips.counters["instructions"].sum(axis=1) == expect).all()
    # the four knobs are felt, no element repeats another, and every device's
    # longest machine is its `link_lat` 2, `dram_service` 0 one
    digests = [measure.digest(on_chips.cycles[e], {k: v[e] for k, v in on_chips.counters.items()})
               for e in range(16)]
    assert len(set(digests)) == 16
    for chip in range(4):
        block = on_chips.cycles[4 * chip:4 * chip + 4].max(axis=1)
        assert block.argmax() == 1


def test_device_0_runs_the_one_chip_cells_fleet(ran):
    """Elements 0-3 are `rung3.nocsweep-b4`'s four machines to the letter:
    that cell's fleet, on no mesh, gives what device 0 gave."""
    _, cfg, trace, _, ovs, (on_chips, _) = ran
    control = cells.load_cell(CONTROL)["config"]["run"]
    assert control["fleet"]["overrides"] == ovs[:4]
    four = FleetEngine(cfg, [trace] * 4, ovs[:4], chunk_steps=control["chunk_steps"])
    four.run()
    np.testing.assert_array_equal(on_chips.cycles[:4], four.cycles)
    np.testing.assert_array_equal(on_chips.steps_run[:4], four.steps_run)
    for k, v in four.counters.items():
        np.testing.assert_array_equal(on_chips.counters[k][:4], v, err_msg=k)


@pytest.mark.parametrize("e", range(16))
def test_an_element_whole_against_the_reference_and_golden(spec, ran, e):
    machine, _, _, ev, ovs, (on_chips, _) = ran
    runner = cells.load_runner(spec["runner"])
    mine = runner.element_machine(machine, ovs[e])
    ref = assert_reference_equals_golden(reference, mine, ev)  # the two agree, then:
    np.testing.assert_array_equal(on_chips.cycles[e], np.asarray(ref.cycles, np.int64))
    for k, v in on_chips.counters.items():
        if k in reference.COUNTERS:
            np.testing.assert_array_equal(v[e], np.asarray(ref.counters[k], np.int64), err_msg=k)
        else:
            assert not v[e].any(), k
    chunk = on_chips.chunk_steps
    assert int(on_chips.steps_run[e]) == -(-ref.step_count // chunk) * chunk


# ---- the cell is what `primetpu sweep --devices 4` runs ------------------------

@pytest.mark.parametrize("e", range(1, 16))
def test_an_override_is_what_its_vary_string_parses_to(spec, e):
    from primesim_tpu.cli import _parse_vary

    ov = spec["config"]["run"]["fleet"]["overrides"][e]
    assert _parse_vary(vary_string(ov)) == ov
    # as ISSUE 51 writes the grid: the outer knobs first, then the one-chip cell's two
    assert list(ov) == [k for k in ("llc_lat", "dram_lat", "link_lat", "dram_service") if k in ov]


def test_the_dict_space_machines_are_apply_overrides_machines(spec):
    """`runners/fleet.py::element_machine` (what the reference is given) and
    `sim/fleet.py::apply_overrides` (what the program runs) build the same
    sixteen machines from the file's."""
    runner = cells.load_runner(spec["runner"])
    machine = spec["config"]["machine"]
    cfg = MachineConfig.from_dict(machine)
    for ov in spec["config"]["run"]["fleet"]["overrides"]:
        assert MachineConfig.from_dict(runner.element_machine(machine, ov)) == \
            apply_overrides(cfg, ov)
    last = apply_overrides(cfg, spec["config"]["run"]["fleet"]["overrides"][-1])
    assert (last.llc.latency, last.dram_lat, last.noc.link_lat, last.dram_service,
            last.noc.router_lat) == (16, 80, 2, 25, 1)


def test_cmd_sweeps_fan_builds_the_files_machines_on_a_mesh_of_four(spec, monkeypatch):
    """`primetpu sweep configs/rung3_1024core_o3.json --synth ... --vary ...
    --devices 4` with sixteen strings (element 0, the machine as it stands,
    spelt as a knob at its own value) hands `FleetEngine` the configurations
    `apply_overrides` builds from the cell's file, and the first four devices."""
    ovs = spec["config"]["run"]["fleet"]["overrides"]
    cfg, traces, overrides, kw = handed_to_fleet_by_sweep(
        monkeypatch, "rung3_1024core_o3.json", spec, extra=("--devices", "4"),
        first="link_lat=1")
    mine = MachineConfig.from_dict(spec["config"]["machine"])
    assert cfg == mine and kw["chunk_steps"] == 8
    assert kw["mesh"].shape[AXIS] == 4 and [d.id for d in kw["mesh"].devices.flat] == [0, 1, 2, 3]
    assert overrides[1:] == ovs[1:] and len(traces) == 16  # the one trace fanned over the varies
    assert [apply_overrides(cfg, ov) for ov in overrides] == \
        [apply_overrides(mine, ov) for ov in ovs]
    assert all(t is traces[0] for t in traces)
    assert len({json.dumps(ov, sort_keys=True) for ov in overrides}) == 16
