"""A fleet on a mesh (DESIGN.md §22 as it stands since PR 51): the batch
axis over the chips, every machine whole on one chip, B / D a chip; each
chip builds its own machines and runs them in its own loop to their end;
nothing crosses chips. On the virtual devices `tests/test_multichip.py`
uses: the layout, the build, the loop's compiled text, the job sample's
`caps`, the refusal of a B the mesh does not divide, and every element
bit-exact with a solo `Engine`, the golden model and the benchmark's plain
reference, on rung 3's selectors (the router walk, the DRAM queue, O3) at
16 cores, with knobs and trace lengths that differ, with and without
barriers."""

import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark_modules import ROOT, assert_reference_equals_golden  # puts benchmark/ on the path

import cells  # noqa: E402
import measure  # noqa: E402
import reference  # noqa: E402

from primesim_tpu.config.machine import MachineConfig  # noqa: E402
from primesim_tpu.obs import process_store  # noqa: E402
from primesim_tpu.parallel import sharding  # noqa: E402
from primesim_tpu.parallel.sharding import AXIS, DeviceMeshError, tile_mesh  # noqa: E402
from primesim_tpu.sim.engine import Engine  # noqa: E402
from primesim_tpu.sim.fleet import (FleetEngine, apply_overrides, fleet_run_chunk,  # noqa: E402
                                    fleet_run_loop)
from primesim_tpu.sim.state import init_state  # noqa: E402
from primesim_tpu.trace import synth  # noqa: E402
from primesim_tpu.trace.format import fold_ins  # noqa: E402

B, D, CHUNK = 8, 4, 8
MACHINE = {  # rung 3's selectors at a small size (tests/test_reference_sync.py's)
    "n_cores": 16, "n_banks": 16,
    "core": {"cpi": 1, "o3_overlap_256": 128},
    "l1": {"size": 256, "ways": 2, "line": 64, "latency": 2},
    "llc": {"size": 512, "ways": 4, "line": 64, "latency": 12},
    "noc": {"mesh_x": 4, "mesh_y": 4, "link_lat": 1, "router_lat": 1,
            "contention": True, "contention_model": "router", "contention_lat": 1},
    "dram_lat": 100, "dram_queue": True, "dram_service": 0,
    "quantum": 1000, "local_run_len": 8,
}
SYNC_KEYS = {"lock_slots": 1024, "barrier_slots": 64}  # the sync reference's; the stock one has none
# every knob of the cell's grid, no two elements alike
OVS = [{}, {"link_lat": 2}, {"dram_service": 25}, {"link_lat": 2, "dram_service": 25},
       {"dram_lat": 80}, {"llc_lat": 16, "link_lat": 2}, {"llc_lat": 16, "dram_lat": 80},
       {"llc_lat": 16, "dram_lat": 80, "link_lat": 2, "dram_service": 25}]
COLLECTIVES = ("all-reduce", "all-gather", "all-to-all", "collective-permute", "reduce-scatter")


def _traces(sync: bool) -> list:
    """Eight traces of eight lengths: the chips end chunks apart."""
    if sync:
        return [fold_ins(synth.barrier_phases(16, n_phases=1 + e % 4, seed=60 + e))
                for e in range(B)]
    return [fold_ins(synth.fft_like(16, n_phases=1 + e % 3, points_per_core=4 + 3 * e,
                                    seed=40 + e)) for e in range(B)]


@pytest.fixture(scope="module")
def cfg():
    return MachineConfig.from_dict({**MACHINE, **SYNC_KEYS})


@pytest.fixture(scope="module", params=["plain", "sync"])
def ran(request, cfg):
    """One fleet of eight on four devices, run in one dispatch."""
    traces = _traces(request.param == "sync")
    fleet = FleetEngine(cfg, traces, OVS, chunk_steps=CHUNK, mesh=tile_mesh(D))
    fleet.run()
    return request.param, traces, fleet, process_store().samples()[-1]


def test_chips_end_chunks_apart_and_the_sample_says_so(ran):
    kind, traces, fleet, sample = ran
    assert fleet.done() and fleet.has_sync == (kind == "sync")
    steps = fleet.steps_run
    per_chip = [int(steps[c * 2:c * 2 + 2].max()) for c in range(D)]
    assert len(set(per_chip)) > 1  # no chip waited for another: each ran its own chunks
    caps = sample["caps"]
    assert (sample["label"], sample["steps"]) == ("fleet", int(steps.max()))
    assert caps["elements"] == B and caps["element_steps"] == steps.tolist()
    assert caps["chips"] == D and caps["chip_steps"] == per_chip
    assert max(caps["chip_steps"]) == sample["steps"]
    assert measure.engine_fields(fleet)["n_devices"] == D


@pytest.mark.parametrize("e", range(B))
def test_an_element_equals_its_solo_engine_golden_and_the_reference(ran, cfg, e):
    kind, traces, fleet, _ = ran
    solo = Engine(apply_overrides(cfg, OVS[e]), traces[e], chunk_steps=CHUNK)
    solo.run()
    np.testing.assert_array_equal(fleet.cycles[e], solo.cycles)
    assert len(solo.counters) == 26
    for k, v in solo.counters.items():
        np.testing.assert_array_equal(fleet.counters[k][e], v, err_msg=k)
    for k, v in solo.step_stats.items():
        np.testing.assert_array_equal(fleet.step_stats[k][e], v, err_msg=k)
    assert int(fleet.steps_run[e]) == solo.steps_run
    # the plain reference on the machine the benchmark's runner gives it for this element
    ref_module = cells.load_reference("sync", ROOT) if kind == "sync" else reference
    machine = {**MACHINE, **SYNC_KEYS} if kind == "sync" else MACHINE
    mine = cells.load_runner("fleet").element_machine(machine, OVS[e])
    assert MachineConfig.from_dict(mine) == apply_overrides(cfg, OVS[e])
    ref = assert_reference_equals_golden(ref_module, mine, traces[e].events)
    np.testing.assert_array_equal(fleet.cycles[e], np.asarray(ref.cycles, np.int64))
    for k, v in fleet.counters.items():
        if k in ref_module.COUNTERS:
            np.testing.assert_array_equal(v[e], np.asarray(ref.counters[k], np.int64), err_msg=k)
        else:
            assert not v[e].any(), k
    assert int(fleet.steps_run[e]) == -(-ref.step_count // CHUNK) * CHUNK


# ---- the layout and the build -----------------------------------------------

@pytest.fixture(scope="module")
def built(cfg):
    return FleetEngine(cfg, _traces(False), OVS, chunk_steps=CHUNK, mesh=tile_mesh(D))


def test_no_device_holds_more_than_its_own_machines(built):
    for leaf in jax.tree.leaves((built.state, built.events)):
        assert tuple(leaf.sharding.spec) == (AXIS,), leaf.sharding
        assert len(leaf.addressable_shards) == D
        for shard in leaf.addressable_shards:
            assert shard.data.shape == (B // D, *leaf.shape[1:])
            # contiguous blocks, in the order the overrides are written
            assert shard.index[0] == slice(shard.device.id * B // D, (shard.device.id + 1) * B // D)
    assert measure.engine_fields(built)["n_devices"] == D
    assert [d.id for d in built.mesh.devices.flat] == list(range(D))


def test_the_build_on_the_chips_is_the_stack_of_solo_init_states(built, cfg):
    stacked = jax.tree.map(lambda *xs: np.stack([np.asarray(x) for x in xs]),
                           *[init_state(apply_overrides(cfg, ov)) for ov in OVS])
    for (path, got), want in zip(jax.tree_util.tree_leaves_with_path(built.state),
                                 jax.tree.leaves(stacked)):
        assert got.dtype == want.dtype, path
        np.testing.assert_array_equal(np.asarray(got), want, err_msg=str(path))
    assert int(built.state.knobs.link_lat[1]) == 2 and int(built.state.knobs.dram_lat[4]) == 80
    # one compiled builder a geometry and B, whatever the knobs
    before = sharding._fleet_state_builder.cache_info().currsize
    FleetEngine(cfg, _traces(False), OVS[::-1], chunk_steps=CHUNK, mesh=tile_mesh(D))
    assert sharding._fleet_state_builder.cache_info().currsize == before


def test_the_loops_text_holds_no_collective(built):
    args = (built.geom_cfg, CHUNK, built.events, built.state)
    loop = fleet_run_loop.lower(*args, jnp.asarray(4, jnp.int32), has_sync=True)
    chunk = fleet_run_chunk.lower(*args, has_sync=True)
    for lowered in (loop, chunk):
        for text in (lowered.as_text(), lowered.compile().as_text()):
            found = [c for c in COLLECTIVES if re.search(rf"\b{c}(-start)?\(", text)]
            assert not found, found
            assert "all_reduce" not in text and "all_gather" not in text
    # and the step was not handed the mesh: its two seams are not in the text
    assert loop.as_text().count("shard_map") == 1 or "manual" in loop.as_text()


def test_the_chunked_path_runs_each_chips_machines_too(built, cfg):
    traces = _traces(False)
    fleet = FleetEngine(cfg, traces, OVS, chunk_steps=CHUNK, mesh=tile_mesh(D))
    while not fleet.done():
        fleet.step_chunk()
    assert tuple(fleet.state.cycles.sharding.spec) == (AXIS,)
    whole = FleetEngine(cfg, traces, OVS, chunk_steps=CHUNK)
    whole.run()
    np.testing.assert_array_equal(fleet.cycles, whole.cycles)
    np.testing.assert_array_equal(fleet.steps_run, whole.steps_run)
    for k, v in whole.counters.items():
        np.testing.assert_array_equal(fleet.counters[k], v, err_msg=k)


def test_a_splice_into_a_slot_keeps_the_layout(cfg):
    """`make_slots` / `replace_element` on a mesh (the served bucket's
    calls, at more than one slot): the spliced machine lands on its slot's
    device, the others are untouched, and it runs to its solo result."""
    traces = _traces(False)
    slots = FleetEngine.make_slots(cfg, 4, max(t.max_len for t in traces), chunk_steps=CHUNK,
                                   mesh=tile_mesh(2))
    slots.replace_element(2, traces[3], OVS[3])
    slots.replace_element(1, traces[1], OVS[5])
    for leaf in jax.tree.leaves((slots.state, slots.events)):
        assert tuple(leaf.sharding.spec) == (AXIS,)
        assert {s.data.shape[0] for s in leaf.addressable_shards} == {2}
    while not slots.done():
        slots.step_chunk()
    for slot, (e, ov) in {2: (3, OVS[3]), 1: (1, OVS[5])}.items():
        solo = Engine(apply_overrides(cfg, ov), traces[e], chunk_steps=CHUNK)
        solo.run()
        np.testing.assert_array_equal(slots.cycles[slot], solo.cycles)
        for k, v in solo.counters.items():
            np.testing.assert_array_equal(slots.counters[k][slot], v, err_msg=k)
    assert not slots.counters["instructions"][[0, 3]].any()  # the idle slots stayed idle


def test_without_a_mesh_the_sample_says_one_chip(cfg):
    traces = _traces(False)[:3]
    fleet = FleetEngine(cfg, traces, OVS[:3], chunk_steps=CHUNK)
    fleet.run()
    caps = process_store().samples()[-1]["caps"]
    assert caps["chips"] == 1 and caps["chip_steps"] == [int(fleet.steps_run.max())]
    assert caps["elements"] == 3


# ---- a B the mesh does not divide -----------------------------------------------

def test_six_machines_on_four_devices_are_refused_before_anything_is_built(cfg):
    traces = _traces(False)[:6]
    with pytest.raises(DeviceMeshError, match=r"6 machines.*4 devices") as refused:
        FleetEngine(cfg, traces, OVS[:6], chunk_steps=CHUNK, mesh=tile_mesh(4))
    assert refused.value.location() == {"devices": 4}
    sharding.check_fleet_mesh(8, 4)
    sharding.check_fleet_mesh(1, 4)  # ONE machine is cut over the chips, as `Engine`'s is
    assert sharding.fleet_is_cut(1, 4) and not sharding.fleet_is_cut(1, 1)
    assert not sharding.fleet_is_cut(4, 4) and not sharding.fleet_is_cut(6, 4)
    assert [sharding.fleet_devices(n, 4) for n in (1, 2, 3, 4, 6, 8, 15, 16)] == \
        [4, 2, 3, 4, 3, 4, 3, 4]


def test_a_fleet_of_one_on_a_mesh_is_cut_as_the_solo_engine_is(cfg):
    trace = _traces(False)[2]
    fleet = FleetEngine(cfg, [trace], [OVS[1]], chunk_steps=CHUNK, mesh=tile_mesh(4))
    assert tuple(fleet.state.cycles.sharding.spec) == (None, AXIS)
    assert tuple(fleet.state.dirm.sharding.spec) == (None, AXIS)
    fleet.run()
    solo = Engine(apply_overrides(cfg, OVS[1]), trace, chunk_steps=CHUNK)
    solo.run()
    np.testing.assert_array_equal(fleet.cycles[0], solo.cycles)
    caps = process_store().samples()[-2]["caps"]  # the fleet's: the solo's came after
    assert caps["chips"] == 4 and caps["chip_steps"] == [solo.steps_run] * 4


def test_what_a_quarantine_leaves_lies_on_the_devices_that_divide_it(cfg):
    """`supervisor.build_fleet_isolated`: eight sources on four devices, one
    unreadable: the seven left lie on one device (no stand-in machine), six
    on three."""
    from primesim_tpu.sim.supervisor import build_fleet_isolated

    def bad():
        raise OSError("no such trace")

    traces = _traces(False)
    fleet, quarantined = build_fleet_isolated(
        cfg, traces[:3] + [bad] + traces[4:], OVS, chunk_steps=CHUNK, mesh=tile_mesh(4))
    assert [i for i, _ in quarantined] == [3] and fleet.n_elements == 7
    assert fleet.mesh.shape[AXIS] == 1 and fleet.element_ids == [0, 1, 2, 4, 5, 6, 7]
    fleet, quarantined = build_fleet_isolated(
        cfg, [bad, bad] + traces[2:], OVS, chunk_steps=CHUNK, mesh=tile_mesh(4))
    assert fleet.n_elements == 6 and fleet.mesh.shape[AXIS] == 3
    assert {s.data.shape[0] for s in fleet.state.cycles.addressable_shards} == {2}
    fleet.run()
    solo = Engine(apply_overrides(cfg, OVS[7]), traces[7], chunk_steps=CHUNK)
    solo.run()
    np.testing.assert_array_equal(fleet.cycles[5], solo.cycles)
    # nothing quarantined: the sweep as it was asked for has to lie on the mesh
    with pytest.raises(DeviceMeshError):
        build_fleet_isolated(cfg, traces[:6], OVS[:6], chunk_steps=CHUNK, mesh=tile_mesh(4))


# ---- `primetpu sweep --devices` -----------------------------------------------

SYNTH = "fft_like:n_phases=2,points_per_core=6,seed=9"


def _cli(capsys, argv) -> tuple:
    from primesim_tpu.cli import main

    rc = main(argv)
    out, err = capsys.readouterr()
    return rc, [json.loads(ln) for ln in out.strip().splitlines() if ln.startswith("{")], err


@pytest.fixture(scope="module")
def cfg_path(tmp_path_factory, cfg):
    path = tmp_path_factory.mktemp("fleet_on_chips") / "machine.json"
    path.write_text(cfg.to_json())
    return str(path)


def _vary(ov: dict) -> str:
    return ",".join(f"{k}={v}" for k, v in ov.items()) or "link_lat=1"  # element 0 as it stands


def test_cli_sweep_with_six_points_on_four_devices_exits_2(capsys, cfg_path):
    argv = ["sweep", cfg_path, "--synth", SYNTH, "--fold", "--chunk-steps", "8", "--devices", "4"]
    for ov in OVS[:6]:
        argv += ["--vary", _vary(ov)]
    for extra in ([], ["--strict"]):
        rc, _, err = _cli(capsys, argv + extra)
        assert rc == 2
        error = json.loads([ln for ln in err.splitlines() if ln.startswith("{")][-1])["error"]
        assert error["type"] == "DeviceMeshError" and error["location"] == {"devices": 4}
        assert "6 machines" in error["detail"] and "4 devices" in error["detail"]


def test_cli_sweep_on_four_devices_equals_eight_solo_runs(capsys, cfg_path, cfg, tmp_path):
    argv = ["sweep", cfg_path, "--synth", SYNTH, "--fold", "--chunk-steps", "8", "--devices", "4"]
    for ov in OVS:
        argv += ["--vary", _vary(ov)]
    rc, lines, _ = _cli(capsys, argv)
    assert rc == 0
    elems = [ln["detail"] for ln in lines if ln["metric"] == "simulated_MIPS"]
    assert [d["fleet_index"] for d in elems] == list(range(B))
    keys = ("instructions", "max_core_cycles", "noc_msgs")
    for e, ov in enumerate(OVS):
        solo = tmp_path / f"solo{e}.json"
        solo.write_text(apply_overrides(cfg, ov).to_json())
        rc, out, _ = _cli(capsys, ["run", str(solo), "--synth", SYNTH, "--fold",
                                   "--chunk-steps", "8"])
        assert rc == 0
        assert {k: out[-1]["detail"][k] for k in keys} == {k: elems[e][k] for k in keys}, e
    assert len({d["max_core_cycles"] for d in elems}) > 4  # the knobs are felt
