"""Pod-scale composition: fleets on a mesh + the pipelined rung-5
path (ISSUE 16 tentpole, DESIGN.md §22; the fleet's layout since PR 51).

The contracts under test:

- `FleetEngine(..., mesh=...)` lays its batch axis over the chips, every
  machine whole on one chip (`fleet_state_pspecs()`), and per-element
  results are BIT-EXACT vs the unsharded fleet (and, transitively, vs a
  solo Engine) — across knob sweeps, fault injection, prefix forking, and
  checkpoint kill -> resume. tests/test_fleet_on_chips.py holds the
  layout, the build and the loop themselves.
- `state_pspecs()` is a TRIPWIRE for MachineState: adding a state field
  without deciding its partitioning fails here, not as a silent
  replication regression on a real pod.
- the ingest pipeline (segments -> SegmentSpool -> PipelineStreamEngine)
  assembles windows byte-identical to the plain StreamEngine fill, so
  pipelined runs are bit-exact; `--devices N` on a CLI sweep is bit-exact
  with `--devices 0`; bad mesh shapes exit 2 with one structured
  {"error": ...} line.
"""

import dataclasses
import json
import os

import numpy as np
import pytest

from primesim_tpu.config.machine import (
    FAULT_CORE_FAILSTOP,
    MachineConfig,
    small_test_config,
)
from primesim_tpu.parallel.sharding import (
    AXIS,
    DeviceMeshError,
    fleet_events_pspec,
    fleet_state_pspecs,
    state_pspecs,
    tile_mesh,
    validate_devices,
)
from primesim_tpu.sim.engine import Engine
from primesim_tpu.sim.fleet import FleetEngine, apply_overrides
from primesim_tpu.trace import synth

from test_fleet import assert_element_matches_solo

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHUNK = 16


def _cfg(n_cores=16, **kw):
    kw.setdefault("n_banks", 8)
    kw.setdefault("quantum", 200)
    return small_test_config(n_cores, **kw)


def _traces(n_cores=16):
    return [
        synth.false_sharing(n_cores, n_mem_ops=40, seed=11),
        synth.uniform_random(n_cores, n_mem_ops=60, seed=12),
        synth.lock_contention(n_cores, n_critical=6, seed=13),
        synth.fft_like(n_cores, n_phases=2, points_per_core=8, seed=14),
    ]


OVS = [
    {},
    {"llc_lat": 25, "dram_lat": 140, "l1_lat": 4},
    {"quantum": 150, "cpi": 2},
    {"link_lat": 3, "router_lat": 2},
]


def _assert_fleets_equal(a, b):
    np.testing.assert_array_equal(a.cycles, b.cycles)
    np.testing.assert_array_equal(a.steps_run, b.steps_run)
    for k, v in a.counters.items():
        np.testing.assert_array_equal(v, b.counters[k], err_msg=k)
    for f in a.state._fields:
        va, vb = getattr(a.state, f), getattr(b.state, f)
        if hasattr(va, "_fields"):
            for sub in va._fields:
                np.testing.assert_array_equal(
                    np.asarray(getattr(va, sub)),
                    np.asarray(getattr(vb, sub)),
                    err_msg=f"state field {f}.{sub}",
                )
            continue
        np.testing.assert_array_equal(
            np.asarray(va), np.asarray(vb), err_msg=f"state field {f}"
        )


# ---- pspec <-> MachineState tripwire --------------------------------------


def test_state_pspecs_cover_machine_state_exactly():
    """Adding a MachineState (or TimingKnobs/FaultState) field without
    deciding its partitioning must fail HERE, not as a silently
    replicated array on a real pod."""
    import jax
    from jax.sharding import PartitionSpec as P

    from primesim_tpu.sim.state import init_state

    specs = state_pspecs()
    st = init_state(_cfg(8, n_banks=4))
    is_p = lambda x: isinstance(x, P)  # noqa: E731
    assert jax.tree.structure(specs, is_leaf=is_p) == jax.tree.structure(st)
    for spec in jax.tree.leaves(specs, is_leaf=is_p):
        assert isinstance(spec, P), f"{spec!r}: not a PartitionSpec"
    fspecs = fleet_state_pspecs()
    assert jax.tree.structure(fspecs, is_leaf=is_p) == jax.tree.structure(st)
    for spec in jax.tree.leaves(fspecs, is_leaf=is_p):
        assert tuple(spec) == (AXIS,), f"{spec!r}: machines whole, the batch over the chips"
    assert tuple(fleet_events_pspec()) == (AXIS,)
    # the fleet of one machine on several chips (the pool's unit) is cut as `Engine`'s is
    cut = fleet_state_pspecs(cut=True)
    assert jax.tree.structure(cut, is_leaf=is_p) == jax.tree.structure(st)
    for spec, solo in zip(jax.tree.leaves(cut, is_leaf=is_p),
                          jax.tree.leaves(specs, is_leaf=is_p)):
        assert tuple(spec) == (None, *solo), f"{spec!r}: batch axis must stay unsharded"
    assert tuple(fleet_events_pspec(cut=True)) == (None, AXIS)


def test_state_pspecs_shard_the_core_and_bank_axes():
    specs = state_pspecs()
    assert tuple(specs.cycles) == (AXIS,)
    assert tuple(specs.dirm) == (AXIS,)
    assert tuple(specs.counters) == (None, AXIS)
    assert tuple(specs.faults.core_dead) == (AXIS,)


# ---- typed --devices validation -------------------------------------------


def test_validate_devices_typed_errors():
    cfg = _cfg(16, n_banks=8)
    validate_devices(cfg, 8)  # sound: divides both axes, 8 visible
    with pytest.raises(DeviceMeshError) as e:
        validate_devices(cfg, 5)
    assert e.value.location() == {"devices": 5, "visible": 8}
    with pytest.raises(DeviceMeshError) as e:
        validate_devices(cfg, 16)
    assert "visible" in str(e.value)
    with pytest.raises(DeviceMeshError):
        validate_devices(cfg, 0)
    # banks constrain too: 16 cores / 4 banks, devices=8 divides cores
    # but not banks
    with pytest.raises(DeviceMeshError) as e:
        validate_devices(_cfg(16, n_banks=4), 8)
    assert "n_banks" in str(e.value)


def test_cli_devices_errors_exit_2_with_structured_json(capsys):
    from primesim_tpu.cli import main

    cfg = os.path.join(REPO, "configs", "rung1_64core_fft.json")
    for args in (
        ["run", cfg, "--synth", "fft_like", "--devices", "5"],
        ["sweep", cfg, "--synth", "fft_like", "--devices", "48"],
    ):
        rc = main(args)
        assert rc == 2
        err = capsys.readouterr().err.strip().splitlines()[-1]
        obj = json.loads(err)
        assert obj["error"]["type"] == "DeviceMeshError"
        assert obj["error"]["location"]["devices"] in (5, 48)


# ---- fleet-on-a-mesh parity (machines whole, B / D a chip) ----------------


# heavy GSPMD compiles on the 8-device virtual mesh: slow-marked so the
# tier-1 budget stays seed-level; the multichip-fleet CI job runs these
@pytest.mark.slow
@pytest.mark.parametrize("devices", [2, 4])
def test_sharded_fleet_bit_exact_vs_unsharded_and_solo(devices):
    cfg = _cfg()
    traces = _traces()
    plain = FleetEngine(cfg, traces, OVS, chunk_steps=CHUNK)
    plain.run()
    sharded = FleetEngine(
        cfg, traces, OVS, chunk_steps=CHUNK, mesh=tile_mesh(devices)
    )
    sharded.run()
    _assert_fleets_equal(sharded, plain)
    # spot-check one element against a solo Engine of the effective cfg
    assert_element_matches_solo(
        sharded, 1, apply_overrides(cfg, OVS[1]), traces[1],
        chunk_steps=CHUNK,
    )


def test_sharded_fleet_state_is_actually_sharded():
    import jax

    cfg = _cfg()
    fleet = FleetEngine(
        cfg, _traces(), OVS, chunk_steps=CHUNK, mesh=tile_mesh(4)
    )
    spec = fleet.state.cycles.sharding.spec
    assert tuple(spec) == (AXIS,), spec
    assert tuple(fleet.events.sharding.spec) == (AXIS,)
    assert len(fleet.state.cycles.sharding.mesh.devices.flat) == 4
    fleet.run()
    # outputs keep the layout (`shard_map`'s out_specs, no host gather mid-run)
    assert tuple(fleet.state.cycles.sharding.spec) == (AXIS,)
    del jax


# heavy GSPMD compiles on the 8-device virtual mesh: slow-marked so the
# tier-1 budget stays seed-level; the multichip-fleet CI job runs these
@pytest.mark.slow
def test_sharded_fleet_fault_injection_parity():
    cfg = dataclasses.replace(
        _cfg(),
        faults_enabled=True,
        max_fault_events=1,
        fault_events=((30, FAULT_CORE_FAILSTOP, 3, 0),),
    )
    traces = [_traces()[1]] * 4
    ovs = [{"fault_seed": 100 + i} for i in range(4)]
    plain = FleetEngine(cfg, traces, ovs, chunk_steps=CHUNK)
    plain.run()
    sharded = FleetEngine(
        cfg, traces, ovs, chunk_steps=CHUNK, mesh=tile_mesh(4)
    )
    sharded.run()
    _assert_fleets_equal(sharded, plain)
    assert int(np.asarray(sharded.state.faults.core_dead).sum()) > 0


# heavy GSPMD compiles on the 8-device virtual mesh: slow-marked so the
# tier-1 budget stays seed-level; the multichip-fleet CI job runs these
@pytest.mark.slow
def test_sharded_fleet_prefix_fork_parity():
    """Prefix forking mutates fleet slots host-side (fork_element); the
    sharded fleet must re-lay the state out and stay bit-exact."""
    from primesim_tpu.config.machine import FAULT_LINK_DEGRADE
    from primesim_tpu.sim.prefix import execute_prefix_plan, plan_prefix

    cfg = dataclasses.replace(
        _cfg(),
        faults_enabled=True,
        max_fault_events=1,
        fault_events=((40, FAULT_LINK_DEGRADE, 0, 3),),
    )
    tr = _traces()[3]
    ovs = [{"fault_seed": 7 + i} for i in range(4)]
    plain = FleetEngine(cfg, [tr] * 4, ovs, chunk_steps=CHUNK)
    plain.run()

    forked = FleetEngine(
        cfg, [tr] * 4, ovs, chunk_steps=CHUNK, mesh=tile_mesh(4)
    )
    groups = plan_prefix(forked.elem_cfgs, forked.traces, chunk_steps=CHUNK)
    assert groups and groups[0].prefix_steps > 0
    st = execute_prefix_plan(forked, groups)
    assert st["forked_elements"] == 4
    assert tuple(forked.state.cycles.sharding.spec) == (AXIS,)
    forked.run()
    _assert_fleets_equal(forked, plain)


# heavy GSPMD compiles on the 8-device virtual mesh: slow-marked so the
# tier-1 budget stays seed-level; the multichip-fleet CI job runs these
@pytest.mark.slow
def test_sharded_fleet_checkpoint_kill_resume_parity(tmp_path):
    from primesim_tpu.sim.checkpoint import (
        load_fleet_checkpoint,
        save_fleet_checkpoint,
    )

    cfg = _cfg()
    traces = _traces()
    plain = FleetEngine(cfg, traces, OVS, chunk_steps=CHUNK)
    plain.run()

    first = FleetEngine(
        cfg, traces, OVS, chunk_steps=CHUNK, mesh=tile_mesh(4)
    )
    first.run_steps(2 * CHUNK)  # mid-run cut, then the "crash"
    path = str(tmp_path / "fleet.npz")
    save_fleet_checkpoint(path, first)
    del first

    resumed = FleetEngine(
        cfg, traces, OVS, chunk_steps=CHUNK, mesh=tile_mesh(2)
    )
    load_fleet_checkpoint(path, resumed)  # a resume crosses meshes
    assert tuple(resumed.state.cycles.sharding.spec) == (AXIS,)
    assert len(resumed.state.cycles.sharding.device_set) == 2
    resumed.run()
    _assert_fleets_equal(resumed, plain)


# heavy GSPMD compiles on the 8-device virtual mesh: slow-marked so the
# tier-1 budget stays seed-level; the multichip-fleet CI job runs these
@pytest.mark.slow
def test_sharded_stream_engine_bit_exact(tmp_path):
    from primesim_tpu.ingest.stream import StreamEngine

    cfg = _cfg()
    tr = synth.fft_like(16, n_phases=2, points_per_core=12, seed=31)
    plain = StreamEngine(cfg, tr, window_events=32)
    plain.warmup()
    plain.run()
    sharded = StreamEngine(cfg, tr, window_events=32, mesh=tile_mesh(8))
    sharded.warmup()
    sharded.run()
    np.testing.assert_array_equal(sharded.cycles, plain.cycles)
    for k, v in plain.counters.items():
        np.testing.assert_array_equal(sharded.counters[k], v, err_msg=k)


# heavy GSPMD compiles on the 8-device virtual mesh: slow-marked so the
# tier-1 budget stays seed-level; the multichip-fleet CI job runs these
@pytest.mark.slow
def test_cli_sweep_devices_bit_exact_vs_unsharded(capsys):
    from primesim_tpu.cli import main

    cfg_path = os.path.join(REPO, "configs", "rung1_64core_fft.json")
    base = [
        "sweep", cfg_path,
        "--synth", "fft_like:n_phases=2,points_per_core=8",
        "--vary", "llc_lat=10", "--vary", "llc_lat=20",
        "--chunk-steps", "64",
    ]

    def run(extra):
        assert main(base + extra) == 0
        lines = [
            json.loads(ln)
            for ln in capsys.readouterr().out.strip().splitlines()
        ]
        for d in lines:
            d["detail"].pop("wall_s", None)
            d["value"] = None  # MIPS embeds wall clock
        return lines

    assert run(["--devices", "2"]) == run([])


# ---- ingest pipeline (rung-5 stages) --------------------------------------


def test_segment_roundtrip_and_identity_check(tmp_path):
    from primesim_tpu.ingest.pipeline import (
        normalize_segment,
        read_segment,
        segment_path,
        write_segment,
    )

    cfg = _cfg(8, n_banks=4)
    tr = synth.uniform_random(8, n_mem_ops=50, seed=5)
    arr, n_valid = normalize_segment(cfg, tr, 0, 64)
    assert arr.shape == (8, 64, 4) and n_valid > 0
    p = segment_path(str(tmp_path), 0)
    write_segment(p, 0, 64, arr)
    np.testing.assert_array_equal(read_segment(p, 0, 64), arr)
    with pytest.raises(ValueError, match="identity"):
        read_segment(p, 1, 64)


# heavy GSPMD compiles on the 8-device virtual mesh: slow-marked so the
# tier-1 budget stays seed-level; the multichip-fleet CI job runs these
@pytest.mark.slow
def test_pipeline_stream_engine_bit_exact_vs_plain(tmp_path):
    """Windows assembled from pre-normalized segments carry the same
    bytes as the plain host fill — results bit-exact, segments evicted
    as the cursors pass them."""
    from primesim_tpu.ingest.pipeline import (
        PipelineStreamEngine,
        SegmentSpool,
        normalize_segment,
        segment_path,
        write_segment,
    )
    from primesim_tpu.ingest.stream import StreamEngine

    cfg = _cfg(8, n_banks=4)
    tr = synth.lock_contention(8, n_critical=8, seed=6)  # ragged lengths
    L = 32
    real_max = int((np.asarray(tr.lengths) - 1).max())
    n_segments = -(-real_max // L)
    for k in range(n_segments):  # "ingest stage" ran ahead of the sim
        arr, _ = normalize_segment(cfg, tr, k, L)
        write_segment(segment_path(str(tmp_path), k), k, L, arr)

    plain = StreamEngine(cfg, tr, window_events=16)
    plain.warmup()
    plain.run()
    spool = SegmentSpool(str(tmp_path), L, n_segments, timeout_s=5.0)
    piped = PipelineStreamEngine(cfg, tr, spool, window_events=16)
    piped.warmup()
    piped.run()
    np.testing.assert_array_equal(piped.cycles, plain.cycles)
    for k, v in plain.counters.items():
        np.testing.assert_array_equal(piped.counters[k], v, err_msg=k)
    assert spool.waits == 0  # everything was resident: no stalls


def test_pipeline_spool_blocks_until_segment_appears(tmp_path):
    from primesim_tpu.ingest.pipeline import (
        SegmentSpool,
        normalize_segment,
        segment_path,
        write_segment,
    )

    cfg = _cfg(8, n_banks=4)
    tr = synth.uniform_random(8, n_mem_ops=40, seed=9)
    arr, _ = normalize_segment(cfg, tr, 0, 64)
    wrote = {"done": False}

    def late_ingest():  # the wait_cb plays the part of a slow stage 1
        if not wrote["done"]:
            wrote["done"] = True
            write_segment(segment_path(str(tmp_path), 0), 0, 64, arr)

    spool = SegmentSpool(
        str(tmp_path), 64, 1, wait_cb=late_ingest, poll_s=0.01,
        timeout_s=5.0,
    )
    segs = spool.acquire(0, 0)
    np.testing.assert_array_equal(segs[0], arr)
    assert spool.waits == 1
    with pytest.raises(RuntimeError, match="stalled"):
        SegmentSpool(str(tmp_path), 64, 3, poll_s=0.01,
                     timeout_s=0.05).acquire(2, 2)


# heavy GSPMD compiles on the 8-device virtual mesh: slow-marked so the
# tier-1 budget stays seed-level; the multichip-fleet CI job runs these
@pytest.mark.slow
def test_run_pipelined_end_to_end_with_workers(tmp_path):
    """The full stage composition in miniature: pool ingest workers ->
    SegmentSpool -> supervised PipelineStreamEngine, bit-exact vs a
    plain supervised stream run, segments persisted for resume."""
    from primesim_tpu.ingest.pipeline import run_pipelined, segment_path
    from primesim_tpu.ingest.stream import StreamEngine

    cfg_path = os.path.join(REPO, "configs", "rung1_64core_fft.json")
    with open(cfg_path) as f:
        cfg = MachineConfig.from_json(f.read())
    spec = "fft_like:n_phases=2,points_per_core=8"
    tr = synth.fft_like(64, n_phases=2, points_per_core=8)
    pool_dir = str(tmp_path / "pool")
    eng, sup, stats = run_pipelined(
        cfg, tr,
        synth_spec=spec,
        window_events=64,
        seg_events=128,
        ingest_workers=2,
        pool_dir=pool_dir,
        supervisor_kwargs={"snapshot_dir": str(tmp_path / "ckpt"),
                           "checkpoint_every_chunks": 4},
    )
    assert stats["pool"]["units_done"] == stats["segments"]
    assert os.path.exists(segment_path(pool_dir, 0))
    plain = StreamEngine(cfg, tr, window_events=64)
    plain.warmup()
    plain.run()
    np.testing.assert_array_equal(eng.cycles, plain.cycles)
    for k, v in plain.counters.items():
        np.testing.assert_array_equal(eng.counters[k], v, err_msg=k)
    assert sup.committed > 0


def test_ingest_units_join_the_lease_ledger_identity():
    from primesim_tpu.pool.units import build_ingest_units, build_units

    cfg = _cfg(8, n_banks=4)
    units = build_ingest_units(cfg, None, "fft_like", 128, 3)
    assert [u["unit_id"] for u in units] == ["g00000", "g00001", "g00002"]
    assert len({u["key"] for u in units}) == 3  # seg_index joins the key
    # sim units without a mesh keep their pre-pod key shape: devices
    # joins the identity only when set
    a = build_units(cfg, [], ["fft_like"], [{}], fold=False,
                    chunk_steps=64, max_steps=1000)
    b = build_units(cfg, [], ["fft_like"], [{}], fold=False,
                    chunk_steps=64, max_steps=1000, devices=4)
    assert a[0]["key"] != b[0]["key"]
    assert "devices" not in a[0]


# ---- rung-5 smoke slice (slow) --------------------------------------------


@pytest.mark.slow
def test_rung5_pipelined_sharded_smoke(tmp_path):
    """A thin slice of the acceptance run: the rung-5 wafer config,
    sharded over the 8-device virtual mesh, pipelined ingest, supervised
    with checkpoints — completing end-to-end on a short synthetic
    workload."""
    from primesim_tpu.ingest.pipeline import run_pipelined

    with open(os.path.join(
        REPO, "configs", "rung5_16384core_wafer.json"
    )) as f:
        cfg = MachineConfig.from_json(f.read())
    tr = synth.fft_like(16384, n_phases=1, points_per_core=2)
    eng, sup, stats = run_pipelined(
        cfg, tr,
        synth_spec="fft_like:n_phases=1,points_per_core=2",
        window_events=32,
        ingest_workers=2,
        pool_dir=str(tmp_path / "pool"),
        mesh=tile_mesh(8),
        supervisor_kwargs={"snapshot_dir": str(tmp_path / "ckpt"),
                           "checkpoint_every_chunks": 2},
    )
    assert stats["pool"]["units_done"] == stats["segments"]
    assert sup.committed > 0
    assert int(eng.counters["instructions"].sum()) > 0
    assert bool(np.asarray(eng.done))
