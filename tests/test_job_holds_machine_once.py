"""While a job runs, the device holds its machine once (PR 54, DESIGN.md §6).

Two halves. The solo fused loop, `run_loop`, DONATES the state it is given:
the compiled program lays every leaf of its result in the argument's
buffer, and the caller's arrays are deleted. And `init_state` lays `dirm`
and `l1` by ONE op each (a row broadcast over the rows), the same bytes as
the `concatenate` of their parts it was until then, so the one-chip build
no longer passes through the machine twice.

Nothing else donates: `fleet_run_loop` (tried on the chip and taken back:
the compiler kept less of the fleet's step in fast memory), and the chunked
paths (`run_steps` with `overlap`, a supervisor's rollback, element surgery
from a snapshot), which keep their source.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark_modules import ROOT, LiveBytes

from primesim_tpu.config.machine import (
    CacheConfig,
    MachineConfig,
    NocConfig,
    small_test_config,
)
from primesim_tpu.parallel import sharding
from primesim_tpu.parallel.sharding import tile_mesh
from primesim_tpu.sim.engine import Engine, run_chunk, run_loop
from primesim_tpu.sim.fleet import FleetEngine, fleet_run_chunk, fleet_run_loop
from primesim_tpu.sim.state import (
    I,
    MachineState,
    dirm_width,
    init_state,
)
from primesim_tpu.sim.supervisor import RunSupervisor
from primesim_tpu.stats.counters import COUNTER_NAMES, N_BLOCK_ROWS
from primesim_tpu.trace import synth

CHUNK = 8


def _cfg(**kw):
    kw.setdefault("quantum", 200)
    return small_test_config(16, n_banks=8, **kw)


def _trace(seed=41):
    return synth.fft_like(16, n_phases=2, points_per_core=12, seed=seed)


def _state_bytes(state) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(state))


def _deleted(state) -> list:
    return [x.is_deleted() for x in jax.tree.leaves(state)]


def _same_state(a, b):
    for (path, x), y in zip(jax.tree_util.tree_leaves_with_path(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y), err_msg=str(path))


# ---- (a) the compiled loops take the state in place -----------------------

def _solo(mesh):
    eng = Engine(_cfg(), _trace(), chunk_steps=CHUNK, mesh=mesh)
    return run_loop, run_chunk, (eng.cfg, CHUNK, eng.events, eng.state), eng


def _fleet(mesh):
    fleet = FleetEngine(_cfg(), [_trace(41 + e) for e in range(4)],
                        [{}, {"dram_lat": 150}, {"llc_lat": 14}, {"quantum": 100}],
                        chunk_steps=CHUNK, mesh=mesh)
    return (fleet_run_loop, fleet_run_chunk,
            (fleet.geom_cfg, CHUNK, fleet.events, fleet.state), fleet)


LOOPS = {"solo": _solo, "fleet": _fleet}


def _aliases_nothing(compiled) -> bool:
    return (compiled.memory_analysis().alias_size_in_bytes == 0
            and "input_output_alias" not in compiled.as_text().split("\n", 1)[0])


@pytest.mark.parametrize("devices", [1, 4])
@pytest.mark.parametrize("kind", sorted(LOOPS))
def test_the_solo_fused_loop_lays_its_result_in_the_state_it_is_given(kind, devices):
    """The compiler's own count: of `run_loop`'s outputs' bytes, the whole
    state's are aliased to an argument, on one device and on a mesh of four
    (a device's share there). `fleet_run_loop` and the chunk loops alias
    nothing."""
    mesh = tile_mesh(devices) if devices > 1 else None
    loop, chunk, args, eng = LOOPS[kind](mesh)
    compiled = loop.lower(*args, jnp.asarray(4, jnp.int32), has_sync=eng.has_sync).compile()
    assert _aliases_nothing(chunk.lower(*args, has_sync=eng.has_sync).compile())
    if kind == "fleet":
        assert _aliases_nothing(compiled)
        return
    mem = compiled.memory_analysis()
    a_device = _state_bytes(jax.tree.map(lambda x: x.addressable_shards[0].data, eng.state))
    assert mem.alias_size_in_bytes >= a_device > 0
    assert mem.alias_size_in_bytes <= mem.output_size_in_bytes
    # and by name: every leaf of the state is a parameter some output aliases
    header = compiled.as_text().split("\n", 1)[0]
    assert "input_output_alias" in header
    assert header.count("may-alias") + header.count("must-alias") >= len(
        jax.tree.leaves(eng.state))


@pytest.mark.parametrize("devices", [1, 4])
def test_only_the_state_is_donated(devices):
    """`events` is not: an engine runs again on it. Nor the chunk count."""
    mesh = tile_mesh(devices) if devices > 1 else None
    eng = Engine(_cfg(), _trace(), chunk_steps=CHUNK, mesh=mesh)
    events, handed, n = eng.events, eng.state, jnp.asarray(1, jnp.int32)
    out = run_loop(eng.cfg, CHUNK, events, handed, n, has_sync=eng.has_sync)
    assert all(_deleted(handed)) and not any(_deleted(out))
    assert not any(_deleted(events)) and not n.is_deleted()
    # a deleted array still lowers (the benchmark's warm-up asks for the
    # program's text after its first call) and is still told its mesh
    text = run_loop.lower(eng.cfg, CHUNK, events, handed, n, has_sync=eng.has_sync).as_text()
    assert ("sharding" in text) == (devices > 1)
    if devices == 1:  # (on the CPU's mesh the refused call wedges the devices' queues)
        with pytest.raises((RuntimeError, ValueError), match="deleted|donated"):
            run_loop(eng.cfg, CHUNK, events, handed, n, has_sync=eng.has_sync)


# ---- the holders of a state across a fused run ----------------------------

def test_a_fused_run_drops_the_chunk_it_had_speculated():
    """`run_steps` with `overlap` keeps its source (the committed state and
    the chunk speculated from it, both alive); a fused `run()` after it
    takes the state in place and drops the speculation."""
    cfg, tr = _cfg(), _trace()
    ref = Engine(cfg, tr, chunk_steps=CHUNK)
    ref.run_chunked()
    eng = Engine(cfg, tr, chunk_steps=CHUNK)
    eng.overlap = True
    eng.run_steps(2 * CHUNK)
    src, nxt, _ = eng._pending
    assert src is eng.state and not any(_deleted(src)) and not any(_deleted(nxt))
    eng.run_steps(CHUNK)  # adopts `nxt`: the speculation's source was kept whole
    assert eng.state.dirm is nxt.dirm and not any(_deleted(src))
    held = eng.state
    eng.run()
    assert eng._pending is None and all(_deleted(held))
    np.testing.assert_array_equal(eng.cycles, ref.cycles)
    _same_state(eng.state, ref.state)
    fleet = FleetEngine(cfg, [tr, tr], [{}, {"dram_lat": 150}], chunk_steps=CHUNK)
    fleet.overlap = True
    fleet.run_steps(2 * CHUNK)
    held = fleet.state
    assert fleet._pending[0] is held
    fleet.run()  # the fleet's loop keeps its source, speculation and all
    assert not any(_deleted(held)) and fleet.state is not held
    np.testing.assert_array_equal(fleet.cycles[0], ref.cycles)


def test_a_supervisors_rollback_reads_the_state_it_kept():
    """The supervisor's snapshot is a reference to the engine's state; the
    chunk that dies after its work ran on it undonated, so the rollback
    restores arrays that are alive, and the run ends where a fused one
    does."""
    cfg, tr = _cfg(), _trace()
    ref = Engine(cfg, tr, chunk_steps=CHUNK)
    ref.run()
    eng = Engine(cfg, tr, chunk_steps=CHUNK)
    orig, calls, kept = eng.run_steps, {"n": 0}, []

    def flaky(n):
        calls["n"] += 1
        if calls["n"] == 2:
            kept.append(eng.state)
            orig(n)
            raise RuntimeError("UNAVAILABLE: died after the work")
        return orig(n)

    eng.run_steps = flaky
    sup = RunSupervisor(eng, backoff_s=0.001)
    sup.run()
    assert sup.retries == 1 and not any(_deleted(kept[0]))
    np.testing.assert_array_equal(eng.cycles, ref.cycles)
    for k, v in ref.counters.items():
        np.testing.assert_array_equal(eng.counters[k], v, err_msg=k)


@pytest.mark.parametrize("surgery", ["restore_element", "fork_element"])
def test_a_snapshot_outlives_the_fused_runs_of_engines_built_from_it(surgery):
    """A prefix snapshot holds device arrays (`sim/prefix.py`: the prefix
    engine's own state). Element surgery copies them into the fleet's
    batch, so nothing a fleet's run does reaches the snapshot: a second
    fleet is built from the same snapshot afterwards and ends where an
    uninterrupted solo run does; and the prefix engine's own fused run,
    which DOES consume its state, comes last."""
    cfg, tr = _cfg(), _trace()
    ref = Engine(cfg, tr, chunk_steps=CHUNK)
    ref.run()
    prefix = Engine(cfg, tr, chunk_steps=CHUNK)
    prefix.run_steps(2 * CHUNK)
    prefix._drain()
    snap = {"state": prefix.state, "cycle_base": np.int64(prefix.cycle_base),
            "steps_run": np.int64(prefix.steps_run),
            "host_counters": {k: v.copy() for k, v in prefix.host_counters.items()},
            "host_stats": {k: v.copy() for k, v in prefix.host_stats.items()}}
    for _ in range(2):
        fleet = FleetEngine(cfg, [tr, tr], [{}, {}], chunk_steps=CHUNK)
        for i in range(2):
            getattr(fleet, surgery)(i, snap)
        fleet.run()
        assert not any(_deleted(snap["state"]))
        for i in range(2):
            np.testing.assert_array_equal(fleet.cycles[i], ref.cycles)
            for k, v in ref.counters.items():
                np.testing.assert_array_equal(fleet.counters[k][i], v, err_msg=k)
    assert not any(_deleted(prefix.state))
    prefix.run()  # and the snapshot's own engine runs on, fused: its arrays go
    assert all(_deleted(snap["state"]))
    np.testing.assert_array_equal(prefix.cycles, ref.cycles)


# ---- (b) `init_state` lays each large leaf once ----------------------------

def _concatenate_form(cfg: MachineConfig, stat_rows: bool = True) -> MachineState:
    """`init_state` as it stood until PR 54: `l1` and `dirm` a `concatenate`
    of their parts."""
    C, B = cfg.n_cores, cfg.n_banks
    s1, w1 = cfg.l1.sets, cfg.l1.ways
    s2, w2 = cfg.llc.sets, cfg.llc.ways
    return init_state(cfg, stat_rows)._replace(
        l1=jnp.concatenate([
            jnp.full((C, w1 * s1), -1, jnp.int32),
            jnp.full((C, w1 * s1), I, jnp.int32),
            jnp.zeros((C, 3 * w1 * s1), jnp.int32)], axis=1),
        dirm=jnp.concatenate([
            jnp.full((B * s2, 2 * w2), -1, jnp.int32),
            jnp.zeros((B * s2, dirm_width(cfg) - 2 * w2), jnp.int32)], axis=1),
    )


def _small(cfg: MachineConfig, cores: int, mesh_x: int, mesh_y: int, **kw) -> MachineConfig:
    """A shipped configuration's selectors and cache shapes on `cores`
    cores and banks (its L1 and LLC sets cut to a few)."""
    return dataclasses.replace(
        cfg, n_cores=cores, n_banks=cores,
        l1=dataclasses.replace(cfg.l1, size=cfg.l1.ways * cfg.l1.line * 4),
        llc=dataclasses.replace(cfg.llc, size=cfg.llc.ways * cfg.llc.line * 8),
        noc=dataclasses.replace(cfg.noc, mesh_x=mesh_x, mesh_y=mesh_y),
        core=dataclasses.replace(cfg.core, cpi_per_core=None), **kw)


def _shipped(name: str, cores: int, mesh_x: int, mesh_y: int, **kw):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        machine = json.load(f)["machine"]
    return _small(MachineConfig.from_dict(machine), cores, mesh_x, mesh_y, **kw)


GEOMETRIES = {
    # the directory's three row forms: the full map in one word, the coarse
    # vector (rung 5's `sharer_group` 64 on 16384 cores: 8 words a way; here
    # 4 on 64), the full map in blocks (rung 4's `sharer_chunk_words` 8 on
    # 128 words a way; here 1 on 2)
    "mesh1024": lambda: _shipped("mesh1024", 16, 4, 4),
    "rung2-sweep-b16": lambda: _shipped("rung2-sweep-b16", 16, 4, 4),
    "rung3": lambda: _shipped("rung3", 16, 4, 4),
    "rung3-sync": lambda: _shipped("rung3-sync", 16, 4, 4),
    "rung4-x4": lambda: _shipped("rung4-x4", 64, 8, 8, sharer_chunk_words=1),
    # the same machine whole on one device (PR 55: `rung4.fft-m18-4k`)
    "rung4": lambda: _shipped("rung4", 64, 8, 8, sharer_chunk_words=1),
    "rung5": lambda: _shipped("rung5", 64, 8, 8, sharer_group=4),
    "two-words-16-banks": lambda: MachineConfig(
        n_cores=64, n_banks=16,
        l1=CacheConfig(size=1024, ways=2, line=64, latency=2),
        llc=CacheConfig(size=4096, ways=4, line=64, latency=10),
        noc=NocConfig(mesh_x=4, mesh_y=4), quantum=500, sharer_chunk_words=1),
}


@pytest.mark.parametrize("stat_rows", [True, False])
@pytest.mark.parametrize("name", sorted(GEOMETRIES))
def test_init_state_lays_the_concatenate_forms_bytes(name, stat_rows):
    cfg = GEOMETRIES[name]()
    got, want = init_state(cfg, stat_rows), _concatenate_form(cfg, stat_rows)
    assert got.dirm.shape == (cfg.n_banks * cfg.llc.sets, dirm_width(cfg))
    assert got.l1.shape == (cfg.n_cores, 5 * cfg.l1.ways * cfg.l1.sets)
    assert got.counters.shape[0] == (N_BLOCK_ROWS if stat_rows else len(COUNTER_NAMES))
    for (path, x), y in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree.leaves(want)):
        assert x.dtype == y.dtype and x.shape == y.shape, path
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y), err_msg=str(path))
    # a row: tag/owner pairs invalid, everything after them zero
    w2 = cfg.llc.ways
    assert (np.asarray(got.dirm[:, :2 * w2]) == -1).all()
    assert not np.asarray(got.dirm[:, 2 * w2:]).any()


@pytest.mark.parametrize("name", sorted(GEOMETRIES))
def test_the_compiled_builders_lay_the_same_bytes_on_a_mesh(name):
    """`_state_builder` (a machine cut over four devices, no stat rows) and
    `build_fleet_state` (whole machines a device) call the same
    `init_state`."""
    cfg = GEOMETRIES[name]()
    mesh = tile_mesh(4)
    built = sharding.build_state(cfg, mesh)
    assert tuple(built.dirm.sharding.spec)[0] == sharding.AXIS
    assert len(built.dirm.addressable_shards) == 4
    assert built.dirm.addressable_shards[0].data.shape[0] == built.dirm.shape[0] // 4
    _same_state(built, _concatenate_form(cfg, stat_rows=False))
    fleet = sharding.build_fleet_state([cfg] * 4, mesh)
    one = _concatenate_form(cfg)
    _same_state(fleet, jax.tree.map(lambda x: np.stack([np.asarray(x)] * 4), one))
    _same_state(sharding.build_fleet_state([cfg] * 2),
                jax.tree.map(lambda x: np.stack([np.asarray(x)] * 2), one))


def test_the_one_chip_build_holds_each_large_leaf_once():
    """The build's jaxpr: `dirm` and `l1` are each the result of ONE
    `broadcast_in_dim` of a `[W]` row, and no op of the build takes an
    operand as large as either (a `concatenate` would: its parts)."""
    cfg = GEOMETRIES["rung3"]()
    jaxpr = jax.make_jaxpr(lambda: init_state(cfg))().jaxpr
    made_by = {id(v): e for e in jaxpr.eqns for v in e.outvars}
    leaves = dict(zip(MachineState._fields, jaxpr.outvars))  # `dirm`, `l1` before the nested leaves
    for leaf in ("dirm", "l1"):
        out = leaves[leaf]
        eqn = made_by[id(out)]
        assert out.aval.shape == getattr(init_state(cfg), leaf).shape
        assert eqn.primitive.name == "broadcast_in_dim", (leaf, eqn)
        (row,) = eqn.invars
        assert row.aval.shape == (out.aval.shape[1],)
    least = min(leaves["dirm"].aval.size, leaves["l1"].aval.size)
    for e in jaxpr.eqns:
        assert all(v.aval.size < least for v in e.invars if hasattr(v, "aval")), e


# ---- (c) nothing of a job outlives it (PR 55) -------------------------------

def test_the_second_job_in_a_row_finds_nothing_of_the_first(monkeypatch):
    """`rung4.fft-m18-4k`'s machine is 9.7 GB of a chip's 15.75: a second
    copy is `RESOURCE_EXHAUSTED`, not a figure in `hbm_peak_gb`. So what a
    job leaves behind is held to nothing, at a small size, through the
    benchmark's own `runners/solo.py` (`warm_up`, then `run_job` twice, as
    `measure.run_cell` calls them) with the collector OFF: an engine, a
    result or a sample that kept its job's state alive in a reference cycle
    would still lie there when the next engine is built (`timed_job`
    collects only after that). From `place`: every job's engine was built
    over the same bytes, the process's peak never passed two machines, and
    a job held its machine once."""
    import gc

    import cells  # benchmark/ is on the path (benchmark_modules)
    import measure
    import trafficgen
    from primesim_tpu.obs import process_store
    from primesim_tpu.sim import engine
    from primesim_tpu.trace.format import Trace

    cfg = GEOMETRIES["rung4"]()
    with open(os.path.join(ROOT, "benchmark", "traffic", "fft-m18-4k.json")) as f:
        traffic = json.load(f)
    ev = trafficgen.make_trace(traffic, cfg.n_cores, 404)
    trace = Trace(ev, measure._lengths(ev))
    run = {"chunk_steps": CHUNK, "step_impl": "xla", "devices": 1}
    solo = cells.load_runner("solo")
    monkeypatch.setattr(engine, "alloc_now", LiveBytes())
    gc.collect()
    under = engine.alloc_now()[0]["bytes_in_use"]  # other tests' arrays, if any
    monkeypatch.setattr(gc, "collect", lambda *a: 0)  # `timed_job`'s own
    gc.disable()
    try:
        solo.warm_up(cfg, run, trace, None, True)  # a traced run's: it lowers again
        jobs = [solo.run_job(cfg, run, trace, ev, None, None) for _ in range(2)]
        after = engine.alloc_now()[0]["bytes_in_use"]
    finally:
        gc.enable()
    assert jobs[0]["digest"] == jobs[1]["digest"] and not jobs[0]["not_at_end"]
    first, second = (s["place"] for s in process_store().samples()[-2:])
    state = first["state_bytes"][0]
    assert state == _state_bytes(init_state(cfg)) and second["state_bytes"] == [state]
    # nothing of the warm-up under the first job, nothing of the first under the second
    assert first["alloc"]["bytes_in_use"] == second["alloc"]["bytes_in_use"] == [under] == [after]
    events = jobs[0]["events_shape"]
    trace_bytes = _state_bytes(Engine(cfg, trace, chunk_steps=CHUNK).events)
    assert trace_bytes >= int(np.prod(events[0])) * events[1]
    for place in (first, second):
        # built: the state and the trace; run: the same and the loop's small results
        assert place["alloc_built"]["bytes_in_use"] == [under + state + trace_bytes]
        held = place["alloc_run"]["bytes_in_use"][0] - under
        assert state + trace_bytes <= held < state + trace_bytes + state // 10
        assert place["alloc_run"]["peak_bytes_in_use"][0] - under < 2 * state
