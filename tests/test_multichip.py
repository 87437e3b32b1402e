"""Multi-chip sharding parity: the sharded engine must be bit-exact.

Runs the vectorized engine over the 8-virtual-device CPU mesh (conftest
forces ``xla_force_host_platform_device_count=8``) with cores/banks sharded
over the tile axis, and asserts cycle counts and every stat counter match
the single-device run and the golden scalar model. This is the
single-host stand-in for PriME's multi-node MPI runs (SURVEY.md §4d).
"""

import jax
import numpy as np
import pytest

from primesim_tpu.config.machine import (
    CacheConfig,
    CoreConfig,
    MachineConfig,
    NocConfig,
    small_test_config,
)
from primesim_tpu.golden.sim import GoldenSim
from primesim_tpu.parallel.sharding import AXIS, tile_mesh
from primesim_tpu.sim.engine import Engine
from primesim_tpu.trace import synth


def _run_all(cfg, trace, mesh):
    g = GoldenSim(cfg, trace)
    g.run()
    e1 = Engine(cfg, trace, chunk_steps=64)
    e1.run()
    e8 = Engine(cfg, trace, chunk_steps=64, mesh=mesh)
    e8.run()
    return g, e1, e8


def test_eight_device_mesh_exists():
    assert len(jax.devices()) == 8


# the sharded parity matrix: machine x trace shape. `chunked` is rung 4's
# pair of selectors (a CPI a core, the full sharer map reduced in blocks:
# two words, blocks of one), `coarse` rung 5's (one sharer bit to four cores)
MACHINES = {
    "plain": lambda: small_test_config(n_cores=16, n_banks=8),
    "chunked": lambda: small_test_config(
        n_cores=64, n_banks=16, sharer_chunk_words=1,
        core=CoreConfig(cpi_pattern=(1, 1, 3, 3), o3_overlap_256=64),
        noc=NocConfig(mesh_x=8, mesh_y=8, link_lat=1, router_lat=1),
    ),
    "coarse": lambda: small_test_config(n_cores=16, n_banks=8, sharer_group=4),
}
GENERATORS = {
    "uniform_random": lambda n: synth.uniform_random(n, n_mem_ops=80, seed=7),
    "false_sharing": lambda n: synth.false_sharing(n, n_mem_ops=40, seed=3),
    "fft_like": lambda n: synth.fft_like(n, seed=5),
}


@pytest.mark.parametrize("gen", sorted(GENERATORS))
@pytest.mark.parametrize("machine", sorted(MACHINES))
def test_sharded_parity(machine, gen):
    cfg = MACHINES[machine]()
    trace = GENERATORS[gen](cfg.n_cores)
    mesh = tile_mesh(8)
    g, e1, e8 = _run_all(cfg, trace, mesh)
    np.testing.assert_array_equal(e8.cycles, g.cycles)
    np.testing.assert_array_equal(e8.cycles, e1.cycles)
    c_g, c_1, c_8 = g.counters, e1.counters, e8.counters
    for k in c_g:
        np.testing.assert_array_equal(c_8[k], c_g[k], err_msg=k)
        np.testing.assert_array_equal(c_8[k], c_1[k], err_msg=k)


def test_state_is_actually_sharded():
    cfg = small_test_config(n_cores=16, n_banks=8)
    trace = synth.stream(16)
    mesh = tile_mesh(8)
    e = Engine(cfg, trace, mesh=mesh)
    shardings = {
        "cycles": e.state.cycles.sharding,
        "dirm": e.state.dirm.sharding,
        "events": e.events.sharding,
    }
    for name, s in shardings.items():
        spec = s.spec
        assert spec and spec[0] == AXIS, (name, spec)
    # born sharded: after `Engine.__init__` alone no device holds a shard of
    # the directory or of the L1s with the array's full leading dimension
    for name in ("dirm", "l1"):
        whole = getattr(e.state, name)
        shards = whole.addressable_shards
        assert len(shards) == 8 and len({s.device for s in shards}) == 8
        assert all(s.data.shape[0] == whole.shape[0] // 8 for s in shards), name
    # and it still runs to completion sharded
    e.run()
    g = GoldenSim(cfg, trace)
    g.run()
    np.testing.assert_array_equal(e.cycles, g.cycles)


def test_state_builder_never_holds_an_unsharded_directory():
    """The program that builds a sharded machine's state
    (`parallel/sharding.py::build_state`) has, in its compiled text for one
    device of eight, no array with the directory's or the L1s' full leading
    dimension: each device fills its own shard and nothing else. (`Engine`
    used to build the whole state on device 0 and shard it afterwards:
    rung 4's 9.66 GB directory then fits no chip.)"""
    from primesim_tpu.parallel.sharding import _state_builder
    from primesim_tpu.sim.state import dirm_width

    cfg = MachineConfig(
        n_cores=256, n_banks=256,
        # an L1 of 1.3 MB over the cores: XLA folds a smaller one into a
        # literal that every device slices
        l1=CacheConfig(size=16384, ways=2, line=64, latency=2),
        llc=CacheConfig(size=4096, ways=4, line=64, latency=12),
        noc=NocConfig(mesh_x=16, mesh_y=16),
        quantum=600,
    )
    txt = _state_builder(tile_mesh(8)).lower(cfg).compile().as_text()
    rows, width = cfg.n_banks * cfg.llc.sets, dirm_width(cfg)
    assert f"s32[{rows // 8},{width}]" in txt  # the shard is there
    assert f"[{rows}," not in txt  # the whole directory is not
    l1_width = 5 * cfg.l1.ways * cfg.l1.sets
    assert f"s32[{cfg.n_cores // 8},{l1_width}]" in txt
    assert f"s32[{cfg.n_cores},{l1_width}]" not in txt


def test_global_tile_mesh_single_process():
    # parallel.distributed: in a single-process job the global mesh equals
    # the local-device mesh and the engine runs bit-exact on it (multi-host
    # behavior is XLA's SPMD contract over the same code path)
    from primesim_tpu.parallel.distributed import (
        global_tile_mesh,
        process_info,
    )

    info = process_info()
    assert info["process_count"] == 1 and info["global_devices"] == 8
    mesh = global_tile_mesh()
    cfg = small_test_config(8, n_banks=8)
    tr = synth.readers_writer(8, n_rounds=2, seed=92)
    e = Engine(cfg, tr, chunk_steps=16, mesh=mesh)
    e.run()
    g = GoldenSim(cfg, tr)
    g.run()
    np.testing.assert_array_equal(e.cycles, g.cycles)


def test_sharded_parity_256core():
    # VERDICT r4 #7: multi-chip correctness beyond toy shapes — 256 cores
    # / 256 banks sharded over all 8 devices, bit-exact vs the golden
    # scalar model (and transitively vs the unsharded engine, proven by
    # the other parity suites on the same generators)
    cfg = MachineConfig(
        n_cores=256, n_banks=256,
        l1=CacheConfig(size=1024, ways=2, line=64, latency=2),
        llc=CacheConfig(size=4096, ways=4, line=64, latency=12),
        noc=NocConfig(mesh_x=16, mesh_y=16),
        quantum=600,
    )
    tr = synth.readers_writer(256, n_rounds=2, block_lines=4, seed=93)
    e = Engine(cfg, tr, chunk_steps=64, mesh=tile_mesh(8))
    e.run()
    g = GoldenSim(cfg, tr)
    g.run()
    np.testing.assert_array_equal(e.cycles, g.cycles)
    ec = e.counters
    for k, v in g.counters.items():
        np.testing.assert_array_equal(ec[k], v, err_msg=k)


def test_sharded_step_never_allgathers_directory():
    # the round-2 regression's failure mode: a layout/sharding slip that
    # makes XLA materialize the FULL sharers/llc_meta array on every
    # device each step. Compile the sharded chunk and assert no
    # all-gather/all-reduce touches a directory-shaped operand.
    import re

    from primesim_tpu.parallel.sharding import shard_events, shard_state
    from primesim_tpu.sim.engine import run_chunk
    from primesim_tpu.sim.state import init_state

    cfg = MachineConfig(
        n_cores=256, n_banks=256,
        l1=CacheConfig(size=1024, ways=2, line=64, latency=2),
        llc=CacheConfig(size=4096, ways=4, line=64, latency=12),
        noc=NocConfig(mesh_x=16, mesh_y=16),
        quantum=600,
    )
    tr = synth.false_sharing(256, n_mem_ops=8, seed=94)
    mesh = tile_mesh(8)
    import jax.numpy as jnp

    events = shard_events(mesh, jnp.asarray(tr.line_events(cfg.line_bits)))
    st = shard_state(mesh, init_state(cfg))
    txt = run_chunk.lower(cfg, 4, events, st, has_sync=False).compile().as_text()
    B_S2 = cfg.n_banks * cfg.llc.sets  # full (unsharded) leading dim
    bad = [
        l
        for l in txt.splitlines()
        if re.search(r"all-gather|all-reduce", l) and f"[{B_S2}," in l
    ]
    assert not bad, "directory arrays all-gathered:\n" + "\n".join(bad[:5])
