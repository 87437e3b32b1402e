"""Router-occupancy NoC contention model (SURVEY.md §2 #6, BASELINE rung 3).

Hand-computed golden charges, golden-vs-engine bit-exact parity with the
model enabled (memory + sync paths), and the load-dependence property the
rung-3 "NoC-congestion heavy" config exists to show.
"""

import numpy as np
import pytest

from primesim_tpu.config.machine import (
    CacheConfig,
    MachineConfig,
    NocConfig,
    small_test_config,
)
from primesim_tpu.golden.sim import GoldenSim
from primesim_tpu.trace import synth
from primesim_tpu.trace.format import EV_LD, EV_LOCK, EV_UNLOCK, from_event_lists

from test_parity import assert_parity


def cfg4(contention=True, **kw):
    return small_test_config(
        4,
        noc=NocConfig(
            mesh_x=2, mesh_y=2, link_lat=1, router_lat=1,
            contention=contention, contention_lat=1,
        ),
        **kw,
    )


def test_golden_same_tile_transactions_queue():
    # lines 0 and 4 share home bank 0 (tile 0) but land in different
    # (bank,set) slots: both win the same step, count=2 at tile 0, each
    # charged +1. Cold LLC miss path: l1 + req + llc + dram + rep (+1).
    tr = from_event_lists([[(EV_LD, 4, 0)], [(EV_LD, 4, 4 * 64)], [], []])
    g = GoldenSim(cfg4(), tr)
    g.run()
    # c0 (tile 0 -> tile 0): 2+1+10+100+1 = 114 + 1 contention
    # c1 (tile 1 -> tile 0): 2+3+10+100+3 = 118 + 1 contention
    np.testing.assert_array_equal(g.cycles[:2], [115, 119])
    np.testing.assert_array_equal(g.counters["noc_contention_cycles"][:2], [1, 1])
    # same trace without contention: no extra
    g0 = GoldenSim(cfg4(contention=False), tr)
    g0.run()
    np.testing.assert_array_equal(g0.cycles[:2], [114, 118])


def test_golden_different_tiles_no_queue():
    # lines 0 (bank 0, tile 0) and 1 (bank 1, tile 1): disjoint home
    # tiles, no contention charge
    tr = from_event_lists([[(EV_LD, 4, 0)], [(EV_LD, 4, 64)], [], []])
    g = GoldenSim(cfg4(), tr)
    g.run()
    assert g.counters["noc_contention_cycles"].sum() == 0


def test_golden_lock_rmw_queues_with_memory():
    # core 0's LD and core 1's LOCK both target home tile 0 in the same
    # step: the lock RMW queues behind the memory transaction and vice
    # versa (+1 each)
    tr = from_event_lists(
        [[(EV_LD, 4, 0)], [(EV_LOCK, 0, 4 * 64), (EV_UNLOCK, 0, 4 * 64)], [], []]
    )
    g = GoldenSim(cfg4(), tr)
    g.run()
    assert g.counters["noc_contention_cycles"][0] == 1  # LD queued once
    # lock attempt queued once; unlock ran alone in the next step
    assert g.counters["noc_contention_cycles"][1] == 1


@pytest.mark.parametrize(
    "gen",
    ["false_sharing", "uniform_random", "lock_contention", "barrier_phases"],
)
def test_parity_with_contention(gen):
    cfg = cfg4(n_banks=4, quantum=300)
    tr = {
        "false_sharing": lambda: synth.false_sharing(4, n_mem_ops=40, seed=51),
        "uniform_random": lambda: synth.uniform_random(4, n_mem_ops=50, seed=52),
        "lock_contention": lambda: synth.lock_contention(4, n_critical=8, seed=53),
        "barrier_phases": lambda: synth.barrier_phases(4, n_phases=2, seed=54),
    }[gen]()
    assert_parity(cfg, tr, chunk_steps=50)


def test_parity_contention_8core_hot_bank():
    # every core hammers lines on ONE home bank: maximal router occupancy
    cfg = small_test_config(
        8, n_banks=4,
        noc=NocConfig(mesh_x=2, mesh_y=2, contention=True, contention_lat=3),
    )
    evs = [
        [(EV_LD, 4, (4 * i) * 64) for i in range(6)] for _ in range(8)
    ]  # lines 0,4,8,...: all bank 0
    assert_parity(cfg, from_event_lists(evs))


# -------------------------------------------------- per-link ("link") model


def test_engine_path_links_match_scalar_walk():
    # the vectorized XY path builder must be link-for-link identical to
    # the scalar noc.mesh.xy_links reference on every tile pair
    import numpy as np

    from primesim_tpu.noc.mesh import xy_links
    from primesim_tpu.noc.topology import path_links as _path_links
    import jax.numpy as jnp

    cfg = small_test_config(4, noc=NocConfig(mesh_x=4, mesh_y=3))
    nt = cfg.n_tiles
    a = np.repeat(np.arange(nt), nt).astype(np.int32)
    b = np.tile(np.arange(nt), nt).astype(np.int32)
    got = np.asarray(_path_links(cfg, jnp.asarray(a), jnp.asarray(b)))
    for k in range(nt * nt):
        want = xy_links(int(a[k]), int(b[k]), 4)
        row = tuple(x for x in got[k].tolist() if x >= 0)
        assert row == want, (int(a[k]), int(b[k]), row, want)


def test_golden_link_model_shared_link_queues():
    # 1x4 mesh (tiles 0-1-2-3 in a row). Core 0 (tile 0) -> bank 2
    # (tile 2) and core 1 (tile 1) -> bank 3 (tile 3): requests share the
    # eastward link out of tile 1 (and tile 2's), so BOTH transactions
    # queue (+1 each) even though their home TILES differ — exactly what
    # the tile model cannot see.
    cfg = small_test_config(
        4, n_banks=4,
        noc=NocConfig(mesh_x=4, mesh_y=1, contention=True,
                      contention_model="link", contention_lat=1),
    )
    tr = from_event_lists(
        [[(EV_LD, 4, 2 * 64)], [(EV_LD, 4, 3 * 64)], [], []]
    )
    g = GoldenSim(cfg, tr)
    g.run()
    np.testing.assert_array_equal(
        g.counters["noc_contention_cycles"][:2], [1, 1]
    )
    # same machine under the tile model: different home tiles, no charge
    cfg_t = small_test_config(
        4, n_banks=4,
        noc=NocConfig(mesh_x=4, mesh_y=1, contention=True,
                      contention_model="tile", contention_lat=1),
    )
    gt = GoldenSim(cfg_t, tr)
    gt.run()
    assert gt.counters["noc_contention_cycles"].sum() == 0


def test_golden_link_model_disjoint_paths_free():
    # 2x2 mesh: core 0 (tile 0) -> bank 1 (tile 1) east link; core 2
    # (tile 2) -> bank 3 (tile 3) east link at the other row — disjoint
    cfg = small_test_config(
        4, n_banks=4,
        noc=NocConfig(mesh_x=2, mesh_y=2, contention=True,
                      contention_model="link", contention_lat=1),
    )
    tr = from_event_lists(
        [[(EV_LD, 4, 1 * 64)], [], [(EV_LD, 4, 3 * 64)], []]
    )
    g = GoldenSim(cfg, tr)
    g.run()
    assert g.counters["noc_contention_cycles"].sum() == 0


@pytest.mark.parametrize(
    "gen", ["false_sharing", "lock_contention", "barrier_phases"]
)
@pytest.mark.slow
def test_parity_link_model(gen):
    cfg = small_test_config(
        8, n_banks=4, quantum=300,
        noc=NocConfig(mesh_x=4, mesh_y=2, contention=True,
                      contention_model="link", contention_lat=2),
    )
    tr = {
        "false_sharing": lambda: synth.false_sharing(8, n_mem_ops=40, seed=71),
        "lock_contention": lambda: synth.lock_contention(8, n_critical=8, seed=72),
        "barrier_phases": lambda: synth.barrier_phases(8, n_phases=2, seed=73),
    }[gen]()
    assert_parity(cfg, tr, chunk_steps=50)


@pytest.mark.slow
def test_parity_link_model_16core_hot_path():
    # many cores streaming through the same mesh column: heavy shared-link
    # occupancy, engine and golden must agree bit-exactly
    cfg = MachineConfig(
        n_cores=16, n_banks=16,
        l1=CacheConfig(size=1024, ways=2, line=64, latency=2),
        llc=CacheConfig(size=8192, ways=4, line=64, latency=10),
        noc=NocConfig(mesh_x=4, mesh_y=4, contention=True,
                      contention_model="link", contention_lat=1),
        quantum=400,
    )
    evs = [
        [(EV_LD, 4, ((c + i) % 16) * 64) for i in range(8)] for c in range(16)
    ]
    assert_parity(cfg, from_event_lists(evs), chunk_steps=50)


def test_contention_is_load_dependent():
    # the rung-3 property: a hot-BANK workload (all cores stream distinct
    # sets of the same bank, staggered so several (bank,set) winners land
    # on one tile per step) takes longer — and reports queueing cycles —
    # with contention on than off. (Same-LINE traffic alone never queues:
    # the (bank,set) serializer admits one winner per slot per step.)
    evs = [
        [(EV_LD, 4, (4 * ((i + 2 * c) % 16)) * 64) for i in range(12)]
        for c in range(8)
    ]  # lines 0,4,8,...: all home bank 0, 16 distinct sets
    tr = from_event_lists(evs)
    on = GoldenSim(small_test_config(8, n_banks=4, noc=NocConfig(
        mesh_x=2, mesh_y=2, contention=True, contention_lat=2)), tr)
    on.run()
    off = GoldenSim(small_test_config(8, n_banks=4, noc=NocConfig(
        mesh_x=2, mesh_y=2, contention=False)), tr)
    off.run()
    assert on.counters["noc_contention_cycles"].sum() > 0
    assert on.cycles.max() > off.cycles.max()
