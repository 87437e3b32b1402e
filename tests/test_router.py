"""Hop-by-hop router NoC model (SURVEY.md §2 #6 [DRIVER], VERDICT r4 #2).

Covers: exact analytic equivalence when uncontended, hand-computed FIFO
queueing on a shared link, cross-step link-clock carry, golden-vs-engine
bit-exact parity (memory + sync paths, including with local runs and the
fused run_loop's on-device rebase), and the load-dependence property.
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
from primesim_tpu.trace.format import EV_LD, EV_ST, from_event_lists

from test_parity import assert_parity


def rcfg(n=4, mesh_x=2, mesh_y=2, **kw):
    return small_test_config(
        n,
        noc=NocConfig(
            mesh_x=mesh_x, mesh_y=mesh_y, link_lat=1, router_lat=1,
            contention=True, contention_model="router",
        ),
        **kw,
    )


def test_uncontended_equals_analytic():
    # a single transaction must cost exactly the analytic latency: the
    # hop walk with empty queues IS hops*link + (hops+1)*router
    tr = from_event_lists([[(EV_LD, 4, 0)], [], [], []])
    g_r = GoldenSim(rcfg(), tr)
    g_r.run()
    g_0 = GoldenSim(
        small_test_config(4, noc=NocConfig(mesh_x=2, mesh_y=2)), tr
    )
    g_0.run()
    np.testing.assert_array_equal(g_r.cycles, g_0.cycles)
    assert g_r.counters["noc_contention_cycles"].sum() == 0


def test_shared_link_fifo_queues():
    # 1x4 mesh: core 0 (tile 0) -> bank 2, core 1 (tile 1) -> bank 3.
    # Both requests cross the eastward link out of tile 1; core 1 has the
    # larger (clock, core) key, so it queues exactly link_lat behind core
    # 0's nominal arrival there.
    cfg = rcfg(4, mesh_x=4, mesh_y=1, n_banks=4)
    tr = from_event_lists([[(EV_LD, 4, 2 * 64)], [(EV_LD, 4, 3 * 64)], [], []])
    g = GoldenSim(cfg, tr)
    g.run()
    np.testing.assert_array_equal(
        g.counters["noc_contention_cycles"][:2], [0, 1]
    )
    # the touched links' clocks advanced to their last departures
    assert (g.link_free != 0).any()


def test_link_clock_carries_across_steps():
    # same shared-link pair twice: the second round's packets queue
    # behind the FIRST round's link departures (cross-step state), so
    # round 2 charges more than a fresh round-1-only run
    cfg = rcfg(4, mesh_x=4, mesh_y=1, n_banks=4)
    one = from_event_lists(
        [[(EV_LD, 4, 2 * 64)], [(EV_LD, 4, 3 * 64)], [], []]
    )
    two = from_event_lists(
        [
            [(EV_LD, 4, 2 * 64), (EV_LD, 4, 6 * 64)],
            [(EV_LD, 4, 3 * 64), (EV_LD, 4, 7 * 64)],
            [],
            [],
        ]
    )
    g1 = GoldenSim(cfg, one)
    g1.run()
    g2 = GoldenSim(cfg, two)
    g2.run()
    assert (
        g2.counters["noc_contention_cycles"].sum()
        > g1.counters["noc_contention_cycles"].sum()
    )


@pytest.mark.parametrize(
    "gen",
    ["false_sharing", "uniform_random", "lock_contention", "barrier_phases"],
)
def test_parity_router(gen):
    cfg = rcfg(4, n_banks=4, quantum=300)
    tr = {
        "false_sharing": lambda: synth.false_sharing(4, n_mem_ops=40, seed=61),
        "uniform_random": lambda: synth.uniform_random(4, n_mem_ops=50, seed=62),
        "lock_contention": lambda: synth.lock_contention(4, n_critical=8, seed=63),
        "barrier_phases": lambda: synth.barrier_phases(4, n_phases=2, seed=64),
    }[gen]()
    assert_parity(cfg, tr, chunk_steps=50)


def test_parity_router_16core_hot_path():
    # many cores streaming through the same mesh column: deep per-link
    # FIFOs and multi-step queue carry; engine must stay bit-exact
    cfg = MachineConfig(
        n_cores=16, n_banks=16,
        l1=CacheConfig(size=1024, ways=2, line=64, latency=2),
        llc=CacheConfig(size=8192, ways=4, line=64, latency=10),
        noc=NocConfig(mesh_x=4, mesh_y=4, contention=True,
                      contention_model="router"),
        quantum=400,
    )
    evs = [
        [(EV_LD, 4, ((c + i) % 16) * 64) for i in range(8)] for c in range(16)
    ]
    assert_parity(cfg, from_event_lists(evs), chunk_steps=50)


def test_parity_router_with_local_runs_and_o3():
    # rung-3-shaped machine features together: router + local runs + O3
    # overlap + heterogeneous CPI; exercises the fused run_loop path with
    # its on-device link_free rebase
    from primesim_tpu.config.machine import CoreConfig

    cfg = small_test_config(
        8, n_banks=8, quantum=500, local_run_len=4,
        core=CoreConfig(cpi_pattern=(1, 2), o3_overlap_256=64),
        noc=NocConfig(mesh_x=4, mesh_y=2, contention=True,
                      contention_model="router"),
    )
    evs = []
    rng = np.random.default_rng(5)
    for c in range(8):
        core = []
        for i in range(30):
            line = int(rng.integers(0, 24))
            t = EV_ST if rng.random() < 0.4 else EV_LD
            core.append((t, 2, line * 64))
        evs.append(core)
    assert_parity(cfg, from_event_lists(evs), chunk_steps=16)


def test_router_is_load_dependent():
    # rung-3 property: hot-bank streaming takes longer (and reports
    # queueing cycles) with the router than without contention
    evs = [
        [(EV_LD, 4, (4 * ((i + 2 * c) % 16)) * 64) for i in range(12)]
        for c in range(8)
    ]
    tr = from_event_lists(evs)
    on = GoldenSim(rcfg(8, n_banks=4), tr)
    on.run()
    off = GoldenSim(
        small_test_config(
            8, n_banks=4, noc=NocConfig(mesh_x=2, mesh_y=2)
        ),
        tr,
    )
    off.run()
    assert on.counters["noc_contention_cycles"].sum() > 0
    assert on.cycles.max() > off.cycles.max()


def test_engine_link_free_matches_golden():
    # short run, no rebase: the engine's epoch-relative link clocks must
    # equal the golden's absolute ones exactly
    import jax.numpy as jnp

    from primesim_tpu.sim.engine import Engine

    cfg = rcfg(4, n_banks=4)
    tr = from_event_lists(
        [[(EV_LD, 4, 2 * 64)], [(EV_LD, 4, 3 * 64)], [], []]
    )
    g = GoldenSim(cfg, tr)
    g.run()
    e = Engine(cfg, tr, chunk_steps=8)
    e.run()
    np.testing.assert_array_equal(
        np.asarray(e.state.link_free) + int(e.cycle_base), g.link_free
    )


# --- a barrier arrival rides the walk's first leg (PR 52) -----------------


def _sync_router_cfg():
    # rung 3's selectors at 16 cores on a 4 x 4 mesh. Four banks: a
    # barrier's home is tile `id % 16`, the tile of "its" bank `id % 4`,
    # so an arrival laid as a request to that bank walks the wrong links
    from primesim_tpu.config.machine import CoreConfig

    return small_test_config(
        16, n_banks=4, quantum=500, local_run_len=4, dram_queue=True,
        core=CoreConfig(o3_overlap_256=64),
        noc=NocConfig(mesh_x=4, mesh_y=4, contention=True,
                      contention_model="router"),
    )


def _barriers_among_shared_lines():
    """The even cores: a few loads, then a barrier of their eight, six
    times over; the odd cores stream loads and stores over sixteen shared
    lines all the while. The barriers live on tiles 5 and 10, the lines
    on tiles 0 to 3: arrivals and other lanes' home transactions cross in
    the mesh's columns and rows."""
    from primesim_tpu.trace.format import EV_BARRIER

    rng = np.random.default_rng(52)
    per_core = []
    for c in range(16):
        evs = []
        if c % 2 == 0:
            for p in range(6):
                for i in range(int(rng.integers(1, 4))):
                    evs.append((EV_LD, 2, int(rng.integers(0, 16)) * 64))
                evs.append((EV_BARRIER, 8, (5, 10)[p % 2]))
        else:
            for i in range(40):
                t = EV_ST if rng.random() < 0.3 else EV_LD
                evs.append((t, 2, int(rng.integers(0, 16)) * 64))
        per_core.append(evs)
    return from_event_lists(per_core)


SYNC_WALKS = {
    "barriers_among_shared_lines": _barriers_among_shared_lines,
    "ocean_like": lambda: synth.ocean_like(
        16, seed=52, grid_n=18, levels=2, visits=3, lock_reductions=1),
}


def _links_shared_by_arrivals_and_home_txns(cfg, trace):
    """The golden model step by step -> (model, the number of (step, link)
    pairs at which a barrier arrival and ANOTHER lane's home transaction
    stood in one link's FIFO)."""
    g = GoldenSim(cfg, trace)
    shared = 0
    while not g.done():
        before = g.counters["barrier_waits"].copy()
        g.step()
        arrived = set(np.flatnonzero(g.counters["barrier_waits"] - before))
        for users in g._rtr_users.values():
            cores = {c for _, c in users}
            shared += bool(cores & arrived) and bool(cores - arrived)
    return g, shared


@pytest.mark.parametrize("name", sorted(SYNC_WALKS))
def test_parity_arrivals_and_home_txns_share_links(name):
    """A `has_sync` walk lays a barrier lane's arrival in its FIRST leg's
    slots (no third leg): where arrivals and other lanes' home
    transactions queue at the same directed links in the same step, every
    counter, every core's clock, the stat rows and the links' clocks are
    the golden model's."""
    from primesim_tpu.sim.engine import Engine

    cfg, trace = _sync_router_cfg(), SYNC_WALKS[name]()
    g, shared = _links_shared_by_arrivals_and_home_txns(cfg, trace)
    assert shared > 20, shared
    assert g.counters["noc_contention_cycles"].sum() > 0
    assert_parity(cfg, trace, chunk_steps=16)  # clocks, counters, caches
    e = Engine(cfg, trace, chunk_steps=16)
    e.run()
    assert e.has_sync
    np.testing.assert_array_equal(
        np.asarray(e.state.link_free).astype(np.int64) + int(e.cycle_base),
        g.link_free)
    np.testing.assert_array_equal(
        e.step_stats["noc_entries"], g.stats["noc_entries"])


def _step_sorts(cfg, trace, has_sync):
    """The operand length of every `sort` equation of one `step`, through
    every sub-jaxpr."""
    import jax

    from primesim_tpu.sim.engine import Engine
    from primesim_tpu.sim.step import step

    eng = Engine(cfg, trace, chunk_steps=8)
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "sort":
                found.append(int(np.prod(eqn.invars[0].aval.shape)))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(
        lambda ev, st: step(cfg, ev, st, has_sync=has_sync))(
            eng.events, eng.state).jaxpr)
    return sorted(found)


def test_has_sync_step_sorts_the_entries_of_a_step_without():
    """The step of a trace with locks and barriers holds the sorts of the
    step without, to the length: none longer than two legs a lane at the
    longest path's width plus the links' table entries (a third leg made
    it C x 3 x H + NL, and a sort pays for its length padded to a power
    of two: 2^18 for rung 3's 2^17)."""
    from primesim_tpu.noc.mesh import n_links
    from primesim_tpu.noc.topology import path_width

    cfg = _sync_router_cfg()
    trace = _barriers_among_shared_lines()
    with_sync = _step_sorts(cfg, trace, True)
    assert with_sync == _step_sorts(cfg, trace, False)
    assert max(with_sync) == cfg.n_cores * 2 * path_width(cfg) + n_links(cfg)
