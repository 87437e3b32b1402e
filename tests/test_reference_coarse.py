"""The coarse directory's plain reference (`benchmark/references/
coarse_dir.py`, what the benchmark holds rung 5 to) against the golden
model, with fewer banks than cores and G > 1: per-core cycles, every
counter and the step count. Loaded as the harness loads it, so the
vetting of its imports runs too."""

import numpy as np
import pytest

from benchmark_modules import ROOT, assert_reference_equals_golden  # puts benchmark/ on the path

import cells
import reference
import trafficgen

coarse = cells.load_reference("coarse_dir", ROOT)


def _machine(G, n=64, banks=16, mx=8, my=8, full=False):
    return {
        "n_cores": n, "n_banks": banks,
        "core": {"cpi": 1, "o3_overlap_256": 128},
        "l1": {"size": 256, "ways": 2, "line": 64, "latency": 2},
        "llc": {"size": 512, "ways": 4, "line": 64, "latency": 12},
        "noc": {"mesh_x": mx, "mesh_y": my, "link_lat": 1, "router_lat": 2,
                "contention": full, "contention_model": "router", "contention_lat": 1},
        "dram_lat": 100, "dram_queue": full, "dram_service": 0,
        "quantum": 1000, "local_run_len": 8, "sharer_group": G,
    }


def _trace(gen, n=64):
    if gen == "fft_like":
        return cells.load_generator(gen)(n, 3, n_phases=3, points_per_core=16, ins_per_mem=4)
    # small caches and a hot shared range: evictions, probes, upgrades, broadcasts
    return cells.load_generator(gen)(n, 3, n_mem_ops=48, working_set=1 << 14,
                                     write_frac=0.4, shared_frac=0.5, ins_per_mem=2)


def _assert_equals_golden(machine, ev):
    return assert_reference_equals_golden(coarse, machine, ev)


@pytest.mark.parametrize("G,gen,full", [
    (4, "fft_like", False), (32, "fft_like", False),
    (4, "uniform_random", False), (32, "uniform_random", False),
    (32, "uniform_random", True),  # with the router walk and the DRAM queue
])
def test_coarse_reference_equals_golden(G, gen, full):
    ev = _trace(gen)
    m = _machine(G, full=full)
    ref = _assert_equals_golden(m, ev)
    stock = reference.RefSim({k: v for k, v in m.items() if k != "sharer_group"}, ev)
    stock.run()
    assert sum(ref.counters["invalidations"]) > sum(stock.counters["invalidations"])
    assert sum(ref.counters["probes"]) and sum(ref.counters["llc_writebacks"])
    if gen == "uniform_random":  # writes to shared lines: the broadcasts that the model is
        assert sum(ref.counters["upgrades"]) and sum(ref.counters["llc_hits"]) > 1000
    if full:
        assert sum(ref.counters["noc_contention_cycles"]) and sum(ref.counters["dram_queue_cycles"])


def test_a_broadcast_reaches_the_whole_group_and_serialises_over_the_requester_too():
    """8 cores in a row, G 4, line 0 at home tile 0. Cores 6 and 7 (group
    1) read it, then core 7 writes: three messages (4, 5, 6: the requester
    gets none), but the latency is the round trip to tile 7, the
    requester's own slot and the farthest of its group."""
    LD, ST, END = trafficgen.EV_LD, trafficgen.EV_ST, trafficgen.EV_END
    ev = np.zeros((8, 3, 4), np.int32)
    ev[:, :, 0] = END
    ev[6, 0] = (LD, 8, 0, 1)
    ev[7, :2] = [(LD, 8, 0, 1), (ST, 8, 0, 1)]
    m = _machine(4, n=8, banks=8, mx=8, my=1)
    ref = _assert_equals_golden(m, ev)
    assert ref.counters["invalidations"][7] == 3 and ref.counters["upgrades"][7] == 1
    # one way over h hops is 3h + 2 (link 1, router 2); the O3 overlap hides half.
    # Core 6 wins the first arbitration; core 7 reads at step 1 with a probe of core 6:
    # 1 + (2 + 23 + 12 + 2 * 20 + 23) / 2 = 51. Its store is an upgrade: request 2 + 23 + 12,
    # the broadcast's round trip to tile 7 2 * 23 (2 * 20 if the requester's own slot
    # were left out: 102 at the end), the reply 23: 51 + 1 + 106 / 2 = 105
    assert ref.cycles[7] == 105


def test_coarse_reference_refuses_what_it_does_not_model():
    ev = _trace("fft_like")
    for G in (1, 3, None, True):
        with pytest.raises(coarse.UnsupportedMachine):
            coarse.RefSim({**_machine(4), "sharer_group": G}, ev)
    no_key = _machine(4)
    del no_key["sharer_group"]
    with pytest.raises(coarse.UnsupportedMachine):
        coarse.RefSim(no_key, ev)
    with pytest.raises(coarse.UnsupportedMachine):
        coarse.RefSim({**_machine(4), "coherence": "moesi"}, ev)
    with pytest.raises(coarse.UnsupportedMachine):
        coarse.RefSim(_machine(128), ev)  # more cores to a bit than the machine has
    ev[0, 0, 0] = 6  # a barrier
    with pytest.raises(coarse.UnsupportedMachine):
        coarse.RefSim(_machine(4), ev)


def test_coarse_reference_loads_as_the_harness_loads_it():
    assert issubclass(coarse.RefSim, reference.RefSim)
    assert coarse.COUNTERS == reference.COUNTERS
    assert coarse.UnsupportedMachine is reference.UnsupportedMachine
    cells._refuse_foreign_imports(coarse.__file__)  # raises on an import of the program or JAX
