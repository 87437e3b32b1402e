"""The plain reference of a machine that synchronises (`benchmark/
references/sync.py`, what the benchmark holds `rung3.ocean-n258` to)
against the golden model: per-core cycles, all 21 counters and the step
count, on barriers, locks and both, with the router's link walk and
without. Loaded as the harness loads it, so the vetting of its imports
runs too."""

import numpy as np
import pytest

from benchmark_modules import ROOT, assert_reference_equals_golden  # puts benchmark/ on the path

import cells
import reference
from primesim_tpu.trace import synth
from primesim_tpu.trace.format import (EV_BARRIER, EV_INS, EV_LOCK, EV_UNLOCK, fold_ins,
                                       from_event_lists)

sync = cells.load_reference("sync", ROOT)

INS, LD, ST, END = reference.EV_INS, reference.EV_LD, reference.EV_ST, reference.EV_END
SYNC_COUNTERS = ("barrier_waits", "lock_acquires", "lock_spins")


def _machine(n=16, banks=16, mx=4, router=True, local_run_len=8, quantum=1000):
    """Rung 3's selectors at a small size: the router walk, the DRAM queue
    and the O3 window, or none of them; caches small enough to evict."""
    return {
        "n_cores": n, "n_banks": banks,
        "core": {"cpi": 1, "o3_overlap_256": 128 if router else 0},
        "l1": {"size": 256, "ways": 2, "line": 64, "latency": 2},
        "llc": {"size": 512, "ways": 4, "line": 64, "latency": 12},
        "noc": {"mesh_x": mx, "mesh_y": mx, "link_lat": 1, "router_lat": 1,
                "contention": router, "contention_model": "router", "contention_lat": 1},
        "dram_lat": 100, "dram_queue": router, "dram_service": 0,
        "quantum": quantum, "local_run_len": local_run_len,
        "lock_slots": 1024, "barrier_slots": 64,
    }


def _mixed(n):
    """`test_sync.py::test_parity_mixed_barrier_then_locks`: a subset
    barrier whose waiters freeze while the others run on for thousands of
    cycles, then every core contends one lock."""
    lock = [(EV_LOCK, 0, 0), (EV_UNLOCK, 0, 0)]
    rows = [[(EV_BARRIER, 2, 0)] + lock, [(EV_INS, 20_000, 0), (EV_BARRIER, 2, 0)] + lock]
    rows += [[(EV_INS, 50, 0)] * 600 + lock for _ in range(n - 2)]
    return from_event_lists(rows)


TRACES = {
    "ocean": lambda n: fold_ins(synth.ocean_like(n, seed=3, grid_n=34, levels=2, visits=2)),
    "ocean_locked": lambda n: fold_ins(synth.ocean_like(
        n, seed=4, grid_n=34, levels=2, visits=2, lock_reductions=1)),
    "barriers": lambda n: fold_ins(synth.barrier_phases(n, n_phases=3, seed=21)),
    "barriers_subset": lambda n: synth.barrier_phases(n, n_phases=3, subset=True, seed=21),
    "locks": lambda n: fold_ins(synth.lock_contention(n, n_critical=6, seed=22)),
    "mixed": lambda n: _mixed(4),
}


def _check(machine, ev):
    ref = assert_reference_equals_golden(sync, machine, ev)
    t = ev[:, :, 0]
    assert sum(ref.counters["barrier_waits"]) == int((t == EV_BARRIER).sum())
    assert sum(ref.counters["lock_acquires"]) == int((t == EV_LOCK).sum())
    return ref


@pytest.mark.parametrize("quantum", [64, 1000])
@pytest.mark.parametrize("local_run_len", [0, 8])
@pytest.mark.parametrize("router", [False, True])
@pytest.mark.parametrize("banks", [4, 16])
@pytest.mark.parametrize("trace", sorted(TRACES))
def test_sync_reference_equals_golden(trace, banks, router, local_run_len, quantum):
    ev = TRACES[trace](16).events
    if trace == "mixed":  # four cores, as the test it comes from
        m = _machine(4, banks // 4, 2, router, local_run_len, quantum)
    else:
        m = _machine(16, banks, 4, router, local_run_len, quantum)
    ref = _check(m, ev)
    if router and trace != "mixed":
        assert sum(ref.counters["noc_contention_cycles"]) > 0
    if trace in ("ocean_locked", "locks", "mixed"):
        assert sum(ref.counters["lock_spins"]) > 0


def test_sync_reference_equals_golden_at_64_cores():
    """Rung 3's machine cut to 8 x 8, OCEAN with two levels and a lock
    reduction: 4 x 4 points a core, the arrivals of 64 cores on one tile."""
    ev = fold_ins(synth.ocean_like(64, seed=9, grid_n=34, levels=2, visits=2,
                                   lock_reductions=1)).events
    ref = _check(_machine(64, 64, 8), ev)
    assert sum(ref.counters["barrier_waits"]) == 11 * 64
    assert sum(ref.counters["invalidations"]) and sum(ref.counters["noc_contention_cycles"])


def _rows(*rows):
    T = max(map(len, rows)) + 1
    ev = np.zeros((len(rows), T, 4), np.int32)
    ev[:, :, 0] = END
    for c, row in enumerate(rows):
        ev[c, :len(row)] = row
    return ev


@pytest.mark.parametrize("router", [False, True])
def test_two_cores_and_one_barrier(router):
    """Core 0 (tile 0, the barrier's home) arrives at once: one router, 1
    cycle. Core 1 (tile 1) retires 50 instructions and the 5 folded into
    the barrier, and its arrival crosses one link: 55 + 3 = 58. Both leave
    at the latest arrival plus the wake-up message back to their tile: 58
    + 1 and 58 + 3. Uncontended, the router walk charges the same."""
    m = _machine(2, 2, 2, router)
    m["noc"]["mesh_y"] = 1
    ev = _rows([(EV_BARRIER, 2, 0, 0)], [(INS, 50, 0, 0), (EV_BARRIER, 2, 0, 5)])
    ref = _check(m, ev)
    assert ref.step_count == 1
    assert ref.cycles == [59, 61]
    assert ref.counters["instructions"] == [1, 56]
    assert ref.counters["barrier_waits"] == [1, 1]
    assert ref.counters["noc_msgs"] == [2, 2] and ref.counters["noc_hops"] == [0, 2]
    assert ref.barrier_count[0] == 0 and ref.barrier_time[0] == 0  # the slot is cleared
    assert not sum(ref.counters["noc_contention_cycles"])


def test_two_cores_and_one_lock():
    """The lock's line 0 is at home on tile 0: a round trip costs core 0
    1 + 12 + 1 = 14 and core 1 3 + 12 + 3 = 18 cycles. Step 1: both try at
    clock 0, core 0 wins on its id (3 + 14 = 17), core 1 spins (4 + 18 =
    22, its batch of 4 charged now and not again). Step 2: core 0 retires
    100 instructions, core 1 spins again (40). Step 3: core 0 unlocks (117
    + 14), and core 1, tried after the unlocks of its step, is granted (58)."""
    m = _machine(2, 2, 2, router=False, local_run_len=0)
    m["noc"]["mesh_y"] = 1
    ev = _rows([(EV_LOCK, 0, 0, 3), (INS, 100, 0, 0), (EV_UNLOCK, 0, 0, 0)],
               [(EV_LOCK, 0, 0, 4)])
    ref = _check(m, ev)
    assert ref.step_count == 3
    assert ref.cycles == [131, 58]
    assert ref.counters["lock_spins"] == [0, 2] and ref.counters["lock_acquires"] == [1, 1]
    assert ref.counters["instructions"] == [3 + 1 + 100 + 1, 4 + 1]
    assert ref.counters["noc_msgs"] == [4, 6] and ref.counters["noc_hops"] == [0, 6]
    assert ref.lock_holder == {0: 1}  # core 1 never unlocks


def test_two_lines_in_one_slot_are_one_lock():
    m = {**_machine(2, 2, 2, router=False, local_run_len=0), "lock_slots": 2}
    m["noc"]["mesh_y"] = 1
    ev = _rows([(EV_LOCK, 0, 0, 0)], [(EV_LOCK, 0, 2 * 64, 0), (INS, 1, 0, 0)])
    ref = sync.RefSim(m, ev)
    ref.step()
    assert ref.counters["lock_acquires"] == [1, 0] and ref.counters["lock_spins"] == [0, 1]


def test_sync_reference_refuses_what_it_does_not_model():
    ev = TRACES["barriers"](16).events
    sync.RefSim(_machine(), ev)
    bad = ev.copy()
    bad[0, 0, 0] = 7  # an event type above BARRIER
    with pytest.raises(sync.UnsupportedMachine):
        sync.RefSim(_machine(), bad)
    bad = ev.copy()
    first = np.argwhere(bad[:, :, 0] == EV_BARRIER)[0]
    bad[first[0], first[1], 2] = 64  # a barrier id at barrier_slots
    with pytest.raises(sync.UnsupportedMachine, match="barrier ids"):
        sync.RefSim(_machine(), bad)
    sync.RefSim({**_machine(), "barrier_slots": 128}, bad)
    for key in ("lock_slots", "barrier_slots"):
        missing = _machine()
        del missing[key]
        with pytest.raises(sync.UnsupportedMachine, match=key):
            sync.RefSim(missing, ev)
        for value in (0, 3, 1.5, True, "64"):
            with pytest.raises(sync.UnsupportedMachine, match=key):
                sync.RefSim({**_machine(), key: value}, ev)
    # every key the stock reference refuses
    for extra in ({"sharer_group": 4}, {"coherence": "moesi"}, {"sharer_chunk_words": 1},
                  {"prefetcher": "stride"}, {"faults_enabled": True}):
        with pytest.raises(sync.UnsupportedMachine):
            sync.RefSim({**_machine(), **extra}, ev)
    for noc in ({"topology": "torus"}, {"contention_model": "link"}, {"vc": 2}):
        m = _machine()
        m["noc"].update(noc)
        with pytest.raises(sync.UnsupportedMachine):
            sync.RefSim(m, ev)
    m = _machine()
    m["core"]["cpi_pattern"] = [1, 3]
    with pytest.raises(sync.UnsupportedMachine):
        sync.RefSim(m, ev)
    m = _machine()
    del m["dram_lat"]
    with pytest.raises(sync.UnsupportedMachine):
        sync.RefSim(m, ev)
    with pytest.raises(sync.UnsupportedMachine):
        sync.RefSim(_machine(), ev[:8])  # half the cores
    with pytest.raises(sync.UnsupportedMachine):
        sync.RefSim(_machine(), ev[:, :-1])  # a row that does not end with END


def test_the_stock_reference_refuses_the_machine_and_the_trace():
    ev = TRACES["barriers"](16).events
    with pytest.raises(reference.UnsupportedMachine):
        reference.RefSim(_machine(), ev)
    stock = {k: v for k, v in _machine().items() if k not in ("lock_slots", "barrier_slots")}
    with pytest.raises(reference.UnsupportedMachine, match="INS/LD/ST/END"):
        reference.RefSim(stock, ev)


def test_without_sync_events_it_is_the_stock_reference():
    ev = cells.load_generator("fft_like")(16, 3, n_phases=2, points_per_core=8, ins_per_mem=4)
    m = _machine()
    mine = sync.RefSim(m, ev)
    mine.run()
    stock = reference.RefSim({k: v for k, v in m.items()
                              if k not in ("lock_slots", "barrier_slots")}, ev)
    stock.run()
    assert mine.cycles == stock.cycles and mine.step_count == stock.step_count
    for k in reference.COUNTERS:
        assert mine.counters[k] == stock.counters[k], k
    assert not any(sum(mine.counters[k]) for k in SYNC_COUNTERS)


def test_sync_reference_loads_as_the_harness_loads_it():
    assert issubclass(sync.RefSim, reference.RefSim) and sync.RefSim is not reference.RefSim
    assert sync.COUNTERS == reference.COUNTERS + SYNC_COUNTERS and len(sync.COUNTERS) == 21
    assert sync.UnsupportedMachine is reference.UnsupportedMachine
    cells._refuse_foreign_imports(sync.__file__)  # raises on an import of the program or JAX
    spec = cells.load_cell("rung3.ocean-n258")
    assert spec["reference"] == "sync"
    machine = spec["config"]["machine"]
    sync.RefSim(machine, np.full((machine["n_cores"], 1, 4), END, np.int32))
