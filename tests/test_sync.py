"""Synchronization-event semantics (DESIGN.md phase 2.7).

The reference models pthread mutex/barrier calls by Pin interception
(SURVEY.md §2 #1, §3.5); here the PTPU v3 LOCK/UNLOCK/BARRIER events drive
lock-table arbitration and barrier freeze/release in both engines. Tests:

- hand-computed golden cycle counts for the canonical cases (uncontended
  lock, contended lock with unlock-then-grant in the same step, spin
  charging, barrier release, barrier slot reuse, lock-slot collision);
- golden-vs-JAX bit-exact parity on every hand trace and on the sync
  workload generators (incl. folded `pre` batches and local runs);
- the relaxed-sync fidelity bound: lock grant order is step order, so
  mutual exclusion in SIMULATED time may be violated by at most one
  quantum (DESIGN.md §3-sync caveat) — asserted by tracking every
  holder transition;
- clock rebase across chunk boundaries with an OCCUPIED barrier slot
  (barrier_time is epoch-relative and must rebase with the clocks).
"""

import dataclasses

import numpy as np
import pytest

from primesim_tpu.config.machine import MachineConfig, small_test_config
from primesim_tpu.golden.sim import GoldenSim
from primesim_tpu.trace import synth
from primesim_tpu.trace.format import (
    EV_BARRIER,
    EV_INS,
    EV_LD,
    EV_LOCK,
    EV_ST,
    EV_UNLOCK,
    fold_ins,
    from_event_lists,
)

from test_parity import assert_parity

# small_test_config(4): 2x2 mesh (one_way lat = 2*hops + 1), l1 lat 2,
# llc lat 10, dram 100, quantum 1000, cpi 1. core_tile(c) = c % 4.
# Mutex addr 0 -> line 0 -> slot 0 -> home bank 0 -> tile 0.
# Lock round trip from core 0: 1 + 10 + 1 = 12; from core 1: 3 + 10 + 3 = 16.


def cfg4(**kw) -> MachineConfig:
    return small_test_config(4, **kw)


def run_golden(cfg, trace):
    g = GoldenSim(cfg, trace)
    g.run()
    return g


def test_golden_uncontended_lock():
    tr = from_event_lists(
        [[(EV_LOCK, 0, 0), (EV_UNLOCK, 0, 0)], [], [], []]
    )
    g = run_golden(cfg4(), tr)
    assert g.cycles[0] == 12 + 12  # acquire RT + release RT
    assert g.counters["lock_acquires"][0] == 1
    assert g.counters["lock_spins"][0] == 0
    assert g.counters["instructions"][0] == 2
    assert g.counters["noc_msgs"][0] == 4
    assert g.lock_holder[0] == -1  # released at the end
    assert_parity(cfg4(), tr)


def test_golden_contended_lock_unlock_then_grant_same_step():
    # Both cores request at cycle 0; core 0 wins by (cycles, core_id).
    # Step 2: core 0's UNLOCK and core 1's retry happen in the SAME step —
    # unlocks are processed before grants, so core 1 acquires immediately.
    tr = from_event_lists(
        [
            [(EV_LOCK, 0, 0), (EV_UNLOCK, 0, 0)],
            [(EV_LOCK, 0, 0), (EV_UNLOCK, 0, 0)],
            [],
            [],
        ]
    )
    g = run_golden(cfg4(), tr)
    np.testing.assert_array_equal(g.cycles[:2], [24, 48])
    np.testing.assert_array_equal(g.counters["lock_acquires"][:2], [1, 1])
    np.testing.assert_array_equal(g.counters["lock_spins"][:2], [0, 1])
    assert_parity(cfg4(), tr)


def test_golden_spin_charging():
    # Core 0 holds the lock across an INS batch; core 1 spins, paying a
    # full RMW round trip (16 cycles from tile 1) per failed attempt.
    tr = from_event_lists(
        [
            [(EV_LOCK, 0, 0), (EV_INS, 100, 0), (EV_UNLOCK, 0, 0)],
            [(EV_LOCK, 0, 0)],
            [],
            [],
        ]
    )
    g = run_golden(cfg4(), tr)
    # c0: 12 (grant) + 100 (INS) + 12 (unlock) = 124
    # c1: spin@step1 16, spin@step2 32, grant@step3 48
    np.testing.assert_array_equal(g.cycles[:2], [124, 48])
    assert g.counters["lock_spins"][1] == 2
    assert g.counters["lock_acquires"][1] == 1
    assert g.lock_holder[0] == 1  # never unlocked by core 1
    assert_parity(cfg4(), tr)


def test_golden_barrier_release():
    # c0 arrives at cycle 1 (tile 0 -> home 0: lat 1); c1 works 50 cycles
    # then arrives at 53 (tile 1 -> home 0: lat 3). Both release from the
    # slot max (53) + wake-up message.
    tr = from_event_lists(
        [
            [(EV_BARRIER, 2, 0)],
            [(EV_INS, 50, 0), (EV_BARRIER, 2, 0)],
            [],
            [],
        ]
    )
    g = run_golden(cfg4(), tr)
    np.testing.assert_array_equal(g.cycles[:2], [54, 56])
    np.testing.assert_array_equal(g.counters["barrier_waits"][:2], [1, 1])
    np.testing.assert_array_equal(g.counters["instructions"][:2], [1, 51])
    assert g.barrier_count[0] == 0 and g.barrier_time[0] == 0  # drained
    assert_parity(cfg4(), tr)


def test_golden_barrier_reuse():
    # The same barrier id is used twice: the slot must re-arm (count and
    # max-arrival clock reset) after the first release.
    tr = from_event_lists(
        [
            [(EV_BARRIER, 2, 0), (EV_BARRIER, 2, 0)],
            [(EV_BARRIER, 2, 0), (EV_INS, 10, 0), (EV_BARRIER, 2, 0)],
            [],
            [],
        ]
    )
    g = run_golden(cfg4(), tr)
    # round 1: arrivals 1 and 3 -> release at 3: c0=4, c1=6
    # round 2: c0 arrives 5; c1 works to 16, arrives 19 -> c0=20, c1=22
    np.testing.assert_array_equal(g.cycles[:2], [20, 22])
    np.testing.assert_array_equal(g.counters["barrier_waits"][:2], [2, 2])
    assert_parity(cfg4(), tr)


def test_golden_lock_slot_collision():
    # Two DISTINCT mutexes whose lines collide in the lock table (line 0
    # and line 1024 with lock_slots=1024) contend conservatively; with a
    # 2048-slot table they do not.
    m2 = 1024 * 64
    evs = [
        [(EV_LOCK, 0, 0), (EV_UNLOCK, 0, 0)],
        [(EV_LOCK, 0, m2), (EV_UNLOCK, 0, m2)],
        [],
        [],
    ]
    g = run_golden(cfg4(lock_slots=1024), from_event_lists(evs))
    assert g.counters["lock_spins"][1] == 1  # false contention
    g2 = run_golden(cfg4(lock_slots=2048), from_event_lists(evs))
    assert g2.counters["lock_spins"][1] == 0  # distinct slots
    assert_parity(cfg4(lock_slots=1024), from_event_lists(evs))
    assert_parity(cfg4(lock_slots=2048), from_event_lists(evs))


def test_golden_lock_reacquire():
    # A core that already holds the lock re-acquires it immediately even
    # if another, earlier-keyed core is spinning on the slot.
    tr = from_event_lists(
        [
            [
                (EV_LOCK, 0, 0),
                (EV_INS, 5, 0),
                (EV_LOCK, 0, 0),  # re-acquire while c1 spins
                (EV_UNLOCK, 0, 0),
            ],
            [(EV_LOCK, 0, 0), (EV_UNLOCK, 0, 0)],
            [],
            [],
        ]
    )
    g = run_golden(cfg4(), tr)
    assert g.counters["lock_acquires"][0] == 2
    assert g.counters["lock_acquires"][1] == 1
    assert g.counters["lock_spins"][1] >= 2  # spun while c0 held + reheld
    assert_parity(cfg4(), tr)


def test_relaxed_sync_skew_bounded_by_quantum():
    """Lock grant order is STEP order, not simulated-time order: a waiter
    may acquire at a simulated cycle earlier than the holder's release
    cycle. DESIGN.md's clock-window invariant bounds this skew by one
    quantum — track every holder transition and assert
    acquire_cycle >= release_cycle - Q."""
    Q = 64
    cfg = small_test_config(8, quantum=Q)
    tr = synth.lock_contention(8, n_critical=6, n_locks=2, seed=7)
    g = GoldenSim(cfg, tr)
    last_release = {}  # slot -> release cycle of previous holder
    prev = g.lock_holder.copy()
    violations = []
    for _ in range(10_000):
        if g.done():
            break
        g.step()
        for s in np.nonzero(g.lock_holder != prev)[0]:
            old, new = int(prev[s]), int(g.lock_holder[s])
            if old >= 0 and new != old:
                last_release[s] = int(g.cycles[old])
            if new >= 0 and new != old:
                acq = int(g.cycles[new])
                if s in last_release and acq < last_release[s] - Q:
                    violations.append((s, acq, last_release[s]))
        prev = g.lock_holder.copy()
    assert g.done()
    assert not violations, violations


# ---------------------------------------------------------- parity (gens)


@pytest.mark.parametrize("subset", [False, True])
def test_parity_barrier_phases(subset):
    cfg = small_test_config(8, n_banks=4)
    assert_parity(cfg, synth.barrier_phases(8, n_phases=3, subset=subset, seed=21))


def test_parity_lock_contention_folded_local_runs():
    # folded pre batches + local runs + sync in one config: sync events
    # must stop local runs and charge their pre batch exactly once
    cfg = small_test_config(8, n_banks=4, local_run_len=4)
    assert_parity(cfg, fold_ins(synth.lock_contention(8, n_critical=10, seed=22)))


def test_parity_sync_small_quantum():
    cfg = small_test_config(8, n_banks=4, quantum=64)
    assert_parity(cfg, synth.lock_contention(8, n_critical=8, seed=23), chunk_steps=50)
    assert_parity(cfg, synth.barrier_phases(8, n_phases=2, seed=24), chunk_steps=50)


def test_parity_barrier_across_rebase():
    """A frozen barrier waiter holds an epoch-relative arrival clock in
    barrier_time; chunk-boundary clock rebases (both the on-device run_loop
    and the host run_chunked variant) must rebase occupied barrier slots
    with the core clocks or the release cycle is wrong by delta.

    Core 0 works ~10k cycles then waits; core 1 grinds through 400 small
    INS events (the rebase delta tracks core 1's clock while core 0 is
    frozen). quantum=64 and chunk_steps=16 force many rebases while the
    slot is occupied.
    """
    from primesim_tpu.sim.engine import Engine

    cfg = small_test_config(2, n_banks=2, quantum=64)
    tr = from_event_lists(
        [
            [(EV_INS, 10_000, 0), (EV_BARRIER, 2, 0), (EV_LD, 4, 0)],
            [(EV_INS, 50, 0)] * 400 + [(EV_BARRIER, 2, 0), (EV_LD, 4, 64)],
        ]
    )
    g = run_golden(cfg, tr)
    e = Engine(cfg, tr, chunk_steps=16)
    e.run()
    np.testing.assert_array_equal(e.cycles, g.cycles)
    e2 = Engine(cfg, tr, chunk_steps=16)
    e2.run_chunked()
    np.testing.assert_array_equal(e2.cycles, g.cycles)


def test_parity_mixed_barrier_then_locks():
    """Stress the clock-window invariant (DESIGN.md §3-sync): a subset
    barrier's waiters freeze with early clocks while non-participants
    free-run thousands of cycles; afterwards ALL cores contend the same
    lock. The packed arbitration key is only exact if released waiters
    re-enter the Q-window — golden asserts the invariant every step and
    parity proves the key stayed exact."""
    from primesim_tpu.trace.format import EV_INS

    cfg = small_test_config(4, quantum=64)
    tr = from_event_lists(
        [
            [(EV_BARRIER, 2, 0), (EV_LOCK, 0, 0), (EV_UNLOCK, 0, 0)],
            [
                (EV_INS, 20_000, 0),
                (EV_BARRIER, 2, 0),
                (EV_LOCK, 0, 0),
                (EV_UNLOCK, 0, 0),
            ],
            [(EV_INS, 50, 0)] * 600 + [(EV_LOCK, 0, 0), (EV_UNLOCK, 0, 0)],
            [(EV_INS, 50, 0)] * 600 + [(EV_LOCK, 0, 0), (EV_UNLOCK, 0, 0)],
        ]
    )
    assert_parity(cfg, tr, chunk_steps=50)


@pytest.mark.parametrize("lock_reductions", [0, 1])
def test_parity_ocean_like_on_rung3_cut_to_8x8(lock_reductions):
    """The benchmark's `rung3.ocean-n258` at 64 cores: rung 3's machine
    file (router walk, DRAM queue, O3 window, local runs) cut to an 8 x 8
    mesh, on the cell's shape of trace (8 x 8 points a core, four levels,
    the descent of one V-cycle, 23 global barriers): every arrival is the
    first leg of its lane in the step's link walk, and a frozen core sits
    out the quantum barrier. With one global lock reduction too."""
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "configs", "rung3_1024core_o3.json")) as f:
        machine = json.load(f)
    machine.update(n_cores=64, n_banks=64)
    machine["noc"].update(mesh_x=8, mesh_y=8)
    cfg = MachineConfig.from_dict(machine)
    tr = fold_ins(synth.ocean_like(64, seed=404, grid_n=66, levels=4, visits=4,
                                   lock_reductions=lock_reductions))
    assert int((tr.events[:, :, 0] == EV_BARRIER).sum()) == 23 * 64
    assert_parity(cfg, tr, chunk_steps=8)


def test_trace_rejects_bad_barrier_ids():
    from primesim_tpu.sim.engine import Engine

    cfg = small_test_config(2, n_banks=2, barrier_slots=4)
    tr = from_event_lists([[(EV_BARRIER, 2, 9)], [(EV_BARRIER, 2, 9)]])
    with pytest.raises(ValueError, match="barrier ids"):
        GoldenSim(cfg, tr)
    with pytest.raises(ValueError, match="barrier ids"):
        Engine(cfg, tr)
