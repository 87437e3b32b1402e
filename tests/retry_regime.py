"""The retry regime at a size tier-1 can simulate: `ycsb_like` on rung 3's
machine cut to a small mesh, with so few records that over a fifth of the
requests lose their (bank, set) and are presented again, and the golden
model stepped under watch for the case the regime exists to reach: a read
that could have joined its line's sharers, demoted into the arbitration
because a request of the step targets its home (bank, set), losing it to a
writer. For the parity tests of `Engine`, of the benchmark's stock
reference and of the sharded step."""

import functools
import json
import os

import numpy as np

from primesim_tpu.config.machine import MachineConfig
from primesim_tpu.golden.sim import GoldenSim
from primesim_tpu.trace import synth
from primesim_tpu.trace.format import EV_ST, fold_ins

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# cores -> records. The ranks are drawn over YCSB's 10^10 items whatever the
# table's size (the hottest 3.8 %, two fifths of the draws spread evenly by
# the hash), so it takes this few records for a fifth of so few cores'
# requests to collide: a harder regime than the cell's one in fifteen
RECORDS = {64: 8, 256: 16}


def machine_and_trace(n_cores: int, router: bool, seed: int = 404):
    """(rung 3's machine as a dict, cut to sqrt(n) x sqrt(n); the trace).
    Not `router`: the plain machine, without the router walk, the DRAM
    queue and the O3 window."""
    with open(os.path.join(ROOT, "configs", "rung3_1024core_o3.json")) as f:
        machine = json.load(f)
    side = int(round(n_cores ** 0.5))
    # `dram_service`: the program's default, stated because the reference wants every key
    machine.update(n_cores=n_cores, n_banks=n_cores, dram_service=0)
    machine["noc"].update(mesh_x=side, mesh_y=side)
    if not router:
        machine["noc"]["contention"] = False
        machine["dram_queue"] = False
        machine["core"]["o3_overlap_256"] = 0
    trace = fold_ins(synth.ycsb_like(n_cores, seed=seed, ops_per_core=8,
                                     recordcount=RECORDS[n_cores]))
    return machine, trace


def run_watched(gold: GoldenSim) -> int:
    """Run the golden model to its end; the number of steps in which a
    read that was eligible to join was retried while a writer won its
    (bank, set). Read off the model's own state, step by step: which
    reads `_join_eligible` passed, whose `retries` rose, and the event a
    core whose write reached the directory has just retired."""
    eligible: list = []
    inner = gold._join_eligible

    def spy(c, line):
        ok = inner(c, line)
        if ok:
            eligible.append((c, line))
        return ok

    gold._join_eligible = spy

    def slot(line):
        return gold._bank(line), gold._bank_set(line)

    def writes():
        return gold.counters["l1_write_misses"] + gold.counters["upgrades"]

    lost = 0
    while not gold.done():
        eligible.clear()
        retries0, writes0 = gold.counters["retries"].copy(), writes()
        gold.step()
        writers = np.flatnonzero(writes() - writes0)
        won = gold.events[writers, gold.ptr[writers] - 1]  # a winner retires its event
        assert (won[:, 0] == EV_ST).all()
        won_slots = {slot(int(line)) for line in won[:, 2]}
        lost += any(gold.counters["retries"][c] > retries0[c] and slot(line) in won_slots
                    for c, line in eligible)
    return lost


@functools.lru_cache(maxsize=None)
def golden_in_the_regime(n_cores: int, router: bool):
    """(machine dict, trace, the finished golden model), the regime
    asserted: retries over a fifth of the served requests, and at least
    one step in which a join-eligible read lost to a writer. Run once a
    process: the callers read the three and change none."""
    machine, trace = machine_and_trace(n_cores, router)
    gold = GoldenSim(MachineConfig.from_dict(machine), trace)
    lost = run_watched(gold)
    c = {k: int(v.sum()) for k, v in gold.counters.items()}
    served = c["l1_read_misses"] + c["l1_write_misses"] + c["upgrades"]
    assert 5 * c["retries"] > served, (c["retries"], served)
    assert lost >= 1
    assert c["invalidations"] > c["l1_write_misses"] + c["upgrades"]  # a write finds readers
    return machine, trace, gold
