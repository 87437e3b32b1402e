"""The big.LITTLE machine's plain reference (`benchmark/references/
biglittle.py`, what the benchmark holds rung 4 to) against the golden
model, with a CPI a core and the chunked sharer reduction's field stated:
per-core cycles, every counter and the step count. Loaded as the harness
loads it, so the vetting of its imports runs too."""

import numpy as np
import pytest

from benchmark_modules import ROOT, assert_reference_equals_golden  # puts benchmark/ on the path

import cells
import reference

biglittle = cells.load_reference("biglittle", ROOT)


def _machine(chunk, pattern=(1, 1, 3, 3), n=64, banks=16, mx=8, my=8, full=False):
    return {
        "n_cores": n, "n_banks": banks,
        "core": {"cpi": 1, "cpi_pattern": list(pattern), "o3_overlap_256": 64},
        "l1": {"size": 256, "ways": 2, "line": 64, "latency": 2},
        "llc": {"size": 512, "ways": 4, "line": 64, "latency": 14},
        "noc": {"mesh_x": mx, "mesh_y": my, "link_lat": 1, "router_lat": 1,
                "contention": full, "contention_model": "router", "contention_lat": 1},
        "dram_lat": 120, "dram_queue": full, "dram_service": 0,
        "quantum": 1000, "local_run_len": 8, "sharer_chunk_words": chunk,
    }


def _trace(gen, n=64):
    if gen == "fft_like":
        return cells.load_generator(gen)(n, 3, n_phases=3, points_per_core=16, ins_per_mem=4)
    # small caches and a hot shared range: evictions, probes, upgrades, invalidations
    return cells.load_generator(gen)(n, 3, n_mem_ops=48, working_set=1 << 14,
                                     write_frac=0.4, shared_frac=0.5, ins_per_mem=2)


def _assert_equals_golden(machine, ev):
    return assert_reference_equals_golden(biglittle, machine, ev)


@pytest.mark.parametrize("chunk,gen,full", [
    (1, "fft_like", False), (2, "fft_like", False),
    (1, "uniform_random", False), (2, "uniform_random", False),
    (2, "uniform_random", True),  # with the router walk and the DRAM queue
])
def test_biglittle_reference_equals_golden(chunk, gen, full):
    ev = _trace(gen)
    m = _machine(chunk, full=full)
    ref = _assert_equals_golden(m, ev)
    # the CPI a core is what the model is: the same machine with every core
    # big gives other cycles (with the FIFOs not always more: slow cores
    # queue less) and the same instructions
    plain = {k: v for k, v in m.items() if k != "sharer_chunk_words"}
    plain["core"] = {"cpi": 1, "o3_overlap_256": 64}
    stock = reference.RefSim(plain, ev)
    stock.run()
    assert ref.counters["instructions"] == stock.counters["instructions"]
    assert ref.cycles != stock.cycles
    assert full or sum(ref.cycles) > sum(stock.cycles)
    assert sum(ref.counters["probes"]) and sum(ref.counters["llc_writebacks"])
    if gen == "uniform_random":
        assert sum(ref.counters["upgrades"]) and sum(ref.counters["invalidations"])
    if full:
        assert sum(ref.counters["noc_contention_cycles"]) and sum(ref.counters["dram_queue_cycles"])


def test_the_chunk_width_changes_no_count():
    ev = _trace("uniform_random")
    runs = []
    for chunk in (0, 1, 2):
        ref = biglittle.RefSim(_machine(chunk), ev)
        ref.run()
        runs.append((ref.cycles, ref.counters, ref.step_count))
    assert runs[0] == runs[1] == runs[2]


def test_a_big_and_a_little_core_on_the_same_instructions():
    """Two cores, CPI 1 and 3, the same run: 10 instructions, a load of the
    core's own line with 5 instructions folded in before it, 7 instructions.
    The memory latency is the machine's, the same for both; every
    instruction beside it costs the core's own CPI."""
    INS, LD, END = reference.EV_INS, reference.EV_LD, reference.EV_END
    ev = np.zeros((2, 4, 4), np.int32)
    ev[:, :, 0] = END
    for c in (0, 1):
        ev[c, :3] = [(INS, 10, 0, 0), (LD, 8, (2 + c) * 64, 5), (INS, 7, 0, 0)]
    m = _machine(1, pattern=(1, 3), n=2, banks=2, mx=2, my=1)
    ref = _assert_equals_golden(m, ev)
    # the miss: L1 2, the request to the core's own tile 1 (no hop, one router), LLC 14,
    # two messages to the co-located controller and DRAM 120, the reply 1: 138, of which
    # the O3 window hides 138 * 64 >> 8 = 34: 104
    miss = 2 + 1 + 14 + 120 + 1
    miss -= (miss * 64) >> 8
    assert miss == 104
    assert ref.cycles == [10 + 5 + miss + 7, 3 * (10 + 5 + 7) + miss]
    assert ref.counters["instructions"] == [23, 23]


def test_biglittle_reference_refuses_what_it_does_not_model():
    ev = _trace("fft_like")
    for pattern in (None, [], [0, 1], [1.5], [True], "13"):
        bad = _machine(1)
        bad["core"]["cpi_pattern"] = pattern
        with pytest.raises(biglittle.UnsupportedMachine):
            biglittle.RefSim(bad, ev)
    no_key = _machine(1)
    del no_key["core"]["cpi_pattern"]
    with pytest.raises(biglittle.UnsupportedMachine):
        biglittle.RefSim(no_key, ev)
    for chunk in (-1, 1.5, True, "8"):
        with pytest.raises(biglittle.UnsupportedMachine):
            biglittle.RefSim(_machine(chunk), ev)
    per_core = _machine(1)
    per_core["core"]["cpi_per_core"] = [1] * 64
    with pytest.raises(biglittle.UnsupportedMachine):
        biglittle.RefSim(per_core, ev)
    with pytest.raises(biglittle.UnsupportedMachine):
        biglittle.RefSim({**_machine(1), "sharer_group": 4}, ev)
    with pytest.raises(biglittle.UnsupportedMachine):
        biglittle.RefSim({**_machine(1), "coherence": "moesi"}, ev)
    ev[0, 0, 0] = 6  # a barrier
    with pytest.raises(biglittle.UnsupportedMachine):
        biglittle.RefSim(_machine(1), ev)


def test_the_stock_reference_refuses_the_biglittle_machine():
    ev = _trace("fft_like")
    with pytest.raises(reference.UnsupportedMachine):
        reference.RefSim(_machine(1), ev)


def test_biglittle_reference_loads_as_the_harness_loads_it():
    assert issubclass(biglittle.RefSim, reference.RefSim)
    assert biglittle.COUNTERS == reference.COUNTERS
    assert biglittle.UnsupportedMachine is reference.UnsupportedMachine
    cells._refuse_foreign_imports(biglittle.__file__)  # raises on an import of the program or JAX


# ---- the one-chip program, the golden model and the reference, one machine --

@pytest.mark.parametrize("cores,chunk", [
    (64, 1),  # two words a way, in blocks of one
    (64, 2),  # one block
    (256, 8),  # rung 4's `sharer_chunk_words`: eight words a way, one block
    (512, 8),  # sixteen words a way, two blocks of eight
])
def test_the_one_chip_program_equals_golden_equals_the_reference(cores, chunk):
    """`rung4.fft-m18-4k`'s machine kind at a small size, through the path
    the cell runs (`Engine` on one device, the fused loop, no mesh): rung 4's
    CPI pattern of eight, its caches and latencies, the full map walked in
    blocks, an `fft_like` trace of the cell's parity size. Per-core cycles,
    every counter and the step count, three ways."""
    import json
    import os

    import measure
    from primesim_tpu.config.machine import MachineConfig
    from primesim_tpu.golden.sim import GoldenSim
    from primesim_tpu.sim.engine import Engine
    from primesim_tpu.trace.format import Trace

    with open(os.path.join(ROOT, "benchmark", "configs", "rung4.json")) as f:
        machine = json.load(f)["machine"]
    assert machine["core"]["cpi_pattern"] == [1, 1, 1, 1, 3, 3, 3, 3]
    machine = {**machine, "n_cores": cores, "n_banks": 64, "sharer_chunk_words": chunk,
               "noc": {**machine["noc"], "mesh_x": 8, "mesh_y": 8}}
    ev = cells.load_generator("fft_like")(cores, 55, n_phases=2, points_per_core=4, ins_per_mem=8)
    cfg = MachineConfig.from_dict(machine)
    assert cfg.n_sharer_words == cores // 32 and cfg.sharer_group == 1
    trace = Trace(ev, measure._lengths(ev))
    gold = GoldenSim(cfg, trace)
    gold.run()
    ref = assert_reference_equals_golden(biglittle, machine, ev, gold)
    eng = Engine(cfg, trace, chunk_steps=8)
    eng.run()
    assert eng.mesh is None and eng.steps_run == -(-ref.step_count // 8) * 8
    assert np.array_equal(eng.cycles, np.asarray(ref.cycles))
    assert len(set(eng.cycles[:8].tolist())) > 1  # big and LITTLE cores part
    counters = eng.counters
    for k in biglittle.COUNTERS:
        assert np.array_equal(counters[k], np.asarray(ref.counters[k])), k
    assert sum(ref.counters["invalidations"]) and sum(ref.counters["probes"])
