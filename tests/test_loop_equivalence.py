"""The two executions of one trace that the tree holds must agree in
EVERYTHING a run leaves behind: `Engine.run()` (the fused `run_loop`:
one dispatch, the drain and the rebase on the device) against
`Engine.run_chunked()` (`run_chunk` a chunk, `_drain` and `_rebase` on
the host): cycles, every counter and stat row, every leaf of the final
`MachineState`, `cycle_base`, `steps_run`. On every workload generator
and the machine modes whose state crosses chunks (router link clocks,
DRAM queue clocks, barrier slots, local runs, the coarse and the
chunked sharer vector, the stride prefetcher on a moesi torus).

This is the guard ROADMAP D16 names: whoever folds the chunked host loop
into `run_loop(max_chunks=1)` extends this file first.
"""

import numpy as np
import pytest

from primesim_tpu.config.machine import (
    CacheConfig,
    MachineConfig,
    NocConfig,
    small_test_config,
)
from primesim_tpu.sim.engine import Engine
from primesim_tpu.trace import synth
from primesim_tpu.trace.format import fold_ins

GENERATOR_TRACES = {
    "uniform_random": lambda: synth.uniform_random(8, n_mem_ops=50, seed=42),
    "stream": lambda: synth.stream(8, n_mem_ops=50, seed=43),
    "pointer_chase": lambda: synth.pointer_chase(
        8, n_mem_ops=40, n_nodes=32, seed=44
    ),
    "false_sharing": lambda: synth.false_sharing(8, n_mem_ops=40, seed=45),
    "fft_like": lambda: synth.fft_like(
        8, n_phases=2, points_per_core=8, seed=46
    ),
    "readers_writer": lambda: synth.readers_writer(8, n_rounds=3, seed=47),
    "lock_contention": lambda: synth.lock_contention(8, n_critical=6, seed=48),
    "barrier_phases": lambda: synth.barrier_phases(8, n_phases=3, seed=49),
}


def _plain_cfg():
    return small_test_config(8, n_banks=4, quantum=300)


def _router_dram_cfg():
    noc = NocConfig(
        mesh_x=2, mesh_y=2, link_lat=1, router_lat=1,
        contention=True, contention_model="router", contention_lat=2,
    )
    return small_test_config(
        8, n_banks=4, quantum=400, noc=noc, dram_queue=True, dram_service=8
    )


def _chunked_sharer_cfg():
    # 64 cores: two sharer words a way, scanned one word a block
    return MachineConfig(
        n_cores=64, n_banks=16,
        l1=CacheConfig(size=1024, ways=2, line=64, latency=2),
        llc=CacheConfig(size=4096, ways=4, line=64, latency=10),
        noc=NocConfig(mesh_x=4, mesh_y=4),
        quantum=500, sharer_chunk_words=1,
    )


def _zoo_cfg():
    # tests/test_zoo.py's machine with every selector off its default: the
    # stride prefetcher's three planes are state that crosses chunks too
    noc = NocConfig(mesh_x=4, mesh_y=2, link_lat=1, router_lat=2,
                    topology="torus")
    return small_test_config(
        8, n_banks=4, quantum=400, noc=noc, coherence="moesi",
        prefetcher="stride", prefetch_degree=4, prefetch_lat=3)


MACHINES = {"plain": _plain_cfg, "router-dram": _router_dram_cfg,
            "zoo": _zoo_cfg}

CASES = [
    pytest.param(MACHINES[m], GENERATOR_TRACES[g], id=f"{m}-{g}")
    for m in sorted(MACHINES) for g in sorted(GENERATOR_TRACES)
] + [
    pytest.param(
        lambda: small_test_config(8, n_banks=4, quantum=400, sharer_group=4),
        lambda: synth.readers_writer(8, n_rounds=3, seed=10),
        id="coarse-readers_writer"),
    pytest.param(
        lambda: small_test_config(8, n_banks=4, quantum=400, local_run_len=4),
        lambda: fold_ins(
            synth.fft_like(8, n_phases=2, points_per_core=8, seed=50)),
        id="local-runs-folded-fft_like"),
    pytest.param(
        _chunked_sharer_cfg,
        lambda: synth.readers_writer(64, n_rounds=2, block_lines=4, seed=14),
        id="chunked-sharers-readers_writer"),
]


def _leaves(state):
    for f in state._fields:
        v = getattr(state, f)
        if hasattr(v, "_fields"):  # nested pytree (knobs, faults)
            for sub in v._fields:
                yield f"{f}.{sub}", getattr(v, sub)
        else:
            yield f, v


@pytest.mark.parametrize("make_cfg, make_trace", CASES)
def test_fused_loop_equals_chunked_host_loop(make_cfg, make_trace):
    cfg, trace = make_cfg(), make_trace()
    # chunks short enough that every run crosses several drains and rebases
    fused = Engine(cfg, trace, chunk_steps=4)
    fused.run()
    chunked = Engine(cfg, trace, chunk_steps=4)
    chunked.run_chunked()
    assert fused.steps_run == chunked.steps_run > 4
    assert int(fused.cycle_base) == int(chunked.cycle_base)
    np.testing.assert_array_equal(fused.cycles, chunked.cycles, err_msg="cycles")
    assert fused.counters.keys() == chunked.counters.keys()
    for name, row in fused.counters.items():
        np.testing.assert_array_equal(
            row, chunked.counters[name], err_msg=f"counter {name}")
    assert fused.step_stats.keys() == chunked.step_stats.keys()
    for name, row in fused.step_stats.items():
        np.testing.assert_array_equal(
            row, chunked.step_stats[name], err_msg=f"stat row {name}")
    others = dict(_leaves(chunked.state))
    for name, leaf in _leaves(fused.state):
        np.testing.assert_array_equal(
            np.asarray(leaf), np.asarray(others[name]),
            err_msg=f"state leaf {name}")
