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

Since PR 54 the two also differ in who owns the state: the solo fused loop
is GIVEN it (`run_loop` donates `st`: the arrays handed in are deleted and
the result lies in their buffers), the chunked walk and the fleet's fused
loop keep their source. The same equalities hold, for `Engine` and
`FleetEngine`, and a fused run after `load_checkpoint` works on the loaded
arrays.
"""

import jax
import numpy as np
import pytest

from primesim_tpu.config.machine import (
    CacheConfig,
    MachineConfig,
    NocConfig,
    small_test_config,
)
from primesim_tpu.sim.engine import Engine
from primesim_tpu.sim.fleet import FleetEngine
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
    handed = fused.state
    fused.run()
    assert all(x.is_deleted() for x in jax.tree.leaves(handed))  # the loop owned it
    chunked = Engine(cfg, trace, chunk_steps=4)
    built = chunked.state
    chunked.run_chunked()
    assert not any(x.is_deleted() for x in jax.tree.leaves(built))  # the walk kept its source
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


FLEET_CASES = [
    pytest.param(MACHINES[m], [GENERATOR_TRACES[g] for g in gens], id=f"{m}-{'+'.join(gens)}")
    for m, gens in (
        ("plain", ("fft_like", "false_sharing", "stream")),
        ("router-dram", ("barrier_phases", "lock_contention", "uniform_random")),
        ("zoo", ("readers_writer", "pointer_chase", "fft_like")),
    )
]
OVERRIDES = [{}, {"dram_lat": 150}, {"quantum": 200, "llc_lat": 14}]


@pytest.mark.parametrize("make_cfg, make_traces", FLEET_CASES)
def test_fused_fleet_loop_equals_chunked_fleet_walk(make_cfg, make_traces):
    """`FleetEngine.run()` (`fleet_run_loop`, which keeps its source) against
    `run_steps` to the end (`fleet_run_chunk`): machines of three
    lengths, so the fused loop freezes the finished ones, which the walk
    steps on: every leaf but `step` and the counter block, whose stat rows
    a finished router machine's steps still count (`FleetEngine.run_steps`)."""
    cfg, traces = make_cfg(), [make() for make in make_traces]
    fused = FleetEngine(cfg, traces, OVERRIDES, chunk_steps=4)
    handed = fused.state
    fused.run()
    assert not any(x.is_deleted() for x in jax.tree.leaves(handed))
    chunked = FleetEngine(cfg, traces, OVERRIDES, chunk_steps=4)
    built = chunked.state
    chunked.run_steps(10_000_000)
    assert chunked.done() and not any(x.is_deleted() for x in jax.tree.leaves(built))
    np.testing.assert_array_equal(fused.steps_run, chunked.steps_run)
    assert len(set(fused.steps_run.tolist())) > 1 and fused.steps_run.min() > 4
    np.testing.assert_array_equal(fused.cycle_base, chunked.cycle_base)
    np.testing.assert_array_equal(fused.cycles, chunked.cycles, err_msg="cycles")
    for name, row in fused.counters.items():
        np.testing.assert_array_equal(row, chunked.counters[name], err_msg=f"counter {name}")
    others = dict(_leaves(chunked.state))
    for name, leaf in _leaves(fused.state):
        if name not in ("step", "counters"):
            np.testing.assert_array_equal(
                np.asarray(leaf), np.asarray(others[name]), err_msg=f"state leaf {name}")


@pytest.mark.parametrize("kind", ["engine", "fleet"])
def test_a_fused_run_after_load_checkpoint_owns_the_loaded_state(tmp_path, kind):
    """A checkpointed walk, then `load_checkpoint` into a fresh engine and a
    fused `run()`: the loaded arrays are the solo loop's to consume (the
    engine built them for nobody else; the fleet's loop leaves them), and a
    SECOND `run()` on the finished engine takes the first one's result.
    Bit-exact with one uninterrupted run."""
    cfg = _router_dram_cfg()
    traces = [GENERATOR_TRACES[g]() for g in ("fft_like", "barrier_phases")]

    def make():
        if kind == "engine":
            return Engine(cfg, traces[0], chunk_steps=4)
        return FleetEngine(cfg, traces, OVERRIDES[:2], chunk_steps=4)

    whole = make()
    whole.run()
    walked = make()
    walked.run_steps(8)
    path = str(tmp_path / "mid.npz")
    walked.save_checkpoint(path)
    resumed = make()
    built = resumed.state
    resumed.load_checkpoint(path)
    loaded = resumed.state
    resumed.run()
    owned = kind == "engine"  # `run_loop` donates, `fleet_run_loop` does not
    assert all(x.is_deleted() for x in jax.tree.leaves(loaded)) == owned
    assert any(x.is_deleted() for x in jax.tree.leaves(loaded)) == owned
    assert not any(x.is_deleted() for x in jax.tree.leaves(built))  # never handed over
    np.testing.assert_array_equal(resumed.cycles, whole.cycles)
    np.testing.assert_array_equal(resumed.steps_run, whole.steps_run)
    for name, row in whole.counters.items():
        np.testing.assert_array_equal(resumed.counters[name], row, err_msg=name)
    finished = resumed.state
    resumed.run()  # at END: no chunk runs, the state changes hands all the same
    assert all(x.is_deleted() for x in jax.tree.leaves(finished)) == owned
    np.testing.assert_array_equal(resumed.cycles, whole.cycles)
    np.testing.assert_array_equal(resumed.steps_run, whole.steps_run)
