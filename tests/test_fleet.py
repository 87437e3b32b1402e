"""FleetEngine correctness (ISSUE 2 tentpole).

The contract is crisp: fleet element i must be BIT-EXACT with a solo
`Engine` run of the same effective (config, trace) — final cycles, every
stat counter, and the full machine state (L1/LLC/directory arrays, sync
tables, LRU stamps, even the step counter: `fleet_run_loop` freezes a
finished element at exactly the chunk boundary where a solo run_loop with
the same chunk_steps stops, every leaf but the ones its step cannot
change: `FREEZE_EXEMPT`, below). And a whole parameter sweep
must be ONE compilation: the static jit key is the timing-normalized
geometry, with every timing knob traced.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from primesim_tpu.analysis.recompile import recompile_sentinel
from primesim_tpu.config.machine import (
    FAULT_LINK_DEGRADE,
    CacheConfig,
    small_test_config,
)
from primesim_tpu.sim.engine import Engine
from primesim_tpu.sim.fleet import (
    FREEZE_EXEMPT,
    FleetEngine,
    apply_overrides,
    fleet_run_chunk,
    fleet_run_loop,
)
from primesim_tpu.sim.validate import llc_views
from primesim_tpu.trace import synth
from primesim_tpu.trace.format import EV_INS, EV_LD, EV_ST, from_event_lists


def assert_element_matches_solo(fleet, i, cfg_eff, trace, chunk_steps):
    solo = Engine(cfg_eff, trace, chunk_steps=chunk_steps)
    solo.run()
    np.testing.assert_array_equal(
        fleet.cycles[i], solo.cycles, err_msg=f"elem {i} cycles"
    )
    assert fleet.steps_run[i] == solo.steps_run, f"elem {i} steps_run"
    assert fleet.cycle_base[i] == solo.cycle_base, f"elem {i} cycle_base"
    fc = fleet.element_counters(i)
    for k, v in solo.counters.items():
        np.testing.assert_array_equal(
            fc[k], v, err_msg=f"elem {i} counter {k}"
        )
    for k, v in solo.step_stats.items():
        np.testing.assert_array_equal(
            fleet.step_stats[k][i], v, err_msg=f"elem {i} stat row {k}"
        )
    es = fleet.element_state(i)
    for f in es._fields:
        if f == "knobs":
            continue  # knobs are inputs, compared via cfg_eff already
        a, b = getattr(es, f), getattr(solo.state, f)
        if hasattr(a, "_fields"):  # nested pytree (faults): leaf-wise
            for sub in a._fields:
                np.testing.assert_array_equal(
                    np.asarray(getattr(a, sub)),
                    np.asarray(getattr(b, sub)),
                    err_msg=f"elem {i} state field {f}.{sub}",
                )
            continue
        np.testing.assert_array_equal(
            np.asarray(a),
            np.asarray(b),
            err_msg=f"elem {i} state field {f}",
        )


def test_fleet_parity_mixed_traces_and_knobs():
    # the acceptance bar: B=4 elements, ALL with distinct traces AND
    # distinct traced timing knobs, one of them a sync (lock) workload
    cfg = small_test_config(8, n_banks=4, quantum=300)
    traces = [
        synth.false_sharing(8, n_mem_ops=40, seed=11),
        synth.uniform_random(8, n_mem_ops=60, seed=12),
        synth.lock_contention(8, n_critical=6, seed=13),
        synth.barrier_phases(8, n_phases=3, seed=14),
    ]
    overrides = [
        {},
        {"llc_lat": 25, "dram_lat": 140, "l1_lat": 4},
        {"quantum": 150, "cpi": 2},
        {"link_lat": 3, "router_lat": 2, "cpi": [1, 2, 1, 2, 3, 1, 1, 2]},
    ]
    # the whole 4-element knob sweep must be ONE compilation of the
    # fleet program (jit key = timing-normalized geometry)
    with recompile_sentinel(allowed=1, watch=("fleet",),
                            label="mixed traces+knobs sweep"):
        fleet = FleetEngine(cfg, traces, overrides, chunk_steps=32)
        fleet.run()
    assert fleet.done() and list(fleet.done_mask()) == [True] * 4
    for i, (t, ov) in enumerate(zip(traces, overrides)):
        assert_element_matches_solo(
            fleet, i, apply_overrides(cfg, ov), t, chunk_steps=32
        )


def test_fleet_parity_contention_and_dram_queue_knobs():
    # traced knobs that feed the queueing models: contention_lat (tile
    # model) and dram_service/dram_lat (memory-controller queue)
    cfg = small_test_config(
        8,
        n_banks=4,
        dram_queue=True,
        dram_service=20,
    )
    import dataclasses

    cfg = dataclasses.replace(
        cfg,
        noc=dataclasses.replace(cfg.noc, contention=True,
                                contention_model="tile"),
    )
    traces = [
        synth.false_sharing(8, n_mem_ops=40, seed=21),
        synth.uniform_random(8, n_mem_ops=50, seed=22),
        synth.fft_like(8, n_phases=2, points_per_core=12, seed=23),
    ]
    overrides = [
        {},
        {"contention_lat": 7, "dram_service": 35},
        {"dram_service": 0, "dram_lat": 90, "contention_lat": 2},
    ]
    with recompile_sentinel(allowed=1, watch=("fleet",),
                            label="contention/dram knob sweep"):
        fleet = FleetEngine(cfg, traces, overrides, chunk_steps=32)
        fleet.run()
    for i, (t, ov) in enumerate(zip(traces, overrides)):
        assert_element_matches_solo(
            fleet, i, apply_overrides(cfg, ov), t, chunk_steps=32
        )


@pytest.mark.slow
def test_fleet_parity_router_model():
    # the router NoC model's link_free clocks rebase per element with a
    # per-element quantum — the hairiest drain/rebase interaction
    import dataclasses

    cfg = small_test_config(8, n_banks=4, quantum=400)
    cfg = dataclasses.replace(
        cfg,
        noc=dataclasses.replace(
            cfg.noc, contention=True, contention_model="router"
        ),
    )
    traces = [
        synth.false_sharing(8, n_mem_ops=40, seed=31),
        synth.uniform_random(8, n_mem_ops=50, seed=32),
        synth.false_sharing(8, n_mem_ops=40, seed=33),
    ]
    overrides = [{}, {"link_lat": 4, "quantum": 250}, {"router_lat": 5}]
    with recompile_sentinel(allowed=1, watch=("fleet",),
                            label="router-model knob sweep"):
        fleet = FleetEngine(cfg, traces, overrides, chunk_steps=16)
        fleet.run()
    for i, (t, ov) in enumerate(zip(traces, overrides)):
        assert_element_matches_solo(
            fleet, i, apply_overrides(cfg, ov), t, chunk_steps=16
        )


@pytest.mark.slow
def test_fleet_one_compilation_per_geometry():
    # changing only TRACED timing knobs between fleet runs must not
    # retrigger compilation; changing geometry must
    cfg = small_test_config(8, n_banks=4)
    traces = [synth.uniform_random(8, n_mem_ops=30, seed=41)]
    f1 = FleetEngine(cfg, traces, [{"llc_lat": 12}], chunk_steps=16)
    f1.run()
    n0 = fleet_run_loop._cache_size()
    f2 = FleetEngine(
        cfg, traces, [{"llc_lat": 33, "quantum": 500, "cpi": 3}],
        chunk_steps=16,
    )
    f2.run()
    assert fleet_run_loop._cache_size() == n0, (
        "knob-only change recompiled the fleet loop"
    )
    # sanity: the two runs really simulated different machines
    assert int(f1.cycles.max()) != int(f2.cycles.max())
    cfg_geo = small_test_config(4, n_banks=4)
    f3 = FleetEngine(
        cfg_geo, [synth.uniform_random(4, n_mem_ops=30, seed=42)],
        chunk_steps=16,
    )
    f3.run()
    assert fleet_run_loop._cache_size() == n0 + 1  # new geometry compiles


def _joining_trace(n_cores: int, first_line: int):
    """Rounds of read sharing that end in read-joins: cores 0 and 1 read
    two lines (an E grant, then the probe that leaves the line ownerless
    with two sharers); every other core pads with instructions and then
    reads both, the even cores one line first and the odd cores the other,
    all on one clock: several joiners of one directory entry in one step,
    two entries a step. A store to the round's first line ends the round,
    so joins are demoted and the entry's LRU stamp is read again."""
    per_core = [[] for _ in range(n_cores)]
    for r in range(4):
        a = (first_line + 2 * r) * 64
        b = a + 64
        for c in range(n_cores):
            if c < 2:
                per_core[c] += [(EV_LD, 4, a), (EV_LD, 4, b)]
            else:
                x, y = (a, b) if c % 2 == 0 else (b, a)
                per_core[c] += [(EV_INS, 60, 0), (EV_LD, 4, x), (EV_LD, 4, y)]
        per_core[n_cores - 1] += [(EV_INS, 90, 0), (EV_ST, 4, a)]
    return from_event_lists(per_core)


def _joins_by_step(gold) -> dict:
    """{(step, line): lanes that joined}, filled as the oracle runs."""
    joined: dict = {}
    do_join = gold._do_join

    def counted(c, line, pre, step):
        joined[step, line] = joined.get((step, line), 0) + 1
        do_join(c, line, pre, step)

    gold._do_join = counted
    return joined


# n_banks * sets * ways, the entries of the join table: under one row of
# 128 lanes, over one row and no multiple of it (five ways), a multiple
_JOIN_TABLE_MACHINES = {
    "32_entries": dict(n_banks=2, llc=CacheConfig(size=1024, ways=4, line=64, latency=10)),
    "160_entries": dict(n_banks=4, llc=CacheConfig(size=2560, ways=5, line=64, latency=10)),
    "256_entries": dict(n_banks=4),
}


@pytest.mark.parametrize("n_elements", [2, 3])
@pytest.mark.parametrize("machine", sorted(_JOIN_TABLE_MACHINES))
def test_fleet_join_table_at_awkward_sizes(machine, n_elements):
    # the join-LRU representative table has one form, rows of 128 lanes
    # (`step.py::_join_representative`): whatever the table's size pads to,
    # every element equals its solo Engine and the solo Engine the oracle
    from primesim_tpu.golden.sim import GoldenSim

    cfg = small_test_config(8, **_JOIN_TABLE_MACHINES[machine])
    n = cfg.n_banks * cfg.llc.sets * cfg.llc.ways
    assert n == int(machine.split("_")[0])
    traces = [_joining_trace(8, first_line=3 * i) for i in range(n_elements)]
    overrides = [{}, {"llc_lat": 25, "dram_lat": 140}, {"quantum": 150}][:n_elements]
    fleet = FleetEngine(cfg, traces, overrides, chunk_steps=8)
    fleet.run()
    assert fleet.done()
    for i, (t, ov) in enumerate(zip(traces, overrides)):
        cfg_eff = apply_overrides(cfg, ov)
        assert_element_matches_solo(fleet, i, cfg_eff, t, chunk_steps=8)
        gold = GoldenSim(cfg_eff, t)
        joined = _joins_by_step(gold)
        gold.run()
        # the trace does what it is for: one entry joined twice in a step
        assert max(joined.values()) >= 2, joined
        # the element equals its solo Engine in every field (above), so
        # holding it to the oracle holds the solo Engine to it too
        np.testing.assert_array_equal(fleet.cycles[i], gold.cycles)
        counters = fleet.element_counters(i)
        for k, v in gold.counters.items():
            np.testing.assert_array_equal(counters[k], v, err_msg=f"elem {i} counter {k}")
        # what the representative is for: a joined entry's LRU stamp is
        # refreshed once, however many lanes joined it
        llc_tag, _, llc_lru = llc_views(cfg_eff, fleet.element_state(i))
        np.testing.assert_array_equal(llc_tag, gold.llc_tag, err_msg=f"elem {i} llc_tag")
        np.testing.assert_array_equal(llc_lru, gold.llc_lru, err_msg=f"elem {i} llc_lru")


def _router_dram(cfg):
    return dataclasses.replace(
        cfg, dram_queue=True, dram_service=20,
        noc=dataclasses.replace(
            cfg.noc, contention=True, contention_model="router"),
    )


def _freeze_case(name):
    """(cfg, traces, overrides) of a fleet whose elements finish chunks
    of 8 steps apart: what `fleet_run_loop`'s freeze has to hold."""
    short, long_ = (synth.stream(4, n_mem_ops=4, seed=61),
                    synth.uniform_random(4, n_mem_ops=60, seed=62))
    cfg = small_test_config(4, n_banks=4, local_run_len=4)
    overrides = [{}, {}]
    if name == "router_dram":
        cfg = _router_dram(cfg)
    elif name == "sync":
        short = synth.barrier_phases(4, n_phases=1, work_per_phase=3, seed=63)
        long_ = synth.barrier_phases(4, n_phases=4, seed=64)
    elif name == "faults":
        # every step flips: what a finished machine's step would count or
        # change shows at once
        cfg = dataclasses.replace(
            _router_dram(cfg), faults_enabled=True, max_fault_events=1,
            fault_events=((30, FAULT_LINK_DEGRADE, 1, 3),),
            fault_flip_l1=1.0, fault_flip_llc=1.0, fault_due_rate=0.25)
        overrides = [{"fault_seed": 11}, {"fault_seed": 22}]
    elif name == "quantum":
        overrides = [{"quantum": 100}, {"quantum": 500, "llc_lat": 25}]
    else:
        assert name == "plain"
    # the long element between two short ones: the freeze is by element
    return cfg, [short, long_, short], [overrides[0], overrides[1], overrides[0]]


_FREEZE_CASES = ("plain", "router_dram", "sync", "faults", "quantum")


@pytest.mark.parametrize("case", _FREEZE_CASES)
def test_fleet_freeze_elements_chunks_apart(case):
    # the short elements stop chunks before the long one and the loop
    # steps them on: each must come out as its solo Engine leaves it,
    # `state.step`, steps, cycle base, counters and stat rows included
    cfg, traces, overrides = _freeze_case(case)
    fleet = FleetEngine(cfg, traces, overrides, chunk_steps=8)
    fleet.run()
    assert fleet.done()
    steps = fleet.steps_run
    assert steps[1] - steps[0] >= 16 and steps[2] == steps[0], steps
    for i, (t, ov) in enumerate(zip(traces, overrides)):
        assert_element_matches_solo(
            fleet, i, apply_overrides(cfg, ov), t, chunk_steps=8)


@pytest.mark.parametrize("case", _FREEZE_CASES)
def test_finished_machine_keeps_exempt_leaves(case):
    # the invariant `fleet_run_loop` leans on: a chunk of steps over a
    # machine whose cores all stand at END leaves every FREEZE_EXEMPT
    # leaf as it was (and `step` not: the steps did run)
    cfg, traces, overrides = _freeze_case(case)
    fleet = FleetEngine(cfg, traces[:2], overrides[:2], chunk_steps=8)
    fleet.run()
    assert fleet.done()
    after = fleet_run_chunk(
        fleet.geom_cfg, 8, fleet.events, fleet.state,
        has_sync=fleet.has_sync)
    np.testing.assert_array_equal(after.step, fleet.state.step + 8)
    for f in FREEZE_EXEMPT:
        np.testing.assert_array_equal(
            getattr(after, f), getattr(fleet.state, f), err_msg=f)


@pytest.mark.parametrize("faults", [False, True])
def test_fleet_loop_selects_no_directory(faults):
    # the compiled loop freezes by `select`: none has the shape of the
    # batched `dirm` (nor of `l1`), unless the machine injects faults,
    # whose loop exempts nothing
    cfg = small_test_config(4, n_banks=4)
    if faults:
        cfg = dataclasses.replace(cfg, faults_enabled=True, max_fault_events=1)
    fleet = FleetEngine(
        cfg, [synth.stream(4, n_mem_ops=4, seed=s) for s in (1, 2, 3)],
        chunk_steps=8)
    text = fleet_run_loop.lower(
        fleet.geom_cfg, 8, fleet.events, fleet.state,
        jnp.asarray(4, jnp.int32), has_sync=fleet.has_sync,
    ).compile().as_text()
    for f in FREEZE_EXEMPT:
        leaf = getattr(fleet.state, f)
        shape = ",".join(str(n) for n in leaf.shape)
        assert leaf.shape[0] == 3 and leaf.ndim == 3
        selects = re.findall(rf"= s32\[{shape}\]\S* select\(", text)
        assert bool(selects) == faults, (f, shape, len(selects))
    # the text does say `select` where the freeze is: the clocks, [B, C]
    assert re.search(r"= s32\[3,4\]\S* select\(", text)


def _router_sort_shapes(fleet):
    """The operand shapes of every `sort` under `s.noc/rank` in the
    fleet's `fleet_run_loop`, through every sub-jaxpr."""
    found = []

    def walk(jaxpr, prefix):
        for eqn in jaxpr.eqns:
            path = f"{prefix}/{eqn.source_info.name_stack}"
            if eqn.primitive.name == "sort" and "/s.noc/rank" in path:
                found.append(eqn.invars[0].aval.shape)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub, path)

    walk(jax.make_jaxpr(lambda ev, st: fleet_run_loop(
        fleet.geom_cfg, 8, ev, st, jnp.asarray(1, jnp.int32),
        has_sync=fleet.has_sync))(fleet.events, fleet.state).jaxpr, "")
    return found


@pytest.mark.parametrize("B", [2, 8])
def test_fleet_sorts_the_routers_entries_a_machine_at_a_time(B):
    """Under the fleet's `vmap` the router walk's three entry sorts are
    each machine's solo sort, 1-D of C x legs x H + NL entries, B times
    each (`ranking._entry_sort`), never one sort of `[B, N]`; every
    element is still its solo engine's."""
    cfg = _router_dram(small_test_config(4, n_banks=4, local_run_len=4))
    traces = [synth.uniform_random(4, n_mem_ops=30, seed=71 + s) for s in range(B)]
    overrides = [{}, {"link_lat": 3}] + [{"link_lat": 2}] * (B - 2)
    fleet = FleetEngine(cfg, traces, overrides, chunk_steps=8)
    fleet.run()
    entries = 4 * 2 * 2 + 16
    assert _router_sort_shapes(fleet) == [(entries,)] * (3 * B)
    for i, (t, ov) in enumerate(zip(traces[:3], overrides)):
        assert_element_matches_solo(
            fleet, i, apply_overrides(cfg, ov), t, chunk_steps=8)


def test_fleet_rejections():
    cfg = small_test_config(4, n_banks=4)
    tr = synth.stream(4, n_mem_ops=10, seed=51)
    with pytest.raises(ValueError, match="at least one trace"):
        FleetEngine(cfg, [])
    with pytest.raises(ValueError, match="must match 1:1"):
        FleetEngine(cfg, [tr], [{}, {}])
    with pytest.raises(ValueError, match="unknown timing override"):
        FleetEngine(cfg, [tr], [{"llc_latency": 3}])
    with pytest.raises(ValueError, match="quantum"):
        apply_overrides(cfg, {"quantum": 2**30})


@pytest.mark.slow
def test_fleet_uneven_lengths_and_early_finish():
    # elements finishing chunks apart: the short element must freeze
    # bit-exactly while the long one keeps the fleet's while_loop live
    cfg = small_test_config(4, n_banks=4)
    traces = [
        synth.stream(4, n_mem_ops=4, seed=61),
        synth.uniform_random(4, n_mem_ops=120, seed=62),
        synth.stream(4, n_mem_ops=40, seed=63),
    ]
    fleet = FleetEngine(cfg, traces, chunk_steps=8)
    fleet.run()
    for i, t in enumerate(traces):
        assert_element_matches_solo(fleet, i, cfg, t, chunk_steps=8)


def test_cli_sweep(tmp_path, capsys):
    import json

    from primesim_tpu.cli import main
    from primesim_tpu.config.machine import MachineConfig

    cfg = MachineConfig(n_cores=8, n_banks=8)
    cfg_path = str(tmp_path / "m.json")
    with open(cfg_path, "w") as f:
        f.write(cfg.to_json())
    rep_dir = str(tmp_path / "reports")
    rc = main(
        [
            "sweep", cfg_path,
            "--synth", "false_sharing:n_mem_ops=30",
            "--vary", "llc_lat=10",
            "--vary", "llc_lat=40,dram_lat=200",
            "--vary", "quantum=500",
            "--chunk-steps", "32",
            "--report-dir", rep_dir,
        ]
    )
    assert rc == 0
    lines = [
        json.loads(ln)
        for ln in capsys.readouterr().out.strip().splitlines()
    ]
    assert len(lines) == 4  # 3 elements + aggregate
    assert [d["detail"]["fleet_index"] for d in lines[:3]] == [0, 1, 2]
    assert lines[3]["metric"] == "fleet_aggregate_MIPS"
    assert lines[3]["detail"]["instructions"] == sum(
        d["detail"]["instructions"] for d in lines[:3]
    )
    # element 1's slower LLC/DRAM must cost cycles vs element 0
    assert (
        lines[1]["detail"]["max_core_cycles"]
        > lines[0]["detail"]["max_core_cycles"]
    )
    # one report per element, golden machine line reflects the override
    import os

    rep1 = open(os.path.join(rep_dir, "element_1.txt")).read()
    assert "fleet element 1" in rep1 and "lat 40" in rep1

    # each element must equal a solo CLI run of the same effective config
    from primesim_tpu.sim.fleet import apply_overrides as ao

    solo_cfg = ao(cfg, {"llc_lat": 40, "dram_lat": 200})
    solo_path = str(tmp_path / "solo.json")
    with open(solo_path, "w") as f:
        f.write(solo_cfg.to_json())
    rc = main(
        ["run", solo_path, "--synth", "false_sharing:n_mem_ops=30"]
    )
    assert rc == 0
    d = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert (
        d["detail"]["max_core_cycles"]
        == lines[1]["detail"]["max_core_cycles"]
    )
    assert d["detail"]["instructions"] == lines[1]["detail"]["instructions"]
