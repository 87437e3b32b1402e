"""The committed cell `rung2.sweep-b16` (ISSUE 43) at its parity size, on
the CPU: the sixteen machines of `benchmark/configs/rung2-sweep-b16.json`
through ONE `FleetEngine` on the traffic file's short trace, every element
against a solo `Engine` on its own machine, four of them whole against the
benchmark's stock reference and the golden model; the overrides spelt as
`primetpu sweep --vary` spells them; and the three readers the cell brings,
on a hand-made record and trace."""

import json

import numpy as np
import pytest

from benchmark_modules import (assert_reference_equals_golden, handed_to_fleet_by_sweep,
                               vary_string)

import cells  # noqa: E402  (benchmark/ is on the path now)
import measure  # noqa: E402
import reference  # noqa: E402
import trafficgen  # noqa: E402

from primesim_tpu.config.machine import MachineConfig  # noqa: E402
from primesim_tpu.obs import process_store  # noqa: E402
from primesim_tpu.sim.engine import Engine  # noqa: E402
from primesim_tpu.sim.fleet import FleetEngine, apply_overrides  # noqa: E402
from primesim_tpu.trace.format import Trace  # noqa: E402

CELL = "rung2.sweep-b16"
WHOLE = (0, 7, 10, 13)  # held to the reference and the golden model: the twins and two more


@pytest.fixture(scope="module")
def spec():
    return cells.load_cell(CELL)


@pytest.fixture(scope="module")
def ran(spec):
    """The parity job of a run of seed 43, as `measure.run_cell` builds it."""
    run, machine = spec["config"]["run"], spec["config"]["machine"]
    ev = trafficgen.make_trace(spec["traffic"], machine["n_cores"], 43, parity=True)
    cfg = MachineConfig.from_dict(machine)
    trace = Trace(ev, measure._lengths(ev))
    ovs = run["fleet"]["overrides"]
    fleet = FleetEngine(cfg, [trace] * len(ovs), ovs, chunk_steps=run["chunk_steps"])
    fleet.run()
    return cfg, trace, ev, ovs, fleet


def test_every_element_equals_a_solo_engine_on_its_own_machine(ran):
    cfg, trace, ev, ovs, fleet = ran
    assert fleet.n_elements == 16 and fleet.done()
    expect = trafficgen.total_instructions(ev)
    for e, ov in enumerate(ovs):
        solo = Engine(apply_overrides(cfg, ov), trace, chunk_steps=fleet.chunk_steps)
        solo.run()
        np.testing.assert_array_equal(fleet.cycles[e], solo.cycles, err_msg=f"element {e}")
        for k, v in solo.counters.items():
            np.testing.assert_array_equal(fleet.counters[k][e], v, err_msg=f"element {e} {k}")
        assert int(fleet.steps_run[e]) == solo.steps_run
        assert int(fleet.counters["instructions"][e].sum()) == expect
    # the halved quantum ends an element at another chunk: the freeze is exercised
    assert len(set(fleet.steps_run.tolist())) > 1


def test_the_twins_are_equal_in_every_count(ran):
    _, _, _, ovs, fleet = ran
    assert ovs[0] == {} and ovs[10] == {"quantum": 1000, "llc_lat": 12, "dram_lat": 100}
    assert fleet.elem_cfgs[0] == fleet.elem_cfgs[10]
    np.testing.assert_array_equal(fleet.cycles[0], fleet.cycles[10])
    for k, v in fleet.counters.items():
        np.testing.assert_array_equal(v[0], v[10], err_msg=k)
    for k, v in fleet.step_stats.items():
        np.testing.assert_array_equal(v[0], v[10], err_msg=k)
    digests = [measure.digest(fleet.cycles[e], {k: v[e] for k, v in fleet.counters.items()})
               for e in range(16)]
    assert digests[0] == digests[10] and len(set(digests)) == 15


@pytest.mark.parametrize("e", WHOLE)
def test_an_element_whole_against_the_reference_and_golden(spec, ran, e):
    _, _, ev, _, fleet = ran
    runner = cells.load_runner(spec["runner"])
    machine = runner.element_machines(spec["config"]["machine"], spec["config"]["run"])[e]
    ref = assert_reference_equals_golden(reference, machine, ev)  # the two agree, then:
    np.testing.assert_array_equal(fleet.cycles[e], np.asarray(ref.cycles, np.int64))
    for k, v in fleet.counters.items():
        if k in reference.COUNTERS:
            np.testing.assert_array_equal(v[e], np.asarray(ref.counters[k], np.int64), err_msg=k)
        else:
            assert not v[e].any(), k
    chunk = fleet.chunk_steps
    assert int(fleet.steps_run[e]) == -(-ref.step_count // chunk) * chunk


# ---- the cell is what `primetpu sweep` runs ----------------------------------

def test_each_override_is_what_its_vary_string_parses_to(spec):
    from primesim_tpu.cli import _parse_vary

    ovs = spec["config"]["run"]["fleet"]["overrides"]
    assert vary_string(ovs[1]) == "quantum=500,llc_lat=10,dram_lat=80"
    for ov in ovs[1:]:  # element 0 is the machine as it stands: no string spells `{}`
        assert _parse_vary(vary_string(ov)) == ov


def test_cmd_sweeps_fan_builds_the_files_machines(spec, monkeypatch):
    """`primetpu sweep configs/rung2_256core_parsec.json --synth ... --vary ...`
    with the fifteen strings hands `FleetEngine` the configurations
    `apply_overrides` builds from the file."""
    ovs = spec["config"]["run"]["fleet"]["overrides"]
    cfg, traces, overrides, kw = handed_to_fleet_by_sweep(
        monkeypatch, "rung2_256core_parsec.json", spec)
    mine = MachineConfig.from_dict(spec["config"]["machine"])
    assert cfg == mine and kw["chunk_steps"] == 8 and kw["mesh"] is None
    assert overrides == ovs[1:] and len(traces) == 15  # the one trace fanned over the varies
    assert all(t is traces[0] for t in traces)
    assert [apply_overrides(cfg, ov) for ov in overrides] == \
        [apply_overrides(mine, ov) for ov in ovs[1:]]
    assert len({json.dumps(ov, sort_keys=True) for ov in overrides}) == 15


# ---- the three readers the cell brings --------------------------------------

def _sample(steps, element_steps=None):
    """A job sample as `engine.commit_job` records one: a fleet's, or a solo engine's."""
    machines = len(element_steps or [0])
    caps = {"n_cores": 64 * machines, "local_run_len": 8, "sort_entries": 0}
    if element_steps:
        caps.update(elements=machines, element_steps=element_steps)
    process_store().record(0.0, "fleet" if element_steps else "engine", steps, 1.0,
                           {"instructions": 1}, caps=caps,
                           phases={"init": 0.0, "dispatch": 0.001, "wait": 1.0, "readback": 0.001})


def test_fleet_carry_ms_step_reads_the_ops_that_hold_no_phase():
    read = cells.load_metric("fleet_carry_ms_step")
    job = {"steps": 200, "traced": True, "elements": [{}, {}]}
    ops = {"dynamic-update-slice.173": [0.30, 200],  # no `op_name` at all
           "broadcast_select_fusion.21 jit(fleet_run_loop)/vmap(jit(run_loop))/select_n": [0.20, 200],
           "copy.1846 (unnamed)": [0.10, 200],
           "fusion.9 jit(fleet_run_loop)/vmap(jit(run_loop))/s.chunk/add": [0.05, 25],
           "fusion.7 jit(fleet_run_loop)/vmap(jit(run_loop))/s.local/gather": [0.40, 200],
           "fusion.8 (unnamed)/s.commit/(held)": [0.02, 200]}
    assert read({"jobs": [job]}, {"ops": ops}) == pytest.approx(1e3 * 0.60 / 200)
    assert read({"jobs": [job]}, None) is None
    assert read({"jobs": [dict(job, traced=False)]}, {"ops": ops}) is None
    solo = {"steps": 200, "traced": True}  # a job of one machine: `ph_cover_pct`'s to say
    assert read({"jobs": [solo]}, {"ops": ops}) is None
    assert read({"jobs": [job]}, {"ops": {k: v for k, v in ops.items() if "/s." in k}}) == 0.0


def test_fleet_elem_ms_step_and_fleet_frozen_pct_read_the_jobs_samples():
    elem, frozen = cells.load_metric("fleet_elem_ms_step"), cells.load_metric("fleet_frozen_pct")
    _sample(80, [64, 80, 72, 80])
    _sample(96, [96, 96, 48, 96])
    run = {"jobs": [{"steps": 80, "seconds": 0.8, "trace": 0},
                    {"steps": 96, "seconds": 1.2, "trace": 1}]}
    assert elem(run, None) == pytest.approx(1e3 * 2.0 / 176 / 4)
    assert frozen(run, None) == pytest.approx(100 * (1 - (296 + 336) / (4 * 176)))
    # a traced window shorter than a pass keeps its first job, as every sample reader does
    assert frozen(dict(run, passes=0), None) == pytest.approx(100 * (1 - 296 / 320))
    # no sample of these jobs (the parent's fleet commits none): nothing to read, no raise
    other = {"jobs": [{"steps": 81, "seconds": 0.8}, {"steps": 96, "seconds": 1.2}]}
    assert elem(other, None) is None and frozen(other, None) is None
    assert elem({"jobs": []}, None) is None and frozen({"jobs": []}, None) is None
    # a solo engine's sample names no elements
    _sample(40)
    solo = {"jobs": [{"steps": 40, "seconds": 0.5}]}
    assert elem(solo, None) is None and frozen(solo, None) is None
