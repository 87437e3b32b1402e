"""Tests of the yardstick itself, on the CPU at tiny sizes."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import cells
import reference
import trafficgen
import xplane
from conftest import BENCH, ROOT
from tinycell import CELL, CELL_X4, make_root

HERE = os.path.dirname(os.path.abspath(__file__))


# ---- the generator is the program's, event for event ----------------------

@pytest.mark.parametrize("name,n_cores,args", [
    ("fft_like", 16, dict(n_phases=3, points_per_core=16, ins_per_mem=8)),
    ("fft_like", 8, dict(n_phases=2, points_per_core=4, ins_per_mem=1)),
    ("uniform_random", 16, dict(n_mem_ops=48, working_set=1 << 20, write_frac=0.3,
                                shared_frac=0.2, ins_per_mem=3)),
])
def test_generator_equals_the_programs(name, n_cores, args):
    from primesim_tpu.trace import synth
    from primesim_tpu.trace.format import fold_ins

    mine = cells.load_generator(name)(n_cores, 7, **args)
    theirs = fold_ins(synth.GENERATORS[name](n_cores, seed=7, **args))
    assert np.array_equal(mine, theirs.events)
    assert trafficgen.total_instructions(mine) == theirs.total_instructions()


def test_every_seed_the_same_traces_in_another_order():
    traffic = {"generator": "fft_like", "panel_seeds": [21, 22, 23, 24], "fold": True,
               "args": {"n_phases": 2, "points_per_core": 8, "ins_per_mem": 2},
               "parity_args": {"n_phases": 1}}
    fft = cells.load_generator("fft_like")
    orders = set()
    for seed in (0, 1, 5, 2**31 + 5, 2**33 + 1):
        panel = trafficgen.make_panel(traffic, 8, seed)
        order = [i for i, _ in panel]
        assert sorted(order) == [0, 1, 2, 3] and order == trafficgen.panel_order(traffic, seed)
        assert len({ev.shape for _, ev in panel}) == 1
        for i, ev in panel:  # the work does not depend on the run's seed
            assert np.array_equal(ev, fft(8, traffic["panel_seeds"][i], **traffic["args"]))
        orders.add(tuple(order))
    assert len(orders) >= 3
    short = trafficgen.make_trace(traffic, 8, 2**31 + 5, parity=True)
    assert not np.array_equal(short, trafficgen.make_trace(traffic, 8, 2**31 + 6, parity=True))
    padded = trafficgen.pad_to(short, panel[0][1].shape[1])
    assert padded.shape == panel[0][1].shape
    assert trafficgen.total_instructions(padded) == trafficgen.total_instructions(short)
    with pytest.raises(cells.CellError):
        trafficgen.make_trace({**traffic, "generator": "nope"}, 8, 1)


# ---- the plain reference agrees with the repo's golden model ---------------

def _machine(n, mx, full):
    return {
        "n_cores": n, "n_banks": n,
        "core": {"cpi": 1, "o3_overlap_256": 128 if full else 0},
        "l1": {"size": 256, "ways": 2, "line": 64, "latency": 2},
        "llc": {"size": 512, "ways": 4, "line": 64, "latency": 12},
        "noc": {"mesh_x": mx, "mesh_y": mx, "link_lat": 1, "router_lat": 1,
                "contention": full, "contention_model": "router", "contention_lat": 1},
        "dram_lat": 100, "dram_queue": full, "dram_service": 0,
        "quantum": 1000, "local_run_len": 8,
    }


@pytest.mark.parametrize("full", [False, True])
@pytest.mark.parametrize("gen", ["fft_like", "uniform_random"])
def test_reference_equals_golden(full, gen):
    from primesim_tpu.config.machine import MachineConfig
    from primesim_tpu.golden.sim import GoldenSim
    from primesim_tpu.trace.format import Trace

    if gen == "fft_like":
        ev = cells.load_generator(gen)(16, 3, n_phases=3, points_per_core=16, ins_per_mem=4)
    else:  # small caches and a small shared range: evictions, probes, upgrades
        ev = cells.load_generator(gen)(16, 3, n_mem_ops=48, working_set=1 << 14,
                                       write_frac=0.4, shared_frac=0.5, ins_per_mem=2)
    m = _machine(16, 4, full)
    lengths = (ev[:, :, 0] != trafficgen.EV_END).sum(1) + 1
    gold = GoldenSim(MachineConfig.from_dict(m), Trace(ev, lengths))
    gold.run()
    ref = reference.RefSim(m, ev)
    ref.run()
    assert ref.step_count == gold.step_count
    assert np.array_equal(np.asarray(ref.cycles), gold.cycles)
    for k, v in gold.counters.items():
        if k in reference.COUNTERS:
            assert np.array_equal(np.asarray(ref.counters[k]), v), k
        else:
            assert not v.any(), k
    if full:
        assert sum(ref.counters["noc_contention_cycles"]) > 0
        assert sum(ref.counters["dram_queue_cycles"]) > 0


def test_reference_refuses_what_it_does_not_model():
    ev = cells.load_generator("fft_like")(16, 3, n_phases=1, points_per_core=4, ins_per_mem=1)
    with pytest.raises(reference.UnsupportedMachine):
        reference.RefSim({**_machine(16, 4, False), "coherence": "moesi"}, ev)
    bad = _machine(16, 4, True)
    bad["noc"]["contention_model"] = "link"
    with pytest.raises(reference.UnsupportedMachine):
        reference.RefSim(bad, ev)
    ev[0, 0, 0] = 6  # a barrier
    with pytest.raises(reference.UnsupportedMachine):
        reference.RefSim(_machine(16, 4, False), ev)


# ---- the xplane reduction on a small recorded trace ------------------------

def test_leaves_union_and_gaps():
    evs = [(0, 100, "while"), (10, 30, "a"), (30, 40, "b"), (60, 90, "a"), (120, 130, "c")]
    leaves, containers = xplane._leaves(evs + [(125, 140, "fusion.1"), (128, 135, "copy.2")])
    assert sorted(leaves) == [(10, 30, "a"), (30, 40, "b"), (60, 90, "a"), (120, 130, "c"),
                              (125, 140, "fusion.1"), (128, 135, "copy.2")]
    assert containers == [evs[0]]  # only control flow is a container, not an overlapped op
    assert xplane._union([(s, e) for s, e, _ in leaves], 0, 125) == [[10, 40], [60, 90], [120, 125]]
    host = [(95, 125, "np.asarray"), (0, 200, "outer")]
    got = xplane._explain_all([(0, 10), (40, 60), (100, 120)], [evs[0]], host)
    assert got == pytest.approx({"device: inside while": 30e-9, "host: np.asarray": 20e-9})
    assert xplane._explain_all([(100, 120)], [evs[0]], []) == pytest.approx({"unattributed": 20e-9})


def test_op_names_from_compiled_text():
    text = (
        'HloModule m\n'
        '  %fusion.6 = s32[8]{0} fusion(%p), kind=kLoop, calls=%f, '
        'metadata={op_name="jit(run_loop)/while/body/closed_call/jit(searchsorted)/gather" '
        'stack_frame_id=3}\n'
        '  ROOT sort.2 = s32[8]{0} sort(%q), metadata={op_name="jit(run_loop)/sort"}\n'
        '  %add.1 = s32[] add(%a, %b)\n')
    names = xplane.op_names(text)
    assert names == {"fusion.6": "jit(run_loop)/while/body/closed_call/jit(searchsorted)/gather",
                     "sort.2": "jit(run_loop)/sort"}
    assert xplane._label("%fusion.6 = s32[8]{0} fusion(...)", names) == \
        "fusion.6 jit(run_loop)/jit(searchsorted)/gather"
    assert xplane._label("%add.1 = s32[] add(...)", names) == "add.1"


def test_reduction_of_recorded_tpu_trace():
    """`data/tiny_tpu.xplane.pb`: one job of a 16-core rung-3 machine,
    recorded on a TPU v5e through `measure.run_job` (device op line and
    host spans kept, the rest stripped); the numbers are pinned from it."""
    with open(os.path.join(HERE, "data", "tiny_tpu.expected.json")) as f:
        want = json.load(f)
    path = os.path.join(HERE, "data", "tiny_tpu.xplane.pb")
    red = xplane.reduce(path, want["hlo_text"])
    assert red["n_devices"] == 1 and len(red["ops"]) == want["n_ops"]
    assert red["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert red["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert 0 < red["busy_s"] < red["window_s"]
    assert red["device_ops"][0][0] == want["top_op"]
    assert red["idle_gaps"][0] == want["idle_gaps_top"]
    assert xplane.op_seconds(red, ("sort",)) == pytest.approx(want["sort_s"], rel=1e-9)
    assert xplane.op_seconds(red, ("gather", "take_along_axis"), without=("sort",)) == \
        pytest.approx(want["gather_s"], rel=1e-9)
    assert xplane.op_seconds(red, ("no-such-op",)) is None
    assert sum(s for _, s in red["idle_gaps"]) <= red["window_s"] - red["busy_s"] + 1e-9
    # without the program's text an op is known by its instruction name alone
    bare = xplane.reduce(path)
    assert bare["busy_s"] == red["busy_s"] and bare["device_ops"][0][0] == "fusion.646"


# ---- cells, configurations, traffic and metrics are found by name ----------

@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return make_root(str(tmp_path_factory.mktemp("checkout")))


def test_every_committed_cell_loads():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        spec = cells.load_cell(w["name"])
        assert spec["config"]["run"]["devices"] == w["chips"]
        assert {m["name"] for m in spec["end_to_end"]} >= {"sim_mips", "setup_s"}
        for m in spec["per_layer"]:
            assert callable(cells.load_metric(m["name"]))
        reference.RefSim(spec["config"]["machine"],
                         trafficgen.make_trace(spec["traffic"], 1024, 1, parity=True))
    sort_cells = next(m for m in bench["per_layer"] if m["name"] == "sort_ms_step")["workloads"]
    assert "mesh1024.fft-m16" not in sort_cells


def test_loader_finds_files_added_beside_the_old(tiny_root):
    spec = cells.load_cell(CELL, root=tiny_root)
    assert spec["config"]["machine"]["n_cores"] == 16
    assert spec["traffic"]["panel_seeds"] == [11, 12]
    assert "n_jobs" in [m["name"] for m in spec["per_layer"]]
    assert "sort_ms_step" not in [m["name"] for m in spec["per_layer"]]
    assert cells.load_metric("n_jobs", tiny_root)({"jobs": [1, 2]}, None) == 2
    # a configuration on four devices and a trace shape of its own, as files
    x4 = cells.load_cell(CELL_X4, root=tiny_root)
    assert x4["cell"]["chips"] == 4 and x4["config"]["run"]["devices"] == 4
    assert [i for i, _ in trafficgen.make_panel(x4["traffic"], 16, 3, root=tiny_root)] in ([0, 1], [1, 0])
    with pytest.raises(cells.CellError):
        cells.load_generator("stride_walk")  # the committed benchmark has no such shape
    with pytest.raises(cells.CellError):
        cells.load_cell("no.such-cell", root=tiny_root)
    with pytest.raises(cells.CellError):
        cells.load_metric("no_such_metric", tiny_root)
    assert cells.peak_for(spec["peaks"], "TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(cells.CellError):
        cells.peak_for(spec["peaks"], "TPU v9 imaginary")


def _run(root, *args, env=None):
    e = {**os.environ, "JAX_PLATFORMS": "cpu", "JAX_ENABLE_COMPILATION_CACHE": "false",
         "BENCH_RUN": "ignored", **(env or {})}
    return subprocess.run([sys.executable, os.path.join(root, "benchmark", "run.py"), *args],
                          capture_output=True, text=True, timeout=600, env=e, cwd=root)


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_last_line(tiny_root, trace):
    p = _run(tiny_root, "--workload", CELL, "--seed", str(2**31 + 17),
             "--seconds", "0.3", "--trace", str(trace))
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    out = json.loads(lines[-1])
    assert set(out) == {"correct", "attempted", "failed", "metrics", "device"}
    assert out["correct"] is True and out["failed"] == 0
    # a whole pass of the panel of 2 and the parity job; a traced run ends
    # with the job in flight, so it owes the first job and the parity job
    assert out["attempted"] >= (2 if trace else 3)
    assert set(out["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert out["device"]["platform"] == "cpu" and out["device"]["count"] == 1
    # a rehearsal's numbers never carry a device metric's name
    assert all(k.startswith("cpu_rehearsal.") for k in out["metrics"])
    names = {k.split(".", 1)[1] for k in out["metrics"]}
    if trace:
        assert {"tracegen_s", "compile_s", "job_s_max", "step_ms", "ins_per_step",
                "n_jobs"} <= names
        assert not names & {"sim_mips", "setup_s", "device_idle_pct", "step_roofline"}
    else:
        assert names == {"sim_mips", "setup_s"}  # no HBM reading on the CPU
    for v in out["metrics"].values():
        assert set(v) == {"value", "unit"} and v["value"] > 0
    checks = [l for l in lines if l.startswith("[check] ")]
    assert len(checks) >= 50 and all(l.endswith(" = 0 (limit 0)") for l in checks)
    assert sum(l.startswith("[check] checked.") for l in checks) == 22  # the whole timed job
    assert sum(l.startswith("[check] parity.") for l in checks) == 22


def test_rehearsal_on_four_devices(tiny_root):
    """`run.devices` builds the mesh: a four-device configuration and a
    trace shape added as files run sharded, here over four virtual CPU
    devices; with fewer devices than the cell asks for there is no result."""
    flags = {"XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    p = _run(tiny_root, "--workload", CELL_X4, "--seed", "9", "--seconds", "0.1",
             "--trace", "0", env=flags)
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["device"]["count"] == 4
    assert "[check] jobs.wrong_device_count = 0 (limit 0)" in p.stdout
    p = _run(tiny_root, "--workload", CELL_X4, "--seed", "9", "--seconds", "0.1",
             "--trace", "0", env={"XLA_FLAGS": ""})
    assert p.returncode != 0 and p.stdout == ""


def test_fails_without_the_program(tmp_path):
    import shutil

    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    p = _run(str(tmp_path), "--workload", "mesh1024.fft-m16", "--seed", "1",
             "--seconds", "1", "--trace", "0")
    assert p.returncode != 0 and p.stdout == ""
    p = _run(str(tmp_path), "--workload", "nope", "--seed", "1", "--seconds", "1")
    assert p.returncode != 0 and p.stdout == ""


# ---- `correct` fails when it has to ----------------------------------------

def _execute(tiny_root, **broken):
    import jax

    import run as harness

    spec = cells.load_cell(CELL, root=tiny_root)
    device = {"platform": "cpu", "kind": jax.devices()[0].device_kind}
    return harness.execute(spec, 5, 0.1, False, True, device, time.perf_counter(), **broken)


def _failed(notes):
    return [n.split()[1] for n in notes if n.startswith("[check]") and " = 0 (" not in n]


@pytest.mark.parametrize("which", ["every_job", "timed_jobs", "third_job_on"])
def test_a_broken_timed_path_is_not_correct(tiny_root, monkeypatch, which):
    """The rest of a run with the timed path broken underneath: a count
    altered where it is produced."""
    from primesim_tpu.sim.engine import Engine

    real, calls = Engine.run, []
    first_broken = {"every_job": 1, "timed_jobs": 2, "third_job_on": 4}[which]

    def broken(self, *a, **kw):
        real(self, *a, **kw)
        calls.append(1)
        if len(calls) >= first_broken:  # call 1 is the parity job
            self.host_counters["noc_msgs"][0] += 1

    monkeypatch.setattr(Engine, "run", broken)
    # a clock that ticks once a reading, so that the window holds the same
    # number of passes (more than one) however slow the machine is
    import itertools
    import types

    import measure

    ticks = itertools.count()
    monkeypatch.setattr(measure, "time", types.SimpleNamespace(
        perf_counter=lambda: 0.004 * next(ticks)))
    result, notes = _execute(tiny_root)
    assert result["correct"] is False and result["failed"] >= 1
    assert len(calls) >= 5
    if which == "every_job":
        assert _failed(notes) == ["checked.noc_msgs.cores_differing",
                                  "parity.noc_msgs.cores_differing"]
    elif which == "timed_jobs":  # wrong the same way in every pass: only the reference sees it
        assert _failed(notes) == ["checked.noc_msgs.cores_differing"]
        assert result["failed"] == 1
    else:
        assert _failed(notes) == ["jobs.counts_changed_between_passes"]


def test_a_job_that_stops_early_is_not_correct(tiny_root, monkeypatch):
    from primesim_tpu.sim.engine import Engine

    real = Engine.run
    monkeypatch.setattr(Engine, "run", lambda self, *a, **kw: real(self, max_steps=8)
                        if self.trace.lengths.max() > 40 else real(self))
    result, notes = _execute(tiny_root)
    assert result["correct"] is False
    assert "jobs.raised" in _failed(notes)  # the engine itself reports the deadlock guard


def test_selfcheck_runs_come_out_as_they_have_to(tiny_root):
    import jax

    import selfcheck

    spec = cells.load_cell(CELL, root=tiny_root)
    device = {"platform": "cpu", "kind": jax.devices()[0].device_kind}
    summary = selfcheck.selfcheck(spec, [3, 4], 0.0, True, device)
    assert summary["ok"], summary
    kinds = [(r["kind"], r["correct"]) for r in summary["runs"]]
    assert kinds == [("sound", True), ("control_dram_lat_plus_1", False)] * 2 + \
        [("compile_inside_window", False)]
    assert summary["runs"][-1]["failed_numbers"][0].startswith(
        "[check] window.programs_compiled_or_loaded")
