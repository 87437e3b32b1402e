"""The committed cell `rung3.ocean-n258` and a tiny cell of its kind: it
loads, its machine is rung 3's letter for letter plus the two tables the
reference models, it is held to `references/sync.py`, its three readers
read what they say, and a 16-core cell with a `sync` reference and
`ocean_like`, built as `tinycell.py` builds its cells, comes out `correct`
on the CPU, and not with `dram_lat` one cycle off."""

import json
import os
import time

import numpy as np
import pytest

import cells
import reference
import trafficgen
from conftest import BENCH, ROOT
from tinycell import make_root

CELL = "rung3.ocean-n258"
TINY = "tiny16-sync.ocean-tiny"


@pytest.fixture(scope="module")
def spec():
    return cells.load_cell(CELL)


def test_cell_loads_and_states_rung_3_with_its_sync_tables(spec):
    assert spec["cell"]["chips"] == 1 and spec["config"]["run"] == {
        "chunk_steps": 8, "step_impl": "xla", "devices": 1}
    with open(os.path.join(BENCH, "configs", "rung3.json")) as f:
        rung3 = json.load(f)
    assert spec["config"]["machine"] == {**rung3["machine"], "lock_slots": 1024,
                                         "barrier_slots": 64}
    from primesim_tpu.config.machine import MachineConfig

    cfg = MachineConfig.from_dict(rung3["machine"])  # the program's defaults, stated
    assert (cfg.lock_slots, cfg.barrier_slots) == (1024, 64)
    t = spec["traffic"]
    assert (t["generator"], t["args"], t["panel_seeds"]) == (
        "ocean_like", {"grid_n": 258, "levels": 4, "visits": 4, "ins_per_mem": 3,
                       "barrier_ids": 8, "lock_reductions": 0}, [404])
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == "rung3-sync")
    assert sorted(entry["reduced"]) == sorted(spec["config"]["reduced"])
    assert entry["source"] == spec["config"]["source"]
    names = [m["name"] for m in spec["per_layer"]]
    assert {"ph_sync_ms_step", "ph_syncbar_ms_step", "barrier_pki", "step_ms", "ins_per_step",
            "step_roofline", "device_idle_pct", "ph_cover_pct"} <= set(names)
    # they list their cells and cannot take this one without an edit (PERF.md section 7)
    assert not {"ph_noc_ms_step", "ph_dram_ms_step", "rank_noc_ms_step", "inval_pki",
                "collective_ms_step"} & set(names)
    for name in names:
        assert callable(cells.load_metric(name))
    for m in bench["per_layer"]:
        if m["name"] in ("ph_sync_ms_step", "ph_syncbar_ms_step", "barrier_pki"):
            assert m["workloads"] == [CELL] and m["moves"] == "sim_mips" and m["layer"] == "step"


def test_cell_is_held_to_the_sync_reference(spec):
    assert spec["reference"] == "sync"
    own = cells.load_reference(spec["reference"])
    assert issubclass(own.RefSim, reference.RefSim) and own.RefSim is not reference.RefSim
    assert len(own.COUNTERS) == 21
    machine = spec["config"]["machine"]
    with pytest.raises(reference.UnsupportedMachine):  # the stock one refuses both keys
        reference.RefSim(machine, np.full((1024, 1, 4), trafficgen.EV_END, np.int32))
    ev = trafficgen.make_trace(spec["traffic"], machine["n_cores"], 2**31 + 7, parity=True)
    ref = own.RefSim(machine, ev)
    assert (ref.C, ref.B, ref.n_tiles, ref.lock_slots, ref.barrier_slots) == (
        1024, 1024, 1024, 1024, 64)
    ref.step()
    assert ref.step_count == 1 and sum(ref.counters["llc_misses"]) > 0
    full = trafficgen.make_trace(spec["traffic"], machine["n_cores"], 404)
    assert full.shape == (1024, 885, 4) and trafficgen.total_instructions(full) == 3518067
    assert int((full[:, :, 0] == 6).sum()) == 23 * 1024


def test_barrier_pki_is_a_count_of_the_checked_job():
    read = cells.load_metric("barrier_pki")
    counters = {"instructions": np.array([600, 400]), "barrier_waits": np.array([4, 3])}
    assert read({"checked": {"counters": counters}}, None) == 7.0
    assert read({"checked": None}, None) is None
    assert read({"checked": {"counters": {k: v * 0 for k, v in counters.items()}}}, None) is None
    # a program that does not count barriers gives nothing to read, and does not raise
    assert read({"checked": {"counters": {"instructions": counters["instructions"]}}}, None) is None


def test_the_sync_readers_read_their_scopes_and_nothing_off_the_chip():
    whole, bar = cells.load_metric("ph_sync_ms_step"), cells.load_metric("ph_syncbar_ms_step")
    run = {"jobs": [{"traced": True, "steps": 4}]}
    assert whole(run, None) is None and bar(run, None) is None
    trace = {"ops": {
        "fusion.1 jit(run_loop)/s.sync/barrier/scatter-add": [0.002, 4],
        "fusion.2 jit(run_loop)/s.sync/lock/scatter-min": [0.001, 4],
        "fusion.3 jit(run_loop)/s.sync/select_n": [0.001, 4],
        "fusion.4 jit(run_loop)/s.noc/rank/sort": [0.008, 4],
    }}
    assert bar(run, trace) == pytest.approx(0.5)
    assert whole(run, trace) == pytest.approx(1.0)  # the barrier's work is inside `s.sync`
    # the parent's program has `s.sync` and no sub-scope: one reads, the other finds nothing
    parent = {"ops": {k.replace("/barrier/", "/").replace("/lock/", "/"): v
                      for k, v in trace["ops"].items()}}
    assert whole(run, parent) == pytest.approx(1.0) and bar(run, parent) is None
    # a program without sync events has neither
    none = {"ops": {"fusion.4 jit(run_loop)/s.noc/rank/sort": [0.008, 4]}}
    assert whole(run, none) is None and bar(run, none) is None


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """`tinycell.py`'s checkout, plus one configuration held to `sync`, one
    traffic mix of `ocean_like` and their cell, as files and entries."""
    root = make_root(str(tmp_path_factory.mktemp("checkout")))
    with open(os.path.join(root, "benchmark", "configs", "tiny16.json")) as f:
        config = json.load(f)
    config["name"] = "tiny16-sync"
    config["reference"] = "sync"
    config["machine"].update(lock_slots=1024, barrier_slots=64)
    traffic = {"name": "ocean-tiny", "generator": "ocean_like",
               "args": {"grid_n": 34, "levels": 2, "visits": 3, "ins_per_mem": 3,
                        "barrier_ids": 8, "lock_reductions": 1},
               "parity_args": {"grid_n": 10, "levels": 1, "visits": 1},
               "panel_seeds": [404], "fold": True}
    for rel, data in (("configs/tiny16-sync.json", config), ("traffic/ocean-tiny.json", traffic)):
        with open(os.path.join(root, "benchmark", rel), "w") as f:
            json.dump(data, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny16-sync", "source": "test", "reduced": [],
                             "file": "benchmark/configs/tiny16-sync.json", "why": "test"})
    bench["workloads"].append({"name": TINY, "config": "tiny16-sync", "traffic": "ocean-tiny",
                               "chips": 1, "why": "test"})
    for m in bench["per_layer"]:
        if m["name"] in ("ph_sync_ms_step", "ph_syncbar_ms_step", "barrier_pki"):
            m["workloads"].append(TINY)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def _execute(root, trace=False, **broken):
    import jax

    import run as harness

    spec = cells.load_cell(TINY, root=root)
    device = {"platform": "cpu", "kind": jax.devices()[0].device_kind}
    return harness.execute(spec, 2**31 + 5, 0.1, trace, True, device, time.perf_counter(),
                           **broken)


def test_a_tiny_cell_held_to_the_sync_reference_is_correct(tiny_root):
    result, notes = _execute(tiny_root)
    checks = [n for n in notes if n.startswith("[check] ")]
    assert result["correct"] is True and result["failed"] == 0, [
        n for n in checks if " = 0 (" not in n]
    assert len(checks) == 59 and all(n.endswith(" = 0 (limit 0)") for n in checks)
    for k in ("barrier_waits", "lock_acquires", "lock_spins"):  # modelled, not "unmodelled"
        assert f"[check] checked.{k}.cores_differing = 0 (limit 0)" in checks
        assert f"[check] parity.{k}.cores_differing = 0 (limit 0)" in checks
    assert sum(n.startswith("[run] workload=") and n.endswith(" reference=sync")
               for n in notes) == 1


def test_a_traced_run_of_it_reports_the_count_and_no_device_number(tiny_root):
    result, _ = _execute(tiny_root, trace=True)
    assert result["correct"] is True
    m = result["metrics"]
    # 3 visits and 2 transfers: 17 barriers a core; the count is exact for the trace
    spec = cells.load_cell(TINY, root=tiny_root)
    ev = trafficgen.make_trace(spec["traffic"], 16, 404, root=tiny_root)
    assert m["cpu_rehearsal.barrier_pki"]["value"] == pytest.approx(
        1e3 * 17 * 16 / trafficgen.total_instructions(ev))
    # the CPU's profile has no device plane: nothing to read, and nothing raised
    assert "cpu_rehearsal.ph_sync_ms_step" not in m and "cpu_rehearsal.ph_syncbar_ms_step" not in m


def test_with_dram_lat_one_cycle_off_it_is_not_correct(tiny_root):
    spec = cells.load_cell(TINY, root=tiny_root)
    off = {"dram_lat": spec["config"]["machine"]["dram_lat"] + 1}
    result, notes = _execute(tiny_root, program_machine_patch=off)
    assert result["correct"] is False and result["failed"] >= 1
    failed = [n.split()[1] for n in notes if n.startswith("[check]") and " = 0 (" not in n]
    assert "checked.cycles.cores_differing" in failed and "parity.cycles.cores_differing" in failed
    assert not [f for f in failed if f.startswith(("jobs.", "window."))]
