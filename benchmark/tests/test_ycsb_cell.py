"""The committed cell `rung3.ycsb-a` and a tiny cell of its kind: it loads,
its configuration `rung3-ycsb` is rung 3's machine under the stock
reference with the store it serves, its traffic file states YCSB workload
A's numbers, and its three readers read what they
say: on made-up counters, and on a 16-core run of `ycsb_like` through the
harness on the CPU, recounted from the stock reference and the golden
model's own account of its core-steps."""

import json
import os
import time

import numpy as np
import pytest

import cells
import reference
import trafficgen
from conftest import ROOT
from tinycell import make_root

CELL = "rung3.ycsb-a"
TINY = "tiny16.ycsb-tiny"
NEW = ("retry_pki", "inval_fanout", "slot_end_pct")


@pytest.fixture(scope="module")
def spec():
    return cells.load_cell(CELL)


def test_cell_loads_on_rung_3_as_a_store_of_its_own(spec):
    rand = cells.load_cell("rung3.rand-ws1m")
    assert spec["cell"] == {"name": CELL, "config": "rung3-ycsb", "traffic": "ycsb-a", "chips": 1,
                            "why": spec["cell"]["why"]}
    # rung 3's machine and run, letter for letter, so the compiled program is rung3.rand-ws1m's
    assert spec["config"]["machine"] == rand["config"]["machine"]
    assert spec["config"]["run"] == rand["config"]["run"]
    assert spec["reference"] is None and cells.load_reference(spec["reference"]) is reference
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = bench["configs"][-1]
    assert (entry["name"], entry["file"]) == ("rung3-ycsb", "benchmark/configs/rung3-ycsb.json")
    assert entry["source"] == spec["config"]["source"] and "workloads/workloada" in entry["source"]
    assert sorted(entry["reduced"]) == sorted(spec["config"]["reduced"]) == ["chunk_steps",
                                                                             "ops_per_core"]
    # the deployment the configuration states is the one the traffic file's generator is given
    store, args = spec["config"]["store"], spec["traffic"]["args"]
    assert (store["recordcount"], store["fieldcount"], store["fieldlength"]) == (
        args["recordcount"], args["fieldcount"], args["fieldlength"])
    assert (store["requestdistribution"], store["zipfian_constant"]) == ("zipfian", args["theta"])
    assert (store["readproportion"], store["updateproportion"]) == (
        args["read_frac"], 1 - args["read_frac"])
    assert (store["readallfields"], store["writeallfields"]) == (True, False)
    assert [w["name"] for w in bench["workloads"]][-1] == CELL
    assert [m["name"] for m in bench["per_layer"]][-3:] == list(NEW)
    for m in bench["per_layer"][-3:]:
        assert (m["workloads"], m["moves"], m["source"], m["layer"], m["better"]) == (
            [CELL], "sim_mips", "program_counter", "step", "lower")
    names = {m["name"] for m in spec["per_layer"]}
    assert set(NEW) | {"arb_win_pct", "ins_per_step", "step_ms", "step_roofline", "ph_arb_ms_step",
                       "ph_dir_ms_step", "ph_commit_ms_step", "ph_cover_pct", "device_idle_pct",
                       "host_dispatch_ms_job", "host_readback_ms_job"} <= names
    # they list their cells and cannot take this one without an edit (PERF.md section 7)
    assert not {"ph_noc_ms_step", "ph_dram_ms_step", "rank_noc_ms_step", "inval_pki",
                "slot_active_pct", "slot_quantum_pct", "run_slot_pct", "noc_active_pct",
                "noc_sort_log2_max", "stat_ms_step"} & names
    for name in names:
        assert callable(cells.load_metric(name))
    assert {m["name"] for m in spec["end_to_end"]} == {"sim_mips", "hbm_peak_gb", "setup_s"}


def test_traffic_file_states_workload_a(spec):
    t = spec["traffic"]
    assert (t["generator"], t["panel_seeds"], t["fold"]) == ("ycsb_like", [404], True)
    assert t["args"] == {"ops_per_core": 8, "recordcount": 1000000, "theta": 0.99,
                         "read_frac": 0.5, "fieldcount": 10, "fieldlength": 100,
                         "ins_per_mem": 3, "op_ins": 30}
    assert t["parity_args"] == {"ops_per_core": 2}
    for said in ("workloads/workloada", "CoreWorkload", "SoCC 2010", "zipfian"):
        assert said in t["source"]
    assert {"ops_per_core", "recordcount", "layout", "record_lock", "update", "ins_per_mem",
            "op_ins", "workers"} <= set(t["assumed"])
    ev = trafficgen.make_trace(t, 1024, 404)
    assert ev.shape == (1024, 145, 4) and trafficgen.total_instructions(ev) == 678480
    assert set(np.unique(ev[:, :, 0])) == {trafficgen.EV_LD, trafficgen.EV_ST, trafficgen.EV_END}
    parity = trafficgen.make_trace(t, 1024, 2**31 + 7, parity=True)
    assert parity.shape[1] <= 37 and ((parity[:, :, 2] < 16003072)
                                      & (parity[:, :, 0] != trafficgen.EV_END)).sum() == 2048
    trafficgen.pad_to(parity, ev.shape[1])  # the parity job fits the compiled trace length


def test_the_count_readers_on_counters_made_by_hand():
    retry, fanout = cells.load_metric("retry_pki"), cells.load_metric("inval_fanout")
    counters = {"instructions": np.array([600, 400]), "retries": np.array([20, 12]),
                "invalidations": np.array([40, 23]), "l1_write_misses": np.array([10, 11]),
                "upgrades": np.array([4, 5])}
    run = {"checked": {"counters": counters}}
    assert retry(run, None) == 32.0 and fanout(run, None) == 2.1
    assert retry({"checked": None}, None) is None and fanout({"checked": None}, None) is None
    zero = {"checked": {"counters": {k: v * 0 for k, v in counters.items()}}}
    assert retry(zero, None) is None and fanout(zero, None) is None
    # a program that lacks a counter gives nothing to read, and does not raise
    bare = {"checked": {"counters": {"instructions": counters["instructions"]}}}
    assert retry(bare, None) is None and fanout(bare, None) is None
    # no job sample to read, or one without the stat rows: nothing, and no raise
    end = cells.load_metric("slot_end_pct")
    assert end({"jobs": []}, None) is None
    assert end({"jobs": [{"steps": -1, "trace": 0}], "passes": 1}, None) is None


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """`tinycell.py`'s checkout plus a traffic mix of `ycsb_like` on its
    16-core machine, the three readers opened to the cell."""
    root = make_root(str(tmp_path_factory.mktemp("checkout")))
    traffic = {"name": "ycsb-tiny", "generator": "ycsb_like",
               "args": {"ops_per_core": 6, "recordcount": 4, "theta": 0.99, "read_frac": 0.5,
                        "fieldcount": 10, "fieldlength": 100, "ins_per_mem": 3, "op_ins": 30},
               "parity_args": {"ops_per_core": 2}, "panel_seeds": [404], "fold": True}
    with open(os.path.join(root, "benchmark", "traffic", "ycsb-tiny.json"), "w") as f:
        json.dump(traffic, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["workloads"].append({"name": TINY, "config": "tiny16", "traffic": "ycsb-tiny",
                               "chips": 1, "why": "test"})
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            m["workloads"].append(TINY)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def test_a_tiny_cell_of_the_shape_reports_the_three_counts(tiny_root):
    import jax

    import run as harness
    from primesim_tpu.config.machine import MachineConfig
    from primesim_tpu.golden.sim import GoldenSim
    from primesim_tpu.trace.format import Trace

    spec = cells.load_cell(TINY, root=tiny_root)
    device = {"platform": "cpu", "kind": jax.devices()[0].device_kind}
    result, notes = harness.execute(spec, 2**31 + 5, 0.1, True, True, device, time.perf_counter())
    checks = [n for n in notes if n.startswith("[check] ")]
    assert result["correct"] is True and len(checks) == 53, [
        n for n in checks if " = 0 (" not in n]
    m = {k: v["value"] for k, v in result["metrics"].items()}

    ev = trafficgen.make_trace(spec["traffic"], 16, 404, root=tiny_root)
    ref = reference.RefSim(spec["config"]["machine"], ev)
    ref.run()
    c = {k: sum(v) for k, v in ref.counters.items()}
    assert c["retries"] > 50 and c["invalidations"] > c["l1_write_misses"] + c["upgrades"]
    assert m["cpu_rehearsal.retry_pki"] == pytest.approx(1e3 * c["retries"] / c["instructions"])
    assert m["cpu_rehearsal.inval_fanout"] == pytest.approx(
        c["invalidations"] / (c["l1_write_misses"] + c["upgrades"]))
    assert m["cpu_rehearsal.arb_win_pct"] == pytest.approx(100.0 * (1 - c["retries"] / (
        c["retries"] + c["l1_read_misses"] + c["l1_write_misses"] + c["upgrades"])))

    gold = GoldenSim(MachineConfig.from_dict(spec["config"]["machine"]),
                     Trace(ev, (ev[:, :, 0] != trafficgen.EV_END).sum(1) + 1))
    gold.run()
    chunk = spec["config"]["run"]["chunk_steps"]
    steps = -(-gold.step_count // chunk) * chunk  # the program runs whole chunks
    with_events_left = sum(int(gold.stats[r].sum())
                           for r in ("slot_active", "slot_quantum", "slot_frozen"))
    assert m["cpu_rehearsal.slot_end_pct"] == pytest.approx(
        100.0 - 100.0 * with_events_left / (16 * steps))
    assert 0 < m["cpu_rehearsal.slot_end_pct"] < 100
