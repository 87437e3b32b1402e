"""The committed cell `rung5.dsv3-moe-decode` (ISSUE 57), on the CPU: rung
5's machine with its memory controllers' queues on, under the workload
BASELINE names for the rung, one DeepSeek-V3 expert layer's decode step as
a trace. The cell loads; its files hold each other (the published widths in
the configuration are the generator's arguments in the traffic file and the
catalog's figures); the machine is rung 5's but for `dram_queue`; its five
metrics list this cell and only it and read what they say, on counters made
by hand and on a rehearsal of a 256-core cell of the same files. The shape
itself, and the parity of the program with the reference on it, are
`tests/test_synth_moe_decode.py`'s."""

import json
import os
import time

import jax
import numpy as np
import pytest

from benchmark_modules import ROOT, load_benchmark_tests

import cells  # noqa: E402  (benchmark/ is on the path now)
import trafficgen  # noqa: E402

CELL, CONFIG, TRAFFIC = "rung5.dsv3-moe-decode", "rung5-infer", "dsv3-moe-decode"
NEW = ("dramq_cyc_pki", "moe_dram_ms_step", "moe_dirgrp_ms_step", "moe_core_skew",
       "moe_inval_fanout")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
URL = "https://huggingface.co/deepseek-ai/DeepSeek-V3/blob/main/config.json"

_scratch = load_benchmark_tests("scratchroot")  # a checkout that cells can be added to


def _json(*path):
    with open(os.path.join(ROOT, *path)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def spec():
    return cells.load_cell(CELL)


@pytest.fixture(scope="module")
def bench():
    return _json("BENCHMARK.json")


# ---- the files and the entries ---------------------------------------------

def test_the_cell_loads_on_one_chip_solo_against_the_coarse_reference(spec, bench):
    assert spec["cell"] == {"name": CELL, "config": CONFIG, "traffic": TRAFFIC, "chips": 1,
                            "why": spec["cell"]["why"]}
    assert len(spec["cell"]["why"]) <= 200
    assert spec["config"]["run"] == {"chunk_steps": 8, "step_impl": "xla", "devices": 1}
    assert spec["runner"] == "solo" and spec["reference"] == "coarse_dir"
    assert "bit-exact" in spec["config"]["guarantee"]
    entry = bench["configs"][-1]
    assert (entry["name"], entry["file"]) == (CONFIG, "benchmark/configs/rung5-infer.json")
    assert entry["source"] == spec["config"]["source"] and len(entry["source"]) <= 200
    assert URL in entry["source"] and "rung 5" in entry["source"]
    assert bench["workloads"][-1] == spec["cell"]
    assert {m["name"] for m in spec["end_to_end"]} == {"sim_mips", "hbm_peak_gb", "setup_s"}
    for m in spec["per_layer"]:
        assert callable(cells.load_metric(m["name"])), m["name"]


def test_reduced_and_assumed_say_what_was_cut_and_what_was_set(spec, bench):
    listed = ["attention", "chunk_steps", "layers", "rows", "shared_expert", "tokens"]
    assert sorted(bench["configs"][-1]["reduced"]) == sorted(spec["config"]["reduced"]) == listed
    assert {"dram_queue", "routing", "layout"} <= set(spec["config"]["assumed"])
    # the deepest cut of scale is a cut, not a setting: ISSUE 57's 16 stands beside it
    assert "tokens" not in spec["config"]["assumed"] and "16" in spec["config"]["reduced"]["tokens"]
    assert {"tokens", "rows", "routing", "expert_to_core", "visit", "scratch", "combine",
            "layout", "no_barrier"} <= set(spec["traffic"]["assumed"])
    for text in (*spec["config"]["reduced"].values(), *spec["config"]["assumed"].values(),
                 *spec["traffic"]["assumed"].values()):
        assert "TO FILL" not in text and len(text) > 40


LADDER = {**_json("configs", "rung5_16384core_wafer.json"), "dram_queue": True, "dram_service": 0}


@pytest.mark.parametrize("field", sorted(LADDER))
def test_the_machine_is_rung_5s_with_the_controller_queues_on(spec, field):
    """Rung 5 letter for letter plus `dram_queue` true (`dram_service` 0:
    one line a `dram_lat` a controller, as rung 3 ships it), in the
    benchmark's file and in the program's."""
    machine = spec["config"]["machine"]
    assert sorted(machine) == sorted(LADDER)
    assert machine[field] == LADDER[field]
    fft = _json("benchmark", "configs", "rung5.json")["machine"]
    assert machine[field] == (True if field == "dram_queue" else fft[field])
    program = _json("configs", "rung5_16384core_wafer_dramq.json")
    assert program == {k: v for k, v in LADDER.items() if k != "dram_service"}


def test_an_expert_is_a_directory_group(spec):
    machine, deployment = spec["config"]["machine"], spec["config"]["deployment"]
    assert machine["sharer_group"] == deployment["cores_per_expert"] == 64
    assert machine["n_cores"] == deployment["cores"] == 64 * spec["config"]["n_routed_experts"]
    assert deployment["experts_on_the_machine"] == spec["config"]["n_routed_experts"] == 256
    assert machine["n_cores"] // machine["n_banks"] == 4  # four cores to a controller
    assert (deployment["weight_bytes"], deployment["activation_bytes"],
            deployment["output_bytes"]) == (1, 1, 2)


def test_the_widths_are_the_published_ones_in_all_three_places(spec):
    """The configuration's copy of config.json, the traffic file's `args`,
    and the figures ISSUE 57 quotes from the catalog row."""
    conf, args = spec["config"], spec["traffic"]["args"]
    quoted = {"hidden_size": 7168, "moe_intermediate_size": 2048, "n_routed_experts": 256,
              "num_experts_per_tok": 8, "n_group": 8, "topk_group": 4, "n_shared_experts": 1,
              "first_k_dense_replace": 3, "num_hidden_layers": 61}
    assert {k: conf[k] for k in quoted} == quoted
    assert (args["hidden"], args["inter"], args["experts"], args["top_k"], args["n_group"],
            args["topk_group"]) == (conf["hidden_size"], conf["moe_intermediate_size"],
                                    conf["n_routed_experts"], conf["num_experts_per_tok"],
                                    conf["n_group"], conf["topk_group"])
    if os.path.exists(CATALOG):  # the catalog beside the guide: every key as published
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "DeepSeek-V3")
        assert row["source_url"] == URL
        assert {k: conf[k] for k in row["config"]} == row["config"]


def test_the_traffic_file_states_the_decode_step(spec):
    t = spec["traffic"]
    assert (t["generator"], t["panel_seeds"], t["fold"]) == ("moe_decode_like", [404], True)
    assert t["args"] == {"tokens": 8, "hidden": 7168, "inter": 2048, "experts": 256, "top_k": 8,
                         "n_group": 8, "topk_group": 4, "skew_milli": 500, "gate_rows": 1,
                         "up_rows": 1, "down_rows": 4, "ins_per_mem": 8}
    assert t["parity_args"] == {"tokens": 1}
    assert URL in t["source"] and "moe_decode_like" in t["source"]
    ev = trafficgen.make_trace(t, 16384, 404)
    assert ev.shape == (16384, 193, 4) and trafficgen.total_instructions(ev) == 2486708
    assert set(np.unique(ev[:, :, 0])) == {trafficgen.EV_LD, trafficgen.EV_ST, trafficgen.EV_END}
    parity = trafficgen.make_trace(t, 16384, 2**31 + 7, parity=True)
    mem = parity[:, :, 0] != trafficgen.EV_END
    assert parity.shape[1] == 64 + 8 + 1 and int(mem.sum()) == 8 * 64 * 64 + 8
    trafficgen.pad_to(parity, ev.shape[1])  # the parity job fits the compiled trace length


def test_at_most_half_the_cells_ask_for_four_chips(bench):
    cells_ = bench["workloads"]
    assert len(cells_) == len({w["name"] for w in cells_}) == 13
    assert len(bench["configs"]) == len({c["name"] for c in bench["configs"]}) == 12
    assert sum(w["chips"] == 4 for w in cells_) == 3 <= len(cells_) // 2
    assert [w["name"] for w in cells_ if w["config"] in ("rung5", CONFIG)] == [
        "rung5.fft-m18-16k", CELL]


@pytest.mark.parametrize("name,unit,source", [
    ("dramq_cyc_pki", "cycles/kinstr", "program_counter"),
    ("moe_dram_ms_step", "ms", "device_trace"),
    ("moe_dirgrp_ms_step", "ms", "device_trace"),
    ("moe_core_skew", "x", "program_counter"),
    ("moe_inval_fanout", "msgs/write", "program_counter"),
])
def test_a_new_metric_lists_this_cell_and_only_it(spec, bench, name, unit, source):
    assert [m["name"] for m in bench["per_layer"]][-5:] == list(NEW)
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    assert entry == {"name": name, "unit": unit, "better": "lower", "source": source,
                     "layer": "step", "moves": "sim_mips", "workloads": [CELL]}
    assert name in [m["name"] for m in spec["per_layer"]]
    for w in bench["workloads"]:
        if w["name"] != CELL:
            assert name not in [m["name"] for m in cells.load_cell(w["name"])["per_layer"]]


def test_the_traced_line_carries_what_reports_everywhere(spec):
    names = {m["name"] for m in spec["per_layer"]}
    assert set(NEW) | {"arb_win_pct", "step_roofline", "step_ms", "ins_per_step", "device_idle_pct",
                       "ph_local_ms_step", "ph_probe_ms_step", "ph_arb_ms_step", "ph_dir_ms_step",
                       "ph_commit_ms_step", "ph_cover_pct", "ph_mixed_pct", "host_dispatch_ms_job",
                       "host_readback_ms_job", "job_s_max", "tracegen_s", "compile_s"} <= names
    # closed lists of other cells, left as they are (PERF.md section 7)
    assert not {"ph_dram_ms_step", "ph_dirgrp_ms_step", "inval_fanout", "inval_pki",
                "slot_active_pct", "slot_end_pct", "retry_pki"} & names


# ---- the readers -----------------------------------------------------------

def test_the_count_readers_on_counters_made_by_hand():
    dramq, skew, fanout = (cells.load_metric(n) for n in
                           ("dramq_cyc_pki", "moe_core_skew", "moe_inval_fanout"))
    counters = {"instructions": np.array([600, 0, 150, 50]), "dram_queue_cycles": np.array([7, 0, 1, 0]),
                "invalidations": np.array([40, 0, 23, 0]), "l1_write_misses": np.array([10, 0, 11, 0]),
                "upgrades": np.array([4, 0, 5, 0])}
    run = {"checked": {"counters": counters}}
    assert dramq(run, None) == 10.0 and skew(run, None) == 3.0 and fanout(run, None) == 2.1
    for read in (dramq, skew, fanout):
        assert read({"checked": None}, None) is None
        zero = {"checked": {"counters": {k: v * 0 for k, v in counters.items()}}}
        assert read(zero, None) is None
        # a program that lacks a counter gives nothing to read, and does not raise
        assert read({"checked": {"counters": {"retries": counters["upgrades"]}}}, None) is None


def test_the_device_readers_on_a_trace_made_by_hand():
    """A traced job of 8 steps whose ops carry their phase scope in the
    label, as `xplane.reduce` leaves them: `s.dram` with its rank, and of
    `s.dir` the group work alone."""
    dram, dirgrp = cells.load_metric("moe_dram_ms_step"), cells.load_metric("moe_dirgrp_ms_step")
    run = {"jobs": [{"traced": True, "steps": 8}]}
    trace = {"ops": {
        "sort.7 jit(run_loop)/while/body/s.dram/rank/sort": (0.004, 8),
        "fusion.3 jit(run_loop)/while/body/s.dram/gather": (0.002, 8),
        "fusion.9 jit(run_loop)/while/body/s.dir/grp/reduce_max": (0.001, 8),
        "fusion.4 jit(run_loop)/while/body/s.dir/select_n": (0.016, 8),
    }}
    assert dram(run, trace) == pytest.approx(0.75) and dirgrp(run, trace) == pytest.approx(0.125)
    # a program without the scopes, or a run without a trace: nothing to read, and no raise
    bare = {"ops": {"fusion.1 jit(run_loop)/while/body/add": (0.5, 8)}}
    for read in (dram, dirgrp):
        assert read(run, bare) is None and read(run, None) is None
        assert read({"jobs": []}, trace) is None


TINY = "rung5s.moe-tiny"


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory, spec):
    """A checkout whose one added cell is this cell's files with the machine
    at 256 cores (four experts, an expert a directory group, four cores a
    bank) and three tokens, the five metrics' lists opened to it."""
    dst = str(tmp_path_factory.mktemp("checkout"))
    bench = _scratch.copy_checkout(dst)
    config = json.loads(json.dumps(spec["config"]))
    config["name"] = "rung5s"
    config["machine"].update(n_cores=256, n_banks=64,
                             l1={"size": 2048, "ways": 4, "line": 64, "latency": 2},
                             llc={"size": 8192, "ways": 8, "line": 64, "latency": 16})
    config["machine"]["noc"].update(mesh_x=16, mesh_y=16)
    traffic = json.loads(json.dumps(spec["traffic"]))
    traffic["name"] = "moe-tiny"
    traffic["args"].update(tokens=3, experts=4, top_k=2, n_group=2, topk_group=1)
    _scratch.add_cell(bench, TINY)
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            m["workloads"].append(TINY)
    _scratch.write(dst, bench, {"benchmark/configs/rung5s.json": config,
                                "benchmark/traffic/moe-tiny.json": traffic})
    return dst


def test_a_tiny_cell_of_the_shape_reports_the_three_counts(tiny_root):
    import run as harness

    small = cells.load_cell(TINY, root=tiny_root)
    device = {"platform": "cpu", "kind": jax.devices()[0].device_kind}
    result, notes = harness.execute(small, 2**31 + 5, 0.1, True, True, device, time.perf_counter())
    checks = [n for n in notes if n.startswith("[check] ")]
    assert result["correct"] is True and len(checks) == 53, [
        n for n in checks if " = 0 (" not in n]
    # the three readers that are another reader's take that reader from their own checkout
    for name in ("moe_dram_ms_step", "moe_dirgrp_ms_step", "moe_inval_fanout"):
        assert cells._module("metrics", name, tiny_root).ROOT == tiny_root
    m = {k: v["value"] for k, v in result["metrics"].items()}

    ev = trafficgen.make_trace(small["traffic"], 256, 404, root=tiny_root)
    ref = cells.load_reference("coarse_dir", tiny_root).RefSim(small["config"]["machine"], ev)
    ref.run()
    c = {k: np.asarray(v) for k, v in ref.counters.items()}
    assert m["cpu_rehearsal.dramq_cyc_pki"] == pytest.approx(
        1e3 * c["dram_queue_cycles"].sum() / c["instructions"].sum()) and \
        m["cpu_rehearsal.dramq_cyc_pki"] > 1000
    assert m["cpu_rehearsal.moe_core_skew"] == pytest.approx(
        c["instructions"].max() / c["instructions"].mean())
    assert 1.0 <= m["cpu_rehearsal.moe_core_skew"] < 3.0  # four experts, three tokens at two each
    assert m["cpu_rehearsal.moe_inval_fanout"] == pytest.approx(
        c["invalidations"].sum() / (c["l1_write_misses"].sum() + c["upgrades"].sum()))
    assert m["cpu_rehearsal.moe_inval_fanout"] > 5
    # the CPU has no device plane: the two device readers found nothing, and the line lacks them
    assert not {"cpu_rehearsal.moe_dram_ms_step", "cpu_rehearsal.moe_dirgrp_ms_step"} & set(m)
