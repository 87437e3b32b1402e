"""The per-phase, ranking and collective metric readers, each on a
hand-made `trace["ops"]` and a hand-made compiled-program text."""

import json
import os

import pytest

import cells
import phase_ops
from conftest import ROOT

P = "jit(run_loop)"
# a compiled module's text: one fusion inside one phase, one that spans
# two, an asynchronous all-reduce, a blocking all-gather
HLO = f'''HloModule jit_run_loop, is_scheduled=true

%fused_computation.1 (param_0: s32[16]) -> s32[16] {{
  %param_0 = s32[16]{{0}} parameter(0)
  %add.1 = s32[16]{{0}} add(%param_0, %param_0), metadata={{op_name="{P}/while/body/s.local/add" stack_frame_id=4}}
  ROOT %mul.2 = s32[16]{{0}} multiply(%add.1, %add.1), metadata={{op_name="{P}/while/body/s.local/mul"}}
}}

%fused_computation.2 (param_0.1: s32[16]) -> s32[16] {{
  %param_0.1 = s32[16]{{0}} parameter(0)
  %or.5 = s32[16]{{0}} or(%param_0.1, %param_0.1), metadata={{op_name="{P}/while/body/s.dir/or"}}
  ROOT %add.9 = s32[16]{{0}} add(%or.5, %or.5), metadata={{op_name="{P}/while/body/s.commit/add"}}
}}

ENTRY %main.3 (Arg_0.1: s32[16]) -> s32[16] {{
  %Arg_0.1 = s32[16]{{0}} parameter(0)
  %fusion.1 = s32[16]{{0}} fusion(%Arg_0.1), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{P}/while/body/s.local/mul"}}
  %fusion.2 = s32[16]{{0:T(128)}} fusion(%fusion.1), kind=kLoop, calls=%fused_computation.2, metadata={{op_name="{P}/while/body/s.commit/add"}}
  %all-reduce-start.1 = (s32[16]{{0}}, s32[16]{{0}}) all-reduce-start(%fusion.2), replica_groups={{{{0,1,2,3}}}}, to_apply=%region_0, metadata={{op_name="{P}/while/body/s.arb/scatter-min"}}
  %all-reduce-done.1 = s32[16]{{0}} all-reduce-done(%all-reduce-start.1), metadata={{op_name="{P}/while/body/s.arb/scatter-min"}}
  %ag = s32[64]{{0}} all-gather(%all-reduce-done.1), dimensions={{0}}, metadata={{op_name="{P}/while/body/s.noc/rank/jit(searchsorted)/gather"}}
  ROOT %copy.7 = s32[16]{{0}} copy(%all-reduce-done.1)
}}
'''
# what `xplane.reduce` makes of a trace of that program: label -> [seconds, count]
OPS = {
    f"fusion.1 {P}/s.local/mul": [0.010, 10],
    f"fusion.2 {P}/s.commit/add": [0.030, 10],
    f"all-reduce-start.1 {P}/s.arb/scatter-min": [0.001, 10],
    f"all-reduce-done.1 {P}/s.arb/scatter-min": [0.004, 10],
    f"ag {P}/s.noc/rank/jit(searchsorted)/gather": [0.020, 10],
    f"sort.4 {P}/s.dram/rank/sort": [0.002, 10],
    f"fusion.8 {P}/s.noc/scatter-max": [0.005, 10],
    f"fusion.9 {P}/s.chunk/reduce_min": [0.006, 2],
    "copy.7": [0.002, 10],
}
TRACE = {"ops": OPS, "busy_s": 0.080, "window_s": 0.1}
RUN = {"jobs": [{"steps": 8}, {"steps": 10, "traced": True}], "hlo_text": HLO}
BARE = {"ops": {"fusion.1 jit(run_loop)/jit(take_along_axis)/gather": [0.01, 10], "copy.7": [0.002, 10]},
        "busy_s": 0.012, "window_s": 0.1}  # a program without the scopes
BARE_RUN = {"jobs": RUN["jobs"], "hlo_text": HLO.replace("/s.", "/t.")}

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
NEW = [m for m in BENCH["per_layer"]
       if m["name"].startswith(("ph_", "rank_noc_")) or m["name"] == "collective_ms_step"]


@pytest.mark.parametrize("name,want", [
    ("ph_local_ms_step", 1.0),
    ("ph_probe_ms_step", None),  # no op under the scope
    ("ph_arb_ms_step", 0.5),
    ("ph_dir_ms_step", None),  # `fusion.2` holds s.dir work, but its root is s.commit's
    ("ph_commit_ms_step", 3.0),
    ("ph_noc_ms_step", 2.5),  # its rank included
    ("ph_dram_ms_step", 0.2),
    ("rank_noc_ms_step", 2.0),  # s.noc/rank alone, not s.dram/rank
    ("ph_cover_pct", 100 * 0.072 / 0.080),  # all but s.chunk and the unnamed copy
    ("ph_mixed_pct", 100 * 0.030 / 0.080),  # fusion.2 spans s.dir and s.commit
    ("collective_ms_step", 2.5),  # start + done + the blocking all-gather
])
def test_reader_on_a_hand_made_trace(name, want):
    read = cells.load_metric(name)
    assert read(RUN, TRACE) == pytest.approx(want)
    assert read(RUN, None) is None  # a rehearsal off the chip has no trace
    if name != "collective_ms_step":  # the parent commit: nothing to read, nothing raised
        assert read(BARE_RUN, BARE) is None


def test_collectives_absent_on_one_chip():
    one_chip = {k: v for k, v in OPS.items() if not k.startswith(("all-", "ag "))}
    read = cells.load_metric("collective_ms_step")
    assert read(RUN, {**TRACE, "ops": one_chip}) is None
    # without the program's text an op is known by its instruction name's stem
    assert read({**RUN, "hlo_text": None}, TRACE) == pytest.approx(0.5)


def test_phase_metrics_sum_to_the_cover():
    phases = [m["name"] for m in NEW if m["name"].startswith("ph_") and m["unit"] == "ms"]
    total = sum(cells.load_metric(n)(RUN, TRACE) or 0.0 for n in phases)
    cover = cells.load_metric("ph_cover_pct")(RUN, TRACE)
    assert total == pytest.approx(cover / 100 * 1e3 * TRACE["busy_s"] / 10)


def test_helper_parses_the_compiled_text():
    assert phase_ops.fusion_phases(HLO) == {
        "fusion.1": {"s.local"}, "fusion.2": {"s.dir", "s.commit"}}
    ops = phase_ops.opcodes(HLO)
    assert ops["all-reduce-start.1"] == "all-reduce-start" and ops["ag"] == "all-gather"
    assert ops["fusion.2"] == "fusion" and ops["copy.7"] == "copy"
    assert phase_ops.phase_of(f"fusion.9 {P}/s.chunk/reduce_min") == phase_ops.OUTSIDE
    assert phase_ops.phase_of("fusion.3 jit(run_loop)/jit(axis.local)/add") is None
    assert phase_ops.instruction_seconds(TRACE)["ag"] == 0.020


def test_new_entries_have_readers_and_name_committed_cells():
    assert len(NEW) == 11
    committed = {w["name"] for w in BENCH["workloads"]}
    for m in NEW:
        assert callable(cells.load_metric(m["name"]))
        assert m["source"] == "device_trace" and m["moves"] == "sim_mips"
        assert set(m.get("workloads", ())) <= committed
    lists = {m["name"]: m.get("workloads") for m in NEW}
    assert lists["collective_ms_step"] == ["rung3.fft-m16.x4"]
    assert "mesh1024.fft-m16" not in lists["ph_noc_ms_step"]
    x4 = next(w for w in BENCH["workloads"] if w["name"] == "rung3.fft-m16.x4")
    assert x4["chips"] == 4 and sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1
