"""The stat rows the step keeps beside its counters (stats/counters.py::
STAT_NAMES, DESIGN.md §15): where every core-step and every sorted router
entry went. Held here: golden parity core for core, the partition of the
core-steps, the router's entry count and its histogram against recounts,
that none of it reaches `Engine.counters`, the sharded, vmapped and
windowed engines, checkpoints, the one sample a fused job commits, and the
benchmark's readers of that sample.
"""

import hashlib
import json

import jax
import numpy as np
import pytest

from primesim_tpu.config.machine import CoreConfig, NocConfig, small_test_config
from primesim_tpu.golden.sim import GoldenSim
from primesim_tpu.noc import topology
from primesim_tpu.obs import MetricStore, Recorder, process_store
from primesim_tpu.obs.span import span
from primesim_tpu.parallel.sharding import tile_mesh
from primesim_tpu.sim.engine import Engine
from primesim_tpu.stats.counters import (
    BLOCK_NAMES,
    COUNTER_NAMES,
    N_BLOCK_ROWS,
    STAT_NAMES,
    stat_totals,
)
from primesim_tpu.trace import synth
from primesim_tpu.trace.format import fold_ins

PER_CORE = STAT_NAMES[:-1]  # every row but the histogram


def _rung3(**kw):
    return small_test_config(
        n_cores=16, n_banks=8, local_run_len=4, dram_queue=True,
        core=CoreConfig(o3_overlap_256=128),
        noc=NocConfig(mesh_x=4, mesh_y=4, link_lat=1, router_lat=1, contention=True,
                      contention_model="router", contention_lat=1), **kw)


def _ocean():
    return fold_ins(synth.ocean_like(16, seed=5, grid_n=34, levels=2, visits=2,
                                     lock_reductions=1))


# machine -> (config, trace): no contention model and no sync; rung 3's
# selectors; the same with locks and barriers (`has_sync`)
MACHINES = {
    "plain": lambda: (small_test_config(n_cores=16, n_banks=8, local_run_len=4),
                      synth.false_sharing(16, n_mem_ops=40, seed=77)),
    "rung3": lambda: (_rung3(), synth.fft_like(16, n_phases=3, points_per_core=16, seed=7)),
    "sync": lambda: (_rung3(), _ocean()),
}


def _fused(cfg, trace, **kw):
    eng = Engine(cfg, trace, chunk_steps=32, **kw)
    eng.run()
    return eng


@pytest.fixture(scope="module", params=sorted(MACHINES))
def ran(request):
    cfg, trace = MACHINES[request.param]()
    gold = GoldenSim(cfg, trace)
    gold.run()
    return request.param, cfg, trace, gold, _fused(cfg, trace)


def test_names_and_block():
    assert BLOCK_NAMES == COUNTER_NAMES + STAT_NAMES
    assert N_BLOCK_ROWS == len(BLOCK_NAMES) == len(set(BLOCK_NAMES))
    assert STAT_NAMES[-1] == "noc_sort_log2"


def test_per_core_rows_equal_golden_core_for_core(ran):
    name, cfg, trace, gold, eng = ran
    assert eng.has_sync == (name == "sync")
    np.testing.assert_array_equal(eng.cycles, gold.cycles)
    for k in PER_CORE:
        np.testing.assert_array_equal(eng.step_stats[k], gold.stats[k], err_msg=k)
    assert int(eng.step_stats["slot_active"].sum()) > 0
    assert bool(eng.step_stats["slot_frozen"].sum()) == (name == "sync")
    assert bool(eng.step_stats["noc_entries"].sum()) == (name != "plain")


def test_counters_hold_no_stat_row_and_their_digest_is_goldens(ran):
    _, _, _, gold, eng = ran
    assert tuple(eng.counters) == COUNTER_NAMES
    assert tuple(eng.step_stats) == STAT_NAMES
    assert eng.state.counters.shape == (N_BLOCK_ROWS, 16)

    def digest(cycles, counters):  # benchmark/measure.py::digest
        h = hashlib.sha256(np.ascontiguousarray(cycles, np.int64).tobytes())
        for k in sorted(counters):
            h.update(k.encode())
            h.update(np.ascontiguousarray(counters[k], np.int64).tobytes())
        return h.hexdigest()

    assert digest(eng.cycles, eng.counters) == digest(gold.cycles, gold.counters)


def test_the_block_is_four_whole_tiles():
    """32 rows of int32 are four (8, 128) tiles; a 33rd is a fifth in
    every op over the block (+0.9 % on the plain machine's step, PR 37:
    `arb_requests` went for it, `arb_win_pct` reads modelled counters)."""
    assert N_BLOCK_ROWS == 32 and len(STAT_NAMES) == 6


def test_histogram_lanes_sum_to_the_steps_run(ran):
    name, _, _, _, eng = ran
    lanes = eng.step_stats["noc_sort_log2"]
    assert int(lanes.sum()) == (0 if name == "plain" else eng.steps_run)


@pytest.mark.parametrize("machine", sorted(MACHINES))
def test_partition_and_histogram_step_by_step(machine):
    """One step a chunk, so the host sees every step's deltas: a core-step
    is in exactly one of active, quantum, frozen, or at END; it is in one
    of the first three if the core is not at END after the step and in
    none if it stood at END before it; and the histogram's lane is the
    power of two that holds the step's real entries, recounted from
    `noc_entries`."""
    cfg, trace = MACHINES[machine]()
    eng = Engine(cfg, trace, chunk_steps=1)
    C = cfg.n_cores
    prev = {k: np.zeros(C, np.int64) for k in STAT_NAMES}
    occupied = np.zeros(C, np.int64)
    while not eng.done():
        at_end_before = eng.done_mask()
        eng.run_steps(1)
        now = {k: v.copy() for k, v in eng.step_stats.items()}
        d = {k: now[k] - prev[k] for k in STAT_NAMES}
        prev = now
        slot = d["slot_active"] + d["slot_quantum"] + d["slot_frozen"]
        assert set(np.unique(slot)) <= {0, 1}
        assert (slot[~eng.done_mask()] == 1).all()
        assert (slot[at_end_before] == 0).all()
        assert (d["run_events"] <= cfg.local_run_len).all()
        occupied += slot
        want = np.zeros(C, np.int64)
        if machine != "plain":
            n = int(d["noc_entries"].sum())
            want[0 if n <= 1 else int(np.ceil(np.log2(n)))] = 1
        np.testing.assert_array_equal(d["noc_sort_log2"], want)
    # the partition, whole: what the three rows leave of C x steps is END
    end_core_steps = C * eng.steps_run - int(occupied.sum())
    st = eng.step_stats
    assert (int(st["slot_active"].sum() + st["slot_quantum"].sum() + st["slot_frozen"].sum())
            + end_core_steps == C * eng.steps_run)
    assert 0 <= end_core_steps < C * eng.steps_run


@pytest.mark.parametrize("topo,mx,my", [("mesh", 4, 4), ("mesh", 8, 2), ("torus", 4, 4),
                                        ("ring", 4, 4)])
def test_a_path_holds_as_many_links_as_it_has_hops(topo, mx, my):
    """`noc_entries` adds hop counts where the sort's mask `ok_all` counts
    links (`pth >= 0`): the two agree on every pair of tiles of every
    topology, so `ok_all.sum(1)` is `req_hops + rep_hops` on a home
    transaction's lane plus `arr_hops` on a barrier's."""
    cfg = small_test_config(n_cores=16, n_banks=16,
                            noc=NocConfig(mesh_x=mx, mesh_y=my, topology=topo))
    n = cfg.n_tiles
    a, b = (x.reshape(-1).astype(np.int32) for x in np.meshgrid(np.arange(n), np.arange(n)))
    links = np.asarray(topology.path_links(cfg, a, b))
    assert links.shape[1] == topology.path_width(cfg)
    np.testing.assert_array_equal((links >= 0).sum(1), np.asarray(topology.hops(cfg, a, b)))


def test_a_mesh_carries_no_stat_rows_and_counts_as_one_device_does():
    """On a mesh the block keeps the counters' height and the program
    counts no stat row (rung 4's sharded row gathers lost 4 % to the taller
    block and 10 % with the rows counted, PR 37): the counters and cycles
    are one device's, the stat totals stay zero, the job's sample holds the
    counters alone."""
    cfg, trace = MACHINES["sync"]()
    one = _fused(cfg, trace)
    four = _fused(cfg, trace, mesh=tile_mesh(4))
    assert len(four.state.cycles.devices()) == 4
    assert four.state.counters.shape[0] == len(COUNTER_NAMES)
    assert one.state.counters.shape[0] == N_BLOCK_ROWS
    np.testing.assert_array_equal(four.cycles, one.cycles)
    for k in COUNTER_NAMES:
        np.testing.assert_array_equal(four.counters[k], one.counters[k], err_msg=k)
    assert all(not four.step_stats[k].any() for k in STAT_NAMES)
    mine, = [s for s in process_store().samples()[-1:]]
    assert set(mine["deltas"]) == set(COUNTER_NAMES) and mine["caps"]["n_cores"] == 16


def test_fleet_elements_keep_their_own_stat_rows():
    from primesim_tpu.sim.fleet import FleetEngine

    cfg = _rung3()
    traces = [synth.fft_like(16, n_phases=3, points_per_core=16, seed=s) for s in (7, 8)]
    fleet = FleetEngine(cfg, traces, [{}, {"dram_lat": 150}], chunk_steps=32)
    fleet.run()
    assert tuple(fleet.counters) == COUNTER_NAMES
    solo = _fused(cfg, traces[0])
    for k in PER_CORE:
        np.testing.assert_array_equal(fleet.step_stats[k][0], solo.step_stats[k], err_msg=k)
    assert fleet.step_stats["noc_sort_log2"].shape == (2, 16)


def test_windowed_engine_counts_the_same_core_steps():
    from primesim_tpu.ingest.stream import StreamEngine

    cfg, trace = MACHINES["rung3"]()
    solo = _fused(cfg, trace)
    win = StreamEngine(cfg, trace, window_events=16)
    win.run()
    np.testing.assert_array_equal(win.cycles, solo.cycles)
    assert tuple(win.counters) == COUNTER_NAMES
    for k in PER_CORE:
        np.testing.assert_array_equal(win.step_stats[k], solo.step_stats[k], err_msg=k)


def test_checkpoint_carries_the_stat_totals(tmp_path):
    cfg, trace = MACHINES["sync"]()
    whole = _fused(cfg, trace)
    first = Engine(cfg, trace, chunk_steps=32)
    first.run_steps(64)
    path = str(tmp_path / "ck.npz")
    first.save_checkpoint(path)
    resumed = Engine(cfg, trace, chunk_steps=32)
    resumed.load_checkpoint(path)
    for k in STAT_NAMES:
        np.testing.assert_array_equal(resumed.host_stats[k], first.host_stats[k])
    resumed.run()
    np.testing.assert_array_equal(resumed.cycles, whole.cycles)
    for k in STAT_NAMES:
        np.testing.assert_array_equal(resumed.step_stats[k], whole.step_stats[k], err_msg=k)


def test_checkpoint_of_the_old_height_is_refused(tmp_path):
    from primesim_tpu.sim.checkpoint import atomic_save_npz, load_verified_npz

    cfg, trace = MACHINES["plain"]()
    eng = Engine(cfg, trace, chunk_steps=32)
    eng.run_steps(32)
    path = str(tmp_path / "ck.npz")
    eng.save_checkpoint(path)
    z = dict(load_verified_npz(path))
    z.pop("crc_json", None)
    n = len(COUNTER_NAMES)
    z["state_counters"] = z["state_counters"][:n]
    z["host_counters"] = z["host_counters"][:n]
    old = str(tmp_path / "old.npz")
    atomic_save_npz(old, **z)
    with pytest.raises(ValueError, match=f"has {n} counter rows but this build defines "
                                         f"{N_BLOCK_ROWS}"):
        Engine(cfg, trace, chunk_steps=32).load_checkpoint(old)


def test_span_reads_one_interval_on_both_clocks():
    with span("engine.test") as s:
        assert s.seconds == 0.0
        sum(range(1000))
    assert s.seconds > 0.0
    with pytest.raises(KeyError):
        with span("engine.test") as s2:
            raise KeyError("x")
    assert s2.seconds > 0.0  # the span closed on the way out


def test_run_steps_reads_no_clock_of_its_own():
    import inspect

    src = inspect.getsource(Engine.run_steps)
    assert "perf_counter" not in src and src.count("with span(") == 4


def _assert_job_sample(sample, eng, label="engine"):
    assert sample["label"] == label and sample["steps"] == eng.steps_run
    ph = sample["phases"]
    assert set(ph) == {"init", "dispatch", "wait", "readback"} and min(ph.values()) > 0
    assert sample["wall_s"] == pytest.approx(ph["dispatch"] + ph["wait"] + ph["readback"])
    assert set(sample["deltas"]) == set(BLOCK_NAMES)
    for k in COUNTER_NAMES:
        assert sample["deltas"][k] == int(eng.counters[k].sum()), k
    assert {k: sample["deltas"][k] for k in STAT_NAMES} == stat_totals(eng.step_stats)
    assert isinstance(sample["deltas"]["noc_sort_log2"], list)
    assert sample["caps"] == {
        "n_cores": 16, "local_run_len": 4,
        "sort_entries": 16 * 2 * 6}  # two legs, with or without sync events
    json.dumps(sample)  # plain data, as `dump_jsonl` writes it


def test_fused_run_commits_one_sample_to_the_process_store():
    cfg, trace = MACHINES["sync"]()
    before = process_store().seq
    eng = _fused(cfg, trace)
    assert process_store().seq == before + 1
    _assert_job_sample(process_store().samples()[-1], eng)
    assert process_store() is process_store()


def test_obs_basic_of_a_fused_run_yields_one_sample(tmp_path):
    cfg, trace = MACHINES["sync"]()
    rec = Recorder("basic", metrics_path=str(tmp_path / "m.jsonl"))
    eng = Engine(cfg, trace, chunk_steps=32)
    rec.attach(eng)
    before = process_store().seq
    eng.run()
    assert process_store().seq == before  # the recorder's store, not the process's
    assert len(rec.store) == 1
    _assert_job_sample(rec.store.samples()[0], eng)
    ref = _fused(cfg, trace)  # no simulated bit depends on who listens
    np.testing.assert_array_equal(eng.cycles, ref.cycles)
    rec.finalize()
    line = json.loads(open(tmp_path / "m.jsonl").read().splitlines()[0])
    assert line["deltas"]["slot_frozen"] == int(eng.step_stats["slot_frozen"].sum())
    assert rec.timeline_summary()["total_instructions"] == int(eng.counters["instructions"].sum())


def test_chunks_after_a_fused_job_keep_their_deltas_whole():
    cfg, trace = MACHINES["rung3"]()
    rec = Recorder("full")
    eng = Engine(cfg, trace, chunk_steps=8)
    rec.attach(eng)
    eng.run_steps(16)
    with pytest.raises(RuntimeError, match="max_steps exceeded"):
        eng.run(max_steps=8)  # one chunk, fused: its sample is committed all the same
    eng.run_chunked()
    samples = rec.store.samples()
    assert sum("caps" in s for s in samples) == 1
    assert sum(s["deltas"]["instructions"] for s in samples) == int(
        eng.counters["instructions"].sum())
    names = [e["name"] for e in rec.trace.events if e["ph"] == "B"]
    assert names.count("job") == 1 and names.count("chunk") == len(samples) - 1


def test_metric_store_keeps_a_list_row_and_caps():
    store = MetricStore(capacity=2)
    s = store.record(0.0, "engine", 8, 0.5, {"instructions": np.int64(3), "h": [1, 0, 2]},
                     phases={"dispatch": 0.25}, caps={"n_cores": 4})
    assert s["deltas"] == {"instructions": 3, "h": [1, 0, 2]} and s["caps"] == {"n_cores": 4}
    assert "caps" not in store.record(0.0, "engine", 8, 0.5, {"instructions": 1})
    assert store.summary()["total_instructions"] == 4


def _fleet(cfg, traces, overrides, rec=None, mesh=None):
    from primesim_tpu.sim.fleet import FleetEngine

    fleet = FleetEngine(cfg, traces, overrides, chunk_steps=8, mesh=mesh)
    if rec is not None:
        rec.attach(fleet)
    fleet.run()
    return fleet


def _assert_fleet_sample(sample, fleet):
    """The one sample of `FleetEngine.run` (DESIGN.md §15): B machines'
    totals, the longest element's steps, sizes that say B."""
    B = fleet.n_elements
    assert sample["label"] == "fleet" and sample["steps"] == int(fleet.steps_run.max())
    ph = sample["phases"]
    assert set(ph) == {"init", "dispatch", "wait", "readback"} and min(ph.values()) > 0
    assert sample["wall_s"] == pytest.approx(ph["dispatch"] + ph["wait"] + ph["readback"])
    assert set(sample["deltas"]) == set(BLOCK_NAMES)
    for k in COUNTER_NAMES:
        assert sample["deltas"][k] == int(fleet.counters[k].sum()), k
    assert {k: sample["deltas"][k] for k in STAT_NAMES} == stat_totals(
        {k: v.sum(axis=0) for k, v in fleet.step_stats.items()})
    assert sample["caps"] == {
        "n_cores": B * 16, "local_run_len": 4, "sort_entries": B * 16 * 2 * 6,
        "elements": B, "element_steps": fleet.steps_run.tolist(),
        "chips": 1, "chip_steps": [int(fleet.steps_run.max())]}
    json.dumps(sample)


def test_fleet_run_commits_one_sample_of_all_its_machines():
    cfg, trace = MACHINES["rung3"]()
    ovs = [{}, {"quantum": 100, "llc_lat": 14}, {"dram_lat": 150}]
    before = process_store().seq
    fleet = _fleet(cfg, [trace] * 3, ovs)
    assert process_store().seq == before + 1  # exactly one, whatever B
    _assert_fleet_sample(process_store().samples()[-1], fleet)
    assert len(set(fleet.steps_run.tolist())) > 1  # an element froze before the longest ended
    # to the recorder where one is attached, and no simulated bit depends on who listens
    rec = Recorder("basic")
    heard = _fleet(cfg, [trace] * 3, ovs, rec)
    assert process_store().seq == before + 1 and len(rec.store) == 1
    _assert_fleet_sample(rec.store.samples()[0], heard)
    np.testing.assert_array_equal(heard.cycles, fleet.cycles)
    for k in COUNTER_NAMES:
        np.testing.assert_array_equal(heard.counters[k], fleet.counters[k], err_msg=k)
    assert rec.timeline_summary()["total_instructions"] == int(fleet.counters["instructions"].sum())
    # the build belongs to the first job
    assert fleet._init_s == 0.0


def test_a_fleet_of_one_commits_a_solo_runs_deltas():
    cfg, trace = MACHINES["sync"]()
    eng = Engine(cfg, trace, chunk_steps=8)
    eng.run()
    solo = process_store().samples()[-1]
    fleet = _fleet(cfg, [trace], None)
    mine = process_store().samples()[-1]
    assert (solo["label"], mine["label"]) == ("engine", "fleet")
    assert mine["deltas"] == solo["deltas"] and mine["steps"] == solo["steps"] == eng.steps_run
    assert mine["caps"] == {**solo["caps"], "elements": 1, "element_steps": [eng.steps_run],
                            "chips": 1, "chip_steps": [eng.steps_run]}
    assert fleet.has_sync and mine["caps"]["sort_entries"] == 16 * 2 * 6


def test_a_sharded_fleets_sample_holds_the_rows_its_block_carries():
    """A fleet on a mesh is whole machines a chip, each chip running the
    one-chip fleet's program, so, unlike a sharded `Engine` (`build_state`),
    its block keeps the stat rows there; the sample holds whatever rows were
    drained, and says how many chips ran and how long each."""
    cfg, trace = MACHINES["plain"]()
    fleet = _fleet(cfg, [trace] * 2, [{}, {"dram_lat": 150}], mesh=tile_mesh(2))
    mine = process_store().samples()[-1]
    assert len(fleet.state.cycles.devices()) == 2
    assert mine["caps"]["chips"] == 2
    assert mine["caps"]["chip_steps"] == mine["caps"]["element_steps"]  # one machine a chip
    assert set(mine["deltas"]) == set(BLOCK_NAMES[:fleet.state.counters.shape[1]])
    assert mine["caps"]["n_cores"] == 32 and mine["caps"]["elements"] == 2
    for k in COUNTER_NAMES:
        assert mine["deltas"][k] == int(fleet.counters[k].sum()), k


# ---- where the job's bytes lay: the sample's `place` ----------------------

READINGS = ("alloc", "alloc_built", "alloc_run")  # as init opens, as it closes, as wait closes


def _counting(monkeypatch, held):
    """An allocator that counts, which the CPU's does not: `held` at every
    reading, or, where `held` is a function, `held(n)` at the n-th."""
    from primesim_tpu.sim import engine, fleet

    n = iter(range(10**6))
    read = (lambda: held(next(n))) if callable(held) else (lambda: held)
    monkeypatch.setattr(engine, "alloc_now", read)
    monkeypatch.setattr(fleet, "alloc_now", read)


def _place_job(kind):
    cfg, trace = MACHINES["sync"]()
    if kind == "solo":
        return _fused(cfg, trace), None
    if kind == "four":
        mesh = tile_mesh(4)
        return _fused(cfg, trace, mesh=mesh), mesh
    if kind == "fleet":
        return _fleet(cfg, [trace] * 2, [{}, {"dram_lat": 150}]), None
    mesh = tile_mesh(4)
    return _fleet(cfg, [trace] * 4, None, mesh=mesh), mesh


@pytest.mark.parametrize("kind", ["solo", "four", "fleet", "fleet_four"])
@pytest.mark.parametrize("counts", [False, True])
def test_a_fused_job_commits_where_its_bytes_lay(monkeypatch, kind, counts):
    """`place`, the fifth key of a job's sample (DESIGN.md §15): the chips
    in mesh order and what each one's allocator said as the engine's build
    began, as it ended and as the job's wait ended (PR 54), every fused
    job of both engines (nothing to count on the CPU: `{}` each)."""
    from primesim_tpu.sim.engine import ALLOC_KEYS

    if counts:  # the n-th reading says n in its ten-thousands
        _counting(monkeypatch, lambda n: {
            i: {k: 10000 * n + 1000 * j + i for j, k in enumerate(ALLOC_KEYS)}
            for i in range(8)})
    eng, mesh = _place_job(kind)
    sample = process_store().samples()[-1]
    assert sample is eng.last_job and sample["place"] is eng.place
    chips = [0] if mesh is None else [d.id for d in mesh.devices.flat]
    # `state_bytes` (PR 55): a chip's share of every leaf as the engine was
    # built, counted from the shapes, so on the CPU too: the whole state
    # without a mesh, on a mesh the cut leaves' quarter and the others whole
    total = sum(x.nbytes for x in jax.tree.leaves(eng.state))
    a_chip = sample["place"]["state_bytes"][0]
    assert total / len(chips) <= a_chip <= total
    assert (a_chip == total) == (mesh is None)
    assert sample["place"] == {"devices": chips, "state_bytes": [a_chip] * len(chips), **{
        reading: {k: [10000 * n + 1000 * j + i for i in chips] for j, k in enumerate(ALLOC_KEYS)}
        if counts else {} for n, reading in enumerate(READINGS)}}
    json.dumps(sample)


def test_an_allocator_that_counts_for_some_chips_alone_counts_for_none(monkeypatch):
    _counting(monkeypatch, {0: {
        "bytes_in_use": 1, "largest_free_block_bytes": 2, "peak_bytes_in_use": 3}})
    four, solo = _place_job("four")[0].place, _place_job("solo")[0].place
    for reading in READINGS:
        assert four[reading] == {}
        assert solo[reading] == {
            "bytes_in_use": [1], "largest_free_block_bytes": [2], "peak_bytes_in_use": [3]}


def test_alloc_now_keeps_the_three_counts_of_a_device_that_has_them_all(monkeypatch):
    import jax

    from primesim_tpu.sim import engine

    class Device:
        def __init__(self, i, stats):
            self.id, self._stats = i, stats

        def memory_stats(self):
            return self._stats

    monkeypatch.setattr(jax, "local_devices", lambda: [
        Device(0, {"bytes_in_use": 5, "largest_free_block_bytes": 7,
                   "peak_bytes_in_use": 9, "num_allocs": 1}),
        Device(1, {"bytes_in_use": 5, "largest_free_block_bytes": 7}), Device(2, None)])
    assert engine.alloc_now() == {0: {
        "bytes_in_use": 5, "largest_free_block_bytes": 7, "peak_bytes_in_use": 9}}


def test_a_second_fused_run_carries_the_builds_place_and_its_own_reading(monkeypatch):
    """`alloc` and `alloc_built` are the engine's, read once; `alloc_run` is
    the job's, and an earlier job's sample keeps its own."""
    _counting(monkeypatch, lambda n: {0: {
        "bytes_in_use": n << 20, "largest_free_block_bytes": 1 << 33,
        "peak_bytes_in_use": n << 21}})
    cfg, trace = MACHINES["rung3"]()
    eng = Engine(cfg, trace, chunk_steps=8)
    with pytest.raises(RuntimeError, match="max_steps exceeded"):
        eng.run(max_steps=8)
    first = eng.last_job
    with pytest.raises(RuntimeError, match="max_steps exceeded"):
        eng.run(max_steps=8)

    def reading(n):
        return {"bytes_in_use": [n << 20], "largest_free_block_bytes": [1 << 33],
                "peak_bytes_in_use": [n << 21]}

    assert eng.last_job is not first
    assert first["place"] == {"devices": [0], "alloc": reading(0),
                              "alloc_built": reading(1), "alloc_run": reading(2),
                              "state_bytes": first["place"]["state_bytes"]}
    assert eng.last_job["place"] == {**first["place"], "alloc_run": reading(3)}


def test_a_recorder_writes_place_through_dump_jsonl(monkeypatch, tmp_path):
    _counting(monkeypatch, {0: {
        "bytes_in_use": 9, "largest_free_block_bytes": 11, "peak_bytes_in_use": 13}})
    cfg, trace = MACHINES["rung3"]()
    rec = Recorder("basic", metrics_path=str(tmp_path / "m.jsonl"))
    eng = Engine(cfg, trace, chunk_steps=32)
    rec.attach(eng)
    eng.run()
    assert eng.last_job is rec.store.samples()[0]
    rec.finalize()
    line = json.loads(open(tmp_path / "m.jsonl").read().splitlines()[0])
    assert line["place"] == eng.place and line["place"]["alloc"]["bytes_in_use"] == [9]
    assert line["place"]["alloc_run"]["peak_bytes_in_use"] == [13]
    fleet = _fleet(cfg, [trace] * 2, None, rec=Recorder("basic"))
    assert fleet.last_job["place"] == fleet.place and fleet.last_job["label"] == "fleet"


def test_the_run_summary_says_where_the_wall_went_and_where_the_machine_lay():
    from primesim_tpu import cli

    eng = _fused(*MACHINES["rung3"]())
    job = cli._job_detail(eng)
    assert set(job) == {"phases_ms", "place"} and job["place"] is eng.place
    assert job["phases_ms"] == {k: round(1e3 * v, 3) for k, v in eng.last_job["phases"].items()}
    cfg, trace = MACHINES["rung3"]()
    chunked = Engine(cfg, trace, chunk_steps=32)
    chunked.run_chunked()
    assert cli._job_detail(chunked) is None and cli._job_detail(None) is None


# ---- the benchmark's readers of the job samples ---------------------------

READERS = ("slot_active_pct", "slot_quantum_pct", "slot_frozen_pct", "arb_win_pct",
           "run_slot_pct", "noc_active_pct", "noc_sort_log2_max", "host_readback_ms_job",
           "host_dispatch_ms_job")
INIT_READER = ("engine_init_ms_job",)  # PR 53: the reader of `phases.init`


@pytest.fixture(scope="module")
def window():
    """Two fused jobs as a window's, with the run record's fields the
    readers look at."""
    import benchmark_modules  # noqa: F401  (puts benchmark/ on the path)
    import cells

    cfg, trace = MACHINES["sync"]()
    engines = [_fused(cfg, trace), _fused(cfg, trace)]
    run = {"jobs": [{"steps": e.steps_run} for e in engines], "n_cores": 16}
    return cells, run, engines


@pytest.mark.parametrize("name", READERS + INIT_READER)
def test_reader_on_stored_samples(window, name):
    cells, run, engines = window
    got = cells.load_metric(name)(run, None)
    eng = engines[0]
    st, steps = stat_totals(eng.step_stats), eng.steps_run
    served = sum(int(eng.counters[k].sum())
                 for k in ("l1_read_misses", "l1_write_misses", "upgrades"))
    want = {
        "slot_active_pct": lambda: 100 * st["slot_active"] / (16 * steps),
        "slot_quantum_pct": lambda: 100 * st["slot_quantum"] / (16 * steps),
        "slot_frozen_pct": lambda: 100 * st["slot_frozen"] / (16 * steps),
        "arb_win_pct": lambda: 100 * served / (served + int(eng.counters["retries"].sum())),
        "run_slot_pct": lambda: 100 * st["run_events"] / (16 * steps * 4),
        "noc_active_pct": lambda: 100 * st["noc_entries"] / (steps * 16 * 2 * 6),
        "noc_sort_log2_max": lambda: max(b for b, n in enumerate(st["noc_sort_log2"]) if n),
    }
    if name in want:
        assert got == pytest.approx(want[name]()) and 0 < got <= 100
    else:
        samples = process_store().samples()[-2:]
        key = name.split("_")[1]
        assert got == pytest.approx(1e3 * sum(s["phases"][key] for s in samples) / 2) and got > 0


@pytest.mark.parametrize("name", READERS + INIT_READER)
def test_reader_finds_nothing_where_the_samples_are_not_the_windows(window, name):
    cells, run, _ = window
    read = cells.load_metric(name)
    assert read({"jobs": [], "n_cores": 16}, None) is None
    assert read({"jobs": [{"steps": 1}] + run["jobs"], "n_cores": 16}, None) is None


def test_the_init_reader_reads_the_parents_samples(window, monkeypatch):
    """The parent's samples: everything but `place`."""
    import primesim_tpu.obs

    cells, run, engines = window
    store = MetricStore()
    store._ring.extend({k: v for k, v in e.last_job.items() if k != "place"} for e in engines)
    monkeypatch.setattr(primesim_tpu.obs, "process_store", lambda: store)
    assert cells.load_metric("engine_init_ms_job")(run, None) > 0
    assert cells.load_metric("slot_active_pct")(run, None) > 0


@pytest.mark.parametrize("passes, kept", [(1, 2), (0, 1)])
def test_readers_count_whole_passes_of_a_traced_window(window, passes, kept):
    """A traced window ends with the job in flight: three jobs of a panel
    of two traces are one whole pass, and a window shorter than a pass is
    its first job; either way the count is the seed's, not the clock's."""
    cells, run, engines = window
    eng = _fused(*MACHINES["sync"]())  # the job in flight
    traces = (0, 1, 0) if passes else (0, 1, 1)
    run3 = {"passes": passes, "n_cores": 16, "jobs": [
        {"steps": e.steps_run, "trace": t} for e, t in zip(engines + [eng], traces)]}
    if not passes:
        run3["jobs"] = run3["jobs"][1:]
    t = cells._load("metrics", "slot_active_pct", cells.ROOT, "window_totals")(run3)
    assert t["jobs"] == kept and t["steps"] == kept * eng.steps_run
    assert (cells.load_metric("run_slot_pct")(run3, None)
            == pytest.approx(100 * stat_totals(eng.step_stats)["run_events"]
                             / (16 * eng.steps_run * 4)))


def test_router_readers_find_nothing_on_a_machine_without_the_router():
    import benchmark_modules  # noqa: F401
    import cells

    cfg, trace = MACHINES["plain"]()
    eng = _fused(cfg, trace)
    run = {"jobs": [{"steps": eng.steps_run}], "n_cores": 16}
    assert cells.load_metric("noc_active_pct")(run, None) is None
    assert cells.load_metric("noc_sort_log2_max")(run, None) is None
    assert cells.load_metric("slot_frozen_pct")(run, None) == 0.0
    assert cells.load_metric("slot_active_pct")(run, None) > 0


def test_stat_ms_step_reads_the_stat_scopes_of_the_traced_job():
    import benchmark_modules  # noqa: F401
    import cells

    read = cells.load_metric("stat_ms_step")
    hlo = ('  %fusion.1 = s32[16]{0} fusion(%a), kind=kLoop, calls=%f, '
           'metadata={op_name="jit(run_loop)/while/body/s.noc/stat/eq"}\n')
    run = {"jobs": [{"steps": 100, "traced": True}], "hlo_text": hlo}
    ops = {"fusion.1 jit(run_loop)/s.noc/stat/eq": [0.002, 100],
           "fusion.2 jit(run_loop)/s.noc/rank/sort": [0.5, 100]}
    assert read(run, {"ops": ops}) == pytest.approx(0.02)
    assert read(run, {"ops": {"fusion.2 jit(run_loop)/s.noc/rank/sort": [0.5, 100]}}) == 0.0
    assert read(run, None) is None
    # a program from before the stat rows: its text names no such scope
    assert read(dict(run, hlo_text=hlo.replace("/stat/", "/")), {"ops": ops}) is None


def test_new_metrics_are_appended_to_the_benchmark_and_have_readers():
    import os

    import benchmark_modules
    import cells

    bench = json.load(open(os.path.join(benchmark_modules.ROOT, "BENCHMARK.json")))
    first = [m["name"] for m in bench["per_layer"]].index(list(READERS)[0])
    new = bench["per_layer"][first:first + 10]  # PR 37's ten, in the order it appended them
    assert [m["name"] for m in new] == list(READERS) + ["stat_ms_step"]
    # no stat rows on a mesh: the rows' readers list one-chip cells, these
    # at least (a later `benchmark` PR may append a one-chip cell to a list)
    router = ["rung3.fft-m16", "rung3.rand-ws1m", "rung3.ocean-n258"]
    one_chip = ["mesh1024.fft-m16", "rung3.fft-m16", "rung3.rand-ws1m", "rung5.fft-m18-16k",
                "rung3.ocean-n258"]
    four_chips = {w["name"] for w in bench["workloads"] if w["chips"] != 1}
    pinned = {"slot_frozen_pct": ["rung3.ocean-n258"],
              "slot_active_pct": one_chip, "slot_quantum_pct": one_chip,
              "run_slot_pct": one_chip, "stat_ms_step": one_chip,
              "noc_active_pct": router, "noc_sort_log2_max": router}
    for m in new:
        assert callable(cells.load_metric(m["name"])) and m["moves"] == "sim_mips"
        if m["name"] in pinned:
            assert set(m["workloads"]) >= set(pinned[m["name"]]), m["name"]
            assert not set(m["workloads"]) & four_chips, m["name"]
        else:  # the counters' and the host spans' readers: every cell
            assert "workloads" not in m, m["name"]
