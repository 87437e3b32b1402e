"""Machine zoo + calibration subsystem (DESIGN.md §25, ISSUE 19).

Covers the pluggable-topology contract (vectorized `path_links` vs the
memoized scalar `route_links` walk, link-for-link, on every topology),
MOESI dirty-sharing semantics and its divergence from MESI, the stride
prefetcher's counters, golden/engine parity across zoo
selector combinations, link faults on torus/ring solo-vs-fleet, the
typed ConfigError/CalibError exit-2 contract, checkpoint round-trips of
the prefetcher state (format v7), and the `primetpu calibrate` fit
recovering synthetic ground-truth knobs.
"""

import dataclasses
import json

import numpy as np
import pytest

from primesim_tpu.calib.fit import (
    FIT_KEYS_DEFAULT,
    apply_fit,
    fit,
    knob_start,
    simulate_matrix,
    synthesize_observed,
)
from primesim_tpu.calib.table import (
    CalibEntry,
    CalibError,
    CalibTable,
    parse_table,
)
from primesim_tpu.config.machine import (
    FAULT_LINK_DEGRADE,
    FAULT_LINK_FAIL,
    ConfigError,
    FaultConfigError,
    MachineConfig,
    NocConfig,
    small_test_config,
)
from primesim_tpu.golden.sim import GoldenSim
from primesim_tpu.noc import topology as topo
from primesim_tpu.trace import synth
from primesim_tpu.trace.format import EV_INS, EV_LD, EV_ST, from_event_lists

from test_parity import assert_parity


def zoo_cfg(n_cores=8, mesh_x=4, mesh_y=2, topology="mesh", **kw):
    noc = NocConfig(
        mesh_x=mesh_x, mesh_y=mesh_y, link_lat=1, router_lat=2,
        topology=topology,
    )
    kw.setdefault("n_banks", 4)
    kw.setdefault("quantum", 400)
    return small_test_config(n_cores, noc=noc, **kw)


# ---- topology plugins: scalar reference vs vectorized routes --------------


@pytest.mark.parametrize("topology", ["mesh", "torus", "ring"])
@pytest.mark.parametrize("mx,my", [(4, 4), (5, 3)])
def test_path_links_matches_route_links_all_pairs(topology, mx, my):
    cfg = zoo_cfg(n_cores=mx * my, mesh_x=mx, mesh_y=my, topology=topology)
    tiles = np.arange(cfg.n_tiles, dtype=np.int32)
    a = np.repeat(tiles, cfg.n_tiles)
    b = np.tile(tiles, cfg.n_tiles)
    paths = np.asarray(topo.path_links(cfg, a, b))
    assert paths.shape == (a.size, topo.path_width(cfg))
    hops = np.asarray(topo.hops(cfg, a, b, xp=np))
    for i in range(a.size):
        ref = topo.route_links(cfg, int(a[i]), int(b[i]))
        row = tuple(int(x) for x in paths[i] if x >= 0)
        assert row == ref, (topology, int(a[i]), int(b[i]))
        # hop count is the route length; routes fit the padded width
        assert hops[i] == len(ref)
        assert len(ref) <= topo.path_width(cfg)
    # hops are symmetric and zero only on the diagonal
    h = hops.reshape(cfg.n_tiles, cfg.n_tiles)
    np.testing.assert_array_equal(h, h.T)
    assert (np.diag(h) == 0).all() and (h + np.eye(cfg.n_tiles) > 0).all()


def test_torus_wraps_and_ring_stays_on_spine():
    t = zoo_cfg(n_cores=16, mesh_x=4, mesh_y=4, topology="torus")
    # opposite edge tiles are one wrap hop apart on the torus, not mx-1
    assert int(topo.hops(t, 0, 3, xp=np)) == 1
    assert int(topo.hops(dataclasses.replace(
        t, noc=dataclasses.replace(t.noc, topology="mesh")), 0, 3,
        xp=np)) == 3
    r = zoo_cfg(n_cores=16, mesh_x=4, mesh_y=4, topology="ring")
    # every vertical (N/S) link a ring route uses sits on the column-0
    # spine; cross-row routes pay row -> spine -> row
    for a in range(r.n_tiles):
        for b in range(r.n_tiles):
            for l in topo.route_links(r, a, b):
                if l % 4 in (2, 3):
                    assert (l // 4) % 4 == 0, (a, b, l)


def test_detour_hops_tables_per_topology():
    mesh = zoo_cfg(n_cores=16, mesh_x=4, mesh_y=4, topology="mesh")
    torus = zoo_cfg(n_cores=16, mesh_x=4, mesh_y=4, topology="torus")
    ring = zoo_cfg(n_cores=15, mesh_x=5, mesh_y=3, topology="ring")
    assert (topo.detour_hops_table(mesh) == 2).all()
    assert (topo.detour_hops_table(torus) == 2).all()
    tbl = topo.detour_hops_table(ring).reshape(-1, 4)
    assert tbl.shape[0] == ring.n_tiles
    assert (tbl[:, 0:2] == 5 - 2).all()  # row-ring detour: mx - 2
    assert (tbl[:, 2:4] == 3 - 2).all()  # spine detour: my - 2


# ---- MOESI: derived Owned state semantics ---------------------------------


def _two_core_sharing_trace():
    # core 0 dirties a line; core 1 reads it later (the INS batch orders
    # the arbitration); cores 2/3 idle
    return from_event_lists([
        [(EV_ST, 4, 0)],
        [(EV_INS, 50, 0), (EV_LD, 4, 0)],
        [],
        [],
    ])


def test_moesi_owner_retained_on_gets():
    tr = _two_core_sharing_trace()
    g = GoldenSim(small_test_config(4, coherence="moesi"), tr)
    g.run()
    # the GETS probed the dirty owner but left it in place: the home
    # still names core 0 owner, with both cores recorded as sharers
    assert int(g.counters["probes"][1]) == 1
    b, bs = g._bank(0), g._bank_set(0)
    w = next(w for w in range(g.cfg.llc.ways) if g.llc_tag[b, bs, w] == 0)
    assert int(g.llc_owner[b, bs, w]) == 0
    sharers = g._sharers_from(g.sharers, b, bs, w)
    assert set(sharers) == {0, 1}
    # derived O: core 0's stored M line is effectively Owned; core 1's is
    # a plain shared copy
    assert g._derived_owned(0, 0)
    assert not g._derived_owned(1, 0)


def test_mesi_demotes_owner_on_gets():
    tr = _two_core_sharing_trace()
    g = GoldenSim(small_test_config(4, coherence="mesi"), tr)
    g.run()
    b, bs = g._bank(0), g._bank_set(0)
    w = next(w for w in range(g.cfg.llc.ways) if g.llc_tag[b, bs, w] == 0)
    assert int(g.llc_owner[b, bs, w]) == -1  # written back + demoted
    assert not g._derived_owned(0, 0)


def test_moesi_diverges_from_mesi_on_shared_readers():
    # many readers of one dirty line: MOESI keeps probing the retained
    # owner, MESI demotes it once — the protocols must NOT be aliases
    tr = synth.uniform_random(8, n_mem_ops=96, shared_frac=0.8, seed=11)
    out = {}
    for proto in ("mesi", "moesi"):
        g = GoldenSim(small_test_config(8, coherence=proto), tr)
        g.run()
        out[proto] = (int(g.counters["probes"].sum()),
                      int(g.cycles.sum()))
    assert out["moesi"][0] > out["mesi"][0]
    assert out["moesi"] != out["mesi"]


# ---- stride prefetcher ----------------------------------------------------


def test_stride_prefetcher_covers_stream_misses():
    tr = synth.stream(4, n_mem_ops=96, seed=3)
    base = GoldenSim(small_test_config(4), tr)
    base.run()
    pf = GoldenSim(
        small_test_config(4, prefetcher="stride", prefetch_degree=4,
                          prefetch_lat=2),
        tr,
    )
    pf.run()
    assert int(base.counters["prefetch_hits"].sum()) == 0
    assert int(pf.counters["prefetch_hits"].sum()) > 0
    # a covered miss still fetched the line (dram_accesses counts it) —
    # it just paid the buffer latency instead of dram_lat
    np.testing.assert_array_equal(
        pf.counters["dram_accesses"], base.counters["dram_accesses"]
    )
    np.testing.assert_array_equal(
        pf.counters["instructions"], base.counters["instructions"]
    )
    assert int(pf.cycles.max()) < int(base.cycles.max())


def test_random_trace_trains_no_strides():
    tr = synth.uniform_random(4, n_mem_ops=64, shared_frac=0.0, seed=9)
    g = GoldenSim(
        small_test_config(4, prefetcher="stride", prefetch_degree=2,
                          prefetch_lat=2),
        tr,
    )
    g.run()
    # irregular addresses may fluke an occasional stride, but coverage
    # must be marginal, and the selector must not perturb retirement
    assert int(g.counters["prefetch_hits"].sum()) <= int(
        g.counters["dram_accesses"].sum()) // 4
    base = GoldenSim(small_test_config(4), tr)
    base.run()
    np.testing.assert_array_equal(
        g.counters["instructions"], base.counters["instructions"]
    )


# ---- typed config/table error contract ------------------------------------


@pytest.mark.parametrize(
    "kw,selector",
    [
        (dict(noc=NocConfig(2, 2, 1, 1, topology="taurus")), "noc_topology"),
        (dict(coherence="mosi"), "coherence"),
        (dict(coherence="moesi", sharer_group=2), "coherence"),
        (dict(prefetcher="ghb"), "prefetcher"),
        (dict(prefetcher="stride", prefetch_degree=0), "prefetch_degree"),
        (dict(prefetch_lat=-1), "prefetch_lat"),
    ],
)
def test_config_error_carries_selector_location(kw, selector):
    with pytest.raises(ConfigError) as ei:
        small_test_config(8, **kw)
    assert ei.value.location()["selector"] == selector


def test_ring_link_faults_need_rings_of_three():
    noc = NocConfig(2, 2, 1, 1, topology="ring")
    with pytest.raises(FaultConfigError, match="mesh_x >= 3"):
        small_test_config(
            4, noc=noc, faults_enabled=True, max_fault_events=1,
            fault_events=((1, FAULT_LINK_FAIL, 0, 0),),
        )
    # the same schedule is legal once the rings have a long way around
    cfg = zoo_cfg(
        n_cores=9, mesh_x=3, mesh_y=3, topology="ring",
        faults_enabled=True, max_fault_events=1,
        fault_events=((1, FAULT_LINK_FAIL, 0, 0),),
    )
    assert cfg.noc.topology == "ring"


@pytest.mark.parametrize(
    "mutate,entry,field",
    [
        (lambda t: t["entries"][0].update(generator="nope"), "e0",
         "generator"),
        (lambda t: t["entries"][0].update(metric="mips"), "e0", "metric"),
        (lambda t: t["entries"][0].update(observed=0), "e0", "observed"),
        (lambda t: t["entries"][0]["params"].update(n_mem_ops=1.5), "e0",
         "params"),
        (lambda t: t["entries"].append(dict(t["entries"][0])), "e0", None),
        (lambda t: t.pop("name"), None, "name"),
        (lambda t: t.update(entries=[]), None, "entries"),
    ],
)
def test_calib_table_validation(mutate, entry, field):
    t = {
        "name": "tbl",
        "entries": [{
            "name": "e0", "generator": "stream",
            "params": {"n_mem_ops": 32}, "metric": "total_cycles",
            "observed": 10.0,
        }],
    }
    mutate(t)
    with pytest.raises(CalibError) as ei:
        parse_table(json.dumps(t))
    loc = ei.value.location()
    if entry is not None:
        assert loc.get("entry") == entry
    if field is not None:
        assert loc.get("field") == field


def test_calibrate_cli_typed_errors_exit_2(tmp_path, capsys):
    from primesim_tpu.cli import main

    cfg_path = tmp_path / "m.json"
    cfg_path.write_text(small_test_config(4).to_json())
    bad = tmp_path / "bad.json"
    bad.write_text('{"name": "t", "entries": [{"name": "x"}]}')
    rc = main(["calibrate", str(cfg_path), "--table", str(bad)])
    assert rc == 2
    err = json.loads(capsys.readouterr().err.strip().splitlines()[-1])
    assert err["error"]["type"] == "CalibError"
    assert err["error"]["location"]["entry"] == "x"

    good = tmp_path / "tbl.json"
    good.write_text(json.dumps({
        "name": "t",
        "entries": [{"name": "x", "generator": "stream",
                     "params": {"n_mem_ops": 32},
                     "metric": "total_cycles", "observed": 10.0}],
    }))
    rc = main(["calibrate", str(cfg_path), "--table", str(good),
               "--fit", "warp_speed"])
    assert rc == 2
    err = json.loads(capsys.readouterr().err.strip().splitlines()[-1])
    assert err["error"]["type"] == "CalibError"
    assert err["error"]["location"]["field"] == "fit"


def test_cli_zoo_config_error_exit_2(tmp_path, capsys):
    from primesim_tpu.cli import main

    p = tmp_path / "bad.json"
    p.write_text(json.dumps({"n_cores": 4, "coherence": "dragon"}))
    rc = main(["info", str(p)])
    assert rc == 2
    err = json.loads(capsys.readouterr().err.strip().splitlines()[-1])
    assert err["error"]["type"] == "ConfigError"
    assert err["error"]["location"] == {
        "selector": "coherence", "value": "dragon",
    }


def test_config_comment_keys_are_annotations():
    d = json.loads(small_test_config(4).to_json())
    d["_comment"] = "machine-zoo configs ship provenance notes"
    assert MachineConfig.from_dict(d) == small_test_config(4)


# ---- lint: static selectors must not reach traced selects -----------------


def test_lint_flags_selector_inside_traced_select(tmp_path):
    from primesim_tpu.analysis.lint import run_lint

    def lint(relpath, src):
        p = tmp_path / relpath
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(src)
        res = run_lint(
            paths=[str(tmp_path)], root=str(tmp_path),
            baseline_path=str(tmp_path / "absent_baseline.json"),
            select=["PT-TRACED-BRANCH"],
        )
        p.unlink()
        return res

    bad = lint(
        "pkg/sim/bad.py",
        "import jax.numpy as jnp\n"
        "def f(cfg, a, b):\n"
        "    return jnp.where(cfg.coherence == 'moesi', a, b)\n",
    )
    assert [f.rule for f in bad.findings] == ["PT-TRACED-BRANCH"]
    assert "coherence" in bad.findings[0].message
    good = lint(
        "pkg/sim/good.py",
        "import jax.numpy as jnp\n"
        "def f(cfg, a, b):\n"
        "    if cfg.coherence == 'moesi':\n"
        "        return a\n"
        "    return jnp.where(a > b, a, b)\n",
    )
    assert good.findings == []


# ---- fleet knob plumbing --------------------------------------------------


def test_prefetch_knobs_are_fleet_overrides():
    from primesim_tpu.sim.fleet import KNOB_KEYS, apply_overrides

    cfg = small_test_config(4, prefetcher="stride")
    out = apply_overrides(cfg, {"prefetch_degree": 2, "prefetch_lat": 9})
    assert out == dataclasses.replace(
        cfg, prefetch_degree=2, prefetch_lat=9
    )
    # every fittable calibration knob is a fleet override key
    assert set(knob_start(cfg, FIT_KEYS_DEFAULT)) <= set(KNOB_KEYS)
    assert apply_fit(cfg, {"llc_lat": 7, "dram_lat": 55}) == \
        dataclasses.replace(
            cfg, llc=dataclasses.replace(cfg.llc, latency=7), dram_lat=55
        )


# ---- three-way parity across the zoo (slow: engine compiles) --------------

ZOO_COMBOS = [
    ("torus", "mesi", "none", "uniform_random"),
    ("ring", "mesi", "none", "uniform_random"),
    ("mesh", "moesi", "none", "uniform_random"),
    ("torus", "moesi", "stride", "fft_like"),
    ("ring", "mesi", "stride", "stream"),
]


def _zoo_trace(kind):
    if kind == "uniform_random":
        return synth.uniform_random(8, n_mem_ops=96, shared_frac=0.5, seed=5)
    if kind == "fft_like":
        return synth.fft_like(8, n_phases=2, points_per_core=12, seed=7)
    return synth.stream(8, n_mem_ops=96, seed=3)


@pytest.mark.slow
@pytest.mark.parametrize("topology,coherence,prefetcher,gen", ZOO_COMBOS)
def test_golden_engine_parity_zoo(topology, coherence, prefetcher, gen):
    cfg = zoo_cfg(
        topology=topology, coherence=coherence, prefetcher=prefetcher,
        prefetch_degree=4, prefetch_lat=3,
    )
    assert_parity(cfg, _zoo_trace(gen), chunk_steps=32)


# ---- faults on torus/ring (slow) ------------------------------------------


@pytest.mark.slow
@pytest.mark.parametrize("topology", ["torus", "ring"])
def test_zoo_link_faults_solo_vs_fleet(topology):
    from primesim_tpu.sim.engine import Engine
    from primesim_tpu.sim.fleet import FleetEngine

    cfg = zoo_cfg(
        n_cores=16, mesh_x=4, mesh_y=4, topology=topology,
        faults_enabled=True, max_fault_events=2,
        fault_events=(
            (5, FAULT_LINK_FAIL, 0, 0),       # tile 0 east: busy first hop
            (8, FAULT_LINK_DEGRADE, 22, 7),
        ),
    )
    tr = synth.uniform_random(16, n_mem_ops=96, shared_frac=0.4, seed=13)
    solo = Engine(cfg, tr, chunk_steps=32)
    solo.run()
    assert int(solo.counters["noc_reroutes"].sum()) > 0
    fleet = FleetEngine(cfg, [tr, tr], [{}, {"dram_lat": 140}],
                        chunk_steps=32)
    fleet.run()
    np.testing.assert_array_equal(
        np.asarray(fleet.cycles)[0], solo.cycles,
        err_msg=f"{topology}: fleet[0] != solo",
    )
    for k, v in solo.counters.items():
        np.testing.assert_array_equal(
            np.asarray(fleet.counters[k])[0], v, err_msg=k
        )
    # the overridden element genuinely diverges (the knobs are traced)
    assert int(np.asarray(fleet.cycles)[1].sum()) != int(solo.cycles.sum())


# ---- checkpoint format v7: prefetcher state survives resume (slow) --------


@pytest.mark.slow
def test_checkpoint_roundtrip_restores_prefetcher_state(tmp_path):
    from primesim_tpu.sim.engine import Engine

    cfg = small_test_config(
        8, n_banks=4, quantum=200, coherence="moesi",
        prefetcher="stride", prefetch_degree=4, prefetch_lat=3,
    )
    tr = synth.stream(8, n_mem_ops=96, seed=3)
    ref = Engine(cfg, tr, chunk_steps=16)
    ref.run()
    assert int(ref.counters["prefetch_hits"].sum()) > 0

    a = Engine(cfg, tr, chunk_steps=16)
    a.run_steps(24)
    assert not a.done()
    ckpt = str(tmp_path / "zoo.npz")
    a.save_checkpoint(ckpt)
    b = Engine(cfg, tr, chunk_steps=16)
    b.load_checkpoint(ckpt)
    # the mid-run prefetcher training state came back (v7 payload), so
    # the resumed run replays the same covered misses
    np.testing.assert_array_equal(
        np.asarray(b.state.pf_line), np.asarray(a.state.pf_line)
    )
    b.run()
    np.testing.assert_array_equal(b.cycles, ref.cycles)
    for k, v in ref.counters.items():
        np.testing.assert_array_equal(b.counters[k], v, err_msg=k)


# ---- calibrate: synthetic ground-truth recovery (slow) --------------------


def _calib_table():
    return CalibTable(
        name="selftest",
        entries=(
            CalibEntry("chase", "pointer_chase",
                       {"n_mem_ops": 48, "n_nodes": 16},
                       "cycles_per_mem_op", 1.0),
            CalibEntry("xchg", "uniform_random",
                       {"n_mem_ops": 48, "shared_frac": 1, "seed": 1},
                       "cycles_per_mem_op", 1.0),
        ),
    )


@pytest.mark.slow
def test_calibrate_recovers_synthetic_truth():
    cfg = small_test_config(8, n_banks=4, quantum=500)
    truth = {"llc_lat": 16, "dram_lat": 151}
    table = synthesize_observed(cfg, _calib_table(), truth, chunk_steps=64)
    res = fit(cfg, table, fit_keys=tuple(truth), chunk_steps=64)
    assert res.cost <= 1e-9, res.report()
    assert res.knobs == truth
    assert res.start == {"llc_lat": 10, "dram_lat": 100}
    assert res.batch == 5 * 2  # N_CANDIDATES x entries, constant per run
    # the fitted knobs round-trip into a loadable machine config
    out = apply_fit(cfg, res.knobs)
    assert out.llc.latency == 16 and out.dram_lat == 151
    assert MachineConfig.from_dict(json.loads(out.to_json())) == out


@pytest.mark.slow
def test_simulate_matrix_is_monotone_in_dram_lat():
    cfg = small_test_config(8, n_banks=4, quantum=500)
    rows = simulate_matrix(
        cfg, _calib_table(),
        [{"dram_lat": 50}, {"dram_lat": 100}, {"dram_lat": 200}],
        chunk_steps=64,
    )
    for e in range(2):
        col = [rows[k][e] for k in range(3)]
        assert col[0] < col[1] < col[2]
