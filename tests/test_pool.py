"""Tests for the elastic worker pool (pool/): unit keys, the ledger
fold, the lease state machine (grant / heartbeat-renew / expiry /
redispatch / poison / hedge / first-ACK-wins), coordinator restart
recovery, and the worker's crash-resume bit-exactness.

Determinism discipline: coordinator tests drive a FAKE clock (the
`clock` constructor hook), so lease expiry happens exactly when the test
says — never because a slow CI box stalled a heartbeat. Worker threads
heartbeat on real time against that frozen clock, which renews deadlines
to the same instant and therefore never expires anything by accident.

The subprocess acceptance tests (real SIGKILL of a worker, real SIGKILL
of the coordinator mid-campaign) are @slow: tier-1 pins the protocol
in-process; the CI pool-chaos job runs the real-process wiring.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time

import pytest

from primesim_tpu.config.machine import small_test_config
from primesim_tpu.pool import PoolCoordinator, PoolWorker, SimulatedCrash
from primesim_tpu.pool.units import (
    DONE,
    LEASED,
    PENDING,
    POISON,
    build_units,
    fold_unit_records,
    unit_key,
)
from primesim_tpu.serve.protocol import request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SMALL_SYNTH = "fft_like:n_phases=1,points_per_core=8,ins_per_mem=4,seed={}"
#: several chunks at chunk_steps=8 — room to crash at chunk 2 and resume
CRASH_SYNTH = "fft_like:n_phases=2,points_per_core=16,ins_per_mem=4,seed={}"


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def _cfg():
    return small_test_config(4)


def _units(n=2, synth=SMALL_SYNTH, chunk_steps=16):
    cfg = _cfg()
    synths = [synth.format(i) for i in range(n)]
    return cfg, build_units(
        cfg, [], synths, [{} for _ in range(n)],
        fold=True, chunk_steps=chunk_steps, max_steps=100_000,
    )


def _coord(tmp_path, units, name="pool", **kw):
    kw.setdefault("lease_ttl_s", 5.0)
    return PoolCoordinator(units, str(tmp_path / name), **kw)


def _lease(coord, worker):
    return coord.handle({"verb": "lease", "worker": worker})


def _ack(coord, worker, grant, result=None):
    u = grant["unit"]
    return coord.handle({
        "verb": "ack", "worker": worker, "unit_id": u["unit_id"],
        "epoch": grant["epoch"], "key": u["key"],
        "result": result or {"metric": "x", "value": 1},
        "resumed_steps": 0,
    })


def _reference_detail(cfg, unit):
    """The deterministic fields of a unit's result, computed in-process
    the same way `primetpu sweep` (no --workers) would."""
    from primesim_tpu.serve.scheduler import parse_synth_spec
    from primesim_tpu.sim.fleet import FleetEngine
    from primesim_tpu.sim.supervisor import RunSupervisor

    trace = parse_synth_spec(unit["synth"], cfg.n_cores, unit["fold"])
    fleet = FleetEngine(cfg, [trace], [{}],
                        chunk_steps=int(unit["chunk_steps"]))
    RunSupervisor(fleet, handle_signals=False).run(
        max_steps=int(unit["max_steps"]))
    ec = fleet.element_counters(0)
    return {
        "instructions": int(ec["instructions"].sum()),
        "max_core_cycles": int(fleet.cycles[0].max()),
        "noc_msgs": int(ec["noc_msgs"].sum()),
    }


# ---- unit identity -------------------------------------------------------


def test_unit_key_stable_and_workload_sensitive():
    cfg, units = _units(2)
    _, again = _units(2)
    assert [u["key"] for u in units] == [u["key"] for u in again]
    assert units[0]["key"] != units[1]["key"]  # different synth seed
    # any workload-identity field moves the key...
    bumped = dict(units[0], chunk_steps=units[0]["chunk_steps"] * 2)
    assert unit_key(bumped) != units[0]["key"]
    # ...but warm_cache is an execution HINT, not identity (forking from
    # a proven prefix is bit-exact, so the result is the same result)
    hinted = dict(units[0], warm_cache=True)
    assert unit_key(hinted) == units[0]["key"]


def test_build_units_pairing_mismatch_raises():
    cfg = _cfg()
    with pytest.raises(ValueError, match="fan rule"):
        build_units(cfg, [], [SMALL_SYNTH.format(0)], [{}, {}],
                    fold=True, chunk_steps=16, max_steps=100)


# ---- ledger fold ---------------------------------------------------------


def test_fold_first_ack_wins_under_duplicates_and_reorder():
    lease = {"t": "lease", "unit_id": "u0", "worker": "w0", "epoch": 1,
             "key": "k", "hedge": False}
    ack1 = {"t": "ack", "unit_id": "u0", "worker": "w1", "epoch": 2,
            "key": "k", "result": {"v": "first"}, "resumed_steps": 7}
    ack2 = {"t": "ack", "unit_id": "u0", "worker": "w0", "epoch": 1,
            "key": "k", "result": {"v": "late"}, "resumed_steps": 0}
    # ack arriving BEFORE its lease record is still authoritative, the
    # second ack (hedge loser / redelivery) is discarded whatever its
    # epoch claims
    units, clean = fold_unit_records([ack1, lease, ack2])
    assert units["u0"]["result"] == {"v": "first"}
    assert units["u0"]["result_epoch"] == 2
    assert units["u0"]["resumed_steps"] == 7
    assert units["u0"]["max_epoch"] == 2
    assert not clean
    # order-independent: any interleaving keeps the first ack in stream
    units2, _ = fold_unit_records([lease, ack1, ack1, ack2, ack2])
    assert units2["u0"]["result"] == {"v": "first"}


def test_fold_expire_accumulates_distinct_workers_across_restarts():
    recs = [
        {"t": "expire", "unit_id": "u0", "worker": "w0", "epoch": 1},
        {"t": "expire", "unit_id": "u0", "worker": "w0", "epoch": 2},
        {"t": "expire", "unit_id": "u0", "worker": "w1", "epoch": 3},
    ]
    units, _ = fold_unit_records(recs)
    assert units["u0"]["kills"] == {"w0", "w1"}  # distinct, not 3
    assert units["u0"]["max_epoch"] == 3
    # an expire landing AFTER the ack doesn't un-finish the unit
    ack = {"t": "ack", "unit_id": "u0", "worker": "w2", "epoch": 4,
           "key": "k", "result": {"v": 1}, "resumed_steps": 0}
    units2, _ = fold_unit_records([ack] + recs)
    assert units2["u0"]["result"] == {"v": 1}


def test_fold_poison_sticks_unless_a_result_exists():
    poison = {"t": "poison", "unit_id": "u0", "key": "k",
              "kills": ["w0", "w1"]}
    units, _ = fold_unit_records([poison])
    assert units["u0"]["poison"] and units["u0"]["kills"] == {"w0", "w1"}
    # a hedged twin's result beats the poison verdict — keep the data
    ack = {"t": "ack", "unit_id": "u0", "worker": "w2", "epoch": 3,
           "key": "k", "result": {"v": 1}, "resumed_steps": 0}
    units2, _ = fold_unit_records([ack, poison])
    assert units2["u0"]["result"] == {"v": 1}
    assert not units2["u0"]["poison"]


def test_fold_drain_marker_only_counts_when_last():
    drain = {"t": "drain"}
    lease = {"t": "lease", "unit_id": "u0", "worker": "w0", "epoch": 1,
             "key": "k", "hedge": False}
    assert fold_unit_records([lease, drain])[1] is True
    assert fold_unit_records([drain, lease])[1] is False


# ---- lease state machine (fake clock, direct handle()) -------------------


def test_lease_heartbeat_renew_expire_redispatch_epochs(tmp_path):
    clk = FakeClock()
    cfg, units = _units(1)
    coord = _coord(tmp_path, units, hedge=False, clock=clk)
    try:
        g = _lease(coord, "w0")
        assert g["ok"] and g["epoch"] == 1 and g["checkpoint"] is None
        assert g["unit"]["unit_id"] == "u00000"
        assert g["lease_ttl_s"] == 5.0

        # heartbeat renews: 4s + 4s straddles the original 5s deadline
        clk.advance(4.0)
        hb = coord.handle({"verb": "heartbeat", "worker": "w0",
                           "unit_id": "u00000", "epoch": 1, "steps": 32})
        assert hb["ok"] and not hb.get("lost")
        clk.advance(4.0)
        coord.tick()
        assert coord.stats()["units"][LEASED] == 1  # renewed, still held

        # silence past the TTL: expire -> kill evidence -> PENDING
        clk.advance(6.0)
        coord.tick()
        s = coord.stats()
        assert s["units"][PENDING] == 1
        assert s["counters"]["expired"] == 1

        # re-dispatch bumps the epoch and counts as a redispatch
        g2 = _lease(coord, "w1")
        assert g2["epoch"] == 2
        assert coord.stats()["counters"]["redispatches"] == 1

        # the presumed-dead worker's heartbeat is now stale: lost
        hb2 = coord.handle({"verb": "heartbeat", "worker": "w0",
                            "unit_id": "u00000", "epoch": 1})
        assert hb2["lost"]
        # ...and its old-epoch ack is still ACCEPTED (first-ACK-wins:
        # the unit is deterministic, a slow worker's result counts)
        a = _ack(coord, "w0", g)
        assert a["accepted"]
        assert coord.stats()["units"][DONE] == 1
        assert _lease(coord, "w1").get("done")
    finally:
        coord.close()


def test_idle_reply_when_everything_is_leased(tmp_path):
    clk = FakeClock()
    cfg, units = _units(1)
    coord = _coord(tmp_path, units, hedge=False, clock=clk)
    try:
        assert _lease(coord, "w0")["ok"]
        r = _lease(coord, "w1")
        assert r.get("idle") and r["retry_after_s"] == 1.0  # ttl/5
        hb = coord.handle({"verb": "heartbeat", "worker": "w1",
                           "unit_id": "nope", "epoch": 1})
        assert hb["lost"]  # unknown unit
    finally:
        coord.close()


def test_poison_needs_distinct_workers(tmp_path):
    clk = FakeClock()
    cfg, units = _units(1)
    coord = _coord(tmp_path, units, hedge=False, clock=clk,
                   poison_threshold=2)
    try:
        # the SAME worker dying twice is one distinct killer: no poison
        for _ in range(2):
            assert _lease(coord, "w0")["ok"]
            clk.advance(6.0)
            coord.tick()
        assert coord.stats()["units"][PENDING] == 1

        # a second distinct killer crosses the threshold: quarantine
        assert _lease(coord, "w1")["ok"]
        clk.advance(6.0)
        coord.tick()
        s = coord.stats()
        assert s["units"][POISON] == 1
        assert s["counters"]["poisoned"] == 1
        assert coord.done  # the campaign proceeds without the unit
        assert _lease(coord, "w2").get("done")
        r = coord.results()[0]
        assert r["state"] == POISON and r["kills"] == ["w0", "w1"]
    finally:
        coord.close()


def test_hedge_grants_twin_and_first_ack_wins(tmp_path):
    clk = FakeClock()
    cfg, units = _units(1)
    coord = _coord(tmp_path, units, hedge=True, clock=clk)
    try:
        g0 = _lease(coord, "w0")
        g1 = _lease(coord, "w1")  # PENDING dry, w0 in flight: hedge twin
        assert g1["hedge"] and g1["unit"]["unit_id"] == "u00000"
        assert g1["epoch"] == 2 and coord.stats()["counters"]["hedges"] == 1
        # one twin at a time — a third worker idles
        assert _lease(coord, "w2").get("idle")

        a1 = _ack(coord, "w1", g1, result={"v": "winner"})
        assert a1["accepted"]
        a0 = _ack(coord, "w0", g0, result={"v": "loser"})
        assert a0["duplicate"] and not a0["accepted"]
        s = coord.stats()
        assert s["counters"]["acks"] == 1 and s["counters"]["duplicates"] == 1
        assert coord.results()[0]["result"] == {"v": "winner"}
    finally:
        coord.close()


def test_ack_key_mismatch_is_rejected(tmp_path):
    cfg, units = _units(1)
    coord = _coord(tmp_path, units, hedge=False)
    try:
        g = _lease(coord, "w0")
        bad = coord.handle({
            "verb": "ack", "worker": "w0", "unit_id": "u00000",
            "epoch": g["epoch"], "key": "deadbeefdeadbeef",
            "result": {}, "resumed_steps": 0,
        })
        assert not bad["ok"] and "key mismatch" in bad["error"]["detail"]
        assert coord.stats()["units"][LEASED] == 1  # nothing accepted
    finally:
        coord.close()


# ---- restart recovery ----------------------------------------------------


def test_restart_replays_ledger_and_readopts_inflight_lease(tmp_path):
    clk = FakeClock()
    cfg, units = _units(2)
    pool_dir = str(tmp_path / "pool")
    c1 = PoolCoordinator(units, pool_dir, hedge=False, clock=clk)
    g0 = _lease(c1, "w0")
    assert _ack(c1, "w0", g0, result={"v": "kept"})["accepted"]
    g1 = _lease(c1, "w1")  # in flight at "crash"
    assert g1["unit"]["unit_id"] == "u00001"
    c1.close()  # no drain: simulates kill -9 (the ledger IS the state)

    _, units_again = _units(2)
    c2 = PoolCoordinator(units_again, pool_dir, hedge=False, clock=clk)
    try:
        assert c2.recovered["results_adopted"] == 1
        assert c2.recovered["stale_entries"] == 0
        assert not c2.recovered["clean_drain"]
        s = c2.stats()
        assert s["units"][DONE] == 1 and s["units"][PENDING] == 1
        assert c2.results()[0]["result"] == {"v": "kept"}

        # the worker that outlived the coordinator heartbeats its current
        # epoch: the lease is RE-ADOPTED instead of re-dispatched
        hb = c2.handle({"verb": "heartbeat", "worker": "w1",
                        "unit_id": "u00001", "epoch": g1["epoch"]})
        assert hb["ok"] and not hb.get("lost")
        assert c2.stats()["units"][LEASED] == 1
        assert _ack(c2, "w1", g1)["accepted"]
        assert c2.done
    finally:
        c2.close()


def test_restart_rejects_ledger_of_a_changed_campaign(tmp_path):
    cfg, units = _units(1)
    pool_dir = str(tmp_path / "pool")
    c1 = PoolCoordinator(units, pool_dir, hedge=False)
    assert _ack(c1, "w0", _lease(c1, "w0"))["accepted"]
    c1.close()

    # same unit ids, different workload: the journaled result must NOT
    # be inherited by a campaign it doesn't describe
    _, changed = _units(1, synth=CRASH_SYNTH)
    c2 = PoolCoordinator(changed, pool_dir, hedge=False)
    try:
        assert c2.recovered["results_adopted"] == 0
        assert c2.recovered["stale_entries"] >= 1
        assert c2.stats()["units"][PENDING] == 1
    finally:
        c2.close()


# ---- socket front door ---------------------------------------------------


def test_socket_roundtrip_lease_status_metrics(tmp_path):
    cfg, units = _units(1)
    coord = _coord(tmp_path, units, hedge=False)
    coord.start()
    try:
        sock = coord.socket_path
        g = request(sock, {"verb": "lease", "worker": "w0"})
        assert g["ok"] and g["unit"]["unit_id"] == "u00000"
        st = request(sock, {"verb": "status"})
        assert st["units"][LEASED] == 1 and st["workers_seen"] == ["w0"]
        m = request(sock, {"verb": "metrics"})
        assert 'primetpu_pool_units{state="LEASED"} 1' in m["text"]
        assert "primetpu_pool_leases_total 1" in m["text"]
        bad = request(sock, {"verb": "frobnicate"})
        assert not bad["ok"] and "unknown verb" in bad["error"]["detail"]
    finally:
        coord.close()


# ---- worker execution ----------------------------------------------------


def test_worker_campaign_bit_exact_vs_inprocess(tmp_path):
    """One worker drains a 2-unit campaign over the real socket; every
    deterministic result field matches the in-process sweep path."""
    cfg, units = _units(2)
    coord = _coord(tmp_path, units, lease_ttl_s=30.0)
    coord.start()
    try:
        w = PoolWorker(coord.socket_path, "w0", reconnect_timeout_s=10.0)
        assert w.run() == 0
        assert w.units_done == 2 and coord.done
        for u, r in zip(units, coord.results()):
            assert r["state"] == DONE
            d = r["result"]["detail"]
            assert r["result"]["metric"] == "simulated_MIPS"
            ref = _reference_detail(cfg, u)
            for k, v in ref.items():
                assert d[k] == v, (u["unit_id"], k)
            assert d["fleet_index"] == u["index"]
        # results are durable; unit checkpoints are gone (dead weight)
        assert os.listdir(os.path.join(coord.pool_dir, "units")) == []
    finally:
        coord.close()


def test_worker_crash_redispatch_resumes_checkpoint_bit_exact(tmp_path):
    """The acceptance property in miniature: worker A dies (simulated
    SIGKILL) after 2 committed chunks; the lease expires; worker B
    re-leases the unit, resumes from A's element checkpoint (not step 0),
    and the final result is bit-exact vs an uncrashed run."""
    clk = FakeClock()
    cfg, units = _units(1, synth=CRASH_SYNTH, chunk_steps=8)
    coord = _coord(tmp_path, units, hedge=False, clock=clk)
    coord.start()
    try:
        wa = PoolWorker(coord.socket_path, "wA", reconnect_timeout_s=10.0,
                        crash_after_chunks=2, simulate_crash=True)
        g = request(coord.socket_path, {"verb": "lease", "worker": "wA"})
        with pytest.raises(SimulatedCrash):
            wa.run_unit(g)
        ckpt = os.path.join(coord.pool_dir, "units", "u00000.npz")
        assert os.path.exists(ckpt)  # chunk 2 committed before the kill

        clk.advance(6.0)  # heartbeats stopped with wA: lease expires
        coord.tick()
        s = coord.stats()
        assert s["counters"]["expired"] >= 1
        assert s["units"][PENDING] == 1

        wb = PoolWorker(coord.socket_path, "wB", reconnect_timeout_s=10.0)
        assert wb.run() == 0
        r = coord.results()[0]
        assert r["state"] == DONE
        assert r["resumed_steps"] > 0  # resumed mid-flight, not step 0
        assert r["kills"] == ["wA"]
        assert coord.stats()["counters"]["redispatches"] == 1
        ref = _reference_detail(cfg, units[0])
        for k, v in ref.items():
            assert r["result"]["detail"][k] == v, k
        assert not os.path.exists(ckpt)  # reaped on ack
    finally:
        coord.close()


def test_worker_acks_quarantined_result_for_bad_unit(tmp_path):
    """A unit that can't even materialize must not kill the worker: it
    acks a structured quarantined result and the campaign moves on."""
    cfg, units = _units(1)
    units[0]["synth"] = "no_such_kernel:oops=1"
    units[0]["key"] = unit_key(units[0])
    coord = _coord(tmp_path, units)
    coord.start()
    try:
        w = PoolWorker(coord.socket_path, "w0", reconnect_timeout_s=10.0)
        assert w.run() == 0
        r = coord.results()[0]
        assert r["state"] == DONE
        assert r["result"]["metric"] == "quarantined"
        assert r["result"]["detail"]["status"] == "quarantined"
        assert r["result"]["detail"]["error"]["type"]
    finally:
        coord.close()


# ---- observability -------------------------------------------------------


def test_pool_events_reach_trace_and_report_section(tmp_path):
    import numpy as np

    from primesim_tpu.obs import Recorder
    from primesim_tpu.stats.counters import COUNTER_NAMES
    from primesim_tpu.stats.report import render_report

    clk = FakeClock()
    rec = Recorder("full")
    cfg, units = _units(1)
    coord = _coord(tmp_path, units, hedge=False, clock=clk, obs=rec)
    try:
        g = _lease(coord, "w0")
        clk.advance(6.0)
        coord.tick()  # expire
        g2 = _lease(coord, "w1")  # redispatch
        _ack(coord, "w1", g2)
        kinds = {e["name"] for e in rec.trace.events if e["ph"] == "i"}
        assert {"lease", "expire", "redispatch", "ack"} <= kinds

        counters = {k: np.zeros(4, dtype=np.int64) for k in COUNTER_NAMES}
        text = render_report(cfg, counters, np.zeros(4, dtype=np.int64),
                             pool=coord.pool_report())
        lines = text.splitlines()
        assert "POOL" in lines

        def row(label):
            return next(l for l in lines if l.startswith(f"  {label}"))

        assert row("units done").endswith(" 1")
        assert row("expired leases").endswith(" 1")
        assert row("redispatches").endswith(" 1")
        assert row("units poisoned").endswith(" 0")
    finally:
        coord.close()


# ---- subprocess acceptance (real processes, real SIGKILL) ----------------


def _write_cfg(tmp_path):
    p = str(tmp_path / "cfg.json")
    with open(p, "w") as f:
        f.write(_cfg().to_json())
    return p


def _sweep_cmd(cfg_path, synths, extra=()):
    cmd = [sys.executable, "-m", "primesim_tpu.cli", "sweep", cfg_path,
           "--chunk-steps", "16"]
    for s in synths:
        cmd += ["--synth", s]
    return cmd + list(extra)


def _parse_elements(out):
    rows = [json.loads(ln) for ln in out.splitlines() if ln.strip()]
    elems = [r for r in rows if r["metric"] == "simulated_MIPS"]
    for r in elems:  # wall-clock fields legitimately differ
        r.pop("value")
        r["detail"].pop("wall_s")
    return sorted(elems, key=lambda r: r["detail"]["fleet_index"])


def test_pooled_sweep_parent_never_initialises_a_backend(tmp_path):
    """One process per chip: a parent that initialised a JAX backend
    would hold the chip its workers need. The `sweep --workers` parent —
    argument parsing, mesh-shape validation for `--devices`, coordinator,
    spawning — must finish a campaign without ever creating a backend."""
    code = (
        "import sys\n"
        "from primesim_tpu.cli import main\n"
        "rc = main(sys.argv[1:])\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge.backends_are_initialized(), 'parent touched JAX'\n"
        "sys.exit(rc)\n"
    )
    argv = _sweep_cmd(  # minus its `python -m primesim_tpu.cli` prefix
        _write_cfg(tmp_path), [SMALL_SYNTH.format(0)],
        extra=("--workers", "1", "--devices", "2"),
    )[3:]
    out = subprocess.run(
        [sys.executable, "-c", code, *argv],
        cwd=REPO, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert '"units_done": 1' in out.stdout


@pytest.mark.slow
def test_subprocess_worker_kill9_campaign_bit_exact(tmp_path):
    """Chaos acceptance: one of three workers SIGKILLs itself mid-unit
    (the crash hook the CI pool-chaos job uses); the campaign completes
    with per-element JSON identical to the single-process sweep, and the
    pool report shows the recovery actually happened."""
    cfg_path = _write_cfg(tmp_path)
    synths = [SMALL_SYNTH.format(i) for i in range(4)]
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}

    base = subprocess.run(
        _sweep_cmd(cfg_path, synths), cwd=REPO, env=env,
        capture_output=True, text=True, timeout=420,
    )
    assert base.returncode == 0, base.stderr[-2000:]

    chaos = subprocess.run(
        _sweep_cmd(cfg_path, synths, extra=(
            "--workers", "3", "--lease-ttl", "2.0", "--hedge", "off",
            "--pool-dir", str(tmp_path / "pool"),
        )),
        cwd=REPO, env={**env, "PRIMETPU_POOL_CRASH": "w0:2"},
        capture_output=True, text=True, timeout=420,
    )
    assert chaos.returncode == 0, chaos.stderr[-2000:]

    assert _parse_elements(chaos.stdout) == _parse_elements(base.stdout)
    agg = [json.loads(ln) for ln in chaos.stdout.splitlines()
           if '"fleet_aggregate_MIPS"' in ln]
    pool = agg[0]["detail"]["pool"]
    assert pool["units_done"] == 4 and pool["units_poisoned"] == 0
    # w0's suicide must be visible as expiry -> redispatch (hedging is
    # off, so nothing rescues the unit early)
    assert pool["expired_leases"] >= 1
    assert pool["redispatches"] >= 1


@pytest.mark.slow
def test_subprocess_coordinator_kill9_restart_resumes(tmp_path):
    """Durability acceptance: SIGKILL the whole campaign (coordinator +
    workers share a process group), rerun the identical command with the
    same --pool-dir, and the restart must replay the ledger and resume
    interrupted units from their checkpoints — committed chunks are
    never re-simulated (visible as resumed_steps > 0 in the ack)."""
    cfg_path = _write_cfg(tmp_path)
    pool_dir = str(tmp_path / "pool")
    slow = "fft_like:n_phases=8,points_per_core=256,ins_per_mem=4,seed={}"
    cmd = _sweep_cmd(cfg_path, [slow.format(1), slow.format(2)], extra=(
        "--workers", "1", "--lease-ttl", "3.0", "--pool-dir", pool_dir,
    ))
    cmd[cmd.index("--chunk-steps") + 1] = "8"
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}

    proc = subprocess.Popen(cmd, cwd=REPO, env=env,
                            stdout=subprocess.DEVNULL,
                            stderr=subprocess.DEVNULL,
                            start_new_session=True)
    try:
        deadline = time.monotonic() + 240
        units_dir = os.path.join(pool_dir, "units")
        while time.monotonic() < deadline:
            if os.path.isdir(units_dir) and os.listdir(units_dir):
                break
            assert proc.poll() is None, "campaign finished before the kill"
            time.sleep(0.5)
        else:
            pytest.fail("no unit checkpoint appeared before the kill")
        time.sleep(3.0)  # let a few more chunks commit
        os.killpg(proc.pid, signal.SIGKILL)
        proc.wait(timeout=30)
    finally:
        if proc.poll() is None:
            os.killpg(proc.pid, signal.SIGKILL)

    redo = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                          text=True, timeout=540)
    assert redo.returncode == 0, redo.stderr[-2000:]
    assert len(_parse_elements(redo.stdout)) == 2

    from primesim_tpu.serve.journal import JobJournal

    records, _ = JobJournal(pool_dir).replay()
    folded, _ = fold_unit_records(records)
    assert any(u["result"] is not None and u["resumed_steps"] > 0
               for u in folded.values()), "nothing resumed mid-flight"
