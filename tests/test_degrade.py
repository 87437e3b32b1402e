"""Degraded-mode elasticity (DESIGN.md §26): unified disk-pressure
governance + device-loss recovery.

The ENOSPC tests drive the `disk.preflight` chaos site — a plan event
opens a sustained window during which every free-space probe reports
zero bytes, so the evict -> compact -> backpressure ladder runs on a
healthy filesystem. Each governed write site (journal append, snapshot
rotation, exec/warm cache stores) must degrade without losing an ACKed
record or a committed chunk.

The device-loss tests drive the `devices.revoke` site against sharded
supervised runs; they need more than one visible device, so the
mesh-shrinking assertions skip on a 1-device backend and run for real
in CI under `XLA_FLAGS=--xla_force_host_platform_device_count=8` (the
degrade-chaos job). The slow acceptance test at the bottom needs no
such ambient setup: it forces virtual device counts on its OWN
subprocesses — an 8-device run is SIGKILLed mid-flight and resumed
under 4 visible devices, bit-exact with the unsharded reference.
"""

import json
import os
import signal
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

from primesim_tpu.chaos import plan as CP
from primesim_tpu.chaos import sites as CS
from primesim_tpu.config.machine import MachineConfig, small_test_config
from primesim_tpu.parallel import sharding
from primesim_tpu.util import diskpressure
from primesim_tpu.util.diskpressure import DiskPressureError

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

MULTIDEV = len(jax.devices()) >= 2


def _enospc_plan(calls: int, occurrence: int = 1) -> CP.FaultPlan:
    return CP.FaultPlan(seed=0, events=(
        CP.FaultEvent(site="disk.preflight", occurrence=occurrence,
                      action="enospc_window", args=(("calls", calls),)),
    ))


def _revoke_plan(n: int = 1, occurrence: int = 2) -> CP.FaultPlan:
    return CP.FaultPlan(seed=0, events=(
        CP.FaultEvent(site="devices.revoke", occurrence=occurrence,
                      action="revoke", args=(("n", n),)),
    ))


@pytest.fixture(autouse=True)
def _clean_slate(monkeypatch):
    # a snapshot store registers an evictor for good (sim/supervisor.py), so
    # a worker that ran tests/test_supervisor.py first holds dozens; each
    # burns probes of a chaos `enospc_window`, which then closes before an
    # append of this file is refused. The ladder these tests see is theirs.
    monkeypatch.setattr(diskpressure, "_EVICTORS", {})
    monkeypatch.setattr(diskpressure, "_COMPACTORS", {})
    yield
    CS.deactivate()
    sharding.restore_devices()
    diskpressure.configure(budget_bytes=None)
    from primesim_tpu.sim import exec_cache

    exec_cache.configure(False)


# ---- the disk-pressure core ----------------------------------------------


def test_preflight_passes_with_free_space(tmp_path):
    before = diskpressure.stats["rejections"]
    diskpressure.preflight(str(tmp_path / "x.npz"), 1024)
    assert diskpressure.stats["rejections"] == before


def test_preflight_ladder_then_backpressure(tmp_path):
    """A window wider than one ladder pass rejects with a typed,
    retryable error; once the window drains, the same write passes."""
    CS.install(_enospc_plan(calls=50))
    with pytest.raises(DiskPressureError) as ei:
        diskpressure.preflight(str(tmp_path / "x.npz"), 1024)
    assert ei.value.retry_after_s > 0
    assert "need_bytes" in ei.value.location()
    CS.deactivate()  # window state dies with the runtime
    diskpressure.preflight(str(tmp_path / "x.npz"), 1024)


def test_cache_budget_feeds_prune(tmp_path, monkeypatch):
    """--cache-budget (diskpressure.configure) outranks the env var in
    prune_warm_cache's budget resolution."""
    from primesim_tpu.sim.checkpoint import prune_warm_cache

    root = tmp_path / "warm"
    root.mkdir()
    for i in range(3):
        p = root / (f"{i:064x}" + ".npz")
        p.write_bytes(b"x" * 4096)
        sc = root / (f"{i:064x}" + ".json")
        sc.write_text(json.dumps({"steps": 1}))
        os.utime(p, (i, i))
    monkeypatch.setenv("PRIMETPU_CACHE_MAX_BYTES", str(1 << 30))
    diskpressure.configure(budget_bytes=5000)  # room for one entry
    prune_warm_cache(str(root))
    left = [n for n in os.listdir(root) if n.endswith(".npz")]
    assert len(left) == 1  # env var alone would have kept all three


# ---- ENOSPC at each governed write site ----------------------------------


def test_journal_append_enospc_no_acked_record_lost(tmp_path):
    """Sustained ENOSPC at journal append: the append either lands or
    raises typed backpressure — never a torn/silent loss — and retries
    succeed once the window drains. Every ACKed record replays."""
    from primesim_tpu.serve.journal import JobJournal

    # no compactor: every surviving record must appear verbatim in the
    # replay (a compacting journal may legally FOLD notes away, which is
    # the compaction rung working, not a loss)
    j = JobJournal(str(tmp_path / "j"))
    j.append({"t": "note", "msg": "pre-pressure"})
    CS.install(_enospc_plan(calls=9))
    acked, rejected = [], 0
    for i in range(10):
        rec = {"t": "note", "msg": f"r{i}"}
        try:
            j.append(rec)
        except DiskPressureError:
            rejected += 1
            continue  # a real client backs off and retries
        acked.append(rec["msg"])
    CS.deactivate()
    j.append({"t": "note", "msg": "post-pressure"})
    j.close()
    assert rejected > 0 and acked  # both sides of the window exercised
    replayed, dropped = JobJournal(str(tmp_path / "j")).replay()
    assert dropped == 0
    msgs = [r["msg"] for r in replayed if r.get("t") == "note"]
    assert msgs.count("pre-pressure") == 1
    assert msgs.count("post-pressure") == 1
    for m in acked:
        assert msgs.count(m) == 1  # ACKed exactly once, never lost


def test_checkpoint_write_enospc_leaves_no_debris(tmp_path):
    """atomic_save_npz preflights before the temp file exists: a
    rejected snapshot write leaves NO partial artifact, and the same
    write succeeds after the pressure clears."""
    from primesim_tpu.sim.checkpoint import atomic_save_npz

    path = str(tmp_path / "ck" / "snap.npz")
    os.makedirs(os.path.dirname(path))
    CS.install(_enospc_plan(calls=50))
    with pytest.raises(DiskPressureError):
        atomic_save_npz(path, a=np.arange(8))
    CS.deactivate()
    assert os.listdir(os.path.dirname(path)) == []  # no .tmp, no torn npz
    atomic_save_npz(path, a=np.arange(8))
    assert os.path.exists(path)


def test_supervised_run_rides_out_checkpoint_enospc(tmp_path):
    """A supervised run whose snapshot rotations ALL hit disk pressure
    still commits every chunk and finishes bit-exact — the rotation is
    skipped with a disk-pressure log line, never a crash."""
    from primesim_tpu.sim.engine import Engine
    from primesim_tpu.sim.supervisor import RunSupervisor
    from primesim_tpu.trace import synth

    cfg = small_test_config(8, n_banks=4)
    trace = synth.fft_like(8, n_phases=1, points_per_core=12, seed=3)

    ref = Engine(cfg, trace, chunk_steps=32)
    RunSupervisor(ref, handle_signals=False).run()

    CS.install(_enospc_plan(calls=500))  # outlasts every rotation
    eng = Engine(cfg, trace, chunk_steps=32)
    sup = RunSupervisor(eng, snapshot_dir=str(tmp_path / "snaps"),
                        checkpoint_every_chunks=1, handle_signals=False)
    sup.run()
    CS.deactivate()
    assert sup.checkpoints_written == 0
    assert any(kind == "disk-pressure" for _, kind, _ in sup._events_log)
    np.testing.assert_array_equal(
        np.asarray(eng.cycles), np.asarray(ref.cycles))
    for k, v in eng.counters.items():
        np.testing.assert_array_equal(
            np.asarray(v), np.asarray(ref.counters[k]), err_msg=k)


def test_exec_cache_write_enospc_degrades_to_recompile(tmp_path):
    """ENOSPC at the exec-cache store: the run keeps its freshly
    compiled executable (no committed chunk lost), the save degrades to
    a structured fallback warning, and no cache debris lands."""
    from primesim_tpu.sim import exec_cache
    from primesim_tpu.sim.engine import Engine
    from primesim_tpu.trace import synth

    cfg = small_test_config(8, n_banks=4)
    trace = synth.fft_like(8, n_phases=1, points_per_core=12, seed=5)
    ref = Engine(cfg, trace, chunk_steps=32)
    ref.run_chunked(max_steps=10_000_000)

    cache = exec_cache.configure(True, root=str(tmp_path / "exec"))
    CS.install(_enospc_plan(calls=500))
    eng = Engine(cfg, trace, chunk_steps=32)
    eng.run_chunked(max_steps=10_000_000)
    CS.deactivate()
    assert any(w.get("stage") == "save" for w in cache.warnings)
    assert not [n for n in os.listdir(str(tmp_path / "exec"))
                if n.endswith(".tmp")]
    np.testing.assert_array_equal(
        np.asarray(eng.cycles), np.asarray(ref.cycles))


def test_fsck_flags_enospc_debris(tmp_path):
    """fsck: zero-length artifacts and .tmp leftovers are repairable
    orphans; --repair quarantine sweeps them aside (never deletes)."""
    from primesim_tpu.analysis.fsck import run_fsck

    (tmp_path / "empty.npz").write_bytes(b"")
    (tmp_path / "half.tmp").write_bytes(b"torn")
    rep = run_fsck(str(tmp_path))
    kinds = {(f.kind, f.path) for f in rep.findings}
    assert ("orphan", "empty.npz") in kinds
    assert ("orphan", "half.tmp") in kinds
    assert all(f.repairable for f in rep.findings)
    rep2 = run_fsck(str(tmp_path), repair="quarantine")
    assert sorted(rep2.quarantined) == ["empty.npz", "half.tmp"]
    assert (tmp_path / ".fsck-quarantine" / "empty.npz").exists()


# ---- device-loss recovery -------------------------------------------------


def test_classify_device_loss():
    from primesim_tpu.parallel.sharding import DeviceMeshError
    from primesim_tpu.sim.supervisor import classify_failure

    assert classify_failure(RuntimeError("DEVICE_LOST: chip 3")) == \
        "device_loss"
    # DeviceMeshError IS a ValueError; it must classify as device loss,
    # not fall into the never-retry programming-error guard
    assert classify_failure(
        DeviceMeshError("mesh broke", devices=4, visible=2)
    ) == "device_loss"
    assert classify_failure(ValueError("plain bug")) is None


def test_largest_valid_submesh():
    from primesim_tpu.parallel.sharding import (
        DeviceMeshError,
        largest_valid_submesh,
    )

    cfg = MachineConfig(n_cores=8, n_banks=8)
    assert largest_valid_submesh(cfg, 8) == 8
    assert largest_valid_submesh(cfg, 7) == 4
    assert largest_valid_submesh(cfg, 3) == 2
    assert largest_valid_submesh(cfg, 1) == 1
    with pytest.raises(DeviceMeshError):
        largest_valid_submesh(cfg, 0)
    cfg2 = MachineConfig(n_cores=8, n_banks=4)
    assert largest_valid_submesh(cfg2, 8) == 4  # must divide banks too


@pytest.mark.skipif(not MULTIDEV, reason="needs >= 2 visible devices")
def test_supervisor_reshards_after_device_revocation(tmp_path):
    """Seeded revocation at a chunk boundary: the supervisor re-places
    the newest verified snapshot onto the largest valid smaller mesh
    and finishes bit-exact with the unsharded reference."""
    from primesim_tpu.sim.engine import Engine
    from primesim_tpu.sim.supervisor import RunSupervisor
    from primesim_tpu.trace import synth

    cfg = small_test_config(8, n_banks=8)
    trace = synth.fft_like(8, n_phases=1, points_per_core=12, seed=7)

    ref = Engine(cfg, trace, chunk_steps=32)
    RunSupervisor(ref, handle_signals=False).run()

    n = sharding.largest_valid_submesh(cfg, len(jax.devices()))
    mesh = sharding.tile_mesh(devices=jax.devices()[:n])
    eng = Engine(cfg, trace, chunk_steps=32, mesh=mesh)
    sup = RunSupervisor(eng, snapshot_dir=str(tmp_path / "snaps"),
                        checkpoint_every_chunks=1, handle_signals=False)
    CS.install(_revoke_plan(n=1, occurrence=2))
    sup.run()
    CS.deactivate()
    sharding.restore_devices()
    assert sup.degrade_rungs and \
        sup.degrade_rungs[0].startswith(f"reshard:{n}->")
    assert "degrade_rungs" in sup.summary()
    np.testing.assert_array_equal(
        np.asarray(eng.cycles), np.asarray(ref.cycles))
    for k, v in eng.counters.items():
        np.testing.assert_array_equal(
            np.asarray(v), np.asarray(ref.counters[k]), err_msg=k)


@pytest.mark.skipif(not MULTIDEV, reason="needs >= 2 visible devices")
def test_worker_releases_unit_on_shrunken_mesh():
    """A pool worker with revoked devices re-leases a sharded unit onto
    the largest valid smaller mesh and records the granted size on the
    unit (re-keying its geometry bucket) instead of quarantining."""
    from primesim_tpu.pool.worker import PoolWorker

    cfg = small_test_config(8, n_banks=8)
    n = sharding.largest_valid_submesh(cfg, len(jax.devices()))
    w = PoolWorker(socket_path="/nonexistent.sock", worker_id="tw")
    unit = {"devices": n}
    mesh = w._unit_mesh(unit, cfg)
    assert "_granted_devices" not in unit  # full grant, no degrade
    assert len(mesh.devices.flatten()) == n

    sharding.revoke_devices([jax.devices()[n - 1].id])
    unit2 = {"devices": n}
    mesh2 = w._unit_mesh(unit2, cfg)
    granted = unit2["_granted_devices"]
    assert granted == sharding.largest_valid_submesh(cfg, n - 1)
    assert len(mesh2.devices.flatten()) == granted
    assert w.units_degraded == 1
    sharding.restore_devices()


def test_capacity_campaign_invariant_g():
    """A small fixed-seed capacity_loss campaign must fire faults and
    hold invariant G (single-device backends exercise the ENOSPC half;
    multi-device backends the revocation half too)."""
    from primesim_tpu.chaos import campaign as C

    rep = C.run_campaign(n_trials=3, seed0=77,
                         classes=("capacity_loss",), max_events=3)
    assert rep["ok"], rep["violations"]
    assert rep["trials"] == 3
    assert rep["fired_events"] > 0


# ---- acceptance: SIGKILL an 8-device run, resume on 4 --------------------


def _run_cli(argv, n_devices, wait_snapshot_dir=None, kill=None):
    """Run the CLI in a subprocess under a FORCED virtual device count;
    optionally SIGKILL it once a snapshot exists. Returns (rc, stdout)."""
    code = (
        "import sys; from primesim_tpu.cli import main; "
        "sys.exit(main(%r))" % (argv,)
    )
    env = dict(
        os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
        XLA_FLAGS=f"--xla_force_host_platform_device_count={n_devices}",
    )
    proc = subprocess.Popen([sys.executable, "-c", code], env=env,
                            stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE)
    try:
        if kill is not None:
            from primesim_tpu.sim.supervisor import SnapshotStore

            deadline = time.time() + 180
            while time.time() < deadline:
                if (os.path.isdir(wait_snapshot_dir)
                        and SnapshotStore(wait_snapshot_dir).snapshots()):
                    break
                if proc.poll() is not None:
                    break
                time.sleep(0.05)
            if proc.poll() is None:
                proc.send_signal(kill)
        out, err = proc.communicate(timeout=300)
    finally:
        proc.kill()
    return proc.returncode, out.decode(), err.decode()


def _run_summary(out):
    """The run summary JSON line (--exec-cache appends a stats line
    after it, so 'last JSON line' is not the summary)."""
    for ln in reversed(out.splitlines()):
        if ln.startswith("{"):
            det = json.loads(ln).get("detail") or {}
            if "instructions" in det:
                return det
    raise AssertionError("no run-summary JSON line in CLI output")


@pytest.mark.slow
def test_kill_8dev_resume_4dev_bit_exact(tmp_path):
    """The headline acceptance: an 8-device sharded supervised run is
    SIGKILLed mid-flight; a restart that can only see 4 devices resumes
    from the surviving snapshot onto the smaller mesh and finishes
    bit-exact with the unsharded reference — with --exec-cache and
    --attest riding along intact."""
    cfg_path = str(tmp_path / "m.json")
    with open(cfg_path, "w") as f:
        f.write(MachineConfig(n_cores=8, n_banks=8).to_json())
    spec = "fft_like:n_phases=6,points_per_core=96"
    ckdir = str(tmp_path / "ck")
    cache = str(tmp_path / "cache")
    os.environ.setdefault("PRIMETPU_CACHE_DIR", cache)
    base = ["run", cfg_path, "--synth", spec, "--chunk-steps", "8",
            "--checkpoint-dir", ckdir, "--checkpoint-every", "1",
            "--exec-cache", "on", "--attest", "chain"]

    rc, out, err = _run_cli(base + ["--devices", "8"], n_devices=8,
                            wait_snapshot_dir=ckdir, kill=signal.SIGKILL)
    if rc == 0:
        pytest.skip("run finished before SIGKILL could land")
    assert rc == -signal.SIGKILL

    rc, out, err = _run_cli(base + ["--devices", "4", "--resume"],
                            n_devices=4)
    assert rc == 0, err[-2000:]
    resumed = _run_summary(out)
    assert resumed.get("resumed_from"), "resume did not use the snapshot"

    rc, out, err = _run_cli(
        ["run", cfg_path, "--synth", spec, "--chunk-steps", "8"],
        n_devices=1,
    )
    assert rc == 0, err[-2000:]
    ref = _run_summary(out)
    assert resumed["instructions"] == ref["instructions"]
    assert resumed["max_core_cycles"] == ref["max_core_cycles"]
