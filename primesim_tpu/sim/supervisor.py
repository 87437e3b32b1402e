"""Resilient execution layer — RunSupervisor (DESIGN.md §10).

PriME's value is long campaigns: thousand-core configs and parameter
sweeps that run for hours. At that scale the limiting factor is not peak
MIPS but surviving the failures the fleet WILL throw at a long run —
preemption (TPU pods are preemptible by default), device OOM on an
over-ambitious chunk size, transient runtime errors, corrupt input
traces in a thousand-element sweep, and torn checkpoint files from the
previous crash. `RunSupervisor` wraps any of the three engines (solo
`Engine`, windowed `StreamEngine`, batched `FleetEngine`) and drives it
chunk by committed chunk with:

- **rotating atomic snapshots** — `ckpt-<seq>.npz` files written through
  `checkpoint.atomic_save_npz` (tmp + fsync + `os.replace`, per-array
  CRC32 manifest); `resume()` walks them newest-first and falls back
  past any that raise `CheckpointCorrupt`, so one torn file never
  strands a run. Cadence: every K committed chunks and/or W
  wall-seconds.
- **preemption handling** — SIGTERM/SIGINT set a flag; at the next
  committed chunk boundary the supervisor checkpoints and raises
  `Preempted`. The engine's chunk boundary is already a consistent cut,
  so the resumed run is bit-exact with an uninterrupted one
  (tests/test_supervisor.py).
- **retry with exponential backoff + graceful degradation** — failures
  whose text carries a transient gRPC-style status (UNAVAILABLE,
  DEADLINE_EXCEEDED, ...) are retried with doubling backoff; OOM
  (RESOURCE_EXHAUSTED) first halves `chunk_steps` (chunking only
  changes the drain/rebase cadence, never results); after
  `max_retries` the supervisor tries moving the run to the CPU backend
  once before giving up. Every decision lands in the run log
  (`log_lines()`, rendered into the report).
- **post-chunk invariant guard** — `--guard=off|warn|fail` runs
  `validate.check_chunk_invariants` (MESI/directory consistency, clock
  window, monotone counters) on every committed chunk.
- **fleet fault isolation** — `build_fleet_isolated` validates every
  element (trace loadable, core count, overrides, barrier ids) BEFORE
  batching and quarantines bad ones with their typed error, so one
  malformed element costs one JSON line, not the whole sweep.
- **chaos mode** — when the wrapped config arms ARCHITECTURAL fault
  injection (primesim_tpu.faults, DESIGN.md §12) the supervisor logs
  the armed schedule and every fault-counter movement at chunk
  boundaries; snapshots carry the fault state (checkpoint format v5),
  so a chaos run preempted mid-fault resumes bit-exactly.
"""

from __future__ import annotations

import json
import os
import re
import signal
import sys
import time

import numpy as np

from ..chaos import sites as chaos_sites
from ..stats.counters import COUNTER_NAMES
from .checkpoint import CheckpointCorrupt
from .validate import check_chunk_invariants


class Preempted(RuntimeError):
    """A SIGTERM/SIGINT arrived mid-run; the supervisor committed the
    current chunk, wrote a snapshot (`.checkpoint`, None when no
    snapshot dir was configured), and stopped cleanly. Rerun with
    `--resume` to continue bit-exactly."""

    def __init__(self, message: str, checkpoint: str | None = None,
                 signum: int | None = None):
        super().__init__(message)
        self.checkpoint = checkpoint
        self.signum = signum


class GuardViolation(RuntimeError):
    """`--guard=fail`: a post-chunk invariant check failed. The run
    stopped BEFORE checkpointing the bad state — the newest snapshot
    predates the violation."""


# Failure classification is textual by design: the JAX runtime surfaces
# device errors as XlaRuntimeError (jaxlib version-dependent import
# path) whose message embeds the gRPC-style status name.
_OOM_MARKERS = ("RESOURCE_EXHAUSTED", "Out of memory", "out of memory")
_TRANSIENT_MARKERS = (
    "UNAVAILABLE",
    "DEADLINE_EXCEEDED",
    "ABORTED",
    "INTERNAL",
    "CANCELLED",
    "failed to connect",
    "Socket closed",
    # typed admission backpressure from util/diskpressure — the window
    # heals; back off and retry rather than kill the run
    "DiskPressureError",
)
# a device dropping out of the mesh: the runtime's own phrasing on real
# hardware, the typed mesh validator, and the chaos-injected synthetic
_DEVICE_LOSS_MARKERS = (
    "DEVICE_LOST",
    "device lost",
    "Device lost",
    "device unhealthy",
    "DeviceMeshError",
    "chip unreachable",
    "heartbeat timeout on device",
)


def classify_failure(exc: BaseException) -> str | None:
    """'device_loss' | 'oom' | 'transient' | None (permanent) for an
    engine dispatch failure. Deliberate errors (ValueError config/trace
    mismatches, AssertionError invariants, KeyboardInterrupt) are never
    retried — but device loss is checked FIRST, because the typed
    DeviceMeshError a vanished mesh raises is a ValueError, and it is
    precisely the recoverable case the reshard ladder exists for."""
    if isinstance(exc, (KeyboardInterrupt, SystemExit)):
        return None
    text = f"{type(exc).__name__}: {exc}"
    if any(m in text for m in _DEVICE_LOSS_MARKERS):
        return "device_loss"
    if isinstance(exc, (AssertionError, ValueError)):
        return None
    if any(m in text for m in _OOM_MARKERS):
        return "oom"
    if any(m in text for m in _TRANSIENT_MARKERS):
        return "transient"
    return None


class JobContext:
    """Per-JOB supervision context for the serving daemon (serve/):
    the retry-with-backoff policy RunSupervisor applies per chunk,
    re-scoped to one job's whole lifetime. The scheduler consults it
    whenever the job's element fails (batch dispatch error attributed to
    the job, admission failure, guard violation): `next_retry(exc)`
    returns the backoff delay in seconds for another attempt, or None
    when the job must move to a terminal state instead (permanent error,
    or the retry budget is spent). Attempts and every decision are
    recorded so the job's journal/terminal record carries the audit
    trail, mirroring RunSupervisor.log_lines()."""

    def __init__(self, max_retries: int = 2, backoff_s: float = 0.5):
        self.max_retries = int(max_retries)
        self.backoff_s = float(backoff_s)
        self.attempts = 0
        self.log: list[str] = []

    def next_retry(self, exc: BaseException) -> float | None:
        kind = classify_failure(exc)
        if kind is None:
            self.log.append(f"permanent: {type(exc).__name__}: {exc}")
            return None
        if self.attempts >= self.max_retries:
            self.log.append(
                f"give-up: {kind} failure persisted after "
                f"{self.max_retries} retries: {exc}"
            )
            return None
        self.attempts += 1
        delay = min(self.backoff_s * (2 ** (self.attempts - 1)), 30.0)
        self.log.append(
            f"retry {self.attempts}/{self.max_retries} after {kind} "
            f"failure ({exc}); backoff {delay:.2f}s"
        )
        return delay


_SNAP_RE = re.compile(r"ckpt-(\d{8})\.npz")


class SnapshotStore:
    """Rotating checkpoint directory: `ckpt-<seq:08d>.npz`, newest wins,
    oldest pruned past `keep`. Sequence numbers only grow (they restart
    from the newest surviving file on resume), so "latest" is a pure
    filename sort — no mtime trust."""

    def __init__(self, directory: str, keep: int = 3):
        self.dir = str(directory)
        self.keep = max(1, int(keep))
        os.makedirs(self.dir, exist_ok=True)
        # disk-pressure rung 1 (after caches, before backpressure):
        # rotated snapshots are droppable down to the newest one — the
        # resume anchor itself is never evicted
        from ..util import diskpressure

        diskpressure.register_evictor(
            f"snapshots:{self.dir}", self._evict_rotated, priority=1
        )

    def _evict_rotated(self, need_bytes: int) -> int:
        removed = 0
        for p in self.snapshots()[1:]:
            try:
                os.unlink(p)
                removed += 1
            except OSError:
                pass
        return removed

    def snapshots(self) -> list[str]:
        """Snapshot paths, newest (highest sequence) first."""
        found = []
        for name in os.listdir(self.dir):
            m = _SNAP_RE.fullmatch(name)
            if m:
                found.append((int(m.group(1)), os.path.join(self.dir, name)))
        return [p for _, p in sorted(found, reverse=True)]

    def save(self, save_fn) -> str:
        """Write the next snapshot via `save_fn(path)` (the engines'
        `save_checkpoint`, already atomic), then prune."""
        snaps = self.snapshots()
        seq = (
            int(_SNAP_RE.fullmatch(os.path.basename(snaps[0])).group(1)) + 1
            if snaps
            else 1
        )
        path = os.path.join(self.dir, f"ckpt-{seq:08d}.npz")
        save_fn(path)
        for p in self.snapshots()[self.keep:]:
            try:
                os.unlink(p)
            except OSError:
                pass
        return path


class RunSupervisor:
    """Drive an engine to completion chunk by chunk, surviving what the
    fused `run()` paths cannot (module docstring). The wrapped engine is
    advanced through its own public stepping surface (`run_steps` /
    `_advance_window`), so supervised results are bit-exact with
    unsupervised ones — supervision changes WHEN work is committed,
    never what is computed.

    `on_chunk(supervisor)` fires after every committed chunk, before the
    guard/preemption checks — the deterministic injection point the
    crash-recovery tests use (`os.kill` from the callback lands the
    signal at an exact chunk boundary)."""

    def __init__(
        self,
        engine,
        snapshot_dir: str | None = None,
        keep_snapshots: int = 3,
        checkpoint_every_chunks: int = 0,
        checkpoint_every_s: float = 0.0,
        guard: str = "off",
        max_retries: int = 4,
        backoff_s: float = 0.5,
        handle_signals: bool = True,
        on_chunk=None,
        obs=None,
    ):
        if guard not in ("off", "warn", "fail"):
            raise ValueError(f"guard must be off|warn|fail, got {guard!r}")
        self.engine = engine
        self.kind = (
            "stream"
            if hasattr(engine, "_advance_window")
            else "fleet" if hasattr(engine, "elem_cfgs") else "solo"
        )
        self.store = (
            SnapshotStore(snapshot_dir, keep_snapshots)
            if snapshot_dir
            else None
        )
        self.checkpoint_every_chunks = int(checkpoint_every_chunks)
        self.checkpoint_every_s = float(checkpoint_every_s)
        self.guard = guard
        self.max_retries = int(max_retries)
        self.backoff_s = float(backoff_s)
        self.handle_signals = handle_signals
        self.on_chunk = on_chunk
        # telemetry sink (obs.Recorder) — every supervision event that
        # lands in the RESILIENCE audit trail is mirrored onto the
        # flight recorder's "supervisor" timeline row
        self.obs = obs
        self.committed = 0  # chunks committed under this supervisor
        self.retries = 0
        self.guard_warnings = 0
        self.checkpoints_written = 0
        self.resumed_from: str | None = None
        self.stalled_elements: list[int] = []  # fleet: budget-exhausted
        self._events_log: list[tuple[float, str, str]] = []
        self._t0 = time.monotonic()
        self._preempt: int | None = None
        self._prev_handlers: dict = {}
        self._prev_totals: dict[str, int] | None = None
        self._cpu_fallback_done = False
        self._stream_finished = False
        # which device-loss ladder rungs fired, in order ("reshard:8->4",
        # "cpu-fallback") — surfaced in summary() and the RESILIENCE log
        self.degrade_rungs: list[str] = []
        # chaos mode (DESIGN.md §12): when the wrapped engine's config
        # arms fault injection, the supervisor narrates every fault the
        # machine absorbs into the RESILIENCE audit trail
        cfg = getattr(engine, "cfg", None)
        self._chaos = bool(getattr(cfg, "faults_enabled", False))
        self._fault_seen: dict[str, int] = {}

    # ---- logging --------------------------------------------------------

    def _log(self, kind: str, msg: str) -> None:
        self._events_log.append((time.monotonic() - self._t0, kind, msg))
        if self.obs is not None:
            self.obs.supervisor_event(kind, msg)

    def log_lines(self) -> list[str]:
        """Human-readable supervision log (rendered into the report)."""
        return [
            f"[+{t:7.1f}s] {kind}: {msg}" for t, kind, msg in self._events_log
        ]

    def summary(self) -> dict:
        return {
            "supervised": True,
            "committed_chunks": self.committed,
            "checkpoints_written": self.checkpoints_written,
            "resumed_from": self.resumed_from,
            "retries": self.retries,
            "guard": self.guard,
            "guard_warnings": self.guard_warnings,
            "stalled_elements": self.stalled_elements,
            "degrade_rungs": list(self.degrade_rungs),
        }

    # ---- snapshots ------------------------------------------------------

    def checkpoint(self) -> str | None:
        """Write the next rotating snapshot (None without a store).

        Disk pressure that survives the whole evict+compact ladder skips
        THIS rotation instead of killing the run — a wider resume window
        is strictly better than no run at all."""
        if self.store is None:
            return None
        from ..util.diskpressure import DiskPressureError

        try:
            path = self.store.save(self.engine.save_checkpoint)
        except DiskPressureError as e:
            self._log("disk-pressure", f"snapshot skipped: {e}")
            return None
        self.checkpoints_written += 1
        self._log("checkpoint", os.path.basename(path))
        return path

    def resume(self) -> str | None:
        """Restore the newest VALID snapshot into the engine.

        Corrupt snapshots (torn write, failed CRC) are skipped with a
        log entry and the next-newest is tried; config/trace mismatches
        are real errors and propagate (resuming the wrong run silently
        is worse than dying). Returns the restored path, or None when
        the directory holds no snapshots (fresh start)."""
        if self.store is None:
            raise ValueError("resume() requires a snapshot_dir")
        snaps = self.store.snapshots()
        if not snaps:
            self._log("resume", "no snapshots found; starting fresh")
            return None
        for path in snaps:
            try:
                self.engine.load_checkpoint(path)
            except CheckpointCorrupt as e:
                self._log(
                    "resume-skip",
                    f"{os.path.basename(path)} invalid, trying older ({e})",
                )
                continue
            self.resumed_from = path
            self._log("resume", f"resumed from {os.path.basename(path)}")
            # a forked run's snapshot is self-describing (format v6):
            # surface the provenance in the audit trail so "this element
            # never simulated steps 0..P itself" is on the record
            pre = getattr(self.engine, "prefix_steps", None)
            forked = (
                int(np.asarray(pre).max()) if pre is not None else 0
            )
            if forked > 0:
                self._log(
                    "resume-prefix",
                    f"restored state carries prefix-fork provenance "
                    f"(max prefix_steps={forked})",
                )
            return path
        raise CheckpointCorrupt(
            f"{self.store.dir}: all {len(snaps)} snapshots are corrupt"
        )

    # ---- signals --------------------------------------------------------

    def _on_signal(self, signum, frame) -> None:
        if self._preempt is not None:
            # second signal: the operator is insisting — die now
            raise KeyboardInterrupt
        self._preempt = signum

    def _install_signals(self) -> None:
        if not self.handle_signals:
            return
        for sig in (signal.SIGTERM, signal.SIGINT):
            try:
                self._prev_handlers[sig] = signal.signal(sig, self._on_signal)
            except ValueError:  # not the main thread
                pass

    def _restore_signals(self) -> None:
        for sig, h in self._prev_handlers.items():
            signal.signal(sig, h)
        self._prev_handlers = {}

    # ---- engine surface (kind dispatch) ---------------------------------

    def _done(self) -> bool:
        if self.kind == "stream":
            return self._stream_finished or self.engine.done()
        return self.engine.done()

    def _steps_used(self) -> int:
        if self.kind == "fleet":
            return int(self.engine.steps_run.max())
        return int(self.engine.steps_run)

    def _counter_totals(self) -> dict[str, int]:
        return {
            k: int(np.asarray(v).sum())
            for k, v in self.engine.host_counters.items()
        }

    def _host_snapshot(self) -> dict:
        """References/copies of everything `_advance_chunk` mutates, so a
        failed dispatch can be rolled back before a retry (the device
        computation is functional; only these host fields move)."""
        eng = self.engine
        snap = {
            "state": eng.state,
            "steps_run": (
                eng.steps_run.copy()
                if isinstance(eng.steps_run, np.ndarray)
                else eng.steps_run
            ),
            "cycle_base": (
                eng.cycle_base.copy()
                if isinstance(eng.cycle_base, np.ndarray)
                else eng.cycle_base
            ),
            "host_counters": {k: v.copy() for k, v in eng.host_counters.items()},
            "host_stats": {k: v.copy() for k, v in eng.host_stats.items()},
        }
        if self.kind == "stream":
            snap["cursor"] = eng.cursor.copy()
        if getattr(eng, "attest", None) is not None:
            # the chain must roll back with the state it covers, or a
            # retried chunk would be linked twice
            snap["attest"] = eng.attest.snapshot()
        return snap

    def _host_restore(self, snap: dict) -> None:
        eng = self.engine
        eng.state = snap["state"]
        eng.steps_run = snap["steps_run"]
        eng.cycle_base = snap["cycle_base"]
        eng.host_counters = snap["host_counters"]
        eng.host_stats = snap["host_stats"]
        if self.kind == "stream":
            eng.cursor = snap["cursor"]
        if "attest" in snap and getattr(eng, "attest", None) is not None:
            eng.attest.restore(snap["attest"])
        # any overlapped speculation was made from a state we just rolled
        # away from; the identity check would reject it, this frees it
        getattr(eng, "discard_prefetch", lambda: None)()

    def _chaos_revoke_check(self) -> None:
        """Chaos `capacity_loss` site: at a chunk boundary, revoke
        device(s) from the live pool and raise the synthetic DEVICE_LOST
        the reshard ladder classifies. Enacted here (not inside the
        hook) because only the supervisor knows which devices its
        engine's mesh holds."""
        ev = chaos_sites.device_revoke("devices.revoke")
        if ev is None:
            return
        from ..parallel import sharding

        mesh = getattr(self.engine, "mesh", None)
        healthy_ids = {d.id for d in sharding.healthy_devices()}
        pool = [
            d
            for d in (
                list(mesh.devices.flat)
                if mesh is not None
                else sharding.healthy_devices()
            )
            if d.id in healthy_ids
        ]
        n = min(int(ev.arg("n", 1)), len(pool) - 1)
        if n < 1:
            return  # a single-device run has nothing left to lose
        victims = [d.id for d in pool[-n:]]
        sharding.revoke_devices(victims)
        raise RuntimeError(
            f"DEVICE_LOST: injected revocation of device id(s) {victims}"
        )

    def _advance_chunk(self, budget_left: int) -> int:
        """Advance the engine by one committed chunk; returns steps run
        (stream reports the device loop's count; solo/fleet report their
        chunk size)."""
        self._chaos_revoke_check()
        if self.kind == "stream":
            k, finished = self.engine._advance_window(budget_left)
            self._stream_finished = finished
            return k
        before = self._steps_used()
        self.engine.run_steps(self.engine.chunk_steps)
        return self._steps_used() - before

    # ---- retry / degradation --------------------------------------------

    def _fallback_to_cpu(self, cause: BaseException,
                         unshard: bool = False) -> bool:
        """Last-resort degradation: move the run to a single (CPU)
        device. Returns False when impossible (already fell back, no
        landing device) — the caller then re-raises the original.

        Mesh-sharded engines are refused UNLESS `unshard=True`: on the
        device-loss ladder this is the final rung, entered only after
        resharding onto a smaller mesh has already failed, and it
        collapses the run onto one healthy device (`engine.mesh = None`;
        parity is mesh-invariant, so results are unchanged)."""
        import jax

        from ..parallel import sharding

        if self._cpu_fallback_done:
            return False
        mesh = getattr(self.engine, "mesh", None)
        if mesh is not None and not unshard:
            self._log(
                "degrade", "cannot fall back to CPU: engine is mesh-sharded"
            )
            return False
        if mesh is None and jax.default_backend() == "cpu":
            return False
        healthy_ids = {d.id for d in sharding.healthy_devices()}
        try:
            cpus = [d for d in jax.devices("cpu") if d.id in healthy_ids]
        except RuntimeError:
            cpus = []
        if cpus:
            target = cpus[0]
        elif unshard and mesh is not None and healthy_ids:
            target = sharding.healthy_devices()[0]
        else:
            return False
        if mesh is not None:
            self.engine.mesh = None
            self._log(
                "degrade",
                f"device-loss final rung: unsharding onto single device "
                f"{target.id} after: {cause}",
            )
        else:
            self._log("degrade", f"moving run to CPU backend after: {cause}")
        jax.config.update("jax_default_device", target)
        for attr in ("events", "state"):
            if hasattr(self.engine, attr):
                setattr(
                    self.engine,
                    attr,
                    jax.device_put(getattr(self.engine, attr), target),
                )
        getattr(self.engine, "discard_prefetch", lambda: None)()
        self._cpu_fallback_done = True
        return True

    def _reshard_after_device_loss(self, cause: BaseException) -> bool:
        """First rung of the device-loss ladder: shrink the mesh onto
        the remaining healthy devices and re-place the run there.

        Prefers re-placing the newest verified snapshot through the
        existing cross-mesh loader path (checkpoint loaders re-shard
        restored state onto `engine.mesh` — re-running from a committed
        boundary is deterministic, so the continuation stays bit-exact);
        with no usable snapshot the live host-visible arrays are
        re-sharded in place. Returns False when there is no mesh to
        shrink, no healthy landing mesh exists, or the healthy set did
        not actually change (so retries cannot loop through here)."""
        from ..parallel import sharding

        mesh = getattr(self.engine, "mesh", None)
        if mesh is None or self.kind == "stream":
            # stream engines re-fill device windows from host cursors;
            # their recovery story is resume-from-snapshot, not live
            # surgery — let the next rung (or the caller) handle it
            return False
        healthy = sharding.healthy_devices()
        healthy_ids = {d.id for d in healthy}
        cur = list(mesh.devices.flat)
        lost = [d.id for d in cur if d.id not in healthy_ids]
        if not lost and len(healthy) >= len(cur):
            return False  # every mesh device still answers
        try:
            n = sharding.largest_valid_submesh(self.engine.cfg, len(healthy))
        except sharding.DeviceMeshError as e:
            self._log("degrade", f"device loss: no landing mesh ({e})")
            return False
        if self.kind == "fleet":  # whole machines, B / n a device
            n = sharding.fleet_devices(self.engine.n_elements, n)
        if n >= len(cur) and not lost:
            return False
        new_mesh = sharding.tile_mesh(devices=healthy[:n])
        self.engine.mesh = new_mesh
        restored = None
        if self.store is not None:
            for path in self.store.snapshots():
                try:
                    self.engine.load_checkpoint(path)
                except (CheckpointCorrupt, ValueError, OSError) as e:
                    self._log(
                        "resume-skip",
                        f"{os.path.basename(path)} unusable during "
                        f"reshard, trying older ({e})",
                    )
                    continue
                restored = path
                break
        # re-place whatever the loader didn't cover: events always ride
        # outside snapshots; state too when nothing was restorable (the
        # old buffers stay readable — virtual meshes never physically
        # lose devices, and on hardware the snapshot path above is the
        # one that fires)
        if self.kind == "fleet":
            self.engine._reshard()
        else:
            self.engine.events = sharding.shard_events(
                new_mesh, self.engine.events
            )
            if restored is None:
                self.engine.state = sharding.shard_state(
                    new_mesh, self.engine.state
                )
        getattr(self.engine, "discard_prefetch", lambda: None)()
        rung = f"reshard:{len(cur)}->{n}"
        self.degrade_rungs.append(rung)
        self._log(
            "degrade",
            f"device loss ({cause}): mesh {len(cur)} -> {n} device(s)"
            + (
                f", re-placed {os.path.basename(restored)}"
                if restored
                else ", re-placed live state"
            ),
        )
        print(
            json.dumps(
                {
                    "event": "degraded",
                    "reason": "device_loss",
                    "lost_devices": lost,
                    "from_devices": len(cur),
                    "to_devices": n,
                    "restored": (
                        os.path.basename(restored) if restored else None
                    ),
                }
            ),
            file=sys.stderr,
            flush=True,
        )
        return True

    def _advance_with_retry(self, budget_left: int) -> int:
        from ..util.backoff import DecorrelatedJitter

        attempt = 0
        # decorrelated jitter (util.backoff): a fault front that knocks
        # over N supervised workers at once must not produce N
        # phase-locked retry storms
        backoff = DecorrelatedJitter(base=self.backoff_s, cap=30.0)
        while True:
            snap = self._host_snapshot()
            try:
                return self._advance_chunk(budget_left)
            except Exception as e:
                self._host_restore(snap)
                kind = classify_failure(e)
                if kind is None:
                    raise
                if kind == "device_loss":
                    # the device-loss ladder, in order: shrink the mesh
                    # onto healthy devices; only when no landing mesh
                    # exists, collapse onto a single (CPU) device; only
                    # then give up. Each rung logs itself.
                    if self._reshard_after_device_loss(e):
                        continue
                    if self._fallback_to_cpu(e, unshard=True):
                        self.degrade_rungs.append("cpu-fallback")
                        continue
                    # nothing to demote (already unsharded on the only
                    # healthy device): indistinguishable from a transient
                    # blip — take the bounded backoff-retry path below
                    kind = "transient"
                if attempt >= self.max_retries:
                    if self._fallback_to_cpu(e):
                        continue  # one full attempt on the CPU backend
                    self._log(
                        "give-up",
                        f"{kind} failure persisted after "
                        f"{self.max_retries} retries: {e}",
                    )
                    raise
                attempt += 1
                self.retries += 1
                chunk = getattr(self.engine, "chunk_steps", 1)
                if kind == "oom" and chunk > 1:
                    # halving only changes the drain/rebase cadence, so
                    # results stay bit-exact; recompile is the cost
                    self.engine.chunk_steps = max(1, chunk // 2)
                    at = getattr(self.engine, "attest", None)
                    if at is not None:
                        # the fingerprint chain is cadence-scoped (§24):
                        # record the halving so this run's chain reads as
                        # incomparable, never as a false divergence
                        at.note_cadence(self.engine.chunk_steps)
                    self._log(
                        "degrade",
                        f"device OOM: chunk_steps {chunk} -> "
                        f"{self.engine.chunk_steps}, retrying "
                        f"(attempt {attempt}/{self.max_retries})",
                    )
                else:
                    delay = backoff.next_delay()
                    self._log(
                        "retry",
                        f"transient failure ({e}); backing off "
                        f"{delay:.2f}s (attempt {attempt}/"
                        f"{self.max_retries})",
                    )
                    time.sleep(delay)

    # ---- chaos mode -----------------------------------------------------

    _CHAOS_KEYS = ("core_failstops", "noc_reroutes", "ecc_corrected",
                   "ecc_due")

    def _chaos_check(self) -> None:
        """Log fault-counter movement since the last committed chunk, so
        the RESILIENCE section records WHEN each injected fault landed."""
        if not self._chaos:
            return
        hc = self.engine.host_counters
        cur = {
            k: int(np.asarray(hc[k]).sum())
            for k in self._CHAOS_KEYS
            if k in hc
        }
        moved = [
            f"{k} +{v - self._fault_seen.get(k, 0)} (total {v})"
            for k, v in cur.items()
            if v > self._fault_seen.get(k, 0)
        ]
        if moved:
            self._log("chaos", "; ".join(moved))
        self._fault_seen = cur

    # ---- guard ----------------------------------------------------------

    def _guard_check(self) -> None:
        if self.guard == "off":
            return
        totals = self._counter_totals()
        try:
            if self.kind == "fleet":
                core_done = self.engine.core_done_mask()
                live = self.engine.live_mask()
                for i, cfg in enumerate(self.engine.elem_cfgs):
                    check_chunk_invariants(
                        cfg,
                        self.engine.element_state(i),
                        done_mask=core_done[i],
                        live_mask=live[i],
                    )
                check_chunk_invariants(
                    self.engine.cfg,
                    None,
                    prev_totals=self._prev_totals,
                    totals=totals,
                )
            else:
                check_chunk_invariants(
                    self.engine.cfg,
                    self.engine.state,
                    done_mask=self.engine.done_mask(),
                    live_mask=self.engine.live_mask(),
                    prev_totals=self._prev_totals,
                    totals=totals,
                )
        except AssertionError as e:
            if self.guard == "warn":
                self.guard_warnings += 1
                self._log("guard-warn", str(e))
            else:
                self._log("guard-fail", str(e))
                raise GuardViolation(str(e)) from e
        self._prev_totals = totals

    # ---- the supervised loop --------------------------------------------

    def run(self, max_steps: int | None = None) -> None:
        """Run the engine to completion under supervision.

        Raises Preempted (after checkpointing) on SIGTERM/SIGINT,
        GuardViolation under `--guard=fail`, RuntimeError when the step
        budget runs out with cores still live (fleet: budget-stalled
        elements are recorded in `stalled_elements` and reported instead
        — one deadlocked element must not void the batch)."""
        if max_steps is None:
            max_steps = (
                self.engine._default_budget()
                if self.kind == "stream"
                else 10_000_000
            )
        budget_left = int(max_steps)
        start_steps = self._steps_used()
        self._install_signals()
        self._prev_totals = self._counter_totals()
        if self._chaos:
            cfg = self.engine.cfg
            self._log(
                "chaos",
                f"fault injection armed: seed {cfg.fault_seed}, "
                f"{len(cfg.fault_events)} scheduled event(s), "
                f"dead policy {cfg.fault_dead_policy}",
            )
            self._fault_seen = {
                k: int(np.asarray(self.engine.host_counters[k]).sum())
                for k in self._CHAOS_KEYS
                if k in self.engine.host_counters
            }
        last_ckpt_t = time.monotonic()
        chunks_since_ckpt = 0
        try:
            while not self._done():
                if self.kind == "stream":
                    stepped = self._advance_with_retry(budget_left)
                    budget_left -= stepped
                else:
                    stepped = self._advance_with_retry(0)
                self.committed += 1
                chunks_since_ckpt += 1
                if self.on_chunk is not None:
                    self.on_chunk(self)
                self._chaos_check()
                self._guard_check()
                if self._preempt is not None:
                    signum = self._preempt
                    path = self.checkpoint()
                    name = signal.Signals(signum).name
                    where = (
                        f"snapshot {os.path.basename(path)}"
                        if path
                        else "no snapshot dir configured"
                    )
                    self._log("preempt", f"{name} at chunk boundary; {where}")
                    raise Preempted(
                        f"preempted by {name} after {self.committed} "
                        f"committed chunks ({where})",
                        checkpoint=path,
                        signum=signum,
                    )
                now = time.monotonic()
                if self.store is not None and (
                    (
                        self.checkpoint_every_chunks > 0
                        and chunks_since_ckpt >= self.checkpoint_every_chunks
                    )
                    or (
                        self.checkpoint_every_s > 0
                        and now - last_ckpt_t >= self.checkpoint_every_s
                    )
                ):
                    self.checkpoint()
                    chunks_since_ckpt = 0
                    last_ckpt_t = now
                if self.kind != "stream":
                    if stepped == 0 or (
                        self._steps_used() - start_steps >= max_steps
                        and not self._done()
                    ):
                        if self.kind == "fleet":
                            self.stalled_elements = [
                                self.engine.element_ids[j]
                                for j in np.flatnonzero(
                                    ~self.engine.done_mask()
                                )
                            ]
                            self._log(
                                "stall",
                                f"step budget exhausted; elements "
                                f"{self.stalled_elements} still live — "
                                "isolating, rest of the batch is complete",
                            )
                            break
                        raise RuntimeError(
                            f"supervised run: step budget ({max_steps}) "
                            "exhausted with cores still live (deadlock?)"
                        )
                elif budget_left <= 0 and not self._done():
                    raise RuntimeError(
                        f"supervised run: step budget ({max_steps}) "
                        "exhausted with the stream unfinished"
                    )
            if self.store is not None:
                self.checkpoint()  # final snapshot: resume == no-op rerun
        finally:
            self._restore_signals()


# ---- fleet fault isolation (pre-run) ------------------------------------


def validate_fleet_element(cfg, trace, override: dict | None = None) -> None:
    """Everything FleetEngine.__init__ would reject about ONE element,
    checked in isolation: override keys/values, core count, addressing
    line size, barrier ids vs the slot table. Raises ValueError (often
    the located TraceError subclass)."""
    from ..trace.format import validate_sync
    from .fleet import apply_overrides

    apply_overrides(cfg, override or {})
    if trace.n_cores != cfg.n_cores:
        raise ValueError(
            f"trace has {trace.n_cores} cores, config {cfg.n_cores}"
        )
    if trace.line_addressed:
        trace.line_events(cfg.line_bits)  # line-size validation only
    validate_sync(trace, cfg.barrier_slots)


def build_fleet_isolated(
    cfg,
    sources: list,
    overrides: list[dict] | None = None,
    chunk_steps: int = 256,
    mesh=None,
):
    """Build a FleetEngine from per-element sources with fault isolation.

    `sources[i]` is a Trace or a zero-arg callable returning one (pass
    callables for file loads so an unreadable/corrupt FILE quarantines
    its element instead of killing the batch). Elements whose load or
    validation fails are dropped; the survivors' batch positions map
    back to caller indices through `fleet.element_ids`.

    Returns `(fleet, quarantined)` where `quarantined` is a list of
    `(original_index, exception)` and `fleet` is None when nothing
    survived."""
    from .fleet import FleetEngine

    sources = list(sources)
    if overrides is None:
        overrides = [{}] * len(sources)
    overrides = list(overrides)
    if len(overrides) != len(sources):
        raise ValueError(
            f"got {len(sources)} trace sources but {len(overrides)} "
            "override dicts (must match 1:1)"
        )
    kept, kept_ovs, ids = [], [], []
    quarantined: list[tuple[int, Exception]] = []
    for i, (src, ov) in enumerate(zip(sources, overrides)):
        try:
            trace = src() if callable(src) else src
            validate_fleet_element(cfg, trace, ov)
        except (ValueError, OSError) as e:
            quarantined.append((i, e))
            continue
        kept.append(trace)
        kept_ovs.append(ov)
        ids.append(i)
    if not kept:
        return None, quarantined
    if mesh is not None and quarantined:
        # what is left of the fleet that was asked for may not lie on the
        # whole mesh (B / D whole machines a chip): it takes the most
        # devices that divide it (ROADMAP D13), never a stand-in machine
        from ..parallel.sharding import fleet_submesh

        mesh = fleet_submesh(mesh, len(kept))
    fleet = FleetEngine(cfg, kept, kept_ovs, chunk_steps=chunk_steps,
                        mesh=mesh)
    fleet.element_ids = ids
    return fleet, quarantined
