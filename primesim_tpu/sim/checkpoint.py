"""Checkpoint / resume (SURVEY.md §5.4).

The reference has no checkpointing — runs are one-shot. Here the entire
simulated machine is one pytree (the scan carry, `MachineState`) plus a
handful of host-side accumulators, so a checkpoint is a single `.npz`:
every state field, the 64-bit counter/clock bases, and fingerprints of the
config and trace (resuming against a different machine or workload is an
error, not silent corruption). Quantum boundaries need no special casing —
any step boundary is a consistent cut.

Bit-exactness contract: run(A+B steps) == run(A) -> save -> load -> run(B),
for cycles, counters, and all cache/directory/sync state
(tests/test_checkpoint.py).

Durability contract (DESIGN.md §10): every save goes through
`atomic_save_npz` — write to `<path>.tmp`, fsync, `os.replace` — so a
crash mid-write can never replace a good snapshot with a torn one, and a
per-array CRC32 manifest inside the npz turns silent media corruption
into a typed `CheckpointCorrupt` at load time (which the supervisor's
snapshot rotation treats as "fall back to the next-newest valid one").
"""

from __future__ import annotations

import hashlib
import json
import os
import tempfile
import zlib

import jax.numpy as jnp
import numpy as np

from ..chaos import sites as chaos
from ..config.machine import MachineConfig
from ..faults.schedule import FaultState
from ..stats.counters import (
    COUNTER_NAMES,
    N_BLOCK_ROWS,
    stack_block,
    unstack_block,
)
from ..util import diskpressure
from .state import MachineState, TimingKnobs

_FORMAT = 7  # v3: fused dirm row (metadata + sharers) replaces
# llc_meta/sharers; 5-plane l1; link_free/dram_free queue clocks.
# v4: nested TimingKnobs state field (flattened to state_knobs__<name>
# keys — npz holds flat arrays only).
# v5: nested FaultState field (state_faults__<name>) + four fault
# counters — resuming a chaos run replays the surviving schedule and
# dead-core/link masks bit-exactly.
# v6: prefix-fork provenance (prefix_steps + warm-cache key) on solo,
# fleet, and element snapshots — --resume of a forked run is
# self-describing, and the warm-state cache (below) shares the format.
# v7: machine-zoo state — per-core stride-prefetcher tracking arrays
# (pf_line/pf_stride/pf_streak) + two TimingKnobs fields
# (prefetch_degree/prefetch_lat); older snapshots lack the arrays, so
# the format bump keeps them from resuming with silently-zeroed
# prefetcher state.
# (PR 37: the counter block gained the stat rows, stats/counters.py::
# BLOCK_NAMES. `state_counters` and `host_counters` hold the block's rows,
# the stat totals below the counters'; a snapshot of the old height is
# refused by the row-count checks below, so the format stays.)

# nested-NamedTuple state fields and their types (flattened by
# _state_arrays to `state_<field>__<sub>` keys; extend here when a new
# nested pytree joins MachineState)
_NESTED = {"knobs": TimingKnobs, "faults": FaultState}

_CRC_KEY = "crc_json"  # reserved npz member: {array name: crc32} manifest


class CheckpointCorrupt(ValueError):
    """The checkpoint file is torn, truncated, or fails CRC verification.

    Distinct from the plain ValueErrors the loaders raise for MISMATCHED
    checkpoints (wrong config/trace/kind): a mismatch means the caller
    pointed a healthy snapshot at the wrong engine and retrying another
    snapshot would silently resume the wrong run, while corruption means
    THIS file is unusable and an older snapshot is the right fallback.
    The supervisor's rotation logic relies on that distinction."""


def atomic_save_npz(path: str, **arrays) -> None:
    """Write an npz atomically with per-array CRC32s.

    The bytes go to a writer-unique temp file beside `path` first, are
    flushed and fsynced, and
    only then `os.replace`d over `path` — so `path` always holds either
    the previous complete snapshot or the new complete snapshot, never a
    torn hybrid (the POSIX rename-is-atomic contract). A `crc_json`
    member maps every array name to the CRC32 of its contiguous bytes;
    `load_verified_npz` recomputes and compares before any array is
    trusted."""
    named = {k: np.asarray(v) for k, v in arrays.items()}
    if _CRC_KEY in named:
        raise ValueError(f"array name {_CRC_KEY!r} is reserved")
    crcs = {
        k: zlib.crc32(np.ascontiguousarray(v).tobytes())
        for k, v in named.items()
    }
    named[_CRC_KEY] = np.frombuffer(
        json.dumps(crcs, sort_keys=True).encode(), dtype=np.uint8
    )
    # disk-pressure gate BEFORE any byte lands: uncompressed total is a
    # conservative ceiling on the compressed npz. On pressure this runs
    # the evict->compact ladder and raises DiskPressureError rather than
    # letting savez die mid-write with an ENOSPC-torn temp file
    diskpressure.preflight(
        path,
        sum(v.nbytes for v in named.values()),
        kind="checkpoint",
    )
    # the temp name must be unique PER WRITER, not per destination: a
    # hedged pool pair checkpoints the same unit path from two processes
    # concurrently, and a shared `<path>.tmp` lets one writer rename the
    # other's file away mid-flight (observed as FileNotFoundError on the
    # loser's os.replace)
    fd, tmp = tempfile.mkstemp(
        dir=os.path.dirname(os.path.abspath(path)) or ".",
        prefix=os.path.basename(path) + ".", suffix=".tmp",
    )
    try:
        with os.fdopen(fd, "wb") as f:
            np.savez_compressed(f, **named)
            f.flush()
            os.fsync(f.fileno())
        # chaos durable-write site: a torn/fsync fault here dies BEFORE
        # the rename, proving `path` keeps its previous complete snapshot
        chaos.durable("checkpoint.write", path=tmp)
        os.replace(tmp, path)
        # fsync the directory so the rename itself survives power loss
        dfd = os.open(os.path.dirname(os.path.abspath(path)), os.O_RDONLY)
        try:
            os.fsync(dfd)
        finally:
            os.close(dfd)
    finally:
        if os.path.exists(tmp):
            try:
                os.unlink(tmp)
            except OSError:
                pass


def load_verified_npz(path: str) -> dict[str, np.ndarray]:
    """Load an npz fully into host memory, verifying the CRC manifest.

    Any read/decode failure (missing file is the exception — that stays
    FileNotFoundError so "no snapshot yet" and "bad snapshot" remain
    distinguishable) and any CRC mismatch raises CheckpointCorrupt.
    Files written before the manifest existed (no `crc_json`) load
    unverified — zipfile's own member CRCs still catch torn writes."""
    try:
        with np.load(path) as z:
            data = {k: np.asarray(z[k]) for k in z.files}
    except FileNotFoundError:
        raise
    except Exception as e:
        raise CheckpointCorrupt(
            f"{path}: unreadable checkpoint ({type(e).__name__}: {e})"
        ) from e
    if _CRC_KEY in data:
        try:
            crcs = json.loads(bytes(data.pop(_CRC_KEY)).decode())
        except Exception as e:
            raise CheckpointCorrupt(
                f"{path}: unreadable CRC manifest ({e})"
            ) from e
        for k, want in crcs.items():
            if k not in data:
                raise CheckpointCorrupt(
                    f"{path}: array {k!r} in CRC manifest is missing"
                )
            got = zlib.crc32(np.ascontiguousarray(data[k]).tobytes())
            if got != int(want):
                raise CheckpointCorrupt(
                    f"{path}: array {k!r} fails CRC32 "
                    f"(stored {int(want)}, recomputed {got})"
                )
    return data


def _require_format(z, path: str) -> None:
    """Loud typed rejection of any snapshot not written by this build's
    format. Older formats predate prefix-fork provenance (v6) and would
    resume with silently-missing fields; newer ones may reinterpret
    arrays. Either way the answer is the same: regenerate, don't guess."""
    got = int(z["format"]) if "format" in z else None
    if got != _FORMAT:
        raise ValueError(
            f"{path}: unsupported checkpoint format {got} (this build "
            f"reads format {_FORMAT} only — re-run to regenerate the "
            "snapshot)"
        )


def _str_field(z, key: str) -> str:
    """Decode an optional uint8-string npz member ('' when absent)."""
    return bytes(z[key]).decode() if key in z else ""


def _state_arrays(st: MachineState) -> dict[str, np.ndarray]:
    """Flatten the state pytree to npz-storable arrays: plain fields as
    `state_<name>`, nested NamedTuples (_NESTED) as
    `state_<name>__<sub>`."""
    arrays = {}
    for k, v in st._asdict().items():
        if isinstance(v, tuple(_NESTED.values())):
            for kk, vv in v._asdict().items():
                arrays[f"state_{k}__{kk}"] = np.asarray(vv)
        else:
            arrays[f"state_{k}"] = np.asarray(v)
    return arrays


def _state_from(z) -> MachineState:
    """Rebuild a MachineState from a v5 npz (inverse of _state_arrays)."""
    fields = {}
    for k in MachineState._fields:
        # nested-pytree fields are flattened, so the flat key is absent
        if k in _NESTED:
            typ = _NESTED[k]
            fields[k] = typ(
                **{
                    kk: jnp.asarray(z[f"state_{k}__{kk}"])
                    for kk in typ._fields
                }
            )
        else:
            fields[k] = jnp.asarray(z[f"state_{k}"])
    return MachineState(**fields)


def trace_fingerprint(trace) -> str:
    h = hashlib.sha256()
    h.update(np.ascontiguousarray(trace.events).tobytes())
    h.update(np.ascontiguousarray(trace.lengths).tobytes())
    # addressing interpretation is part of the workload identity: the same
    # raw arrays read as byte- vs line-addressed are different workloads
    h.update(
        f"line_addressed={trace.line_addressed},{trace.line_bits}".encode()
    )
    return h.hexdigest()


def _payload_digest(arrays: dict, cycle_base, steps_run) -> str:
    """Self-digest over an element checkpoint's payload arrays, computed
    from the in-memory values BEFORE the bytes head to disk. The CRC
    manifest proves the file holds what was written; this proves what
    was written is what the engine held — the two together bracket the
    silent_corruption `checkpoint.payload` site (DESIGN.md §24)."""
    h = hashlib.sha256(b"ptckpt-attest1")
    h.update(np.int64(steps_run).tobytes())
    h.update(np.int64(cycle_base).tobytes())
    for k in sorted(arrays):
        h.update(k.encode())
        h.update(np.ascontiguousarray(arrays[k]).tobytes())
    return h.hexdigest()


def _attest_members(payload: dict | None) -> dict:
    """Optional attestation-chain members (DESIGN.md §24). Only emitted
    when the engine carries a chain, so --attest off checkpoints stay
    byte-identical to pre-attestation files."""
    if payload is None:
        return {}
    return {
        "attest_head": np.frombuffer(
            str(payload["head"]).encode(), dtype=np.uint8),
        "attest_chunks": np.int64(payload["chunks"]),
        "attest_start": np.int64(payload["start"]),
        "attest_chunk_steps": np.int64(payload["chunk_steps"]),
    }


def _attest_from(z) -> dict | None:
    if "attest_chunks" not in z:
        return None
    return {
        "head": _str_field(z, "attest_head"),
        "chunks": int(z["attest_chunks"]),
        "start": int(z["attest_start"]),
        "chunk_steps": int(z["attest_chunk_steps"]),
    }


def save_checkpoint(path: str, engine) -> None:
    """Snapshot an Engine mid-run (drains device counters first)."""
    engine._drain()
    arrays = _state_arrays(engine.state)
    arrays["host_counters"] = stack_block(
        engine.host_counters, engine.host_stats)
    atomic_save_npz(
        path,
        format=np.int64(_FORMAT),
        cycle_base=np.int64(engine.cycle_base),
        steps_run=np.int64(engine.steps_run),
        prefix_steps=np.int64(getattr(engine, "prefix_steps", 0) or 0),
        prefix_cache_key=np.frombuffer(
            str(getattr(engine, "prefix_cache_key", "") or "").encode(),
            dtype=np.uint8,
        ),
        config_json=np.frombuffer(
            engine.cfg.to_json().encode(), dtype=np.uint8
        ),
        trace_sha=np.frombuffer(
            trace_fingerprint(engine.trace).encode(), dtype=np.uint8
        ),
        **_attest_members(
            engine.attest.payload()
            if getattr(engine, "attest", None) is not None else None
        ),
        **arrays,
    )


def save_stream_checkpoint(path: str, eng) -> None:
    """Snapshot a StreamEngine at a window boundary (its consistent cut):
    the machine-state pytree plus the per-core stream cursors and 64-bit
    host accumulators. Valid whenever no device window is in flight —
    i.e. between `_advance_window` dispatches (`run_events` pauses
    there)."""
    arrays = _state_arrays(eng.state)
    arrays["host_counters"] = stack_block(eng.host_counters, eng.host_stats)
    atomic_save_npz(
        path,
        format=np.int64(_FORMAT),
        stream=np.int64(1),
        cycle_base=np.int64(eng.cycle_base),
        steps_run=np.int64(eng.steps_run),
        cursor=eng.cursor,
        window_events=np.int64(eng.W),
        config_json=np.frombuffer(eng.cfg.to_json().encode(), dtype=np.uint8),
        trace_sha=np.frombuffer(
            trace_fingerprint(eng.trace).encode(), dtype=np.uint8
        ),
        **_attest_members(
            eng.attest.payload()
            if getattr(eng, "attest", None) is not None else None
        ),
        **arrays,
    )


def load_stream_checkpoint(path: str, eng) -> None:
    """Restore a streaming snapshot into a freshly-built StreamEngine on
    the same config + trace (fingerprint-validated). Resuming then
    re-fills the window from the restored cursors — bit-exact with an
    uninterrupted run (tests/test_checkpoint.py)."""
    z = load_verified_npz(path)
    _require_format(z, path)
    if "stream" not in z:
        raise ValueError(f"{path}: not a compatible streaming checkpoint")
    if MachineConfig.from_json(bytes(z["config_json"]).decode()) != eng.cfg:
        raise ValueError(f"{path}: checkpoint config does not match engine")
    if bytes(z["trace_sha"]).decode() != trace_fingerprint(eng.trace):
        raise ValueError(f"{path}: checkpoint trace does not match engine")
    if int(z["window_events"]) != eng.W:
        raise ValueError(
            f"{path}: checkpoint window_events {int(z['window_events'])} "
            f"!= engine {eng.W} (windows must match for bit-exact resume)"
        )
    st = _state_from(z)
    if getattr(eng, "mesh", None) is not None:
        # restore the multi-chip layout StreamEngine.__init__ applies
        from ..parallel.sharding import shard_state

        st = shard_state(eng.mesh, st)
    eng.state = st
    eng.cursor = z["cursor"].astype(np.int64)
    eng.cycle_base = np.int64(z["cycle_base"])
    eng.steps_run = int(z["steps_run"])
    eng.host_counters, eng.host_stats = unstack_block(z["host_counters"])
    if getattr(eng, "attest", None) is not None:
        eng.attest.seed(_attest_from(z), int(z["steps_run"]))


def load_checkpoint(path: str, engine) -> None:
    """Restore a snapshot into a freshly-constructed Engine.

    The engine must have been built with the same MachineConfig and Trace
    the checkpoint was taken under (validated by fingerprint).
    """
    z = load_verified_npz(path)
    _require_format(z, path)
    if "stream" in z:
        raise ValueError(
            f"{path}: streaming checkpoint — resume it with a StreamEngine"
        )
    if "fleet" in z:
        raise ValueError(
            f"{path}: fleet checkpoint — resume it with a FleetEngine"
        )
    if "element" in z:
        raise ValueError(
            f"{path}: per-job element checkpoint — splice it into a "
            "serving fleet (FleetEngine.restore_element)"
        )
    cfg_json = bytes(z["config_json"]).decode()
    if MachineConfig.from_json(cfg_json) != engine.cfg:
        raise ValueError(f"{path}: checkpoint config does not match engine config")
    sha = bytes(z["trace_sha"]).decode()
    if sha != trace_fingerprint(engine.trace):
        raise ValueError(f"{path}: checkpoint trace does not match engine trace")
    # the host totals always hold the whole block; the device block is
    # N_BLOCK_ROWS, or the counters' rows alone where a mesh ran the job
    rows = z["host_counters"].shape[0]
    if rows != N_BLOCK_ROWS or z["state_counters"].shape[0] not in (
            len(COUNTER_NAMES), N_BLOCK_ROWS):
        raise ValueError(
            f"{path}: checkpoint has {rows} counter "
            f"rows but this build defines {N_BLOCK_ROWS} — saved by an "
            "incompatible version"
        )
    st = _state_from(z)
    if st.counters.shape != engine.state.counters.shape:
        # saved on a mesh and resumed off one, or the reverse: the block
        # was drained at the save, so it is zero at either height
        st = st._replace(counters=np.zeros(engine.state.counters.shape, np.int32))
    if engine.mesh is not None:
        # restore the multi-chip layout Engine.__init__ applies — without
        # this the full state materializes unsharded on one device
        from ..parallel.sharding import shard_state

        st = shard_state(engine.mesh, st)
    engine.state = st
    engine.cycle_base = np.int64(z["cycle_base"])
    engine.steps_run = int(z["steps_run"])
    engine.prefix_steps = int(z["prefix_steps"]) if "prefix_steps" in z else 0
    engine.prefix_cache_key = _str_field(z, "prefix_cache_key") or None
    engine.host_counters, engine.host_stats = unstack_block(
        z["host_counters"])
    if getattr(engine, "attest", None) is not None:
        engine.attest.seed(_attest_from(z), int(z["steps_run"]))


def save_element_checkpoint(path: str, fleet, i: int, job_id: str = "",
                            trace=None) -> None:
    """Snapshot ONE fleet element solo-shaped — the serving daemon's
    per-JOB checkpoint record (DESIGN.md §14). A fleet chunk boundary is
    a consistent per-element cut (elements are mutually independent), so
    the saved state can later be spliced into ANY slot of ANY serving
    fleet on the same geometry (`FleetEngine.restore_element`) and resume
    bit-exactly — the slot number is not part of the job's identity.

    `trace` overrides the fingerprinted workload: the v2 paged allocator
    runs a job's leading WINDOW in a small bucket while the job's
    identity stays the FULL trace — its checkpoints must verify against
    the trace the job will resume with, not the window splice."""
    fleet._drain()
    arrays = _state_arrays(fleet.element_state(i))
    arrays["host_counters"] = stack_block(
        fleet.host_counters, fleet.host_stats)[:, i]  # [N_BLOCK_ROWS, C]
    at = (fleet.attest.payload(i)
          if getattr(fleet, "attest", None) is not None else None)
    extra = _attest_members(at)
    if at is not None:
        # the self-digest is taken from the in-memory values FIRST;
        # anything that mangles the payload after this point (the
        # silent_corruption site below, a DMA/disk fault in real life)
        # fails verification at load even though the CRC manifest —
        # computed over the already-corrupt bytes — passes
        extra["attest_payload_sha"] = np.frombuffer(
            _payload_digest(arrays, fleet.cycle_base[i],
                            fleet.steps_run[i]).encode(),
            dtype=np.uint8,
        )
    chaos.corrupt("checkpoint.payload",
                  {"host_counters": arrays["host_counters"]})
    pre = getattr(fleet, "prefix_steps", None)
    keys = getattr(fleet, "prefix_cache_keys", None)
    atomic_save_npz(
        path,
        format=np.int64(_FORMAT),
        element=np.int64(1),
        cycle_base=np.int64(fleet.cycle_base[i]),
        steps_run=np.int64(fleet.steps_run[i]),
        prefix_steps=np.int64(int(pre[i]) if pre is not None else 0),
        prefix_cache_key=np.frombuffer(
            str((keys[i] if keys is not None else "") or "").encode(),
            dtype=np.uint8,
        ),
        job_id=np.frombuffer(str(job_id).encode(), dtype=np.uint8),
        config_json=np.frombuffer(
            fleet.elem_cfgs[i].to_json().encode(), dtype=np.uint8
        ),
        trace_sha=np.frombuffer(
            trace_fingerprint(
                trace if trace is not None else fleet.traces[i]
            ).encode(),
            dtype=np.uint8,
        ),
        **extra,
        **arrays,
    )


def load_element_checkpoint(path: str, cfg, trace) -> dict:
    """Load a per-job element checkpoint, validated against the job's
    effective config + trace (fingerprints, same discipline as the solo
    loader). Returns the dict `FleetEngine.restore_element` consumes:
    solo-shaped state, 64-bit cycle base / step count, host counters."""
    z = load_verified_npz(path)
    _require_format(z, path)
    if "element" not in z:
        raise ValueError(f"{path}: not a compatible element checkpoint")
    if MachineConfig.from_json(bytes(z["config_json"]).decode()) != cfg:
        raise ValueError(f"{path}: checkpoint config does not match job")
    if bytes(z["trace_sha"]).decode() != trace_fingerprint(trace):
        raise ValueError(f"{path}: checkpoint trace does not match job")
    if z["state_counters"].shape[0] != N_BLOCK_ROWS:
        raise ValueError(
            f"{path}: checkpoint has {z['state_counters'].shape[0]} counter "
            f"rows but this build defines {N_BLOCK_ROWS} — saved by an "
            "incompatible version"
        )
    if "attest_payload_sha" in z:
        from ..attest.errors import AttestationError

        arrays = {k: v for k, v in z.items() if k.startswith("state_")}
        arrays["host_counters"] = z["host_counters"]
        got = _payload_digest(arrays, z["cycle_base"], z["steps_run"])
        if got != _str_field(z, "attest_payload_sha"):
            raise AttestationError(
                f"{path}: checkpoint payload does not match its attest "
                "self-digest — the file verifies its CRC manifest but "
                "holds values the engine never committed (silent "
                "corruption between hash and write)",
                site="checkpoint.payload",
                unit=_str_field(z, "job_id"),
            )
    host_counters, host_stats = unstack_block(z["host_counters"])
    return {
        "state": _state_from(z),
        "cycle_base": np.int64(z["cycle_base"]),
        "steps_run": np.int64(z["steps_run"]),
        "job_id": bytes(z["job_id"]).decode(),
        "prefix_steps": int(z["prefix_steps"]) if "prefix_steps" in z else 0,
        "prefix_cache_key": _str_field(z, "prefix_cache_key") or None,
        "host_counters": host_counters,
        "host_stats": host_stats,
        "attest": _attest_from(z),
    }


def save_fleet_checkpoint(path: str, fleet) -> None:
    """Snapshot a FleetEngine mid-run: the BATCHED state pytree (leading
    axis = fleet element), per-element 64-bit cycle bases and counter
    accumulators, and per-element config/trace fingerprints. Any chunk
    boundary is a consistent cut, exactly as for the solo engine."""
    fleet._drain()
    arrays = _state_arrays(fleet.state)
    arrays["host_counters"] = stack_block(
        fleet.host_counters, fleet.host_stats)  # [N_BLOCK_ROWS, B, C]
    B = len(fleet.elem_cfgs)
    pre = getattr(fleet, "prefix_steps", None)
    if pre is None:
        pre = np.zeros(B, np.int64)
    keys = getattr(fleet, "prefix_cache_keys", None) or [None] * B
    atomic_save_npz(
        path,
        format=np.int64(_FORMAT),
        fleet=np.int64(1),
        cycle_base=fleet.cycle_base,  # [B] int64
        steps_run=fleet.steps_run,  # [B] int64
        prefix_steps=np.asarray(pre, np.int64),  # [B]
        prefix_keys_json=np.frombuffer(
            json.dumps([k or None for k in keys]).encode(), dtype=np.uint8
        ),
        configs_json=np.frombuffer(
            json.dumps(
                [json.loads(c.to_json()) for c in fleet.elem_cfgs]
            ).encode(),
            dtype=np.uint8,
        ),
        trace_shas=np.frombuffer(
            ",".join(trace_fingerprint(t) for t in fleet.traces).encode(),
            dtype=np.uint8,
        ),
        **(
            {"attest_json": np.frombuffer(
                json.dumps([
                    fleet.attest.payload(i) for i in range(B)
                ], sort_keys=True).encode(), dtype=np.uint8)}
            if getattr(fleet, "attest", None) is not None else {}
        ),
        **arrays,
    )


def load_fleet_checkpoint(path: str, fleet) -> None:
    """Restore a fleet snapshot into a freshly-built FleetEngine over the
    same per-element (config, trace) list — order included (the batch
    axis is positional). Resuming is bit-exact per element
    (tests/test_checkpoint.py)."""
    z = load_verified_npz(path)
    _require_format(z, path)
    if "fleet" not in z:
        raise ValueError(f"{path}: not a compatible fleet checkpoint")
    cfgs = [
        MachineConfig.from_dict(d)
        for d in json.loads(bytes(z["configs_json"]).decode())
    ]
    if cfgs != list(fleet.elem_cfgs):
        raise ValueError(
            f"{path}: checkpoint element configs do not match fleet"
        )
    shas = bytes(z["trace_shas"]).decode().split(",")
    if shas != [trace_fingerprint(t) for t in fleet.traces]:
        raise ValueError(
            f"{path}: checkpoint element traces do not match fleet"
        )
    if z["state_counters"].shape[1] != fleet.state.counters.shape[1]:
        raise ValueError(
            f"{path}: checkpoint has {z['state_counters'].shape[1]} counter "
            f"rows but this build defines {fleet.state.counters.shape[1]} — saved by an "
            "incompatible version"
        )
    st = _state_from(z)
    if getattr(fleet, "mesh", None) is not None:
        # the layout FleetEngine.__init__ builds (whole machines a chip)
        from ..parallel.sharding import shard_fleet_state

        st = shard_fleet_state(fleet.mesh, st)
    fleet.state = st
    fleet.cycle_base = z["cycle_base"].astype(np.int64)
    fleet.steps_run = z["steps_run"].astype(np.int64)
    if "prefix_steps" in z:
        fleet.prefix_steps = z["prefix_steps"].astype(np.int64)
    if "prefix_keys_json" in z:
        fleet.prefix_cache_keys = json.loads(
            bytes(z["prefix_keys_json"]).decode()
        )
    fleet.host_counters, fleet.host_stats = unstack_block(z["host_counters"])
    if getattr(fleet, "attest", None) is not None and "attest_json" in z:
        from ..attest import AttestChain

        for i, p in enumerate(json.loads(bytes(z["attest_json"]).decode())):
            if p and fleet.attest.chain(i) is not None:
                fleet.attest.chains[i] = AttestChain.from_payload(p)


# ---------------------------------------------------------------------------
# Warm-state cache (prefix forking, DESIGN.md §16)
#
# Content-addressed on-disk snapshots of a solo engine after P steps of a
# workload. An entry is valid for ANY run whose first P steps are provably
# identical to the producer's, which the key enforces by hashing exactly
# the inputs that can influence those steps:
#
#   - checkpoint format (state layout identity)
#   - trace fingerprint (events + lengths + addressing)
#   - normalized-geometry hash (cfg.timing_normalized().to_json() — core
#     count, cache shapes, mesh, model selectors, fault capacity/policies)
#   - timing-knob values (knobs_from_config leaves; traced, so not part
#     of the geometry hash)
#   - the fault-schedule PREFIX: scheduled events with step < P (an event
#     at step S fires while executing step index S, so a P-step run fires
#     exactly the events with step < P)
#   - the ECC block (seed + flip/due thresholds) ONLY when a flip rate is
#     nonzero — with both flip thresholds 0 the per-step site hashes are
#     never < threshold, so the seed is architecturally unreachable and
#     seed-varying sweep elements must share one entry
#   - P itself
#
# chunk_steps is deliberately NOT part of the key: every absolute
# observable after P steps is chunking-invariant (the cycle_base/cycles
# split differs by quantum-multiple rebases, but dynamics depend only on
# relative clocks).
# ---------------------------------------------------------------------------

_WARM_DEFAULT_MAX_BYTES = 2 << 30  # 2 GiB before LRU eviction kicks in


def warm_cache_root() -> str:
    """The warm-cache directory: $PRIMETPU_CACHE_DIR, or a per-user
    default under ~/.cache. Created on first use."""
    root = os.environ.get("PRIMETPU_CACHE_DIR") or os.path.join(
        os.path.expanduser("~"), ".cache", "primetpu", "warm"
    )
    os.makedirs(root, exist_ok=True)
    return root


def _geometry_hash(cfg) -> str:
    return hashlib.sha256(cfg.timing_normalized().to_json().encode()).hexdigest()


def _warm_payload(cfg, trace_fp: str) -> dict:
    """The step-count-independent part of the cache key (see module-level
    derivation note above)."""
    from .state import knobs_from_config

    kn = knobs_from_config(cfg)
    payload = {
        "format": _FORMAT,
        "trace": str(trace_fp),
        "geom": _geometry_hash(cfg),
        "knobs": {
            k: np.asarray(v).tolist() for k, v in kn._asdict().items()
        },
    }
    if (
        float(cfg.fault_flip_l1) > 0.0
        or float(cfg.fault_flip_llc) > 0.0
        or float(cfg.fault_due_rate) > 0.0
    ):
        payload["ecc"] = {
            "seed": int(cfg.fault_seed),
            "flip_l1": float(cfg.fault_flip_l1),
            "flip_llc": float(cfg.fault_flip_llc),
            "due_rate": float(cfg.fault_due_rate),
        }
    return payload


def warm_cfg_key(cfg, trace_fp: str) -> str:
    """Hash of the step-independent key inputs — the sidecar index key
    `find_warm_states` scans by."""
    blob = json.dumps(_warm_payload(cfg, trace_fp), sort_keys=True)
    return hashlib.sha256(blob.encode()).hexdigest()


def warm_key(cfg, trace_fp: str, steps: int) -> str:
    """The full content-address of a warm entry: step-independent payload
    + the fault-schedule prefix (events with step < steps) + steps."""
    payload = _warm_payload(cfg, trace_fp)
    payload["events"] = sorted(
        tuple(int(x) for x in e)
        for e in getattr(cfg, "fault_events", ()) or ()
        if int(e[0]) < int(steps)
    )
    payload["steps"] = int(steps)
    blob = json.dumps(payload, sort_keys=True)
    return hashlib.sha256(blob.encode()).hexdigest()


def _warm_paths(root: str, key: str) -> tuple[str, str]:
    return os.path.join(root, f"{key}.npz"), os.path.join(root, f"{key}.json")


def save_warm_state(root: str, cfg, trace_fp: str, steps: int, snap: dict) -> str:
    """Write a warm entry (atomic npz + JSON sidecar) and LRU-prune.

    `snap` is the restore_element-shaped dict a prefix run produces:
    {state, cycle_base, steps_run, host_counters}. Returns the key."""
    key = warm_key(cfg, trace_fp, steps)
    os.makedirs(root, exist_ok=True)
    npz_path, meta_path = _warm_paths(root, key)
    arrays = _state_arrays(snap["state"])
    arrays["host_counters"] = stack_block(
        snap["host_counters"], snap["host_stats"])
    atomic_save_npz(
        npz_path,
        format=np.int64(_FORMAT),
        warm=np.int64(1),
        steps=np.int64(steps),
        cycle_base=np.int64(snap["cycle_base"]),
        steps_run=np.int64(snap["steps_run"]),
        trace_sha=np.frombuffer(str(trace_fp).encode(), dtype=np.uint8),
        **arrays,
    )
    meta = {
        "cfg_key": warm_cfg_key(cfg, trace_fp),
        "key": key,
        "trace_sha": str(trace_fp),
        "steps": int(steps),
    }
    # writer-unique temp name, same discipline as atomic_save_npz:
    # concurrent sweeps warming the same entry must not rename each
    # other's sidecar away mid-write
    fd, tmp = tempfile.mkstemp(
        dir=root, prefix=os.path.basename(meta_path) + ".", suffix=".tmp",
    )
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(meta, f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, meta_path)
    finally:
        if os.path.exists(tmp):
            try:
                os.unlink(tmp)
            except OSError:
                pass
    prune_warm_cache(root)
    return key


def load_warm_state(root: str, key: str, cfg, trace_fp: str, steps: int) -> dict:
    """Load + verify a warm entry and return the restore/fork dict.

    Raises FileNotFoundError when absent (a plain miss), CheckpointCorrupt
    when the file is torn or tampered (the caller recomputes), and
    ValueError when the entry doesn't match the requested identity (a
    hash collision or a renamed file — also recompute)."""
    npz_path, _ = _warm_paths(root, key)
    z = load_verified_npz(npz_path)
    _require_format(z, npz_path)
    if "warm" not in z:
        raise ValueError(f"{npz_path}: not a warm-state cache entry")
    if int(z["steps"]) != int(steps):
        raise ValueError(
            f"{npz_path}: entry holds {int(z['steps'])} steps, wanted {steps}"
        )
    if bytes(z["trace_sha"]).decode() != str(trace_fp):
        raise ValueError(f"{npz_path}: entry trace does not match workload")
    if warm_key(cfg, trace_fp, steps) != key:
        raise ValueError(f"{npz_path}: entry key does not match workload")
    if z["state_counters"].shape[0] != N_BLOCK_ROWS:
        raise ValueError(
            f"{npz_path}: incompatible counter-row count "
            f"{z['state_counters'].shape[0]}"
        )
    try:
        now = None  # LRU touch: refresh mtime so eviction is usage-ordered
        os.utime(npz_path, now)
    except OSError:
        pass
    host_counters, host_stats = unstack_block(z["host_counters"])
    return {
        "state": _state_from(z),
        "cycle_base": np.int64(z["cycle_base"]),
        "steps_run": np.int64(z["steps_run"]),
        "host_counters": host_counters,
        "host_stats": host_stats,
    }


def find_warm_states(root: str, cfg, trace_fp: str) -> list[tuple[int, str]]:
    """Scan the cache for entries reusable by (cfg, trace): sidecars whose
    cfg_key matches AND whose full key recomputes identically under this
    cfg (which checks the fault-schedule prefix below the entry's step
    count). Returns [(steps, key)] sorted deepest-first; unreadable
    sidecars are skipped (the npz CRC check still guards the load)."""
    try:
        names = os.listdir(root)
    except OSError:
        return []
    want_cfg = warm_cfg_key(cfg, trace_fp)
    out = []
    for name in names:
        if not name.endswith(".json") or name.endswith(".json.tmp"):
            continue
        try:
            with open(os.path.join(root, name)) as f:
                meta = json.load(f)
        except (OSError, ValueError):
            continue
        if meta.get("cfg_key") != want_cfg:
            continue
        steps = int(meta.get("steps", 0))
        key = str(meta.get("key", ""))
        if steps > 0 and key and warm_key(cfg, trace_fp, steps) == key:
            out.append((steps, key))
    out.sort(key=lambda sk: (-sk[0], sk[1]))
    return out


def prune_warm_cache(root: str, max_bytes: int | None = None) -> int:
    """Evict least-recently-used entries until the cache fits under
    `max_bytes` (default $PRIMETPU_CACHE_MAX_BYTES or 2 GiB). Returns the
    number of entries removed. Hits refresh mtime, so mtime order IS use
    order.

    The budget is SHARED with the executable cache (§23): warm `.npz`
    entries in `root` and AOT `.bin` entries in `root/exec` form one
    LRU pool, so a burst of geometry sweeps can evict stale executables
    and vice versa — one knob bounds the whole cache tree.

    Budget resolution order: explicit `max_bytes` arg > the process-wide
    `--cache-budget` value (util.diskpressure.budget()) >
    $PRIMETPU_CACHE_MAX_BYTES > the 2 GiB default."""
    if max_bytes is None:
        max_bytes = diskpressure.budget()
    if max_bytes is None:
        max_bytes = int(
            os.environ.get("PRIMETPU_CACHE_MAX_BYTES", _WARM_DEFAULT_MAX_BYTES)
        )
    entries = []
    pools = [(root, ".npz")]
    exec_root = os.path.join(root, "exec")
    if os.path.isdir(exec_root):
        pools.append((exec_root, ".bin"))
    for pool_root, suffix in pools:
        try:
            names = os.listdir(pool_root)
        except OSError:
            continue
        for name in names:
            if not name.endswith(suffix):
                continue
            path = os.path.join(pool_root, name)
            try:
                st = os.stat(path)
            except OSError:
                continue
            entries.append((st.st_mtime, st.st_size, path, suffix))
    total = sum(e[1] for e in entries)
    entries.sort()  # oldest first across BOTH pools
    removed = 0
    for mtime, size, path, suffix in entries:
        if total <= max_bytes:
            break
        for victim in (path, path[: -len(suffix)] + ".json"):
            try:
                os.unlink(victim)
            except OSError:
                pass
        total -= size
        removed += 1
    return removed
