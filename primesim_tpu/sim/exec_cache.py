"""Content-addressed AOT executable cache (DESIGN.md §23).

The warm-state cache (§16) persists *machine state* across processes;
this module is its sibling for the *compiled program*. Every jitted
entry point (solo `run_chunk`/`run_loop`, fleet `fleet_run_chunk`/
`fleet_run_loop`, stream `stream_loop`) is lowered + compiled
ahead-of-time, serialized with `jax.experimental.serialize_executable`,
and written to `$PRIMETPU_CACHE_DIR/exec/<key>.bin` so the *next*
process with the same geometry skips trace, lowering and XLA
compilation entirely.

Key derivation — the sha256 of a canonical-JSON payload over:

  - jax + jaxlib versions (jaxlib pins the XLA commit, so a toolchain
    upgrade silently invalidates every entry: a plain miss, never an
    error)
  - backend platform and device count
  - the checkpoint `_FORMAT` (state pytree layout) and this module's
    own `_FORMAT`, which also stands for what the entry points donate
    (2 since `run_loop` takes its state in place: a blob of before
    aliases nothing and is never loaded for it)
  - the entry-point name
  - `cfg.timing_normalized()` geometry hash — timing knobs are TRACED
    (they live in `state.knobs`), so one executable serves every
    timing variant of a geometry; the model selectors ride inside the
    normalized config JSON
  - the remaining static args (chunk_steps) and static kwargs
    (has_sync)
  - per-leaf avals of the dynamic args: shape, dtype, weak_type, and
    the sharding description for non-trivially-sharded leaves (mesh
    shape and batch size are therefore part of the address), plus the
    pytree structure string

Entries are lowered with the NORMALIZED config substituted for the
static `cfg` so the on-disk artifact is a pure function of geometry —
this is the same contract `FleetEngine` already relies on (it passes
`geom_cfg = cfg.timing_normalized()` as the jit static and is bit-exact
against full-config solo runs).

Durability: `.bin` is MAGIC + CRC32 + pickle of
{payload, in_tree, out_tree}, written writer-unique-temp + fsync +
atomic rename (PT-DURABLE), with a JSON sidecar carrying the full key
payload so `primetpu fsck` can re-derive the address and verify
key<->content agreement offline. Corrupt, truncated, version-mismatched
or otherwise unusable entries degrade to MISS-and-recompile with a
structured warning — the cache can make a run faster, never wrong, and
never dead. LRU budget is shared with the warm-state cache: see
`checkpoint.prune_warm_cache`, which walks both the warm `.npz` pool
and this directory's `.bin` pool under one `PRIMETPU_CACHE_MAX_BYTES`.

Activation is process-global (`configure(enabled=True)`) so deep call
sites (supervisor resume, pool workers, serve buckets) route through
the cache without threading a handle through every constructor. With
the cache off, `call()` is a single `is None` check and a tail call of
the jitted function — bit-identical to the pre-cache stack.
"""

from __future__ import annotations

import json
import hashlib
import logging
import os
import pickle
import struct
import tempfile
import time
import zlib

import jax
import numpy as np

from ..chaos import sites as chaos

log = logging.getLogger("primetpu.exec_cache")

_MAGIC = b"PTEXEC01"
# exec-entry layout; combined with checkpoint._FORMAT in the key. 2 (PR 54):
# `run_loop` donates its state, and an executable serialized before that
# aliases nothing: its entries must not be found under today's keys
_FORMAT = 2


class ExecCacheCorrupt(Exception):
    """A `.bin` entry that cannot be trusted: bad magic, CRC mismatch,
    truncation, or an unpicklable body. Treated as a miss."""


def exec_cache_root() -> str:
    """`$PRIMETPU_CACHE_DIR/exec` (or the per-user default's `exec/`
    subdirectory) — a sibling pool of the warm-state entries so both
    share one tree and one LRU budget. Created on first use."""
    from .checkpoint import warm_cache_root

    root = os.path.join(warm_cache_root(), "exec")
    os.makedirs(root, exist_ok=True)
    return root


def _leaf_desc(x) -> list:
    """Aval descriptor of one dynamic-arg leaf: shape, dtype, weak_type,
    and the sharding string when it is not the trivial single-device
    placement (np arrays and uncommitted single-device jax arrays hash
    identically — both feed the same executable)."""
    if isinstance(x, jax.Array):
        d = [list(x.shape), str(x.dtype), bool(x.aval.weak_type)]
        if not isinstance(x.sharding, jax.sharding.SingleDeviceSharding):
            d.append(str(x.sharding))
        return d
    arr = np.asarray(x)
    return [list(arr.shape), str(arr.dtype), False]


def exec_key_payload(entry: str, statics: tuple, dynamics: tuple,
                     static_kwargs: dict) -> tuple[dict, tuple]:
    """The canonical key payload and the NORMALIZED statics to lower
    with. `statics[0]` must be the MachineConfig; the rest must be
    plain ints (chunk_steps and friends)."""
    from . import checkpoint as ckpt

    cfg = statics[0]
    norm_cfg = cfg.timing_normalized()
    rest = [int(s) for s in statics[1:]]
    leaves, treedef = jax.tree_util.tree_flatten(dynamics)
    payload = {
        "exec_format": _FORMAT,
        "ckpt_format": int(ckpt._FORMAT),
        "jax": jax.__version__,
        "jaxlib": jax.lib.__version__,
        "backend": jax.default_backend(),
        "devices": int(jax.device_count()),
        "entry": entry,
        "geom": hashlib.sha256(norm_cfg.to_json().encode()).hexdigest(),
        "statics": rest,
        "kwargs": {k: bool(v) for k, v in sorted(static_kwargs.items())},
        "tree": str(treedef),
        "avals": [_leaf_desc(x) for x in leaves],
    }
    return payload, (norm_cfg, *rest)


def exec_key(payload: dict) -> str:
    return hashlib.sha256(
        json.dumps(payload, sort_keys=True).encode()
    ).hexdigest()


class ExecCache:
    """One process's view of the on-disk executable pool: an in-process
    memo of loaded executables plus hit/miss/compile-wall accounting."""

    def __init__(self, root: str | None = None):
        self.root = root or exec_cache_root()
        self._memo: dict[str, object] = {}
        self._failed: set[str] = set()  # keys where the AOT path broke
        self.warnings: list[dict] = []  # structured fallback records
        self.stats = {
            "hits": 0,           # disk loads (deserialize, no compile)
            "misses": 0,         # AOT compiles (entry then persisted)
            "memo_hits": 0,      # in-process reuse, no disk touch
            "errors": 0,         # fallbacks to the jitted path
            "compile_wall_s": 0.0,
            "load_wall_s": 0.0,
        }

    # -- public entry points ------------------------------------------------

    def call(self, fn, entry: str, statics: tuple, dynamics: tuple,
             static_kwargs: dict):
        """Run `fn(*statics, *dynamics, **static_kwargs)` through the
        cache; any failure anywhere in the cache machinery falls back to
        the plain jitted call with a structured warning. But for one: an
        execute that failed AFTER it consumed a donated argument (`run_loop`
        owns the state it is given) has nothing left to
        run again on, and what happened is raised."""
        exe, key = self._lookup(fn, entry, statics, dynamics, static_kwargs)
        if exe is None:
            return fn(*statics, *dynamics, **static_kwargs)
        try:
            return exe(*dynamics)
        except Exception as e:  # wrong placement, stale artifact, ...
            self._fallback("execute", entry, key, e)
            if any(isinstance(x, jax.Array) and x.is_deleted()
                   for x in jax.tree_util.tree_leaves(dynamics)):
                raise
            return fn(*statics, *dynamics, **static_kwargs)

    def ensure(self, fn, entry: str, statics: tuple, dynamics: tuple,
               static_kwargs: dict) -> bool:
        """Load-or-compile the executable WITHOUT running it — the
        lease-grant warm path: pay deserialization before the first
        chunk so compile never eats lease TTL. Returns True when an
        executable is resident afterwards."""
        exe, _ = self._lookup(fn, entry, statics, dynamics, static_kwargs)
        return exe is not None

    # -- lookup / compile ---------------------------------------------------

    def _lookup(self, fn, entry, statics, dynamics, static_kwargs):
        try:
            payload, norm_statics = exec_key_payload(
                entry, statics, dynamics, static_kwargs
            )
            key = exec_key(payload)
        except Exception as e:
            self._fallback("key", entry, None, e)
            return None, None
        if key in self._failed:
            return None, key
        exe = self._memo.get(key)
        if exe is not None:
            self.stats["memo_hits"] += 1
            return exe, key
        exe = self._load(key, entry)
        if exe is None:
            exe = self._compile(
                key, payload, fn, entry, norm_statics, dynamics, static_kwargs
            )
        if exe is None:
            self._failed.add(key)
            return None, key
        self._memo[key] = exe
        return exe, key

    def _load(self, key: str, entry: str):
        from jax.experimental.serialize_executable import deserialize_and_load

        t0 = time.perf_counter()
        try:
            blob = self._read_blob(key)
        except FileNotFoundError:
            return None  # plain miss
        except Exception as e:
            self._fallback("load", entry, key, e)
            return None  # corrupt/stale -> miss-and-recompile
        try:
            exe = deserialize_and_load(
                blob["payload"], blob["in_tree"], blob["out_tree"]
            )
        except Exception as e:
            self._fallback("deserialize", entry, key, e)
            return None
        self.stats["hits"] += 1
        self.stats["load_wall_s"] += time.perf_counter() - t0
        self._touch(key)
        return exe

    def _compile(self, key, payload, fn, entry, norm_statics, dynamics,
                 static_kwargs):
        from jax.experimental.serialize_executable import serialize

        t0 = time.perf_counter()
        try:
            exe = fn.lower(
                *norm_statics, *dynamics, **static_kwargs
            ).compile()
        except Exception as e:
            self._fallback("compile", entry, key, e)
            return None
        self.stats["misses"] += 1
        self.stats["compile_wall_s"] += time.perf_counter() - t0
        try:
            ser, in_tree, out_tree = serialize(exe)
            self._write_entry(
                key, payload,
                {"payload": ser, "in_tree": in_tree, "out_tree": out_tree},
            )
        except Exception as e:
            # the executable still works in-process; only persistence broke
            self._fallback("save", entry, key, e)
        return exe

    # -- on-disk format -----------------------------------------------------

    def _paths(self, key: str) -> tuple[str, str]:
        return (os.path.join(self.root, f"{key}.bin"),
                os.path.join(self.root, f"{key}.json"))

    def _read_blob(self, key: str) -> dict:
        bin_path, _ = self._paths(key)
        with open(bin_path, "rb") as f:
            record = f.read()
        head = len(_MAGIC) + 4
        if len(record) < head or record[: len(_MAGIC)] != _MAGIC:
            raise ExecCacheCorrupt(f"{bin_path}: bad magic / truncated")
        (crc,) = struct.unpack("<I", record[len(_MAGIC):head])
        body = record[head:]
        if zlib.crc32(body) & 0xFFFFFFFF != crc:
            raise ExecCacheCorrupt(f"{bin_path}: CRC mismatch")
        try:
            blob = pickle.loads(body)
        except Exception as e:
            raise ExecCacheCorrupt(f"{bin_path}: undecodable body: {e}")
        if not isinstance(blob, dict) or "payload" not in blob:
            raise ExecCacheCorrupt(f"{bin_path}: not an exec entry")
        return blob

    def _write_entry(self, key: str, payload: dict, blob: dict) -> None:
        from .checkpoint import prune_warm_cache

        body = pickle.dumps(blob, protocol=pickle.HIGHEST_PROTOCOL)
        record = _MAGIC + struct.pack("<I", zlib.crc32(body) & 0xFFFFFFFF) + body
        os.makedirs(self.root, exist_ok=True)
        bin_path, meta_path = self._paths(key)
        self._atomic_write(bin_path, record)
        meta = {"key": key, "payload": payload,
                "size": len(record)}
        self._atomic_write(meta_path, json.dumps(meta).encode())
        # shared LRU budget: warm .npz pool + this exec .bin pool
        prune_warm_cache(os.path.dirname(self.root))

    def _atomic_write(self, dst: str, data: bytes) -> None:
        # disk-pressure gate: a DiskPressureError here unwinds into the
        # _write_entry caller's fallback — a cache entry that cannot be
        # persisted costs a recompile, never the run
        from ..util import diskpressure

        diskpressure.preflight(dst, len(data), kind="exec-cache")
        # writer-unique temp name: concurrent processes warming the same
        # entry must not rename each other's file away mid-write
        fd, tmp = tempfile.mkstemp(
            dir=self.root, prefix=os.path.basename(dst) + ".", suffix=".tmp"
        )
        try:
            with os.fdopen(fd, "wb") as f:
                f.write(data)
                f.flush()
                os.fsync(f.fileno())
            chaos.durable("exec_cache.write", path=tmp)
            os.replace(tmp, dst)
        finally:
            if os.path.exists(tmp):
                try:
                    os.unlink(tmp)
                except OSError:
                    pass

    def _touch(self, key: str) -> None:
        try:
            os.utime(self._paths(key)[0], None)  # LRU: mtime is use order
        except OSError:
            pass

    # -- structured fallback ------------------------------------------------

    def _fallback(self, stage: str, entry: str, key, err) -> None:
        rec = {
            "stage": stage,
            "entry": entry,
            "key": key,
            "error": f"{type(err).__name__}: {err}",
        }
        self.warnings.append(rec)
        self.stats["errors"] += 1
        log.warning("exec-cache fallback (recompiling via jit): %s",
                    json.dumps(rec, sort_keys=True))


# -- process-global activation ---------------------------------------------

_ACTIVE: ExecCache | None = None


def configure(enabled: bool, root: str | None = None) -> ExecCache | None:
    """Turn the process-global cache on/off. Deep call sites (engines,
    supervisor resume, pool workers, serve buckets) consult `active()`
    so one CLI flag covers the whole stack."""
    global _ACTIVE
    _ACTIVE = ExecCache(root) if enabled else None
    return _ACTIVE


def active() -> ExecCache | None:
    return _ACTIVE


def call(fn, entry: str, statics: tuple, dynamics: tuple,
         static_kwargs: dict | None = None):
    """Route one jitted-entry-point call through the active cache, or —
    when no cache is configured — straight through `fn` (bit-identical
    to the pre-cache stack: one None check, then a tail call)."""
    kw = static_kwargs or {}
    cache = _ACTIVE
    if cache is None:
        return fn(*statics, *dynamics, **kw)
    return cache.call(fn, entry, statics, dynamics, kw)
