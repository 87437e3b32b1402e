"""The device loops and the host driver of the JAX simulation engine — the
TPU re-host of PriME's backend.

One `step()` (sim/step.py: the step as a list of its phases) advances
every target core by up to `local_run_len` local events plus at most one
arbitrated uncore event. Here are the loops that run it — `run_chunk` (a
`lax.scan` of steps), `run_loop` (a whole run as one program), `stream_loop`
(windowed ingest) — and `Engine`, the host runner. The outer `lax.scan`
step IS the quantum-bounded global clock [DRIVER].

The engine must match `primesim_tpu.golden.sim.GoldenSim` BIT-EXACTLY —
tests/test_parity.py enforces this on every workload generator.

The host driver (`Engine`) dispatches ONE fused device program per run —
`lax.while_loop` over scan chunks with on-device counter draining, clock
rebasing, and termination tests — so a run pays one host->device dispatch,
not one per chunk. What a dispatch costs on the current machine (a TPU v5e
attached to its host) is not measured; SURVEY.md §7 "host->TPU ingest
bandwidth ... is the wall-clock make-or-break".
"""

from __future__ import annotations

import functools
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..config.machine import MachineConfig
from ..noc.topology import path_width
from ..obs.metrics import process_store
from ..obs.span import span
from ..parallel.sharding import build_state, mesh_jit, shard_events
from ..stats.counters import (
    BLOCK_NAMES,
    COUNTER_NAMES,
    STAT_NAMES,
    fold_block,
    stat_totals,
    zero_counters,
    zero_stats,
)
from ..trace.device import DeviceTrace
from ..trace.format import (
    EV_BARRIER,
    EV_END,
    EV_LOCK,
    EV_UNLOCK,
    Trace,
)
from . import exec_cache
from .state import MachineState
from .step import INT32_MAX, P_CHUNK, step

_ACC_BITS = 30  # device counter accumulators carry into hi above 2^30


@functools.partial(
    mesh_jit, static_argnums=(0, 1), static_argnames=("has_sync",)
)
def run_chunk(
    cfg: MachineConfig, n_steps: int, events, st: MachineState,
    has_sync: bool = True, mesh=None,
):
    """lax.scan over `n_steps` steps — the jitted hot loop. `mesh`, here
    and in the loops below: the tile mesh of `events` and `st`, which
    `mesh_jit` reads off them where the caller names none. `st` is NOT
    donated (`run_loop` alone, of all the loops, owns what it is given): the overlapped
    prefetch, the supervisor's snapshots and a restore hold the source
    across a chunk."""

    events = DeviceTrace.of(events, cfg.local_run_len)

    def body(carry, _):
        return step(cfg, events, carry, has_sync=has_sync, mesh=mesh), None

    st, _ = jax.lax.scan(body, st, None, length=n_steps)
    return st


def _np(x) -> np.ndarray:
    """Fetch a device array to host NumPy, working under MULTI-HOST
    sharding too: a cross-process-sharded array is not fully addressable,
    so it is allgathered first (every process computes the same global
    result — SPMD — and every process's Engine then reports it)."""
    if isinstance(x, jax.Array) and not x.is_fully_addressable:
        from jax.experimental import multihost_utils

        x = multihost_utils.process_allgather(x, tiled=True)
    return np.asarray(x)


def _device_done(events, st, faults_enabled=False):
    done = events.at(st.ptr)[:, 0] == EV_END
    if faults_enabled:
        # a fail-stopped core never reaches its END marker; it is done by
        # decree, so a run with injected fail-stops still terminates
        done = done | (st.faults.core_dead != 0)
    return jnp.all(done)


def _drain_and_rebase(cfg, st, acc_lo, acc_hi, base_lo, base_hi, nd):
    """On-device housekeeping shared by run_loop and stream_loop: drain
    the int32 counter block (counters and stat rows) into (lo, hi) carry
    pairs (hi above 2^30), and
    rebase the epoch-relative clocks by a whole number of quanta — the
    minimum over `nd` (not-done) lanes — including occupied barrier
    slots' arrival clocks."""
    Q = st.knobs.quantum  # traced — the fleet rebases per element
    acc_lo = acc_lo + st.counters
    acc_hi = acc_hi + (acc_lo >> _ACC_BITS)
    acc_lo = acc_lo & ((1 << _ACC_BITS) - 1)
    st = st._replace(counters=jnp.zeros_like(st.counters))
    m = jnp.min(jnp.where(nd, st.cycles, INT32_MAX))
    delta = jnp.where(jnp.any(nd), (m // Q) * Q, 0)
    st = st._replace(
        cycles=st.cycles - delta,
        quantum_end=st.quantum_end - delta,
        barrier_time=jnp.where(
            st.barrier_count > 0, st.barrier_time - delta, st.barrier_time
        ),
        # router link clocks are epoch-relative too; the clamp floor is
        # unreachable by any wait comparison (rank*link_lat < 2^21 and
        # live clocks are >= 0 post-rebase), so clamping is observably
        # exact while preventing int32 underflow on long-idle links.
        # Only shifted when the router model is live — otherwise the
        # field stays identically zero on every rebase schedule.
        link_free=(
            jnp.maximum(st.link_free - delta, -(1 << 30))
            if cfg.noc.contention and cfg.noc.contention_model == "router"
            else st.link_free
        ),
        dram_free=(
            jnp.maximum(st.dram_free - delta, -(1 << 30))
            if cfg.dram_queue
            else st.dram_free
        ),
    )
    base_lo = base_lo + delta
    base_hi = base_hi + (base_lo >> _ACC_BITS)
    base_lo = base_lo & ((1 << _ACC_BITS) - 1)
    return st, acc_lo, acc_hi, base_lo, base_hi


def loop_live(cfg, events, carry, max_chunks):
    """`run_loop`'s predicate on one machine's carry: chunks left, and a
    core not yet at END. (`fleet_run_loop` maps it over its machines.)"""
    st, k = carry[0], carry[-1]
    with jax.named_scope(P_CHUNK):
        return (k < max_chunks) & ~_device_done(
            events, st, cfg.faults_enabled
        )


def loop_chunk(cfg, chunk_steps, events, carry, has_sync, mesh):
    """`run_loop`'s body on one machine's carry: a scan of `chunk_steps`
    steps, then the drain and the rebase, and one chunk counted."""
    st, acc_lo, acc_hi, base_lo, base_hi, k = carry

    def sbody(c, _):
        return step(cfg, events, c, has_sync=has_sync, mesh=mesh), None

    st, _ = jax.lax.scan(sbody, st, None, length=chunk_steps)
    with jax.named_scope(P_CHUNK):
        nd = events.at(st.ptr)[:, 0] != EV_END
        if cfg.faults_enabled:
            # dead cores must not bound the rebase minimum: their frozen
            # clocks would pin delta at 0 forever (int32 overflow risk on
            # long post-fault runs)
            nd = nd & (st.faults.core_dead == 0)
        st, acc_lo, acc_hi, base_lo, base_hi = _drain_and_rebase(
            cfg, st, acc_lo, acc_hi, base_lo, base_hi, nd
        )
    return st, acc_lo, acc_hi, base_lo, base_hi, k + 1


@functools.partial(
    mesh_jit, static_argnums=(0, 1), static_argnames=("has_sync",),
    donate_argnums=(3,),
)
def run_loop(cfg: MachineConfig, chunk_steps: int, events, st: MachineState,
             max_chunks, has_sync: bool = True, mesh=None):
    """ONE dispatched device program for a whole simulation run.

    The loop OWNS the state it is given: `st` is donated, its buffers
    become the result's and the caller's arrays are deleted, so a job
    holds its machine once in HBM (DESIGN.md §6; no flag, on every
    platform). `events` is not: an engine runs again on it.

    `lax.while_loop` over scan chunks; after each chunk, ON DEVICE: drain
    int32 step counters into (lo, hi) int32 accumulator pairs (hi carries
    above 2^30, so per-chunk per-core increments must stay < 2^30), rebase
    the epoch-relative clocks by a multiple of the quantum (preserving
    barrier arithmetic) so int32 never overflows, and test termination.
    This replaces the reference's per-quantum MPI barrier + host polling
    (SURVEY.md §3.4) with zero host round-trips until the run completes.
    """
    events = DeviceTrace.of(events, cfg.local_run_len)
    acc_lo = jnp.zeros_like(st.counters)
    acc_hi = jnp.zeros_like(st.counters)
    base_lo = jnp.asarray(0, jnp.int32)
    base_hi = jnp.asarray(0, jnp.int32)
    k = jnp.asarray(0, jnp.int32)
    return jax.lax.while_loop(
        lambda carry: loop_live(cfg, events, carry, max_chunks),
        lambda carry: loop_chunk(
            cfg, chunk_steps, events, carry, has_sync, mesh),
        (st, acc_lo, acc_hi, base_lo, base_hi, k),
    )


@functools.partial(
    mesh_jit, static_argnums=(0,), static_argnames=("has_sync",)
)
def stream_loop(cfg: MachineConfig, events, st: MachineState, exhausted,
                filled, max_steps, has_sync: bool = True, mesh=None):
    """Device loop for WINDOWED (streaming) ingest — SURVEY.md §2 #8's
    bounded-buffer hand-off: the events array holds only a window of each
    core's stream, END-padded; `exhausted[c]` marks cores with no events
    beyond their window and `filled[c]` counts the real events buffered.

    The while_loop cond runs EVERY step and exits while every live core
    still has at least local_run_len + 1 buffered events — the most one
    step can consume — so no step ever observes a window's fake END
    mid-run (which would truncate a local run or drop the core from an
    arbitration it would have joined with the full trace). Windowed
    simulation is therefore BIT-EXACT with the preloaded run, including
    LRU stamps (step_no advances only on executed steps). Counters drain
    and clocks rebase on-device every 64 steps, same arithmetic as
    run_loop. `st` is NOT donated: no benchmark cell runs this loop (R5),
    so nothing could judge it.
    """
    events = DeviceTrace.of(events, cfg.local_run_len)
    need = cfg.local_run_len + 1

    def at_end(s):
        done = events.at(s.ptr)[:, 0] == EV_END
        if cfg.faults_enabled:
            # defensive only — the CLI rejects streaming + faults (the
            # window prefetcher cannot know a core died mid-window), but
            # the device loop must still terminate if reached directly
            done = done | (s.faults.core_dead != 0)
        return done

    def cond(carry):
        st, acc_lo, acc_hi, base_lo, base_hi, k = carry
        # a live lane running low on buffered events hands back to the
        # host BEFORE a step could touch the window boundary
        low = jnp.any(~exhausted & (filled - st.ptr < need))
        return (k < max_steps) & ~low & ~jnp.all(at_end(st))

    def body(carry):
        st, acc_lo, acc_hi, base_lo, base_hi, k = carry
        st = step(cfg, events, st, has_sync=has_sync, mesh=mesh)
        # not-done for the rebase: a core at its window's fake END padding
        # (ptr past `filled` but the stream continues, ~exhausted) is LIVE —
        # it must still bound the rebase minimum, else the uniform shift
        # could push its epoch-relative clock negative (violating the clock
        # invariant even though results stay bit-exact under uniform shifts)
        st, acc_lo, acc_hi, base_lo, base_hi = jax.lax.cond(
            (k & 63) == 63,
            lambda args: _drain_and_rebase(
                cfg, *args, ~(at_end(args[0]) & exhausted)
            ),
            lambda args: args,
            (st, acc_lo, acc_hi, base_lo, base_hi),
        )
        return st, acc_lo, acc_hi, base_lo, base_hi, k + 1

    acc_lo = jnp.zeros_like(st.counters)
    acc_hi = jnp.zeros_like(st.counters)
    base_lo = jnp.asarray(0, jnp.int32)
    base_hi = jnp.asarray(0, jnp.int32)
    k = jnp.asarray(0, jnp.int32)
    return jax.lax.while_loop(
        cond, body, (st, acc_lo, acc_hi, base_lo, base_hi, k)
    )


# ---- where a job's bytes lay: the sample's `place` (DESIGN.md §15) ---------

# what is kept of `Device.memory_stats()`: the bytes held, which parent
# against change move by the loaded programs' own length, the largest
# free block, whose last bytes differ from process to process of one program
# and with them the speed of the step's gathers (PERF.md section 7 (n)), and
# the process's high-water mark, which between two readings of one engine
# says what the span between them passed through
ALLOC_KEYS = ("bytes_in_use", "largest_free_block_bytes", "peak_bytes_in_use")


def alloc_now() -> dict:
    """`ALLOC_KEYS` of every local device's allocator, by device id. An
    engine reads it three times (`job_place`, `place_run`): as its `init`
    span opens, what lies in HBM under the arrays the span is about to lay
    (the loaded programs, an earlier job's buffers not yet freed) and the
    room they are laid into; as that span closes; and as a fused job's
    `wait` span closes, before the engine lets go of the state it handed
    the loop. A platform that keeps no such count (the CPU) has no entry."""
    held = {}
    for d in jax.local_devices():
        stats = d.memory_stats()
        if stats and all(k in stats for k in ALLOC_KEYS):
            held[d.id] = {k: int(stats[k]) for k in ALLOC_KEYS}
    return held


def _alloc_of(held: dict, devices: list) -> dict:
    """Each of `ALLOC_KEYS` -> the value a device of `devices` in `held`
    (an `alloc_now`); `{}` where the platform counts none."""
    if not all(d in held for d in devices):
        return {}
    return {k: [held[d][k] for d in devices] for k in ALLOC_KEYS}


def job_place(held: dict, built: dict, state) -> dict:
    """Where an engine's bytes lie, for its jobs' samples (`place`,
    DESIGN.md §15): `devices`, the ids of the chips `state` lies on, in mesh
    order; `alloc`, each of `ALLOC_KEYS` -> the value a device of
    `devices` in `held`, which the engine read (`alloc_now`) as its `init`
    span opened; and `alloc_built`, the same of `built`, read as that span
    closed: its `peak_bytes_in_use` less `alloc`'s `bytes_in_use` is what
    the build passed through (one machine; two while `init_state`
    concatenated `dirm` from its parts). Each `{}` where the platform
    counts none. And `state_bytes`, the bytes of `state` a device (the sum
    over its leaves of that device's share; counted from the shapes, so on
    every platform): what the differences above are multiples of, so that a
    reader needs no arithmetic on shapes. A buffer's own device address is
    not to be had: `unsafe_buffer_pointer()` answers with a host address on
    the TPU (PERF.md section 7 (n))."""
    devices = [s.device.id for s in state.cycles.addressable_shards]
    # a device's share of every leaf (a whole leaf without a mesh)
    a_device = sum(
        math.prod(x.sharding.shard_shape(x.shape)) * x.dtype.itemsize
        for x in jax.tree.leaves(state))
    return {
        "devices": devices,
        "alloc": _alloc_of(held, devices),
        "alloc_built": _alloc_of(built, devices),
        "state_bytes": [a_device] * len(devices),
    }


def place_run(place: dict) -> dict:
    """`place` of the job whose `wait` span has just closed: the engine's
    own (`job_place`) plus `alloc_run`, the allocator read now, while the
    engine still names the state it handed the loop. `alloc_run`'s
    `bytes_in_use` less `alloc`'s is what the job held: one machine where
    the loop took the state in place (`run_loop` donates it), two where
    the result was laid beside it (a fleet's: `fleet_run_loop` does not). A new dict a job: an earlier job's
    sample keeps its own."""
    return {**place, "alloc_run": _alloc_of(alloc_now(), place["devices"])}


def commit_job(eng, total, steps, phases, element_steps=None,
               chip_steps=None) -> None:
    """The one sample of a fused run (DESIGN.md §15), committed once its
    results are on the host: the job's totals row by row of the block
    `total` [rows, C] (the histogram row as its lanes), its host spans'
    seconds, the static sizes the stat ratios divide by, and `eng.place`,
    where the engine's bytes lie and what the job held (`job_place`,
    `place_run`). `eng` is the `Engine`, or a `FleetEngine` with `total` summed over its elements,
    `steps` the longest element's, `element_steps` each element's own and
    `chip_steps` each chip's loop's (one without a mesh; on a mesh every
    chip runs its own machines to their end, DESIGN.md §22):
    the sizes are then those of all its machines together, so that a
    share of `n_cores` x `steps` counts a frozen element's lanes as not
    active. To the attached `Recorder`, else to the process's store, and
    kept as `eng.last_job` for the run's summary line (`cli`)."""
    cfg = eng.cfg
    machines = 1 if element_steps is None else len(element_steps)
    rows = dict(zip(BLOCK_NAMES, total))  # the rows the block carries
    deltas = {k: int(rows[k].sum()) for k in COUNTER_NAMES}
    if len(rows) > len(COUNTER_NAMES):  # a sharded `Engine`'s block has no stat rows
        deltas.update(stat_totals({k: rows[k] for k in STAT_NAMES}))
    router = cfg.noc.contention and cfg.noc.contention_model == "router"
    caps = {
        "n_cores": machines * cfg.n_cores,
        "local_run_len": cfg.local_run_len,
        # the slots of the router walk's sort: a lane's two legs (the
        # request, or on a barrier lane the arrival, and the reply), each
        # padded to the longest path, with or without sync events
        "sort_entries": machines * cfg.n_cores * 2 * path_width(cfg)
        if router else 0,
    }
    if element_steps is not None:
        caps.update(elements=machines, element_steps=list(element_steps),
                    chips=len(chip_steps), chip_steps=list(chip_steps))
    wall_s = sum(phases.values()) - phases["init"]
    if eng.obs is not None:
        eng.last_job = eng.obs.job_committed(
            eng.obs_label, steps, wall_s, deltas, phases, caps, eng.place)
    else:
        eng.last_job = process_store().record(
            time.time(), eng.obs_label, steps, wall_s, deltas,
            phases=phases, caps=caps, place=eng.place)


class Engine:
    """Host runner (SURVEY.md §2 #8 UncoreManager equivalent).

    `run()` dispatches the whole simulation as ONE device program
    (`run_loop`) and makes a single synchronizing host transfer at the end.
    Chunked host loops (`run_chunked`, kept for debugging/inspection and
    the supervised paths) sync the device every chunk instead; what that
    costs on the current machine is not measured (ROADMAP S4).
    Between-chunk bookkeeping (counter drain to 64-bit, quantum
    rebase of the int32 clocks, termination) happens on device either way.
    """

    def __init__(
        self,
        cfg: MachineConfig,
        trace: Trace,
        chunk_steps: int = 256,
        mesh=None,
    ):
        assert trace.n_cores == cfg.n_cores
        self.cfg = cfg
        self.trace = trace
        # static specialization: traces without sync events skip phase 2.7
        from ..trace.format import validate_sync

        validate_sync(trace, cfg.barrier_slots)
        t = trace.events[:, :, 0]
        self.has_sync = bool(
            ((t == EV_LOCK) | (t == EV_UNLOCK) | (t == EV_BARRIER)).any()
        )
        self.mesh = mesh
        with span("engine.init") as init:
            held = alloc_now()  # before this engine's arrays are laid
            # multi-chip: cores/banks laid out over the tile axis
            # (parallel/); events and state go into that layout from their
            # first byte, never whole onto one device
            events = DeviceTrace.of(
                trace.line_events(cfg.line_bits), cfg.local_run_len)
            self.events = (
                jax.device_put(events) if mesh is None
                else shard_events(mesh, events)
            )
            self.state = build_state(cfg, mesh)
            built = alloc_now()  # what the build passed through: its peak
        self._init_s = init.seconds  # reported with the first job's sample
        self.place = job_place(held, built, self.state)  # in every job's sample
        self.chunk_steps = chunk_steps
        # Counter-accumulator guard (run_loop drains int32 step counters
        # into (lo, hi) pairs whose hi carries above 2^30): any per-core
        # counter's per-CHUNK increment must stay < 2^30. The largest
        # per-step increment is the instructions counter, bounded by
        # (local_run_len + 1) events each retiring at most max(arg, pre+1)
        # instructions.
        ev = trace.events
        per_ev = max(
            1,
            int(ev[:, :, 1].max(initial=0)),
            int(ev[:, :, 3].max(initial=0)) + 1,
        )
        per_step = (cfg.local_run_len + 1) * per_ev
        if chunk_steps * per_step >= 1 << _ACC_BITS:
            raise ValueError(
                f"chunk_steps={chunk_steps} x max per-step instruction "
                f"increment {per_step} overflows the 2^{_ACC_BITS} "
                "per-chunk counter accumulator; lower chunk_steps or split "
                "large INS batches"
            )
        self.cycle_base = np.int64(0)
        self.host_counters = zero_counters(cfg.n_cores)
        # the stat rows' totals (stats/counters.py::STAT_NAMES): drained
        # with the counters, kept apart from them
        self.host_stats = zero_stats(cfg.n_cores)
        self.steps_run = 0
        # telemetry sink (obs.Recorder) — None means the chunked loops
        # report to nobody (they still read the clock at each cut) and
        # the fused run() commits its one sample a job to the process's
        # store (DESIGN.md §15 overhead contract)
        self.obs = None
        self.obs_label = "engine"
        self.last_job = None  # the sample of the last fused run (`commit_job`)
        # attestation chain (attest.SoloAttest) — None means the chunked
        # loop never fingerprints; like obs, the fused run() never
        # consults it (DESIGN.md §24: --attest off is bit-exact by
        # construction)
        self.attest = None
        # prefix-fork provenance (checkpoint format v6): nonzero when this
        # engine's state was seeded from a shared-prefix / warm-cache
        # snapshot rather than run from step 0
        self.prefix_steps = 0
        self.prefix_cache_key = None
        # overlapped chunk dispatch (§23): when True, run_steps enqueues
        # chunk k+1 from the just-committed state before returning, so the
        # caller's host-side durability work (journal fsync, checkpoint
        # write, obs commit) runs concurrently with device compute.
        # _pending holds (source_state, dispatched_result, chunk_steps);
        # validity is the OBJECT IDENTITY of source_state — any rollback,
        # checkpoint load or restore reassigns self.state and thereby
        # invalidates the speculation automatically.
        self.overlap = False
        self._pending = None

    def _drain(self) -> None:
        fold_block(self.host_counters, self.host_stats,
                   _np(self.state.counters).astype(np.int64))
        self.state = self.state._replace(
            counters=jnp.zeros_like(self.state.counters)
        )

    def _event_types_at_ptr(self) -> np.ndarray:
        p = np.minimum(_np(self.state.ptr), self.trace.max_len - 1)
        return self.trace.events[np.arange(self.cfg.n_cores), p, 0]

    def _dead_mask(self) -> np.ndarray:
        """[C] bool — fail-stopped cores (all-False with faults off)."""
        if self.cfg.faults_enabled:
            return _np(self.state.faults.core_dead) != 0
        return np.zeros(self.cfg.n_cores, bool)

    def _rebase(self) -> None:
        cyc = _np(self.state.cycles)
        nd = (self._event_types_at_ptr() != EV_END) & ~self._dead_mask()
        if not nd.any():
            return
        delta = (int(cyc[nd].min()) // self.cfg.quantum) * self.cfg.quantum
        if delta <= 0:
            return
        self.cycle_base += delta
        self.state = self.state._replace(
            cycles=self.state.cycles - np.int32(delta),
            quantum_end=self.state.quantum_end - np.int32(delta),
            # occupied barrier slots hold epoch-relative arrival clocks
            barrier_time=jnp.where(
                self.state.barrier_count > 0,
                self.state.barrier_time - np.int32(delta),
                self.state.barrier_time,
            ),
            link_free=(
                jnp.maximum(self.state.link_free - np.int32(delta), -(1 << 30))
                if self.cfg.noc.contention
                and self.cfg.noc.contention_model == "router"
                else self.state.link_free
            ),
            dram_free=(
                jnp.maximum(self.state.dram_free - np.int32(delta), -(1 << 30))
                if self.cfg.dram_queue
                else self.state.dram_free
            ),
        )

    def done(self) -> bool:
        return bool(self.done_mask().all())

    def done_mask(self) -> np.ndarray:
        """[C] bool — cores whose trace pointer sits on END, plus fail-
        stopped cores (dead by injected fault — they will never reach
        END, so completion means 'everyone else finished')."""
        return (self._event_types_at_ptr() == EV_END) | self._dead_mask()

    def live_mask(self) -> np.ndarray:
        """[C] bool — cores that bound the quantum window: not at END,
        not frozen at a barrier (a frozen core's clock legally lags
        `quantum_end` until release, mirroring the `countable` mask in
        step() phase 0), and not fail-stopped by an injected fault (a
        dead core's clock freezes at its death step). Input to the
        supervisor's clock-window guard (validate.check_chunk_invariants)
        — this exclusion is what keeps `--guard=fail` from false-
        positiving on intentionally injected faults."""
        et = self._event_types_at_ptr()
        frozen = (et == EV_BARRIER) & (_np(self.state.sync_flag) != 0)
        return (et != EV_END) & ~frozen & ~self._dead_mask()

    def run(self, max_steps: int = 10_000_000) -> None:
        """Run to completion in ONE device dispatch (preferred path).

        `max_steps` is a deadlock guard, rounded UP to a whole number of
        `chunk_steps` chunks (the device loop cannot stop mid-chunk): up
        to chunk_steps-1 extra steps may execute before the guard trips.
        """
        max_chunks = -(-max_steps // self.chunk_steps)
        # the loop takes `self.state` in place (`run_loop` donates it): a
        # chunk speculated from it would be a second machine in HBM
        self.discard_prefetch()
        # the three host spans of a fused run, on the profiler's own clock
        # beside the device ops and, in seconds, in the job's sample
        # (DESIGN.md §15): the enqueue, the wait for the device, and the
        # transfers once it is done
        with span("engine.dispatch") as dispatch:
            st, acc_lo, acc_hi, base_lo, base_hi, k = exec_cache.call(
                run_loop, "engine.run_loop",
                (self.cfg, self.chunk_steps),
                (self.events, self.state, jnp.asarray(max_chunks, jnp.int32)),
                {"has_sync": self.has_sync},
            )
        with span("engine.wait") as wait:
            jax.block_until_ready(k)
        self.place = place_run(self.place)  # before the old state is let go
        with span("engine.readback") as readback:
            # everything the host needs of the finished run
            acc_lo = _np(acc_lo).astype(np.int64)
            acc_hi = _np(acc_hi).astype(np.int64)
            total = (acc_hi << _ACC_BITS) + acc_lo
            fold_block(self.host_counters, self.host_stats, total)
            self.cycle_base += (
                np.int64(np.asarray(base_hi)) << _ACC_BITS
            ) + np.int64(np.asarray(base_lo))
            self.state = st
            steps = int(np.asarray(k)) * self.chunk_steps
            self.steps_run += steps
        commit_job(self, total, steps, {
            "init": self._init_s, "dispatch": dispatch.seconds,
            "wait": wait.seconds, "readback": readback.seconds})
        self._init_s = 0.0  # the engine's build belongs to its first job
        if not self.done():
            raise RuntimeError("engine: max_steps exceeded (deadlock?)")

    def run_chunked(
        self, max_steps: int = 10_000_000, debug_invariants: bool = False
    ) -> None:
        """Host-loop variant: one dispatch per chunk + host drain/rebase.

        Semantically identical to `run()`; kept for debugging (state is
        inspectable between chunks) and as the reference for the fused
        loop's on-device bookkeeping. `debug_invariants` checks the
        DESIGN.md §5 machine invariants after every chunk.
        """
        self.run_steps(max_steps - self.steps_run, debug_invariants)
        if not self.done():
            raise RuntimeError("engine: max_steps exceeded (deadlock?)")

    def run_steps(self, n_steps: int, debug_invariants: bool = False) -> None:
        """Advance exactly `n_steps` (rounded up to whole chunks) WITHOUT
        the completion check — the building block for checkpointed runs:
        run_steps(A) -> save_checkpoint -> (later) load_checkpoint ->
        run() is bit-exact with an uninterrupted run()."""
        target = self.steps_run + n_steps
        while self.steps_run < target and not self.done():
            # the cuts of a chunk, each one interval read once (obs/span.py):
            # a host span in the profiler's trace (DESIGN.md §15) and,
            # under --obs, a phase timing. dispatch is the async enqueue;
            # drain's host transfer synchronizes, so "drain" includes the
            # device executing the chunk; rebase is pure host work
            with span("engine.chunk.dispatch") as dispatch:
                self._dispatch_chunk()
            self.steps_run += self.chunk_steps
            with span("engine.chunk.drain") as drain:
                self._drain()
            with span("engine.chunk.rebase") as rebase:
                self._rebase()
            phases = {"dispatch": dispatch.seconds, "drain": drain.seconds,
                      "rebase": rebase.seconds}
            if self.overlap and not self.done():
                with span("engine.chunk.prefetch") as prefetch:
                    self._prefetch_chunk()
                phases["prefetch"] = prefetch.seconds
            if self.obs is not None:
                self.obs.chunk_committed(
                    self.obs_label, self.chunk_steps,
                    dispatch.seconds + drain.seconds + rebase.seconds,
                    self.host_counters, phases=phases,
                )
            if self.attest is not None:
                self.attest.observe(self)
            if debug_invariants:
                self.verify_invariants()

    def _dispatch_chunk(self) -> None:
        """Advance self.state by one chunk: consume the prefetched result
        when it was speculated from EXACTLY this state object at this
        chunk size, else dispatch now (through the exec cache when one is
        active)."""
        pend, self._pending = self._pending, None
        if (
            pend is not None
            and pend[0] is self.state
            and pend[2] == self.chunk_steps
        ):
            self.state = pend[1]
            return
        self.state = exec_cache.call(
            run_chunk, "engine.run_chunk",
            (self.cfg, self.chunk_steps), (self.events, self.state),
            {"has_sync": self.has_sync},
        )

    def _prefetch_chunk(self) -> None:
        """Overlap prong (§23): enqueue chunk k+1 from the committed
        state. JAX's async dispatch returns immediately; the device works
        while the host does durability. The result is NOT committed here
        — _dispatch_chunk adopts it only if the committed state is still
        the same object it was speculated from."""
        src = self.state
        nxt = exec_cache.call(
            run_chunk, "engine.run_chunk",
            (self.cfg, self.chunk_steps), (self.events, src),
            {"has_sync": self.has_sync},
        )
        self._pending = (src, nxt, self.chunk_steps)

    def discard_prefetch(self) -> None:
        """Drop any speculated chunk (state surgery makes it moot; the
        identity check would reject it anyway — this just frees it)."""
        self._pending = None

    def block_until_ready(self) -> None:
        """Synchronize the engine's async device uploads (events + the
        whole state pytree). Call before starting a wall-clock measurement:
        uploads are asynchronous, so a lazy multi-MB transfer otherwise
        completes inside the first timed dispatch and is billed to
        simulation."""
        jax.block_until_ready(self.events)
        jax.block_until_ready(self.state)

    def verify_invariants(self) -> None:
        """Check the DESIGN.md §5 machine invariants on the current state
        (host-side; raises AssertionError naming the violation)."""
        from .validate import check_invariants

        check_invariants(self.cfg, self.state, done_mask=self.done_mask())

    # ---- checkpoint / resume (SURVEY.md §5.4) ----------------------------

    def save_checkpoint(self, path: str) -> None:
        from .checkpoint import save_checkpoint

        save_checkpoint(path, self)

    def load_checkpoint(self, path: str) -> None:
        from .checkpoint import load_checkpoint

        load_checkpoint(path, self)

    # ---- results ---------------------------------------------------------

    @property
    def cycles(self) -> np.ndarray:
        return _np(self.state.cycles).astype(np.int64) + self.cycle_base

    @property
    def counters(self) -> dict[str, np.ndarray]:
        """The modelled machine's counters: COUNTER_NAMES, no stat row."""
        self._drain()
        return self.host_counters

    @property
    def step_stats(self) -> dict[str, np.ndarray]:
        """The step's account of its own lane-slots (STAT_NAMES), [C]
        each: per core, but `noc_sort_log2`, a histogram over its lanes."""
        self._drain()
        return self.host_stats
