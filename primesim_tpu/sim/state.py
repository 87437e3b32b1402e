"""Machine state pytree — the lax.scan carry.

The entire simulated machine (SURVEY.md §5.4: "the scan carry IS the
checkpoint") lives in this one NamedTuple of device arrays: core clocks and
trace pointers (CoreManager state, SURVEY.md §2 #2), L1 arrays (#3), LLC +
directory arrays (#3/#4), the quantum clock (#10), and stat counters (#12).
Everything is int32/uint32 so state stays compact and TPU-friendly; the host
runner rebases clocks and drains counters into int64 between chunks.
"""

from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp
import numpy as np

from ..config.machine import MachineConfig
from ..faults.schedule import FaultState, fault_state_from_config
from ..stats.counters import COUNTER_NAMES, N_BLOCK_ROWS

# MESI encoding (shared with primesim_tpu.golden.sim)
I, S, E, M = 0, 1, 2, 3
# MOESI's Owned state (cfg.coherence == "moesi", DESIGN.md §25). DERIVED,
# never stored: the L1 plane still holds only I/S/E/M, and an access sees
# O when the directory says this core owns the line while other sharers
# are recorded (a GETS left the dirty copy in place). Keeping O out of
# the stored encoding keeps every plane layout
# unchanged; O > M so `>= E`-style "exclusive" tests must be written as
# the explicit (== E) | (== M) pair wherever a derived state can appear.
O = 4


def llc_meta_width(cfg: MachineConfig) -> int:
    """Width of the metadata prefix of a `dirm` row: 4*W2 data columns
    (tag/owner pairs, lru, invalidation epoch) rounded up to a 128-lane
    multiple so both the prefix and the sharer words that follow stay
    lane-aligned (see field note)."""
    return ((4 * cfg.llc.ways + 127) // 128) * 128


def dirm_width(cfg: MachineConfig) -> int:
    """Full `dirm` row width: metadata prefix + W2*NW packed sharer
    words."""
    return llc_meta_width(cfg) + cfg.llc.ways * cfg.n_sharer_words


class TimingKnobs(NamedTuple):
    """Per-simulation TIMING knobs, lifted out of the static
    `MachineConfig` into TRACED device scalars/vectors so one compiled
    program serves a whole parameter sweep (the fleet engine vmaps them
    over a leading batch axis; solo engines carry the config's values).
    GEOMETRY (core count, sets/ways, mesh shape, slot tables) and model
    SELECTORS (contention_model, dram_queue, sharer_group, local_run_len,
    o3_overlap_256) stay static — they change array shapes or the traced
    graph itself. All int32, like every clock they feed."""

    quantum: jnp.ndarray  # [] — relaxed-sync quantum, cycles
    cpi: jnp.ndarray  # [C] — per-core non-memory CPI
    l1_lat: jnp.ndarray  # [] — L1 hit/lookup latency
    llc_lat: jnp.ndarray  # [] — LLC bank lookup latency
    link_lat: jnp.ndarray  # [] — per-hop mesh link traversal
    router_lat: jnp.ndarray  # [] — per-router latency
    dram_lat: jnp.ndarray  # [] — DRAM access latency
    dram_service: jnp.ndarray  # [] — controller occupancy (0 -> dram_lat)
    contention_lat: jnp.ndarray  # [] — queueing cycles per transaction
    prefetch_degree: jnp.ndarray  # [] — stride-prefetch lookahead, lines
    prefetch_lat: jnp.ndarray  # [] — LLC-miss cost on a prefetch hit


def knobs_from_config(cfg: MachineConfig) -> TimingKnobs:
    """The config's timing values as a traced-knob pytree (the solo
    engine's knobs; fleet elements override per batch entry)."""

    def i32(v):
        return jnp.asarray(v, jnp.int32)

    return TimingKnobs(
        quantum=i32(cfg.quantum),
        cpi=jnp.asarray(cfg.core.cpi_vector(cfg.n_cores), jnp.int32),
        l1_lat=i32(cfg.l1.latency),
        llc_lat=i32(cfg.llc.latency),
        link_lat=i32(cfg.noc.link_lat),
        router_lat=i32(cfg.noc.router_lat),
        dram_lat=i32(cfg.dram_lat),
        dram_service=i32(cfg.dram_service),
        contention_lat=i32(cfg.noc.contention_lat),
        prefetch_degree=i32(cfg.prefetch_degree),
        prefetch_lat=i32(cfg.prefetch_lat),
    )


class MachineState(NamedTuple):
    # core (CoreManager)
    cycles: jnp.ndarray  # [C] int32 — per-core clock (epoch-relative)
    ptr: jnp.ndarray  # [C] int32 — next trace event index
    # L1 (private caches), all five fields FUSED into one array of
    # planes: plane f at columns [f*W1*S1, (f+1)*W1*S1), in-plane column
    # w*S1 + s (way-major). Planes: 0 = tag (-1 invalid), 1 = MESI state
    # (locally-written; see pull-based coherence), 2 = LRU step-stamp,
    # 3 = LLC way pointer recorded at fill time (slot*W2 + way of the
    # line's directory entry — phase-1 pull-validation follows it with
    # element gathers instead of W2-wide tag searches; a stale pointer is
    # self-detecting, DESIGN.md §7), 4 = the directory entry's
    # invalidation epoch at fill time (compared by coarse-vector
    # validation only). Fused because per-step cost on this TPU path is
    # dominated by per-KERNEL overhead: one take_along over concatenated
    # plane columns replaces three gathers, and one multi-column scatter
    # replaces the six L1 update scatters. 2D with a large minor dim
    # (>= 2560) so tiling stays natural; a 3D shape would make XLA pad
    # the tiny way dim to 128.
    l1: jnp.ndarray  # [C, 5*W1*S1] int32
    # The WHOLE directory, fused: ROW PER (bank, set) — row slot =
    # bank*S2 + set. Columns:
    #   [2w]            = way w's tag (-1 invalid)
    #   [2w+1]          = way w's owner (-1 none)
    #   [2*W2 + w]      = way w's LRU step-stamp
    #   [3*W2 + w]      = way w's invalidation epoch (bumped on every
    #                     sharer-CLEARING transition; the coarse sharer
    #                     vector's pull-validation compares it against
    #                     the L1's fill-time record so a neighbor's later
    #                     re-share cannot resurrect an invalidated entry)
    #   [4*W2 .. MW)    = zero pad up to llc_meta_width (128 multiple)
    #   [MW + w*NW + i] = way w's packed sharer bit-vector word i
    # ONE full-row gather returns EVERYTHING the step needs about the
    # accessed set — tags, owners, LRU, epochs, sharer words — and the
    # winner/join transition writes back through ONE row scatter-add
    # (winner rows carry exact full-row deltas; join rows just their own
    # sharer bit). Per-step cost on this TPU path is per-KERNEL overhead,
    # so collapsing the former sharers+meta arrays' separate gathers/
    # scatters is the win. Full-row forms are the ones XLA lowers well
    # (windowed dynamic-column forms cost 2-4 ms); the explicit 128-lane
    # alignment of the prefix stops XLA's layout assignment from flipping
    # the array to a dim0-minor (transposed) physical layout, which turns
    # every logical row into a strided walk across tiles. int32
    # throughout: sharer bit arithmetic (shift+mask extraction, popcount,
    # wrapping add-deltas) is representation-identical to uint32.
    dirm: jnp.ndarray  # [B*S2, dirm_width(cfg)] int32
    # hop-by-hop router (contention_model="router"): per-directed-link
    # next-free clock, epoch-relative, carried across steps; rebased with
    # the core clocks (clamped at -(1<<30) — a clock that far in the past
    # can never influence a wait, so the clamp is observably exact)
    link_free: jnp.ndarray  # [n_tiles*4] int32
    # memory-controller queueing (cfg.dram_queue): per-bank next-free
    # clock, same epoch/rebase/clamp treatment as link_free
    dram_free: jnp.ndarray  # [B] int32
    # synchronization state (DESIGN.md §3 phase 2.7)
    lock_holder: jnp.ndarray  # [lock_slots] int32 core id or -1
    barrier_count: jnp.ndarray  # [barrier_slots] int32 arrivals this round
    barrier_time: jnp.ndarray  # [barrier_slots] int32 max arrival clock (epoch-relative)
    sync_flag: jnp.ndarray  # [C] int32 1 = pre charged / arrived at event at ptr
    # global clocks
    quantum_end: jnp.ndarray  # [] int32
    step: jnp.ndarray  # [] int32
    # stride-prefetcher training state (cfg.prefetcher == "stride",
    # DESIGN.md §25): last trained line address, last stride (lines) and
    # the consecutive same-stride streak, per core. Always present so the
    # pytree structure is config-stable (like `faults`); with the
    # selector off (static) step() never reads them and carries the
    # zeros through untouched
    pf_line: jnp.ndarray  # [C] int32
    pf_stride: jnp.ndarray  # [C] int32
    pf_streak: jnp.ndarray  # [C] int32
    # the counter block: one row per COUNTER_NAMES entry, then one per
    # STAT_NAMES entry (stats/counters.py::BLOCK_NAMES)
    counters: jnp.ndarray  # [N_BLOCK_ROWS, C] int32 (on a mesh the counters' rows alone)
    # traced per-simulation timing knobs (see TimingKnobs): constant
    # through a run (step passes them through), but TRACED so one
    # compiled program serves every timing variant of one geometry
    knobs: TimingKnobs
    # traced fault-injection state (faults.schedule.FaultState): seed,
    # schedule arrays, ECC thresholds, and the evolving dead-core/link
    # masks. Always present so the pytree structure is config-stable;
    # with cfg.faults_enabled == False (static) step() never reads it —
    # the faults-off step graph carries the leaves through untouched,
    # keeping it bit-exact vs the goldens at ~zero overhead
    faults: FaultState


def _rows(n_rows: int, *runs) -> jnp.ndarray:
    """`[n_rows, W]` int32, every row the same: `runs` of (value, lanes)
    side by side. ONE broadcast of the `[W]` row (made on the host), so
    the only large buffer the build holds is the result: a `concatenate`
    of the runs as `[n_rows, lanes]` arrays keeps its parts alive while
    the result is laid, and an engine's build then passed through the
    machine twice (PERF.md section 6, PR 54)."""
    row = np.concatenate([np.full(lanes, v, np.int32) for v, lanes in runs])
    return jnp.broadcast_to(row, (n_rows, row.size))


def init_state(cfg: MachineConfig, stat_rows: bool = True) -> MachineState:
    """Every leaf by one op whose only large buffer is its result (`dirm`
    and `l1` a row broadcast, `_rows`), eagerly or inside a compiled
    builder (`parallel/sharding.py`). `stat_rows` false: the counter block
    holds COUNTER_NAMES alone, the program then counts no stat row (`step`
    folds the rows the block has).
    `build_state` asks for that on a mesh (DESIGN.md §15: rung 4's sharded
    row gathers lost 4 % to the taller block and 10 % to the counts)."""
    C, B = cfg.n_cores, cfg.n_banks
    s1, w1 = cfg.l1.sets, cfg.l1.ways
    s2, w2 = cfg.llc.sets, cfg.llc.ways
    nw = cfg.n_sharer_words
    if cfg.quantum * cfg.n_cores >= 2**31:
        raise ValueError(
            "quantum * n_cores must be < 2^31 (conflict-key packing); "
            f"got {cfg.quantum} * {cfg.n_cores}"
        )
    return MachineState(
        cycles=jnp.zeros(C, jnp.int32),
        ptr=jnp.zeros(C, jnp.int32),
        # tag plane, state plane, then lru / way pointer / epoch
        l1=_rows(C, (-1, w1 * s1), (I, w1 * s1), (0, 3 * w1 * s1)),
        # tag/owner pairs, then lru + epochs + pad + sharer words
        dirm=_rows(B * s2, (-1, 2 * w2), (0, dirm_width(cfg) - 2 * w2)),
        link_free=jnp.zeros(cfg.n_tiles * 4, jnp.int32),
        dram_free=jnp.zeros(B, jnp.int32),
        lock_holder=jnp.full(cfg.lock_slots, -1, jnp.int32),
        barrier_count=jnp.zeros(cfg.barrier_slots, jnp.int32),
        barrier_time=jnp.zeros(cfg.barrier_slots, jnp.int32),
        sync_flag=jnp.zeros(C, jnp.int32),
        pf_line=jnp.zeros(C, jnp.int32),
        pf_stride=jnp.zeros(C, jnp.int32),
        pf_streak=jnp.zeros(C, jnp.int32),
        quantum_end=jnp.asarray(cfg.quantum, jnp.int32),
        step=jnp.asarray(0, jnp.int32),
        counters=jnp.zeros(
            (N_BLOCK_ROWS if stat_rows else len(COUNTER_NAMES), C), jnp.int32),
        knobs=knobs_from_config(cfg),
        faults=fault_state_from_config(cfg),
    )
