"""Vectorized JAX simulation engine — the TPU re-host of PriME's backend.

One `step()` advances every target core by up to `local_run_len` local
events (INS batches, L1 hits) plus at most one arbitrated uncore event,
implementing DESIGN.md's canonical per-step semantics branchlessly:

- CoreManager's per-core cycle tick (SURVEY.md §2 #2) is a masked lane
  update over the core axis (the `jax.vmap`-shaped dimension, fused by XLA).
- The private-cache lookup (#3), directory-MESI transition (#4), mesh-NoC
  latency (#6), and DRAM charge (#7) are `where`-chains + gathers/scatters
  over `[C]`-shaped lanes — no data-dependent Python control flow.
- The uncore request serializer (#5: `System::sim()` worker loop) becomes a
  scatter-min arbitration: one winner per LLC (bank,set) per step.
- The relaxed quantum barrier (#10) is the active-mask + quantum_end bump;
  the outer `lax.scan` step IS the quantum-bounded global clock [DRIVER].
- Local runs (#1/#3.2: PriME's non-memory path never crosses a process
  boundary) retire private-hit runs without paying a full step.

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
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..config.machine import MachineConfig
from ..stats.counters import COUNTER_NAMES, zero_counters
from ..trace.format import (
    EV_BARRIER,
    EV_END,
    EV_INS,
    EV_LD,
    EV_LOCK,
    EV_ST,
    EV_UNLOCK,
    Trace,
)
from . import exec_cache
from .state import (
    E,
    I,
    M,
    MachineState,
    O,
    S,
    dirm_width,
    init_state,
    llc_meta_width,
)

INT32_MAX = np.int32(2**31 - 1)
_ACC_BITS = 30  # device counter accumulators carry into hi above 2^30

# The phases of `step` and `run_loop` as `jax.named_scope` names (DESIGN.md
# §15): trace-time metadata that lands in every instruction's `op_name`
# (`jit(run_loop)/.../s.noc/rank/sort`) and changes nothing
# the chip executes. This tuple is the one place that spells them; the
# profiler trace of `--xprof`, the benchmark's per-phase metrics and
# tests/test_phase_scopes.py read them from the compiled program. One
# prefix, at most 8 characters and two levels: the benchmark's breakdown
# keeps 64 characters of a label.
_RANK = "rank"  # second level: the calls into ops/ranking.py
_GRP = "grp"  # second level: the coarse vector's per-group reductions
PHASES = (
    "s.fault",  # phase -1: fault injection
    "s.local",  # phase 0 quantum barrier + 0.5 local runs
    "s.probe",  # 0.9 + 1: the arbitration event, its L1 probe, classification
    "s.arb",  # 2: read-join coalescing, per-(bank,set) arbitration
    "s.dir",  # 3: directory transition, grants, victim, invalidation targets,
    #            prefetcher
    "s.dir/" + _GRP,  # sharer_group > 1 only
    "s.noc",  # NoC contention: tile/link counts, or the hop-by-hop router
    "s.noc/" + _RANK,
    "s.dram",  # memory-controller queue
    "s.dram/" + _RANK,
    "s.commit",  # latency composition, granted state, counters, phase 4.A,
    #               the end-of-step commit
    "s.sync",  # 2.7: locks and barriers
    "s.chunk",  # run_loop's per-chunk drain, rebase and termination test
)
(P_FAULT, P_LOCAL, P_PROBE, P_ARB, P_DIR, _, P_NOC, _, P_DRAM, _, P_COMMIT,
 P_SYNC, P_CHUNK) = PHASES

@functools.lru_cache(maxsize=None)
def _group_tables(cfg: MachineConfig):
    """Static per-(home tile, sharer group) reduction tables for the
    coarse vector (sharer_group > 1): member count, max one-way HOPS over
    members, and summed round-trip hops — the group-level stand-ins for
    the full-map model's per-core [C, C] expansion, sized
    [n_tiles, n_groups] instead. GEOMETRY ONLY (latency knobs are traced
    per simulation; round-trip latency is monotone in hops, so
    2*(hmax*link + (hmax+1)*router) is computed from max2hops at the use
    site). NumPy at trace time; constants in the compiled graph."""
    G = cfg.sharer_group
    C = cfg.n_cores
    n_grp = cfg.n_sharer_groups
    nt = cfg.n_tiles
    mx = cfg.noc.mesh_x
    ids = np.arange(n_grp)[:, None] * G + np.arange(G)[None, :]  # [n_grp, G]
    valid = ids < C
    mt = (ids % nt).astype(np.int32)
    gx, gy = mt % mx, mt // mx
    members = valid.sum(1).astype(np.int32)  # [n_grp]
    max2hops = np.zeros((nt, n_grp), np.int32)
    sum2hops = np.zeros((nt, n_grp), np.int32)
    # int32 temporaries of ~1M elements stay in the host's cache: rung 5's
    # 16384 x 256 x 64 pairs take 1 s so, 14-23 s as int64 blocks of 16M
    step = max(1, (1 << 20) // (n_grp * G))
    for lo in range(0, nt, step):
        t = np.arange(lo, min(lo + step, nt), dtype=np.int32)
        tx, ty = (t % mx)[:, None, None], (t // mx)[:, None, None]
        h = _topo.coord_hops(  # [T, n_grp, G]
            cfg.noc.topology, tx, ty, gx[None], gy[None],
            mx, cfg.noc.mesh_y, xp=np,
        )
        h = np.where(valid[None], h, 0)
        max2hops[t] = h.max(2)
        sum2hops[t] = 2 * h.sum(2, dtype=np.int32)
    # NumPy out (converted at each use site): caching jnp arrays created
    # inside a trace would leak that trace's tracers into later jits
    return members, max2hops, sum2hops


def _one_way(tile_a, tile_b, cfg: MachineConfig, kn):
    """Vectorized one-way latency + hop count under cfg's topology
    (noc/topology.py semantics). Latencies come from the traced knobs;
    cfg supplies geometry — the topology selector is STATIC, so each
    topology compiles its own hop formula."""
    h = _topo.hops(cfg, tile_a, tile_b, xp=jnp)
    return h * kn.link_lat + (h + 1) * kn.router_lat, h


# vectorized route builder (link id = tile*4 + dir, dir 0=E 1=W 2=N 3=S,
# identical numbering for every topology), shared with the fault-injection
# detour model — dispatched on the static `noc_topology` selector by
# noc.topology next to each plugin's scalar reference walk
from ..noc import topology as _topo  # noqa: E402
from ..noc.mesh import concat_legs as _concat_legs  # noqa: E402
from ..noc.topology import path_links as _path_links  # noqa: E402

# sort-based segmented FIFO ranking (DESIGN.md §13) — the shared rank
# primitive of the router and DRAM-queue contention models; replaces the
# historical O(C²·n_seg) one-hot matmuls with one O(E log E) sort,
# integer-equal by construction
from ..ops.ranking import lane_order, segmented_rank  # noqa: E402


def _pick(x, idx):
    """`x[..., idx]` along the last axis, `idx` of `x`'s shape less that
    axis (or broadcasting to it: one index for several rows): a compare
    against an iota and a masked sum instead of a gather.
    Exactly one position matches (`0 <= idx < n`), so the sum is the picked
    word to the bit. For a pick out of a row the core already holds: on the
    v5e an XLA `gather` costs 7-14 ns an ELEMENT whatever the row, while
    this is dense vector work that fuses into its producer."""
    oh = jnp.arange(x.shape[-1], dtype=jnp.int32) == idx[..., None]
    return jnp.sum(jnp.where(oh, x, 0), axis=-1)


def _l1_set_read(cfg: MachineConfig, l1, sets, planes):
    """The `planes` (static plane numbers of the fused L1 array) of each
    core's L1 sets `sets` [C, K] -> [C, K, len(planes), W1].

    The core's own row is read WHOLE and the set selected on the chip: for
    each (plane, way) the static slice `l1[:, c0 : c0 + S1]` (a view of
    the row; reshaping `l1` to put S1 on an axis of its own re-tiles the
    array, a 168 MB copy a step on rung 5) is masked by `iota(S1) == set`
    and summed over the set axis. One lane matches, so the int32 sum is
    the stored word to the bit. Dense vector work: 34 us at 1024 cores
    for the local run's 73728 words, which as one element
    `take_along_axis` cost 1021 us; the select's work grows with S1 and
    the gather's does not, and at 2048 sets x 16384 cores it still wins,
    6.9 against 16.9 ms (scripts/prof/prof_gather.py, PERF.md section 6),
    so there is no second form."""
    S1, W1 = cfg.l1.sets, cfg.l1.ways
    FS = W1 * S1
    oh = sets[:, :, None] == jnp.arange(S1, dtype=jnp.int32)  # [C, K, S1]
    words = [
        jnp.sum(
            jnp.where(oh, jax.lax.slice_in_dim(l1, c0, c0 + S1, axis=1)[:, None], 0),
            axis=2,
        )
        for c0 in (p * FS + w * S1 for p in planes for w in range(W1))
    ]
    return jnp.stack(words, axis=2).reshape(*sets.shape, len(planes), W1)


def _l1_probe(cfg: MachineConfig, arange_c, l1, dirm, line,
              run_patch=None, step_no=None):
    """Gather the accessed L1 set and derive each way's EFFECTIVE MESI state.

    PULL-BASED COHERENCE (the TPU-native shape of MESI): remote
    invalidations and downgrades are never pushed into target L1 arrays —
    that costs O(C * S1 * W1) table gathers per step. Instead each L1 way
    stores only locally-written state, and its effective state is derived
    on access by validating against the directory (which phase 4 maintains
    exactly):
        no local entry, or line absent from LLC          -> I
        directory owner == this core                     -> local state
        this core recorded in the sharer bit-vector      -> S  (covers
                                             probe-downgraded old owners)
        otherwise                                        -> I  (stale)
    Observably equivalent to eager invalidation (DESIGN.md §7); the eager
    golden model + parity tests prove it on every workload.

    The directory entry is located through the way pointer (`l1_ptr`,
    recorded at fill time) — one paired tag/owner gather plus one sharer
    -word gather — instead of a W2-wide tag search of the home set; a
    stale pointer self-detects by tag mismatch and yields exactly the
    search result (DESIGN.md §7).

    The pointer is decomposed into (bank, in-row offset) coordinates and
    the gathers index the LLC/sharer arrays in their NATIVE layouts: a
    `reshape(-1)` flat view of a TPU-tiled array is a physical relayout —
    XLA materializes a full copy of the (537 MB at 1024 cores) sharers
    array every step, the round-2 perf regression.

    Returns (w1cols, tag_rows, lru_rows, weff): the set's column indices,
    tags, LRU stamps, and effective per-way MESI states, all [C, W1].
    """
    S1, W1 = cfg.l1.sets, cfg.l1.ways
    l1s = line & (S1 - 1)
    # the fused L1 array holds four planes (tag/state/lru/ptr; a fifth,
    # the fill-time epoch, under the coarse vector) at a W1*S1-column
    # stride: the accessed set's whole bookkeeping in one read of the row
    w1cols = jnp.arange(W1, dtype=jnp.int32)[None, :] * S1 + l1s[:, None]
    rows = _l1_set_read(
        cfg, l1, l1s[:, None], range(5 if cfg.sharer_group > 1 else 4)
    )[:, 0]  # [C, 4 or 5, W1]
    tag_rows, state_rows, lru_rows, ptr_rows = (rows[:, p] for p in range(4))
    eph_rows = rows[:, 4] if cfg.sharer_group > 1 else None
    if run_patch is not None:
        # the local run's deferred L1 writes (applied only in phase 4.A's
        # fused scatter) patched in-register: silent E->M at wm columns,
        # LRU stamps at hm columns (tag/ptr/epoch planes never change
        # during a run)
        hm, wm, cm = run_patch
        colmatch = cm[:, :, None] == w1cols[:, None, :]  # [C, rl, W1]
        state_rows = jnp.where(
            jnp.any(wm[:, :, None] & colmatch, axis=1), M, state_rows
        )
        lru_rows = jnp.where(
            jnp.any(hm[:, :, None] & colmatch, axis=1), step_no, lru_rows
        )
    weff = _validate_ways(
        cfg, arange_c, tag_rows, state_rows, ptr_rows, eph_rows, dirm,
    )
    return w1cols, tag_rows, lru_rows, weff


def _validate_ways(cfg, arange_c, tag_rows, state_rows, ptr_rows, eph_rows,
                   dirm):
    """Pull-validate each way's locally-written state against the
    directory entry its fill-time way pointer names (see `_l1_probe`):
    two tag/owner element gathers + one sharer-word gather, all [C, W1].

    Under the coarse sharer vector (sharer_group > 1) the core checks
    its GROUP's bit, which may stay set on a NEIGHBOR's behalf after
    this core was invalidated — so the group-bit path additionally
    requires the entry's INVALIDATION EPOCH (bumped by every sharer-
    clearing transition) to still equal the one this core recorded at
    fill time. Epoch-match + group-bit is exactly eager-golden validity:
    every S grant after the last clearing records the current epoch, and
    anything older was invalidated by that clearing. The owner path
    needs no epoch (owner identity is exact)."""
    S2, W2 = cfg.llc.sets, cfg.llc.ways
    NW = cfg.n_sharer_words
    logG = cfg.sharer_group.bit_length() - 1
    g_c = arange_c >> logG
    pway = ptr_rows % W2  # ptr = (bank*S2 + set)*W2 + way
    pslot = ptr_rows // W2
    MW = llc_meta_width(cfg)
    vtag = dirm[pslot, 2 * pway]  # [C, W1]
    vown = dirm[pslot, 2 * pway + 1]
    vsh = dirm[pslot, MW + pway * NW + (g_c[:, None] >> 5)]
    vbit = ((vsh >> (g_c[:, None] & 31)) & 1) != 0
    if cfg.sharer_group > 1:
        veph = dirm[pslot, 3 * W2 + pway]
        vbit = vbit & (veph == eph_rows)
    return jnp.where(
        (state_rows == I) | (vtag != tag_rows),
        I,
        jnp.where(
            vown == arange_c[:, None],
            state_rows,
            jnp.where(vbit, S, I),
        ),
    )  # [C, W1] effective MESI per way


def step(
    cfg: MachineConfig,
    events: jnp.ndarray,
    st: MachineState,
    has_sync: bool = True,
) -> MachineState:
    C = cfg.n_cores
    B = cfg.n_banks
    S1, W1 = cfg.l1.sets, cfg.l1.ways
    S2, W2 = cfg.llc.sets, cfg.llc.ways
    NW = cfg.n_sharer_words
    MW = llc_meta_width(cfg)  # sharer words start here in a dirm row
    T = events.shape[1]
    n_tiles = cfg.n_tiles
    arange_c = jnp.arange(C, dtype=jnp.int32)
    # TIMING comes from the TRACED knob pytree carried in state, never
    # from cfg (which is a jit-static arg and may be timing-normalized):
    # one compiled program per GEOMETRY serves every timing variant, and
    # the fleet engine vmaps per-simulation knob values over the batch
    # axis. cfg keeps geometry and model selectors only.
    kn = st.knobs
    Q = kn.quantum
    cpi_vec = kn.cpi
    l1_lat = kn.l1_lat
    llc_lat = kn.llc_lat
    # Counter deltas accumulate in a host-side dict of [C] lanes and fold
    # into the [n_counters, C] array in ONE stacked add at the end of the
    # step: each `.at[row].add` is its own dynamic-update-slice kernel,
    # and ~25 of them per step cost real per-kernel overhead (the phase
    # profile billed ~0.26 ms to a block of ten) while the dict adds fuse
    # into the surrounding elementwise work for free.
    _cacc: dict[str, object] = {}

    def cadd(cnt, name, amount):
        a = amount.astype(jnp.int32)
        _cacc[name] = a if name not in _cacc else _cacc[name] + a
        return cnt

    def cstack():
        rows = [
            _cacc[k] if k in _cacc else jnp.zeros(C, jnp.int32)
            for k in COUNTER_NAMES
        ]
        return jnp.stack(rows)

    def cflush(cnt):
        return cnt + cstack()

    cnt = st.counters

    # ---- phase -1: fault injection (DESIGN.md §12) -----------------------
    # STATIC gate: faults-off programs contain none of this — the faults
    # pytree passes through untouched and the step graph is the pre-fault
    # one (the bit-exact / zero-overhead contract). Faults-on, everything
    # is TRACED (schedule arrays, counter-based PRNG on (seed, step,
    # site)) so one compiled program serves every seed and schedule of a
    # geometry, and the fleet vmaps straight through it.
    if cfg.faults_enabled:
        with jax.named_scope(P_FAULT):
            from ..faults.inject import ecc_step, fire_events, scrub_dead_cond

            fsf = st.faults
            # only cores that haven't retired END absorb faults: a finished
            # core is powered down, and — critically for the solo-vs-fleet
            # determinism contract — a fleet element keeps stepping after it
            # completes (until the whole batch drains), so any fault counted
            # on an ended core would diverge from the same element run solo
            p_end = jnp.minimum(st.ptr, T - 1)
            alive0 = (events[arange_c, p_end, 0] != EV_END) & (
                fsf.core_dead == 0
            )
            kill_sched, link_dead_n, link_extra_n = fire_events(
                cfg, fsf, st.step
            )
            ecc_corr, ecc_due, l1_due = ecc_step(cfg, fsf, st.step, arange_c)
            kill_new = kill_sched
            if cfg.fault_due_failstop:
                # an uncorrectable error in a core's private cache is fatal
                # to that core (machine-check fail-stop)
                kill_new = kill_new | l1_due.astype(jnp.int32)
            kill_now = kill_new * alive0.astype(jnp.int32)
            cnt = cadd(cnt, "core_failstops", kill_now)
            cnt = cadd(cnt, "ecc_corrected", jnp.where(alive0, ecc_corr, 0))
            cnt = cadd(cnt, "ecc_due", jnp.where(alive0, ecc_due, 0))
            dirm_f, lockh_f, wb_dead = scrub_dead_cond(
                cfg, st.dirm, st.lock_holder, kill_now
            )
            if cfg.fault_dead_policy == "writeback":
                cnt = cadd(cnt, "l1_writebacks", wb_dead)
            fsf = fsf._replace(
                core_dead=fsf.core_dead | kill_now,
                link_dead=link_dead_n,
                link_extra=link_extra_n,
            )
            st = st._replace(dirm=dirm_f, lock_holder=lockh_f, faults=fsf)
            deadb = fsf.core_dead != 0  # [C] — dead cores leave every mask

    with jax.named_scope(P_LOCAL):
        # ---- phase 0: quantum barrier (on step-entry state) ------------------
        # Barrier-frozen cores (arrived, waiting for release) neither bump nor
        # bound the quantum (DESIGN.md §3): they rejoin at release. With local
        # runs enabled the event at ptr is slot 0 of the phase-0.5 prefetch —
        # reuse it instead of a separate gather kernel.
        if cfg.local_run_len:
            _rl0 = cfg.local_run_len
            _ioff0 = jnp.arange(_rl0 + 1, dtype=jnp.int32)
            _pidx0 = jnp.minimum(st.ptr[:, None] + _ioff0[None, :], T - 1)
            _pev0 = events[arange_c[:, None], _pidx0]  # [C, rl+1, 4]
            et0 = _pev0[:, 0, 0]
        else:
            p0 = jnp.minimum(st.ptr, T - 1)
            et0 = events[arange_c, p0, 0]
        countable0 = (et0 != EV_END) & ~((et0 == EV_BARRIER) & (st.sync_flag != 0))
        if cfg.faults_enabled:
            # a fail-stopped core neither bumps nor bounds the quantum — it
            # leaves the barrier instead of deadlocking it
            countable0 = countable0 & ~deadb
        any_countable = jnp.any(countable0)
        any_active = jnp.any(countable0 & (st.cycles < st.quantum_end))
        min_nd = jnp.min(jnp.where(countable0, st.cycles, INT32_MAX))
        bumped = (min_nd // Q + 1) * Q
        quantum_end = jnp.where(any_countable & ~any_active, bumped, st.quantum_end)

        step_no = st.step

        # ---- phase 0.5: local runs (DESIGN.md §3) ----------------------------
        # Up to `local_run_len` local events retire per core before the one
        # arbitrated event below: INS batches, L1 read hits, and L1 write hits
        # in E/M, judged against the step-start directory (unchanged during
        # runs) and the core's own live L1 state. Stops at the first non-local
        # event, the quantum boundary, or the run limit. These are one-hot
        # lane updates on the core's own row only — no cross-core effects.
        #
        # PREFETCHED: during a run the pointer advances by exactly one per
        # retired event, so candidate i sits at ptr0 + i and everything every
        # iteration's hit probe reads is known up front: the directory
        # (llc_meta/sharers) is read-only for the whole phase, l1_tag never
        # changes during a run, and l1_state changes only by deferred silent
        # E->M writes the probe cannot distinguish (match needs != I, write
        # hit needs >= E). So the rl+1 candidate events, their L1 set rows,
        # their home-set metadata, and their self-sharer words come in via
        # FIVE batched gathers, and the unrolled loop below is pure lane
        # arithmetic — the per-iteration element-gathers on the multi-hundred
        # -MB directory arrays (the round-4 local-run wall) are gone.
        #
        # The probe validates against the accessed line's HOME entry (W2-wide
        # tag search of the gathered metadata row) rather than through the L1
        # way pointer; DESIGN.md §7 proves search- and pointer-validation
        # observably identical (a stale pointer self-detects to exactly the
        # search result), and the parity suite re-proves it on every workload.
        cycles_c, ptr_c = st.cycles, st.ptr
        l1_c = st.l1
        FS = W1 * S1  # plane stride in the fused L1 array
        rl = cfg.local_run_len
        logB = B.bit_length() - 1
        if rl:
            pev = _pev0  # [C, rl+1, 4] — gathered once in phase 0
            pline = pev[:, :, 2]  # line-granular (Trace.line_events)
            ps = pline & (S1 - 1)
            # tag + state planes of every candidate's set in ONE read of the
            # core's row (lru/ptr aren't needed for run hit probes; feeding
            # them to the arbitration probe too was tried and measured SLOWER
            # — the extra select/patch kernels outweighed the saved reads).
            # The coarse vector additionally needs the fill-time epoch plane.
            pts = _l1_set_read(
                cfg, st.l1, ps, (0, 1, 4) if cfg.sharer_group > 1 else (0, 1)
            )  # [C, rl+1, 2 or 3, W1]
            ptagr, pstater = pts[:, :, 0], pts[:, :, 1]
            pbank = pline & (B - 1)
            pbset = (pline >> logB) & (S2 - 1)
            pslot = pbank * S2 + pbset
            pmrows = st.dirm[pslot]  # [C, rl+1, DW] — metadata AND sharers
            pmeta = pmrows[:, :, : 2 * W2].reshape(C, rl + 1, W2, 2)
            pmmatch = pmeta[..., 0] == pline[:, :, None]
            pmhas = jnp.any(pmmatch, axis=2)
            pmway = jnp.argmax(pmmatch, axis=2).astype(jnp.int32)
            # way and word picks out of rows already in hand: selects, not
            # gathers (`_pick`); `argmax` keeps first-match order
            pown = _pick(pmeta[..., 1], pmway)
            g_c0 = arange_c >> (cfg.sharer_group.bit_length() - 1)
            # the self sharer word rides the row gather: in-register select
            pshw = _pick(pmrows[:, :, MW:], pmway * NW + (g_c0[:, None] >> 5))
            pbit = ((pshw >> (g_c0[:, None] & 31)) & 1) != 0
            pmatch_l = (ptagr == pline[:, :, None]) & (pstater != I)
            plhit = jnp.any(pmatch_l, axis=2)
            plway = jnp.argmax(pmatch_l, axis=2).astype(jnp.int32)
            plstate = _pick(pstater, plway)
            if cfg.sharer_group > 1:
                # epoch guard (see _validate_ways): the group bit only keeps
                # this core's S line alive if no sharer-clearing transition
                # happened since its fill
                pleph = _pick(pts[:, :, 2], plway)
                pveph = _pick(pmrows[:, :, 3 * W2 : 4 * W2], pmway)
                pbit = pbit & (pveph == pleph)
            peff = jnp.where(
                ~(plhit & pmhas),
                I,
                jnp.where(
                    pown == arange_c[:, None],
                    plstate,
                    jnp.where(pbit, S, I),
                ),
            )  # [C, rl+1] effective MESI of the tag-matching way
            if cfg.coherence == "moesi":
                # derived Owned (DESIGN.md §25): this core owns the line at
                # the home while other sharers are recorded — a run's ST on
                # it must arbitrate (the sharers need invalidating), so the
                # probe's effective E/M demotes to O. sharer_group == 1 under
                # moesi (config validation), so pbit IS the self bit and the
                # word popcount is an exact sharer count.
                psh_all = pmrows[:, :, MW:].reshape(C, rl + 1, W2, NW)
                pwords = _pick(
                    jnp.swapaxes(psh_all, 2, 3), pmway[:, :, None]
                )  # [C, rl+1, NW]: the matching way's sharer words
                ptot = jnp.sum(jax.lax.population_count(pwords), axis=2)
                pothers = (ptot - pbit.astype(jnp.int32)) > 0
                peff = jnp.where(
                    pothers & pmhas & (pown == arange_c[:, None]) & (peff >= E),
                    O,
                    peff,
                )
            phitcol = plway * S1 + ps
        if rl:
            # CLOSED FORM for the run itself (no unrolled loop): a candidate
            # retires iff every earlier candidate was local (prefix-AND via
            # cumprod) and the clock BEFORE it — an exclusive prefix sum of
            # retired costs — is still inside the quantum. The serial
            # recurrence and this form agree exactly: costs are
            # non-negative, so the clock-before sequence is non-decreasing
            # and the first quantum crossing cuts both the same way; a
            # pref-but-quantum-stopped candidate forces every later
            # clock-before past the boundary, so over-counting its cost in
            # the prefix sum can never resurrect a later candidate. L1
            # scatters and counter bumps are single fused ops over the
            # [C, rl] retire masks (nothing in the run reads l1_lru, and the
            # probe treats E and M identically, so the deferred silent E->M
            # is invisible — DESIGN.md §3).
            etr = pev[:, :rl, 0]
            eargr = pev[:, :rl, 1]
            eprer = pev[:, :rl, 3]
            is_ins_k = etr == EV_INS
            r_hit_k = (etr == EV_LD) & (peff[:, :rl] != I)
            # E/M exactly — a derived O (moesi) reads locally but must
            # arbitrate its stores (same pair under mesi, where peff <= M)
            w_hit_k = (etr == EV_ST) & (
                (peff[:, :rl] == E) | (peff[:, :rl] == M)
            )
            hit_k = r_hit_k | w_hit_k
            local_k = is_ins_k | hit_k  # END/sync/miss candidates stop the run
            pref = jnp.cumprod(local_k.astype(jnp.int32), axis=1) != 0
            if cfg.faults_enabled:
                pref = pref & ~deadb[:, None]  # dead cores retire nothing
            cost_k = jnp.where(
                is_ins_k,
                eargr * cpi_vec[:, None],
                eprer * cpi_vec[:, None] + l1_lat,
            )
            cost_p = jnp.where(pref, cost_k, 0)
            clock_before = (
                cycles_c[:, None] + jnp.cumsum(cost_p, axis=1) - cost_p
            )
            retire_k = pref & (clock_before < quantum_end)
            cycles_c = cycles_c + jnp.sum(
                jnp.where(retire_k, cost_k, 0), axis=1
            )
            ptr_c = ptr_c + jnp.sum(retire_k, axis=1).astype(jnp.int32)
            cnt = cadd(cnt, "l1_read_hits", jnp.sum(r_hit_k & retire_k, axis=1))
            cnt = cadd(cnt, "l1_write_hits", jnp.sum(w_hit_k & retire_k, axis=1))
            cnt = cadd(
                cnt,
                "instructions",
                jnp.sum(
                    jnp.where(
                        retire_k,
                        jnp.where(is_ins_k, eargr, eprer + 1),
                        0,
                    ),
                    axis=1,
                ),
            )
            hm = hit_k & retire_k  # [C, rl]
            wm = w_hit_k & retire_k
            cm = phitcol[:, :rl]
            # The run's L1 writes (LRU refreshes, silent E->M) are DEFERRED
            # all the way into phase 4.A's single fused scatter: a second
            # scatter chained on the same array cannot alias its operand and
            # re-materializes it (the 5 ms/step join-lru lesson). Phase 1
            # patches the prefetched planes in-register instead.

    with jax.named_scope(P_PROBE):
        # ---- phase 0.9 + phase 1: the arbitration event and its L1 probe -----
        # addresses arrive LINE-granular (Trace.line_events normalizes byte
        # traces at ingest; v4 line-addressed traces pass through) — 2^31
        # lines = 128 GiB at 64B lines, 64x the byte-addressed range
        if rl:
            # a lane that retired k local events arbitrates candidate k
            # (clamped pidx repeats the final END row, so over-running lanes
            # read END here exactly as a direct gather would). Reusing MORE
            # of the prefetch here (classification, L1 planes, home metadata
            # row) was tried and measured slower: the select/patch kernels
            # cost more than the gathers they replaced.
            consumed = (ptr_c - st.ptr)[:, None]
            ev = _pick(jnp.swapaxes(pev, 1, 2), consumed)  # [C, 4]
        else:
            p = jnp.minimum(ptr_c, T - 1)
            ev = events[arange_c, p]  # [C, 4]
        et, earg, eaddr, epre = ev[:, 0], ev[:, 1], ev[:, 2], ev[:, 3]
        line = eaddr
        l1s = line & (S1 - 1)
        pallas_step = cfg.step_impl == "pallas"
        if pallas_step:
            # [PALLAS] fused probe_classify (DESIGN.md §11): phase 1 AND the
            # LLC home-row parse below run as ONE VMEM-blocked kernel. XLA
            # keeps only the two row gathers that STAGE the directory rows
            # into the kernel (data-dependent row gathers are the one access
            # shape the block model cannot express); everything downstream of
            # them — plane selects, pointer validation, classification,
            # sharer predicates, victim selection — fuses.
            from ..kernels.step_kernels import probe_classify

            DWK = dirm_width(cfg)
            bank = line & (B - 1)
            bset = (line >> logB) & (S2 - 1)
            slot = bank * S2 + bset
            meta_rows = st.dirm[slot]  # [C, DW], reused by commit_step
            w1cols = jnp.arange(W1, dtype=jnp.int32)[None, :] * S1 + l1s[:, None]
            ptr_pre = jnp.take_along_axis(l1_c, w1cols + 3 * FS, axis=1)
            vrows = st.dirm[ptr_pre // W2].reshape(C, W1 * DWK)
            tag_rows, lru_rows, weff, shw, vic_shw, pc_lanes = probe_classify(
                cfg, l1_c, vrows, meta_rows, line, arange_c, step_no,
                *((hm, wm, cm) if rl else ()),
            )
            from ..kernels.step_kernels import (
                PL_HIT_ANY,
                PL_HIT_STATE,
                PL_HIT_WAY,
            )

            hit_any = pc_lanes[:, PL_HIT_ANY] != 0
            hit_way = pc_lanes[:, PL_HIT_WAY]
            hit_state = pc_lanes[:, PL_HIT_STATE]
        else:
            w1cols, tag_rows, lru_rows, weff = _l1_probe(
                cfg, arange_c, l1_c, st.dirm, line,
                run_patch=(hm, wm, cm) if rl else None,
                step_no=step_no,
            )
            l1_match = (tag_rows == line[:, None]) & (weff != I)
            hit_any = jnp.any(l1_match, axis=1)
            hit_way = jnp.argmax(l1_match, axis=1).astype(jnp.int32)
            hit_state = weff[arange_c, hit_way]

        not_done = et != EV_END
        frozen = (et == EV_BARRIER) & (st.sync_flag != 0)
        active = not_done & ~frozen & (cycles_c < quantum_end)
        if cfg.faults_enabled:
            active = active & ~deadb

        is_ins = active & (et == EV_INS)
        is_st_ev = et == EV_ST
        is_mem = active & ((et == EV_LD) | is_st_ev)
        is_lock = active & (et == EV_LOCK)
        is_unlock = active & (et == EV_UNLOCK)
        is_barrier = active & (et == EV_BARRIER)  # arrivals (frozen excluded)

        # (hit classification moved below the LLC parse: the moesi derived-O
        # demotion needs the home row's owner + sharer predicates first)

        # LLC lookup for the accessed line (step-start, all lanes — needed both
        # for join eligibility below and the winner transitions in phase 3).
        # ONE full-row gather returns the home set's tags, owners AND LRU
        # stamps; the owner, victim-owner and victim-LRU reads below become
        # in-register row indexing instead of separate element gathers.
        if pallas_step:
            # [PALLAS] parse already fused into probe_classify; unpack lanes
            from ..kernels.step_kernels import PL_LLC_HAS, PL_LLC_HWAY, PL_OWNER

            llc_has = pc_lanes[:, PL_LLC_HAS] != 0
            llc_hway = pc_lanes[:, PL_LLC_HWAY]
            owner = pc_lanes[:, PL_OWNER]
        else:
            bank = line & (B - 1)
            bset = (line >> logB) & (S2 - 1)
            slot = bank * S2 + bset  # [C], exact (bank,set) id
            meta_rows = st.dirm[slot]  # [C, DW]: the set's metadata AND sharers
            mr2 = meta_rows[:, : 2 * W2].reshape(C, W2, 2)
            llc_tag_rows = mr2[..., 0]  # [C, W2]
            owner_rows = mr2[..., 1]
            llc_match = llc_tag_rows == line[:, None]
            llc_has = jnp.any(llc_match, axis=1)
            llc_hway = jnp.argmax(llc_match, axis=1).astype(jnp.int32)
            owner = owner_rows[arange_c, llc_hway]  # [C]
            # the sharer words came along in the same row gather
            sh_rows = meta_rows[:, MW:].reshape(C, W2, NW)  # [C, W2, NW]
            shw = jnp.take_along_axis(
                sh_rows, llc_hway[:, None, None], axis=1
            )[:, 0]

        # sharer-set predicates from the PACKED words — popcount minus the
        # self bit needs no [C, C] expansion (the expansion, when needed for
        # invalidation targets, happens in phase 3: dense, chunked, or — for
        # the coarse vector — group-table reductions). Bit index = the core's
        # GROUP under cfg.sharer_group (identity at G=1).
        logG = cfg.sharer_group.bit_length() - 1
        g_c = arange_c >> logG
        word_idx = g_c // 32  # [C] self -> sharer word
        bit_idx = g_c % 32

        def unpack_bits(words):  # [C, NW] words -> [C, C] bool per TARGET core
            b = (words[:, :, None] >> jnp.arange(32, dtype=jnp.int32)[None, None, :]) & 1
            groups = b.reshape(C, NW * 32) != 0
            # target core t is recorded iff its GROUP's bit is set (identity
            # expansion at G=1)
            return jnp.take(groups, g_c, axis=1)

        if pallas_step:
            from ..kernels.step_kernels import PL_OTHER_SH, PL_SELF_BIT

            self_bit = pc_lanes[:, PL_SELF_BIT]
            other_sharers = pc_lanes[:, PL_OTHER_SH] != 0
        else:
            self_bit = (
                (shw[arange_c, word_idx] >> bit_idx) & 1
            ).astype(jnp.int32)
            total_sharers = jnp.sum(
                jax.lax.population_count(shw), axis=1
            ).astype(jnp.int32)
            if cfg.sharer_group > 1:
                # coarse: the requester's own group bit may cover OTHER
                # cores, so exclusivity (E grants) requires an empty vector
                # (golden `shared_any`)
                other_sharers = total_sharers > 0
            else:
                other_sharers = (total_sharers - self_bit) > 0

        if cfg.coherence == "moesi":
            # derived Owned (DESIGN.md §25): a stored E/M hit while the home
            # directory still names this core owner WITH other sharers
            # recorded (a GETS left the dirty copy here) is an O hit — reads
            # stay local, but a store must arbitrate as an upgrade to
            # invalidate the sharers. Pure demotion of the classification
            # input; the stored plane is untouched (O is never written).
            hit_state = jnp.where(
                hit_any & llc_has & (owner == arange_c) & other_sharers
                & (hit_state >= E),
                O,
                hit_state,
            )

        read_hit = is_mem & ~is_st_ev & hit_any
        # E/M exactly, never a derived O (the `(== E) | (== M)` pair is
        # `>= E` under mesi, where hit_state <= M)
        write_hit = is_mem & is_st_ev & hit_any & (
            (hit_state == E) | (hit_state == M)
        )
        upg = is_mem & is_st_ev & hit_any & (
            (hit_state == S) | (hit_state == O)
        )
        gets = is_mem & ~is_st_ev & ~hit_any
        getm = is_mem & is_st_ev & ~hit_any

    with jax.named_scope(P_ARB):
        # ---- phase 2: read-join coalescing + per-(bank,set) arbitration ------
        # GETS to an LLC-resident, ownerless, already-shared line may coalesce:
        # the serialized 'plain join' transition (S grant, sharers |= {c}) has
        # latency independent of the sharer set and commutative state updates,
        # so any number retire in one step, bit-exact to any serialization
        # order (DESIGN.md §3). A join only proceeds if no arbitrating request
        # targets its home (bank,set) this step; else it demotes to normal
        # GETS. Disabled under the coarse vector: same-group joiners' bit
        # updates would collide in the fused scatter-add.
        join_elig = gets & llc_has & (owner == -1) & other_sharers
        if cfg.sharer_group > 1:
            join_elig = jnp.zeros_like(join_elig)
        req = (gets & ~join_elig) | getm | upg
        # Packed single-scatter key ordering by (cycles, core_id). Valid because
        # every arbitrating lane's clock lies in [quantum_end - Q, quantum_end):
        # clocks never decrease, quantum bumps stop at min_countable + Q, and a
        # barrier release resumes waiters at the slot's max ARRIVAL clock — set
        # in the same step as the count-completing arrival, whose core was
        # active then — so released clocks re-enter the window too (DESIGN.md
        # §3-sync invariant; the golden model asserts it every step).
        rel = cycles_c - (quantum_end - Q)  # in [0, Q) for active requesters
        key = rel * C + arange_c  # orders by (cycles, core_id); < Q*C < 2^31
        table = jnp.full(B * S2, INT32_MAX, jnp.int32)
        table = table.at[jnp.where(req, slot, B * S2)].min(key, mode="drop")
        slot_busy = table[slot] != INT32_MAX
        join = join_elig & ~slot_busy
        demoted = join_elig & slot_busy
        table = table.at[jnp.where(demoted, slot, B * S2)].min(key, mode="drop")
        req = req | demoted
        winner = req & (table[slot] == key)
        retry = req & ~winner
        cnt = cadd(cnt, "retries", retry)

    with jax.named_scope(P_DIR):
        # ---- phase 3: directory transition on step-start state ---------------
        ctile = arange_c % n_tiles
        btile = bank % n_tiles
        req_lat, req_hops = _one_way(ctile, btile, cfg, kn)
        rep_lat, rep_hops = _one_way(btile, ctile, cfg, kn)
        if cfg.faults_enabled:
            # link-fault penalties of the request/reply legs (detour around
            # dead links + degrade extras — faults/inject.py). The NOMINAL
            # legs are left untouched through the service/contention math:
            # the router model's `extra_home = raw_rt - (req_lat + service +
            # rep_lat)` decomposition and the link/tile contention counts are
            # all defined on the nominal XY path (a detour adds latency, it
            # does not re-route the contention walk), so the fault extras
            # join the composed latencies AFTER that block, and the hop
            # counters bump just before the counter fold.
            from ..faults.inject import leg_fault_penalty

            fx_req, fh_req, rr_req = leg_fault_penalty(
                cfg, st.faults, kn, ctile, btile
            )
            fx_rep, fh_rep, rr_rep = leg_fault_penalty(
                cfg, st.faults, kn, btile, ctile
            )
            flt_rt = fx_req + fx_rep  # round-trip fault extra, home txns

        # barrier home tile (bid lives in the addr field; ids validated
        # < barrier_slots at ingest) — shared by the contention count and the
        # phase-2.7 arrival/release paths
        bid = jnp.where(et == EV_BARRIER, eaddr, 0)
        htile = bid % n_tiles

        # ---- NoC contention (NocConfig.contention) ---------------------------
        # This step's uncore transactions: memory winners + joins (home bank),
        # lock/unlock RMWs (the lock's home == the same btile), barrier
        # arrivals (bid % n_tiles). Tile model: occupancy count per home tile,
        # charge contention_lat * (count - 1). Link model: each transaction's
        # XY request+reply path (barrier arrivals: one way) claims its links;
        # charge contention_lat * bottleneck (count - 1) over the path —
        # mirroring golden's _bump/_contention_extra exactly. The "router"
        # model replaces the analytic request/reply legs wholesale and is
        # computed after the service components are known (below).
        router = cfg.noc.contention and cfg.noc.contention_model == "router"
        home_txn = winner | join
        if has_sync:
            home_txn = home_txn | is_lock | is_unlock
    if cfg.noc.contention and not router:
        with jax.named_scope(P_NOC):
            ccl = kn.contention_lat
            if cfg.noc.contention_model == "link":
                from ..noc.mesh import n_links

                NL = n_links(cfg)
                req_p = _path_links(cfg, ctile, btile)  # [C, H]
                rep_p = _path_links(cfg, btile, ctile)
                arr_p = _path_links(cfg, ctile, htile)
                # every leg's occupancy in ONE concatenated [C, legs*H]
                # scatter-add (the router block's idiom; integer adds are
                # order-independent, so folding the per-path loop is exact)
                lpth, lmask = _concat_legs(
                    [(req_p, home_txn), (rep_p, home_txn)]
                    + ([(arr_p, is_barrier)] if has_sync else [])
                )
                lcnt = jnp.zeros(NL, jnp.int32).at[
                    jnp.where(lmask & (lpth >= 0), lpth, NL)
                ].add(1, mode="drop")

                def _path_worst(pth):
                    cts = lcnt[jnp.where(pth >= 0, pth, 0)]
                    return jnp.max(jnp.where(pth >= 0, cts - 1, 0), axis=1)

                extra_home = ccl * jnp.maximum(_path_worst(req_p), _path_worst(rep_p))
                extra_bar = ccl * _path_worst(arr_p)
            else:
                tcnt = jnp.zeros(n_tiles, jnp.int32)
                tcnt = tcnt.at[jnp.where(home_txn, btile, n_tiles)].add(
                    1, mode="drop"
                )
                if has_sync:
                    tcnt = tcnt.at[jnp.where(is_barrier, htile, n_tiles)].add(
                        1, mode="drop"
                    )
                extra_home = ccl * (tcnt[btile] - 1)  # valid where home_txn
                extra_bar = ccl * (tcnt[htile] - 1)  # valid where is_barrier
            cnt = cadd(
                cnt,
                "noc_contention_cycles",
                jnp.where(home_txn, extra_home, 0)
                + (jnp.where(is_barrier, extra_bar, 0) if has_sync else 0),
            )
    else:
        extra_home = extra_bar = jnp.zeros(C, jnp.int32)

    with jax.named_scope(P_DIR):
        llc_hit = llc_has & winner
        llc_miss = winner & ~llc_has

        has_owner = llc_hit & (owner >= 0) & (owner != arange_c)
        oclamp = jnp.maximum(owner, 0)
        otile = oclamp % n_tiles
        po_lat, po_hops = _one_way(btile, otile, cfg, kn)  # bank -> owner (symmetric back)
        if cfg.faults_enabled:
            # probe legs keep the analytic model's symmetric round-trip shape
            # (2 * po_lat): the forward-leg fault penalty is charged both
            # ways. Safe to bump in place — nothing downstream decomposes the
            # probe leg the way the router block decomposes req/rep.
            fx_po, fh_po, rr_po = leg_fault_penalty(
                cfg, st.faults, kn, btile, otile
            )
            po_lat = po_lat + fx_po
            po_hops = po_hops + fh_po

        is_write_req = getm | upg
        gets_w = gets & winner
        write_w = is_write_req & winner

        # --- GETS grant decision (other_sharers from the phase-1 popcount)
        gets_probe = gets_w & llc_hit & has_owner
        gets_shared = gets_w & llc_hit & ~has_owner & other_sharers
        gets_excl_hit = gets_w & llc_hit & ~has_owner & ~other_sharers

        write_probe = write_w & llc_hit & has_owner

        # --- LLC miss: victim + back-invalidation
        if pallas_step:
            # [PALLAS] victim chosen inside probe_classify (first-minimum
            # LRU over valid ways, identical tie-breaking); vic_shw is a
            # kernel output
            from ..kernels.step_kernels import (
                PL_LLC_VWAY,
                PL_VIC_OWNER,
                PL_VIC_TAG,
            )

            vic_tag = pc_lanes[:, PL_VIC_TAG]
            vic_owner = pc_lanes[:, PL_VIC_OWNER]
            llc_vway = pc_lanes[:, PL_LLC_VWAY]
        else:
            llc_state_valid = llc_tag_rows != -1
            llc_lru_rows = meta_rows[:, 2 * W2 : 3 * W2]  # [C, W2], row gather
            vkey = jnp.where(llc_state_valid, llc_lru_rows, -1)
            llc_vway = jnp.argmin(vkey, axis=1).astype(jnp.int32)
            vic_tag = llc_tag_rows[arange_c, llc_vway]
            vic_owner = owner_rows[arange_c, llc_vway]
            vic_shw = jnp.take_along_axis(
                sh_rows, llc_vway[:, None, None], axis=1
            )[:, 0]
        vic_valid = llc_miss & (vic_tag != -1)

        # --- invalidation + back-invalidation target reductions. Targets come
        # from the packed sharer words (write invalidations to the accessed
        # line's sharers excluding self; back-invalidations to the victim's
        # sharers PLUS its owner — golden adds the owner to vtargets when not
        # already recorded). The reduction is the dense [C, C] expansion
        # (fastest at <= 1024 cores), a lax.scan over K-word blocks bounding
        # temporaries to [C, 32K] (cfg.sharer_chunk_words; BASELINE rung 4),
        # or — under the coarse vector — per-GROUP table reductions sized
        # [C, n_groups] with NO per-core expansion at all (BASELINE rung 5:
        # 16384 cores x 256 groups). Each is bit-exact vs the golden model
        # under the same config.
        inv_row = write_w & llc_hit
        if cfg.sharer_group > 1:
            with jax.named_scope(_GRP):
                n_grp = cfg.n_sharer_groups
                memb_n, max2hops_n, sum2hops_n = _group_tables(cfg)
                memb = jnp.asarray(memb_n)
                max2hops = jnp.asarray(max2hops_n)
                sum2hops = jnp.asarray(sum2hops_n)
                bit5 = jnp.arange(32, dtype=jnp.int32)

                def _group_bools(words):  # [C, NW] -> [C, n_grp]
                    b = (words[:, :, None] >> bit5[None, None, :]) & 1
                    return b.reshape(C, NW * 32)[:, :n_grp] != 0

                grp = _group_bools(shw)
                vic_grp = _group_bools(vic_shw)
                # round-trip latency 2*(h*link + (h+1)*router) is monotone
                # nondecreasing in hop count, so the per-group max over members
                # is the latency AT the max hop count — the geometry-only hops
                # table composes with the TRACED link/router knobs here
                mh_rows = max2hops[btile]  # [C, n_grp]
                ml_rows = 2 * (mh_rows * kn.link_lat + (mh_rows + 1) * kn.router_lat)
                sumh_rows = sum2hops[btile]
                selfg = jnp.arange(n_grp, dtype=jnp.int32)[None, :] == g_c[:, None]
                self_rec = jnp.any(grp & selfg, axis=1)  # requester's group flagged
                # serialization latency spans every recorded core of flagged
                # groups INCLUDING the requester's slot (golden: the home node
                # serializes the whole group broadcast); messages/counters skip
                # the requester
                inv_lat = jnp.where(
                    inv_row,
                    jnp.max(jnp.where(grp, ml_rows, 0), axis=1),
                    0,
                )
                inv_count = jnp.where(
                    inv_row,
                    jnp.sum(jnp.where(grp, memb[None, :], 0), axis=1)
                    - self_rec.astype(jnp.int32),
                    0,
                )
                _, self_hops = _one_way(btile, ctile, cfg, kn)
                inv_hops = jnp.where(
                    inv_row,
                    jnp.sum(jnp.where(grp, sumh_rows, 0), axis=1)
                    - jnp.where(self_rec, 2 * self_hops, 0),
                    0,
                )
                # back-invalidation: every recorded core of the victim's flagged
                # groups, plus its owner when not already recorded
                og = jnp.maximum(vic_owner, 0) >> logG
                own_rec = (
                    jnp.take_along_axis(vic_grp, og[:, None], axis=1)[:, 0]
                    & (vic_owner >= 0)
                )
                own_extra = (vic_owner >= 0) & ~own_rec
                _, own_hops = _one_way(
                    btile, jnp.maximum(vic_owner, 0) % n_tiles, cfg, kn
                )
                back_count = jnp.where(
                    vic_valid,
                    jnp.sum(jnp.where(vic_grp, memb[None, :], 0), axis=1)
                    + own_extra.astype(jnp.int32),
                    0,
                )
                back_hops = jnp.where(
                    vic_valid,
                    jnp.sum(jnp.where(vic_grp, sumh_rows, 0), axis=1)
                    + jnp.where(own_extra, 2 * own_hops, 0),
                    0,
                )
        elif cfg.sharer_chunk_words:
            K = cfg.sharer_chunk_words
            nblk = NW // K
            bit5 = jnp.arange(32, dtype=jnp.int32)

            def _blk(carry, b):
                il, ic, ih, bc, bh = carry
                off = b * K
                sw = jax.lax.dynamic_slice_in_dim(shw, off, K, axis=1)
                vw = jax.lax.dynamic_slice_in_dim(vic_shw, off, K, axis=1)
                tt = off * 32 + jnp.arange(K * 32, dtype=jnp.int32)  # target ids
                tvalid = tt[None, :] < C  # padding bits beyond core C-1
                bits = (
                    ((sw[:, :, None] >> bit5[None, None, :]) & 1).reshape(C, K * 32)
                    != 0
                )
                vbits = (
                    ((vw[:, :, None] >> bit5[None, None, :]) & 1).reshape(C, K * 32)
                    != 0
                )
                plat, phops = _one_way(
                    btile[:, None], (tt % n_tiles)[None, :], cfg, kn
                )
                sh_b = (
                    bits
                    & (tt[None, :] != arange_c[:, None])
                    & inv_row[:, None]
                    & tvalid
                )
                il = jnp.maximum(il, jnp.max(jnp.where(sh_b, 2 * plat, 0), axis=1))
                ic = ic + jnp.sum(sh_b, axis=1).astype(jnp.int32)
                ih = ih + jnp.sum(jnp.where(sh_b, 2 * phops, 0), axis=1).astype(
                    jnp.int32
                )
                ob = (tt[None, :] == vic_owner[:, None]) & (vic_owner >= 0)[:, None]
                bk_b = (vbits | ob) & vic_valid[:, None] & tvalid
                bc = bc + jnp.sum(bk_b, axis=1).astype(jnp.int32)
                bh = bh + jnp.sum(jnp.where(bk_b, 2 * phops, 0), axis=1).astype(
                    jnp.int32
                )
                return (il, ic, ih, bc, bh), None

            z5 = jnp.zeros(C, jnp.int32)
            (inv_lat, inv_count, inv_hops, back_count, back_hops), _ = jax.lax.scan(
                _blk, (z5, z5, z5, z5, z5), jnp.arange(nblk, dtype=jnp.int32)
            )
        elif cfg.pallas_reduce or pallas_step:
            # same dense reduction as the branch below, as ONE Pallas kernel
            # (SURVEY §2 #4's Pallas uncore piece; the step subsystem's third
            # resident kernel — step_impl="pallas" routes it unconditionally);
            # bit-identical. Latencies are the TRACED knobs, so fleet sweeps
            # through this kernel compile once per geometry.
            from ..kernels.reductions import sharer_reductions

            (inv_lat, inv_count, inv_hops, back_count, back_hops) = (
                sharer_reductions(
                    cfg, shw, vic_shw, btile, vic_owner, inv_row, vic_valid,
                    arange_c, kn.link_lat, kn.router_lat,
                )
            )
        else:
            ttile = arange_c % n_tiles  # target tiles
            pair_lat, pair_hops = _one_way(btile[:, None], ttile[None, :], cfg, kn)
            sh_bits = unpack_bits(shw)
            sh_bits = sh_bits & (arange_c[None, :] != arange_c[:, None])
            inv_pairs = sh_bits & inv_row[:, None]  # [C, C]
            inv_lat = jnp.max(jnp.where(inv_pairs, 2 * pair_lat, 0), axis=1)
            inv_count = jnp.sum(inv_pairs, axis=1).astype(jnp.int32)
            inv_hops = jnp.sum(jnp.where(inv_pairs, 2 * pair_hops, 0), axis=1).astype(jnp.int32)
            vic_sh_bits = unpack_bits(vic_shw)
            vic_owner_bit = (arange_c[None, :] == vic_owner[:, None]) & (vic_owner >= 0)[:, None]
            back_pairs = (vic_sh_bits | vic_owner_bit) & vic_valid[:, None]
            back_count = jnp.sum(back_pairs, axis=1).astype(jnp.int32)
            back_hops = jnp.sum(jnp.where(back_pairs, 2 * pair_hops, 0), axis=1).astype(jnp.int32)

        # --- stride prefetcher (DESIGN.md §25; cfg.prefetcher static) ---------
        # Per-core stride detector over the UNCORE access stream (winners +
        # joins — the retired home transactions; retries re-observe the same
        # line next step and must not retrain). An LLC miss whose line sits
        # within prefetch_degree strides ahead of the last trained access on
        # a confirmed stride (streak >= 2) is served from the prefetch buffer:
        # it pays the TRACED prefetch_lat instead of dram_lat and skips the
        # memory-controller queue. dram_accesses still counts every LLC miss
        # (the prefetcher moved the fetch earlier, it did not remove it);
        # prefetch_hits counts the covered ones. State is step-entry: at most
        # one retiring uncore event per core per step, and joins train only
        # their own core, so read-then-train is race-free.
        if cfg.prefetcher == "stride":
            pfl, pfs, pfk = st.pf_line, st.pf_stride, st.pf_streak
            safe_s = jnp.where(pfs == 0, 1, pfs)
            delta = line - pfl
            qd = delta // safe_s
            rem = delta - qd * safe_s
            pf_hit = (
                llc_miss & (pfs != 0) & (pfk >= 2) & (rem == 0)
                & (qd >= 1) & (qd <= kn.prefetch_degree)
            )
            miss_dram = llc_miss & ~pf_hit  # misses that still go to DRAM
            cnt = cadd(cnt, "prefetch_hits", pf_hit)
            pf_train = winner | join
            new_stride = line - pfl
            pf_streak_n = jnp.where(
                pf_train,
                jnp.where((new_stride == pfs) & (pfs != 0), pfk + 1, 1),
                pfk,
            )
            pf_stride_n = jnp.where(pf_train, new_stride, pfs)
            pf_line_n = jnp.where(pf_train, line, pfl)
        else:
            pf_hit = jnp.zeros(C, bool)
            miss_dram = llc_miss
            pf_line_n = st.pf_line
            pf_stride_n = st.pf_stride
            pf_streak_n = st.pf_streak

    # --- memory-controller queue (cfg.dram_queue, SURVEY §2 #7) -----------
    # Miss winners queue at their home bank's controller: wait floor =
    # max(dram_free[bank], bank's earliest nominal arrival this step) +
    # rank*service — the router model's FIFO shape on a per-bank clock.
    # Ranks via the shared sort-based segmented-rank primitive (one dense
    # key order feeds this block AND the router walk); bit-exact vs
    # golden (tests/test_dram.py).
    if cfg.dram_queue or router:
        with (jax.named_scope(P_DRAM if cfg.dram_queue else P_NOC),
              jax.named_scope(_RANK)):
            ord_c = lane_order(key)
    if cfg.dram_queue:
        with jax.named_scope(P_DRAM):
            svc_d = jnp.where(kn.dram_service > 0, kn.dram_service, kn.dram_lat)
            a_nom = (
                cycles_c + epre * cpi_vec + l1_lat + req_lat
                + llc_lat
            )
            dtgt = jnp.where(miss_dram, bank, B)
            dbase = jnp.full(B, INT32_MAX, jnp.int32).at[dtgt].min(
                a_nom, mode="drop"
            )
            # non-miss lanes carry the sentinel segment: their rd is garbage
            # the where/drop masks below never let escape (same tolerance the
            # matmul path's full-table gather relied on)
            with jax.named_scope(_RANK):
                rd = segmented_rank(dtgt[:, None], n_seg=B, order=ord_c)[:, 0]
            dstart = jnp.maximum(
                a_nom,
                jnp.maximum(st.dram_free[bank], dbase[bank]) + rd * svc_d,
            )
            extra_dram = jnp.where(miss_dram, dstart - a_nom, 0)
            dram_free_n = st.dram_free.at[dtgt].max(dstart + svc_d, mode="drop")
            cnt = cadd(cnt, "dram_queue_cycles", extra_dram)
    else:
        extra_dram = jnp.zeros(C, jnp.int32)
        dram_free_n = st.dram_free

    with jax.named_scope(P_COMMIT):
        # --- latency composition (golden order)
        probe_any = gets_probe | write_probe
        # service interval between the request's arrival at the home bank and
        # the reply's injection: LLC lookup + probe legs + invalidation waits
        # + controller queueing + DRAM (memory lanes), plain LLC lookup
        # (joins, lock/unlock RMWs)
        dram_term = jnp.where(miss_dram, kn.dram_lat, 0)
        if cfg.prefetcher != "none":
            # prefetch-covered misses pay the (traced) buffer latency instead
            dram_term = dram_term + jnp.where(pf_hit, kn.prefetch_lat, 0)
        service = jnp.where(
            winner,
            llc_lat
            + jnp.where(probe_any, 2 * po_lat, 0)
            + jnp.where(write_w & llc_hit, inv_lat, 0)
            + dram_term
            + extra_dram,
            llc_lat,
        )
        link_free_n = st.link_free
    if router:
        with jax.named_scope(P_NOC):
            # ---- hop-by-hop router (golden _route/_route_rt, vectorized) ----
            # Model: every directed link keeps a next-free clock carried
            # across steps; a packet waits at link l for
            #   max(link_free[l], base[l]) + rank_l * link_lat
            # (base = the link's earliest NOMINAL same-step arrival, rank =
            # packets on l with smaller (clock, core) key — FIFO
            # serialization at link_lat per packet), then occupies the link
            # for link_lat and pays router_lat at the next router; waits
            # cascade into later hops. The cascade has a closed form: with
            # F_k the wait floor at hop k and c = link_lat + router_lat,
            #   t_k = max(t0 + router_lat, cummax_{k'<=k}(F_k' - k'c)) + kc
            # so one cummax per path replaces the sequential walk, and the
            # per-link departures feed one scatter-max into link_free. Ranks
            # come from the shared sort-based segmented-rank primitive
            # (ops/ranking.py, DESIGN.md §13): O(E log E) over the flattened
            # (link, key) entries instead of the historical O(C²·NL) one-hot
            # matmul, integer-equal by construction. Bit-exact vs the golden
            # scalar walk (tests/test_router.py).
            from ..noc.mesh import n_links

            NL = n_links(cfg)
            L_lat = kn.link_lat
            R_lat = kn.router_lat
            c_hop = kn.link_lat + kn.router_lat
            SENT = jnp.int32(-(1 << 30) - (1 << 21))  # < any real wait floor
            req_p = _path_links(cfg, ctile, btile)  # [C, H]
            rep_p = _path_links(cfg, btile, ctile)
            arr_p = _path_links(cfg, ctile, htile)
            H = req_p.shape[1]
            hidx = jnp.arange(H, dtype=jnp.int32)[None, :]
            first_lock = is_lock & (st.sync_flag == 0)
            mem_lane = winner | join
            pre_chg = mem_lane | is_unlock | first_lock | is_barrier
            t0 = (
                cycles_c
                + jnp.where(pre_chg, epre * cpi_vec, 0)
                + jnp.where(mem_lane, l1_lat, 0)
            )
            # nominal (uncontended) arrival at each hop; reply legs anchor
            # at llc.latency service by definition (golden _bump)
            a_req = t0[:, None] + R_lat + hidx * c_hop
            a_rep = (
                t0[:, None]
                + R_lat
                + req_hops[:, None] * c_hop
                + llc_lat
                + R_lat
                + hidx * c_hop
            )
            # EVERY per-link operation runs once over the concatenated paths
            # ([C, 2H] legs, or [C, 3H] with the barrier-arrival leg): one
            # segmented rank, one base scatter-min, one link_free/base gather
            # pair — per-kernel overhead is the budget, so per-path loops are
            # per-path kernels. The per-(lane, segment) uniqueness contract
            # of segmented_rank holds by construction: request and reply
            # legs traverse reversed DIRECTED links (distinct ids), and the
            # barrier-arrival leg is masked to barrier lanes, disjoint from
            # home-transaction lanes.
            pth_all, mask_all = _concat_legs(
                [(req_p, home_txn), (rep_p, home_txn)]
                + ([(arr_p, is_barrier)] if has_sync else [])
            )
            a_all = jnp.concatenate(
                [a_req, a_rep] + ([a_req] if has_sync else []), axis=1
            )
            ok_all = mask_all & (pth_all >= 0)
            tgt_all = jnp.where(ok_all, pth_all, NL)
            base = jnp.full(NL, INT32_MAX, jnp.int32).at[tgt_all].min(
                a_all, mode="drop"
            )
            # packets ahead of lane i in each hop's same-step FIFO, ordered
            # by the phase-2 arbitration key (masked slots carry garbage the
            # SENT select below discards, as the matmul table gather did)
            with jax.named_scope(_RANK):
                r_all = segmented_rank(tgt_all, n_seg=NL, order=ord_c)
            pc_all = jnp.where(pth_all >= 0, pth_all, 0)
            lf_g = st.link_free[pc_all]  # [C, legs*H] per-hop gather pair —
            bs_g = base[pc_all]  # data-dependent rows, staged in XLA (§13)
            arr_lat_a, arr_hops = _one_way(ctile, htile, cfg, kn)
            if pallas_step:
                # [PALLAS] wait floors + per-leg cummax cascades + departure
                # composition fused in one VMEM kernel (router_kernels.py);
                # the link_free/base row gathers above and the departure
                # scatter-max below stay XLA — the one access shape the
                # block model cannot express (same boundary as the commit
                # kernel's dirm row scatter)
                from ..kernels.router_kernels import router_cascade

                t_rep_end, t_arr_end, d_all = router_cascade(
                    lf_g, bs_g, r_all, ok_all, t0, service, req_hops,
                    rep_hops, arr_hops, L_lat, R_lat, has_sync=has_sync,
                )
            else:
                F_all = jnp.where(
                    ok_all, jnp.maximum(lf_g, bs_g) + r_all * L_lat, SENT
                )  # [C, legs*H] wait floors

                def _cascade(t_start, F, nh):
                    G = F - hidx * c_hop
                    cum = jax.lax.cummax(G, axis=1)
                    t1 = t_start + R_lat
                    t_end = jnp.maximum(t1, cum[:, -1]) + nh * c_hop
                    departs = (
                        jnp.maximum(t1[:, None], cum) + hidx * c_hop + L_lat
                    )
                    return t_end, departs

                t_req_end, d_req = _cascade(t0, F_all[:, :H], req_hops)
                t_rep_end, d_rep = _cascade(
                    t_req_end + service, F_all[:, H : 2 * H], rep_hops
                )
                deps = [d_req, d_rep]
                if has_sync:
                    t_arr_end, d_arr = _cascade(t0, F_all[:, 2 * H :], arr_hops)
                    deps.append(d_arr)
                d_all = jnp.concatenate(deps, axis=1)
            raw_rt = t_rep_end - t0  # valid on home_txn lanes
            extra_home = raw_rt - (req_lat + service + rep_lat)
            if has_sync:
                raw_arr = t_arr_end - t0  # valid on barrier lanes
                extra_bar = raw_arr - arr_lat_a
            link_free_n = st.link_free.at[tgt_all].max(d_all, mode="drop")
            cnt = cadd(
                cnt,
                "noc_contention_cycles",
                jnp.where(home_txn, extra_home, 0)
                + (jnp.where(is_barrier, extra_bar, 0) if has_sync else 0),
            )
    with jax.named_scope(P_COMMIT):
        if router:
            lat = l1_lat + raw_rt  # memory lanes (service included)
            lat_join = lat
        else:
            lat = l1_lat + req_lat + service + rep_lat + extra_home
            # join path: same shape — service is llc.latency on join lanes
            lat_join = (
                l1_lat + req_lat + llc_lat + rep_lat + extra_home
            )
        if cfg.faults_enabled:
            # detour/degrade extras of the request+reply legs join the
            # composed round trip here (see the leg computation above); the
            # hop counts bump with their detours for the counter fold and the
            # phase-2.7 lock legs, now that the router walk is done with the
            # nominal values
            lat = lat + flt_rt
            lat_join = lat_join + flt_rt
            req_hops = req_hops + fh_req
            rep_hops = rep_hops + fh_rep
        ov = cfg.core.o3_overlap_256
        if ov:
            lat = lat - ((lat * ov) >> 8)
            lat_join = lat_join - ((lat_join * ov) >> 8)

        # --- granted L1 state (joins always take S)
        grant = jnp.where(
            join,
            S,
            jnp.where(
                write_w,
                M,
                jnp.where(gets_probe | gets_shared, S, E),  # GETS: E on excl/miss
            ),
        )

        # ---- counters for winners + joins -----------------------------------
        cnt = cadd(cnt, "l1_read_misses", gets_w | join)
        cnt = cadd(cnt, "l1_write_misses", getm & winner)
        cnt = cadd(cnt, "upgrades", upg & winner)
        cnt = cadd(cnt, "llc_hits", llc_hit | join)
        cnt = cadd(cnt, "llc_misses", llc_miss)
        cnt = cadd(cnt, "dram_accesses", llc_miss)
        cnt = cadd(cnt, "llc_writebacks", llc_miss & vic_valid & (vic_owner >= 0))
        cnt = cadd(cnt, "probes", probe_any)
        cnt = cadd(cnt, "invalidations", jnp.where(write_w & llc_hit, inv_count, 0) + back_count)
        noc_msgs = (
            jnp.where(winner | join, 2, 0)  # request + reply
            + jnp.where(probe_any, 2, 0)
            + jnp.where(write_w & llc_hit, 2 * inv_count, 0)
            + jnp.where(llc_miss, 2, 0)  # DRAM (co-located controller)
            + 2 * back_count
        )
        noc_hops = (
            jnp.where(winner | join, req_hops + rep_hops, 0)
            + jnp.where(probe_any, 2 * po_hops, 0)
            + jnp.where(write_w & llc_hit, inv_hops, 0)
            + back_hops
        )
        cnt = cadd(cnt, "noc_msgs", noc_msgs)
        cnt = cadd(cnt, "noc_hops", noc_hops)
        if cfg.faults_enabled:
            # rerouted messages: one-way legs whose XY path crossed a dead
            # link (invalidation fan-outs keep their analytic group/pair
            # latencies — model scope, like the router walk's)
            cnt = cadd(
                cnt,
                "noc_reroutes",
                jnp.where(winner | join, rr_req + rr_rep, 0)
                + jnp.where(probe_any, 2 * rr_po, 0),
            )

        # ---- phase 4.A: local updates ----------------------------------------
        # retire + clock advance (memory events also charge their pre-batched
        # non-memory instructions: epre * cpi, PriME per-BBL batching)
        hit = read_hit | write_hit
        cnt = cadd(cnt, "l1_read_hits", read_hit)
        cnt = cadd(cnt, "l1_write_hits", write_hit)
        retired = is_ins | hit | winner | join
        mem_ret = hit | winner | join
        mem_lat = jnp.where(
            hit, l1_lat, jnp.where(join, lat_join, lat)
        )
        cycles = cycles_c + jnp.where(
            is_ins,
            earg * cpi_vec,
            jnp.where(mem_ret, epre * cpi_vec + mem_lat, 0),
        )
        ptr = ptr_c + retired.astype(jnp.int32)
        cnt = cadd(
            cnt,
            "instructions",
            jnp.where(is_ins, earg, 0) + jnp.where(mem_ret, epre + 1, 0),
        )

        if pallas_step:
            # [PALLAS] fused commit (DESIGN.md §11): victim choice and the
            # writeback counter stay in-register here (they feed cadd), and
            # the join-LRU representative scatter-min keeps its tiny XLA
            # table, but EVERY array write of phase 4.A — the 7 + 2*rl L1
            # plane writes, the directory row delta, and the stacked counter
            # fold — is deferred into ONE commit_step kernel call at the end
            # of the step (after phase 2.7 contributes its counter deltas).
            upg_in_place = upg & winner  # upg requires an L1 hit: in-place
            fill = (winner & ~upg_in_place) | join
            l1_vkey = jnp.where(weff == I, -1, lru_rows)
            l1_vway = jnp.argmin(l1_vkey, axis=1).astype(jnp.int32)
            cnt = cadd(
                cnt, "l1_writebacks", fill & (weff[arange_c, l1_vway] == M)
            )
            takes_own = write_w | gets_excl_hit | llc_miss
            st_val_m = jnp.where(write_hit, M, grant)
            jsw = jnp.where(join, slot * W2 + llc_hway, B * S2 * W2)
            jtab = jnp.full(B * S2 * W2, INT32_MAX, jnp.int32).at[jsw].min(
                key, mode="drop"
            )
            jrep = join & (
                jtab[jnp.minimum(slot * W2 + llc_hway, B * S2 * W2 - 1)] == key
            )
            upd_slot = jnp.where(winner | join, slot, B * S2)
            commit_lanes = jnp.stack(
                [
                    line,
                    hit_way,
                    l1_vway,
                    hit.astype(jnp.int32),
                    write_hit.astype(jnp.int32),
                    upg_in_place.astype(jnp.int32),
                    winner.astype(jnp.int32),
                    join.astype(jnp.int32),
                    llc_hit.astype(jnp.int32),
                    st_val_m,
                    slot,
                    llc_hway,
                    llc_vway,
                    jrep.astype(jnp.int32),
                    takes_own.astype(jnp.int32),
                    gets_probe.astype(jnp.int32),
                    gets_shared.astype(jnp.int32),
                    oclamp,
                ],
                axis=1,
            )  # column order = kernels.step_kernels CL_* indices
        else:
            # L1-side updates touch at most TWO (row, column) slots per core — the
            # retired way, and (for fills) a stale duplicate of the filled tag —
            # so each is a [C]-element scatter into the [C, W1*S1] arrays, not a
            # full-array one-hot select (which rewrites 4x8MB per step at 1024
            # cores). Rows are the core's own, columns flat way*S1 + set; masked
            # lanes scatter to dropped row C.

            # winner L1 update: UPG-in-place vs fill. Victim preference counts
            # directory-invalidated (stale) ways as free, matching eager-MESI's
            # invalid-first rule; the victim writeback fires only on EFFECTIVE M.
            upg_in_place = upg & winner  # upg requires an L1 hit: always in-place
            fill = (winner & ~upg_in_place) | join
            l1_vkey = jnp.where(weff == I, -1, lru_rows)  # lru_rows from the probe
            l1_vway = jnp.argmin(l1_vkey, axis=1).astype(jnp.int32)
            cnt = cadd(cnt, "l1_writebacks", fill & (weff[arange_c, l1_vway] == M))
            upd_way = jnp.where(upg_in_place, hit_way, l1_vway)
            hit_col = hit_way * S1 + l1s
            upd_col = upd_way * S1 + l1s

            # a fill may duplicate a stale way's tag: clear the stale copy so tags
            # stay unique per set (else the refill could "resurrect" it, since the
            # directory once again records this core for the line); uniqueness also
            # means at most one duplicate way exists
            tagm = tag_rows == line[:, None]  # [C, W1], any state
            t_way = jnp.argmax(tagm, axis=1).astype(jnp.int32)
            dup = fill & jnp.any(tagm, axis=1) & (t_way != upd_way)
            dup_row = jnp.where(dup, arange_c, C)
            dup_col = t_way * S1 + l1s

            wj = winner | join
            lru_row = jnp.where(hit | wj, arange_c, C)
            lru_col = jnp.where(hit, hit_col, upd_col)
            st_row = jnp.where(write_hit | wj, arange_c, C)  # silent E->M + grants
            st_col = jnp.where(write_hit, hit_col, upd_col)
            st_val = jnp.where(write_hit, M, grant)
            wj_row = jnp.where(wj, arange_c, C)
            # the filled line's directory entry position (way pointer); joins and
            # LLC hits fill at the line's hit way, misses at the victim
            fill_ptr = slot * W2 + jnp.where(join | llc_hit, llc_hway, llc_vway)
            # invalidation epoch: every sharer-CLEARING transition (M grants,
            # exclusive grants, fills — exactly the owner-taking ones) bumps the
            # entry's epoch so coarse-vector validation can reject pre-clearing
            # fill records (GETS probe/shared grants preserve sharers: no bump);
            # fills record the POST-bump value
            llc_uway = jnp.where(llc_hit, llc_hway, llc_vway)
            takes_own = write_w | gets_excl_hit | llc_miss
            eph_rows2 = meta_rows[:, 3 * W2 : 4 * W2]  # [C, W2]
            eph_way = jnp.where(join, llc_hway, llc_uway)
            new_eph = eph_rows2[arange_c, eph_way] + takes_own.astype(jnp.int32)
            # ALL of this step's L1 writes — the seven phase-4 columns AND the
            # local run's deferred LRU/E->M writes — in ONE scatter on the fused
            # plane array (per-kernel overhead dominates, and a second scatter
            # chained on the same array cannot alias its operand). Targets are
            # pairwise distinct up to benign identical-value duplicates:
            # dup_col != upd_col (a duplicate is a different way than the fill
            # target), hit refresh and grant rows are disjoint lane classes, each
            # write addresses its own plane, run-LRU duplicates of phase-4 LRU
            # writes carry the identical step stamp, and a run E->M colliding
            # with a phase-4 state write at the same way is SUPPRESSED (phase 4
            # wrote after the run in the serialized order, so its value wins).
            l1_rows = [dup_row, dup_row, lru_row, st_row, wj_row, wj_row, wj_row]
            l1_cols = [
                dup_col,  # stale duplicate tag clear
                dup_col + FS,  # stale duplicate state clear
                lru_col + 2 * FS,  # hit refresh / fill LRU stamp
                st_col + FS,  # silent E->M + grant state
                upd_col,  # fill tag
                upd_col + 3 * FS,  # fill way pointer
                upd_col + 4 * FS,  # fill-time entry epoch (post-bump)
            ]
            l1_vals = [
                jnp.full(C, -1, jnp.int32),
                jnp.full(C, I, jnp.int32),
                jnp.broadcast_to(step_no, (C,)),
                st_val,
                line,
                fill_ptr,
                new_eph,
            ]
            rows_mat = jnp.stack(l1_rows, axis=1)
            cols_mat = jnp.stack(l1_cols, axis=1)
            vals_mat = jnp.stack(l1_vals, axis=1)
            if rl:
                own_state_write = (st_row == arange_c)
                run_m_sup = wm & ~(own_state_write[:, None] & (st_col[:, None] == cm))
                rows_mat = jnp.concatenate(
                    [
                        rows_mat,
                        jnp.where(hm, arange_c[:, None], C),
                        jnp.where(run_m_sup, arange_c[:, None], C),
                    ],
                    axis=1,
                )
                cols_mat = jnp.concatenate(
                    [cols_mat, cm + 2 * FS, cm + FS], axis=1
                )
                vals_mat = jnp.concatenate(
                    [
                        vals_mat,
                        jnp.broadcast_to(step_no, (C, rl)),
                        jnp.full((C, rl), M, jnp.int32),
                    ],
                    axis=1,
                )
            l1_n = l1_c.at[rows_mat, cols_mat].set(vals_mat, mode="drop")

            # Directory update: ONE full-row scatter-ADD covers the winner's
            # whole row — tags, owner, LRU, epoch, AND sharer words — plus every
            # join's sharer bit (winner and join slots are disjoint: join slots
            # never have a winner). Winner rows carry the exact full-row delta
            # (new - old; exactly one winner per slot, so old + delta == new,
            # wrap-safe in int32); join rows contribute only the joiner's own
            # bit, masked against the step-start word (self_word & ~shw) so a
            # silently-evicted re-joiner's stale bit cannot carry into the
            # adjacent bit — golden's _set_sharer is idempotent, the masked add
            # matches it; multiple joiners per slot add distinct bits. Join LRU
            # refreshes land in a second element scatter (same-slot joiners write
            # the identical step stamp).
            new_owner = jnp.where(takes_own, arange_c, -1)
            if cfg.coherence == "moesi":
                # dirty sharing: a GETS probe LEAVES the probed owner recorded
                # (its line derives to Owned — DESIGN.md §25) instead of
                # clearing it; every other non-owning transition still clears.
                new_owner = jnp.where(gets_probe, oclamp, new_owner)
            wayeq = jnp.arange(W2, dtype=jnp.int32)[None, :] == llc_uway[:, None]
            new_meta = jnp.concatenate(
                [
                    jnp.stack(
                        [
                            jnp.where(wayeq, line[:, None], llc_tag_rows),
                            jnp.where(wayeq, new_owner[:, None], owner_rows),
                        ],
                        axis=-1,
                    ).reshape(C, 2 * W2),
                    jnp.where(wayeq, step_no, llc_lru_rows),
                    jnp.where(wayeq, new_eph[:, None], eph_rows2),
                    jnp.zeros((C, MW - 4 * W2), jnp.int32),
                ],
                axis=1,
            )

            # new sharer words [C, NW]
            self_word = (
                (jnp.arange(NW)[None, :] == word_idx[:, None]).astype(jnp.int32)
                << bit_idx[:, None]
            )  # bit(c) as packed words
            # the probed owner is re-recorded as a sharer unconditionally: the home
            # node cannot observe silent L1 evictions (golden does the same), and
            # this keeps the transition free of cross-core L1 reads — which under
            # core-axis sharding would all-gather the L1 arrays every step
            og_bit = oclamp >> logG  # owner's sharer-GROUP bit (identity at G=1)
            owner_word = jnp.where(
                jnp.arange(NW)[None, :] == (og_bit // 32)[:, None],
                jnp.int32(1) << (og_bit % 32)[:, None],
                0,
            )
            probe_word = self_word | owner_word
            if cfg.coherence == "moesi":
                # dirty sharing accumulates: existing sharers stay recorded
                # alongside requester + owner (shw == 0 here under mesi — any
                # owner-setting transition cleared it)
                probe_word = shw | probe_word
            new_shw = jnp.where(
                gets_probe[:, None],
                probe_word,
                jnp.where(
                    gets_shared[:, None],
                    shw | self_word,
                    jnp.zeros_like(shw),  # M grants, E grants, misses: cleared
                ),
            )
            way_seg = (
                jnp.arange(W2 * NW, dtype=jnp.int32)[None, :] // NW == llc_uway[:, None]
            )
            old_flat = sh_rows.reshape(C, W2 * NW)
            new_sh_row = jnp.where(
                way_seg,
                jnp.broadcast_to(new_shw[:, None, :], (C, W2, NW)).reshape(C, W2 * NW),
                old_flat,
            )
            join_seg = (
                jnp.arange(W2 * NW, dtype=jnp.int32)[None, :] // NW == llc_hway[:, None]
            )
            join_word = self_word & ~shw  # carry-free when the bit is already set
            join_sh_row = jnp.where(
                join_seg,
                jnp.broadcast_to(join_word[:, None, :], (C, W2, NW)).reshape(C, W2 * NW),
                0,
            )
            # Join LRU refreshes ride the SAME scatter-add: adds only commute for
            # identical targets if exactly one lane carries the delta, so a
            # per-(slot, way) scatter-min on the (small, 16 MB) representative
            # table picks one joiner per joined way to add (step_no - old_lru);
            # same-way co-joiners add zero. A second element scatter chained
            # after the row-add was measured at ~5 ms/step (round-5 ablation: any
            # read-modify-write scatter that cannot alias re-materializes the
            # 800 MB operand), so everything must go through the ONE add.
            jsw = jnp.where(join, slot * W2 + llc_hway, B * S2 * W2)
            jtab = jnp.full(B * S2 * W2, INT32_MAX, jnp.int32).at[jsw].min(
                key, mode="drop"
            )
            jrep = join & (
                jtab[jnp.minimum(slot * W2 + llc_hway, B * S2 * W2 - 1)] == key
            )
            old_lru_h = meta_rows[arange_c, 2 * W2 + llc_hway]
            lru_oh = (
                jnp.arange(MW, dtype=jnp.int32)[None, :]
                == (2 * W2 + llc_hway)[:, None]
            )
            join_meta = jnp.where(
                lru_oh, jnp.where(jrep, step_no - old_lru_h, 0)[:, None], 0
            )
            new_full = jnp.concatenate([new_meta, new_sh_row], axis=1)  # [C, DW]
            delta_row = jnp.where(
                winner[:, None],
                new_full - meta_rows,
                jnp.concatenate([join_meta, join_sh_row], axis=1),
            )
            upd_slot = jnp.where(winner | join, slot, B * S2)
            dirm_n = st.dirm.at[upd_slot].add(delta_row, mode="drop")

    # No phase 4.B: under pull-based coherence, the directory updates above
    # ARE the invalidations/downgrades — remote L1s re-derive their state on
    # their next access (phase 1 validation).

    # ---- phase 2.7: synchronization events (golden/sim.py phase 2.7) -----
    # Sync lanes (LOCK/UNLOCK/BARRIER) are disjoint from every memory lane
    # above (classification is by event type), so ordering after phase 4.A
    # is immaterial; WITHIN sync the canonical order is unlocks -> lock
    # grants -> barrier arrivals -> releases. `has_sync` is static: traces
    # without sync events (checked at ingest) skip this block entirely.
    lock_holder = st.lock_holder
    barrier_count = st.barrier_count
    barrier_time = st.barrier_time
    sync_flag = st.sync_flag
    if has_sync:
        with jax.named_scope(P_SYNC):
            L = cfg.lock_slots
            BS = cfg.barrier_slots
            # mutex address -> lock slot; its home is the line's home bank, so
            # the phase-3 core<->home-bank latencies/hops apply verbatim
            lslot = line & (L - 1)
            lreq_lat, lreq_hops = req_lat, req_hops
            lrep_lat, lrep_hops = rep_lat, rep_hops
            if router:
                # raw_rt already reflects this lane's per-class injection
                # time (pre charged on unlocks and first lock attempts only)
                lat_rt = raw_rt
            else:
                lat_rt = lreq_lat + llc_lat + lrep_lat + extra_home
            if cfg.faults_enabled:
                # lock/unlock RMWs ride the same core<->home-bank legs as the
                # memory path: same round-trip fault extra
                lat_rt = lat_rt + flt_rt

            # unlocks: every unlock is a charged RMW round trip to the lock's
            # home; the slot is released only if this core actually holds it
            cycles = cycles + jnp.where(is_unlock, epre * cpi_vec + lat_rt, 0)
            ptr = ptr + is_unlock.astype(jnp.int32)
            cnt = cadd(cnt, "instructions", jnp.where(is_unlock, epre + 1, 0))
            cnt = cadd(cnt, "noc_msgs", jnp.where(is_unlock, 2, 0))
            cnt = cadd(cnt, "noc_hops", jnp.where(is_unlock, lreq_hops + lrep_hops, 0))
            held = lock_holder[lslot] == arange_c
            lock_holder = lock_holder.at[
                jnp.where(is_unlock & held, lslot, L)
            ].set(-1, mode="drop")

            # lock grants: per-slot scatter-min arbitration on (cycles, core_id)
            # — the golden sort order, same key packing as the (bank,set) table
            # above (the same clock-window invariant covers it). Grant iff the
            # slot is free AFTER unlocks and this core holds the minimum key,
            # OR the core already holds the lock (re-acquire). At most one
            # grant per slot: free excludes re-acquire.
            rel_l = cycles_c - (quantum_end - Q)
            lkey = rel_l * C + arange_c
            ltable = jnp.full(L, INT32_MAX, jnp.int32)
            ltable = ltable.at[jnp.where(is_lock, lslot, L)].min(lkey, mode="drop")
            lwin = is_lock & (ltable[lslot] == lkey)
            holder1 = lock_holder[lslot]
            grant = is_lock & ((holder1 == arange_c) | ((holder1 == -1) & lwin))
            spin = is_lock & ~grant
            # every attempt (grant or spin) is a charged round trip; the pre
            # batch is charged only on the FIRST attempt (sync_flag still 0)
            first = is_lock & (st.sync_flag == 0)
            cycles = (
                cycles
                + jnp.where(first, epre * cpi_vec, 0)
                + jnp.where(is_lock, lat_rt, 0)
            )
            cnt = cadd(
                cnt,
                "instructions",
                jnp.where(first, epre, 0) + grant.astype(jnp.int32),
            )
            cnt = cadd(cnt, "lock_acquires", grant)
            cnt = cadd(cnt, "lock_spins", spin)
            cnt = cadd(cnt, "noc_msgs", jnp.where(is_lock, 2, 0))
            cnt = cadd(cnt, "noc_hops", jnp.where(is_lock, lreq_hops + lrep_hops, 0))
            if cfg.faults_enabled:
                cnt = cadd(
                    cnt,
                    "noc_reroutes",
                    jnp.where(is_unlock | is_lock, rr_req + rr_rep, 0),
                )
            lock_holder = lock_holder.at[jnp.where(grant, lslot, L)].set(
                arange_c, mode="drop"
            )
            sync_flag = jnp.where(grant, 0, jnp.where(spin, 1, sync_flag))
            ptr = ptr + grant.astype(jnp.int32)

            # barrier arrivals: charge pre + the arrival message, freeze the
            # core, bump the slot's count and max-arrival clock (bid/htile
            # hoisted above the contention block)
            barr_lat, barr_hops = _one_way(ctile, htile, cfg, kn)
            wake_lat, wake_hops = _one_way(htile, ctile, cfg, kn)
            barr_charge = raw_arr if router else barr_lat + extra_bar
            if cfg.faults_enabled:
                # barrier arrival and wake-up legs detour like any message
                fx_arr, fh_arr, rr_arr = leg_fault_penalty(
                    cfg, st.faults, kn, ctile, htile
                )
                fx_wk, fh_wk, rr_wk = leg_fault_penalty(
                    cfg, st.faults, kn, htile, ctile
                )
                barr_charge = barr_charge + fx_arr
                barr_hops = barr_hops + fh_arr
                wake_lat = wake_lat + fx_wk
                wake_hops = wake_hops + fh_wk
            cycles = cycles + jnp.where(
                is_barrier, epre * cpi_vec + barr_charge, 0
            )
            cnt = cadd(cnt, "instructions", jnp.where(is_barrier, epre, 0))
            cnt = cadd(cnt, "barrier_waits", is_barrier)
            cnt = cadd(cnt, "noc_msgs", is_barrier)
            cnt = cadd(cnt, "noc_hops", jnp.where(is_barrier, barr_hops, 0))
            if cfg.faults_enabled:
                cnt = cadd(
                    cnt, "noc_reroutes", jnp.where(is_barrier, rr_arr, 0)
                )
            sync_flag = jnp.where(is_barrier, 1, sync_flag)
            barrier_count = barrier_count.at[
                jnp.where(is_barrier, bid, BS)
            ].add(1, mode="drop")
            barrier_time = barrier_time.at[
                jnp.where(is_barrier, bid, BS)
            ].max(cycles, mode="drop")

            # releases: every waiter (frozen earlier or arrived this step) whose
            # slot count reached ITS participant count resumes at the slot's
            # max arrival clock + wake-up message. Waiters' ptr/event are
            # unchanged this step (frozen lanes retire nothing), so the phase-0.9
            # gather is still current for them.
            wait_m = (et == EV_BARRIER) & (sync_flag == 1)
            if cfg.faults_enabled:
                # fail-stop barrier relief (DESIGN.md §12): a dead core will
                # never arrive, so waiters must not require its arrival — the
                # barrier twin of the dead-holder lock release above. A dead
                # core ALREADY counted in a slot (it arrived, froze, then
                # died) still satisfies its own arrival, so it grants no
                # relief there. Like the lock idealization this is a recovery
                # semantics choice: exact for global barriers; a subset
                # barrier is relieved even by a dead non-participant (the
                # trace encodes participant COUNTS, not sets) — chaos mode
                # favors forward progress over subset fidelity.
                dead_counted = (
                    jnp.zeros(BS, jnp.int32)
                    .at[jnp.where(wait_m & deadb, bid, BS)]
                    .add(1, mode="drop")
                )
                missing = jnp.sum(deadb.astype(jnp.int32)) - dead_counted[bid]
                released = wait_m & (barrier_count[bid] + missing >= earg)
            else:
                released = wait_m & (barrier_count[bid] >= earg)
            cycles = jnp.where(released, barrier_time[bid] + wake_lat, cycles)
            cnt = cadd(cnt, "instructions", released)
            cnt = cadd(cnt, "noc_msgs", released)
            cnt = cadd(cnt, "noc_hops", jnp.where(released, wake_hops, 0))
            if cfg.faults_enabled:
                cnt = cadd(
                    cnt, "noc_reroutes", jnp.where(released, rr_wk, 0)
                )
            sync_flag = jnp.where(released, 0, sync_flag)
            ptr = ptr + released.astype(jnp.int32)
            nrel = (
                jnp.zeros(BS, jnp.int32)
                .at[jnp.where(released, bid, BS)]
                .add(1, mode="drop")
            )
            barrier_count = barrier_count - nrel
            drained = barrier_count <= 0
            barrier_count = jnp.where(drained, 0, barrier_count)
            barrier_time = jnp.where(drained, 0, barrier_time)

    with jax.named_scope(P_COMMIT):
        if pallas_step:
            # [PALLAS] end-of-step fused commit: by now phase 2.7's sync
            # counters have joined the delta accumulator, so ONE kernel call
            # performs every deferred array write of the step — the
            # 7 + 2*rl-column L1 plane scatter, the per-core directory row
            # delta, and the full counter fold. The single data-dependent
            # row scatter the block model cannot express stays in XLA.
            from ..kernels.step_kernels import commit_step

            l1_n, delta_row, counters_final = commit_step(
                cfg, l1_c, meta_rows, tag_rows, shw, commit_lanes, arange_c,
                step_no, cnt, cstack(),
                *((hm, wm, cm) if rl else ()),
            )
            dirm_n = st.dirm.at[upd_slot].add(delta_row, mode="drop")
        else:
            counters_final = cflush(cnt)

    return MachineState(
        cycles=cycles,
        ptr=ptr,
        l1=l1_n,
        dirm=dirm_n,
        link_free=link_free_n,
        dram_free=dram_free_n,
        lock_holder=lock_holder,
        barrier_count=barrier_count,
        barrier_time=barrier_time,
        sync_flag=sync_flag,
        quantum_end=quantum_end,
        step=step_no + 1,
        pf_line=pf_line_n,
        pf_stride=pf_stride_n,
        pf_streak=pf_streak_n,
        counters=counters_final,
        knobs=kn,
        # post-injection fault state (phase -1 rebound `st`); faults-off
        # this is the untouched input pytree
        faults=st.faults,
    )


@functools.partial(
    jax.jit, static_argnums=(0, 1), static_argnames=("has_sync",)
)
def run_chunk(
    cfg: MachineConfig, n_steps: int, events, st: MachineState,
    has_sync: bool = True,
):
    """lax.scan over `n_steps` steps — the jitted hot loop."""

    def body(carry, _):
        return step(cfg, events, carry, has_sync=has_sync), None

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


def _device_done(events, st, arange_c, faults_enabled=False):
    T = events.shape[1]
    p = jnp.minimum(st.ptr, T - 1)
    done = events[arange_c, p, 0] == EV_END
    if faults_enabled:
        # a fail-stopped core never reaches its END marker; it is done by
        # decree, so a run with injected fail-stops still terminates
        done = done | (st.faults.core_dead != 0)
    return jnp.all(done)


def _drain_and_rebase(cfg, st, acc_lo, acc_hi, base_lo, base_hi, nd):
    """On-device housekeeping shared by run_loop and stream_loop: drain
    int32 step counters into (lo, hi) carry pairs (hi above 2^30), and
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


@functools.partial(
    jax.jit, static_argnums=(0, 1), static_argnames=("has_sync",)
)
def run_loop(cfg: MachineConfig, chunk_steps: int, events, st: MachineState,
             max_chunks, has_sync: bool = True):
    """ONE dispatched device program for a whole simulation run.

    `lax.while_loop` over scan chunks; after each chunk, ON DEVICE: drain
    int32 step counters into (lo, hi) int32 accumulator pairs (hi carries
    above 2^30, so per-chunk per-core increments must stay < 2^30), rebase
    the epoch-relative clocks by a multiple of the quantum (preserving
    barrier arithmetic) so int32 never overflows, and test termination.
    This replaces the reference's per-quantum MPI barrier + host polling
    (SURVEY.md §3.4) with zero host round-trips until the run completes.
    """
    C = cfg.n_cores
    T = events.shape[1]
    arange_c = jnp.arange(C, dtype=jnp.int32)

    def cond(carry):
        st, acc_lo, acc_hi, base_lo, base_hi, k = carry
        with jax.named_scope(P_CHUNK):
            return (k < max_chunks) & ~_device_done(
                events, st, arange_c, cfg.faults_enabled
            )

    def body(carry):
        st, acc_lo, acc_hi, base_lo, base_hi, k = carry

        def sbody(c, _):
            return step(cfg, events, c, has_sync=has_sync), None

        st, _ = jax.lax.scan(sbody, st, None, length=chunk_steps)
        with jax.named_scope(P_CHUNK):
            p = jnp.minimum(st.ptr, T - 1)
            nd = events[arange_c, p, 0] != EV_END
            if cfg.faults_enabled:
                # dead cores must not bound the rebase minimum: their frozen
                # clocks would pin delta at 0 forever (int32 overflow risk on
                # long post-fault runs)
                nd = nd & (st.faults.core_dead == 0)
            st, acc_lo, acc_hi, base_lo, base_hi = _drain_and_rebase(
                cfg, st, acc_lo, acc_hi, base_lo, base_hi, nd
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


@functools.partial(
    jax.jit, static_argnums=(0,), static_argnames=("has_sync",)
)
def stream_loop(cfg: MachineConfig, events, st: MachineState, exhausted,
                filled, max_steps, has_sync: bool = True):
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
    run_loop.
    """
    C = cfg.n_cores
    T = events.shape[1]
    need = cfg.local_run_len + 1
    arange_c = jnp.arange(C, dtype=jnp.int32)

    def at_end(s):
        p = jnp.minimum(s.ptr, T - 1)
        done = events[arange_c, p, 0] == EV_END
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
        st = step(cfg, events, st, has_sync=has_sync)
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
        self.events = jnp.asarray(trace.line_events(cfg.line_bits))
        self.state = init_state(cfg)
        self.mesh = mesh
        if mesh is not None:
            # multi-chip: lay cores/banks out over the tile axis (parallel/)
            from ..parallel.sharding import shard_events, shard_state

            self.events = shard_events(mesh, self.events)
            self.state = shard_state(mesh, self.state)
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
        self.steps_run = 0
        # telemetry sink (obs.Recorder) — None means the chunked loops
        # report to nobody (they still read the clock at each cut); the
        # fused run() never consults it at all (DESIGN.md §15 overhead
        # contract)
        self.obs = None
        self.obs_label = "engine"
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
        cnt = _np(self.state.counters)
        for i, k in enumerate(COUNTER_NAMES):
            self.host_counters[k] += cnt[i].astype(np.int64)
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
        # the two host spans of a fused run, on the profiler's own clock
        # beside the device ops (DESIGN.md §15); with no profiler attached
        # a TraceAnnotation costs tens of nanoseconds
        with jax.profiler.TraceAnnotation("engine.dispatch"):
            st, acc_lo, acc_hi, base_lo, base_hi, k = exec_cache.call(
                run_loop, "engine.run_loop",
                (self.cfg, self.chunk_steps),
                (self.events, self.state, jnp.asarray(max_chunks, jnp.int32)),
                {"has_sync": self.has_sync},
            )
        with jax.profiler.TraceAnnotation("engine.readback"):
            # one synchronizing transfer for everything the host needs
            acc_lo = _np(acc_lo).astype(np.int64)
            acc_hi = _np(acc_hi).astype(np.int64)
            total = (acc_hi << _ACC_BITS) + acc_lo
            for i, name in enumerate(COUNTER_NAMES):
                self.host_counters[name] += total[i]
            self.cycle_base += (
                np.int64(np.asarray(base_hi)) << _ACC_BITS
            ) + np.int64(np.asarray(base_lo))
            self.state = st
            self.steps_run += int(np.asarray(k)) * self.chunk_steps
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
        span = jax.profiler.TraceAnnotation
        while self.steps_run < target and not self.done():
            # the cuts of a chunk, each one interval twice over: a host
            # span in the profiler's trace (DESIGN.md §15) and, under
            # --obs, a phase timing. dispatch is the async enqueue; drain's
            # host transfer synchronizes, so "drain" includes the device
            # executing the chunk; rebase is pure host work
            t0 = time.perf_counter()
            with span("engine.chunk.dispatch"):
                self._dispatch_chunk()
            t1 = time.perf_counter()
            self.steps_run += self.chunk_steps
            with span("engine.chunk.drain"):
                self._drain()
            t2 = time.perf_counter()
            with span("engine.chunk.rebase"):
                self._rebase()
            t3 = time.perf_counter()
            phases = {"dispatch": t1 - t0, "drain": t2 - t1,
                      "rebase": t3 - t2}
            if self.overlap and not self.done():
                with span("engine.chunk.prefetch"):
                    self._prefetch_chunk()
                phases["prefetch"] = time.perf_counter() - t3
            if self.obs is not None:
                self.obs.chunk_committed(
                    self.obs_label, self.chunk_steps, t3 - t0,
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
        self._drain()
        return self.host_counters
