"""The simulation step as a list of its phases (DESIGN.md §15).

`step()` advances every target core by up to `local_run_len` local
events (INS batches, L1 hits) plus at most one arbitrated uncore event,
implementing DESIGN.md's canonical per-step semantics branchlessly:

- CoreManager's per-core cycle tick (SURVEY.md §2 #2) is a masked lane
  update over the core axis (the `jax.vmap`-shaped dimension, fused by XLA).
- The private-cache lookup (#3), directory-MESI transition (#4), mesh-NoC
  latency (#6), and DRAM charge (#7) are `where`-chains + gathers/scatters
  over `[C]`-shaped lanes — no data-dependent Python control flow.
- The uncore request serializer (#5: `System::sim()` worker loop) becomes a
  scatter-min arbitration: one winner per LLC (bank,set) per step.
- The relaxed quantum barrier (#10) is the active-mask + quantum_end bump.
- Local runs (#1/#3.2: PriME's non-memory path never crosses a process
  boundary) retire private-hit runs without paying a full step.

Its body is the phases in execution order. Each phase is ONE module-level
function (a phase that another phase interrupts is two or three, under
the same scope name) that opens its own `jax.named_scope`; its signature
is its whole interface. Two seams are wide and are records: `Request`
(what each core asks of the uncore this step) and `DirOutcome` (what the
directory decided about it); every other seam is plain arrays. The
functions are plain Python: a `jit`, `named_call` or `checkpoint` round
one would put a component into every instruction's `op_name` and change
what XLA sees. `PHASE_FUNCTIONS` says which functions write under which
scope; tests/test_phase_scopes.py holds the compiled program to it.

The step must match `primesim_tpu.golden.sim.GoldenSim` BIT-EXACTLY —
tests/test_parity.py enforces this on every workload generator.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..config.machine import MachineConfig
from ..noc import topology as _topo
from ..noc.mesh import concat_legs as _concat_legs
from ..noc.mesh import n_links
from ..noc.topology import path_links as _path_links
from ..ops.ranking import (
    lane_order,
    segmented_rank,
    segmented_rank_floor,
    segmented_table_max,
)
from ..parallel.sharding import least_of_entry, read_rows
from ..stats.counters import BLOCK_NAMES
from ..trace.device import DeviceTrace
from ..trace.format import (
    EV_BARRIER,
    EV_END,
    EV_INS,
    EV_LD,
    EV_LOCK,
    EV_ST,
    EV_UNLOCK,
)
from .state import (
    E,
    I,
    M,
    MachineState,
    O,
    S,
    dirm_width,
    llc_meta_width,
)

INT32_MAX = np.int32(2**31 - 1)

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
_CHUNK = "chunk"  # second level: the full map's reductions in blocks of words
_LOCK = "lock"  # second level: unlocks and lock grants
_BARRIER = "barrier"  # second level: barrier arrivals and releases
_STAT = "stat"  # second level: what only the stat rows need (STAT_NAMES)
PHASES = (
    "s.fault",  # phase -1: fault injection
    "s.local",  # phase 0 quantum barrier + 0.5 local runs
    "s.probe",  # 0.9 + 1: the arbitration event, its L1 probe, classification
    "s.probe/" + _STAT,  # where the core-steps that present no event went
    "s.arb",  # 2: read-join coalescing, per-(bank,set) arbitration
    "s.dir",  # 3: directory transition, grants, victim, invalidation targets,
    #            prefetcher
    "s.dir/" + _GRP,  # sharer_group > 1 only
    "s.dir/" + _CHUNK,  # sharer_chunk_words > 0 only
    "s.noc",  # NoC contention: tile/link counts, or the hop-by-hop router
    "s.noc/" + _RANK,
    "s.noc/" + _STAT,  # router only: its real entries, and their histogram
    "s.dram",  # memory-controller queue
    "s.dram/" + _RANK,
    "s.commit",  # latency composition, granted state, counters, phase 4.A,
    #               the end-of-step commit
    "s.sync",  # 2.7: locks and barriers
    "s.sync/" + _LOCK,
    "s.sync/" + _BARRIER,
    "s.chunk",  # run_loop's per-chunk drain, rebase and termination test
)
(P_FAULT, P_LOCAL, P_PROBE, P_ARB, P_DIR, P_NOC, P_DRAM, P_COMMIT, P_SYNC,
 P_CHUNK) = (p for p in PHASES if "/" not in p)

# Which functions write under which scope: every instruction whose
# `op_name` holds a phase has one of these on its call stack, so a phase's
# work written into another phase's function fails a test
# (tests/test_phase_scopes.py) and not a metric's reader. Second levels
# (`/rank`, `/grp`, `/chunk`, `/lock`, `/barrier`, `/stat`) belong to their
# phase.
# `s.chunk` is run_loop's own.
PHASE_FUNCTIONS = {
    P_FAULT: ("_fault",),
    P_LOCAL: ("_local",),
    P_PROBE: ("_probe",),
    P_ARB: ("_arb",),
    P_DIR: ("_dir_legs", "_dir_transition"),
    P_NOC: ("_noc_counts", "_fifo_order", "_router_walk"),
    P_DRAM: ("_fifo_order", "_dram_queue"),
    P_COMMIT: ("_commit_service", "_commit_retire", "_commit_writes",
               "_commit_end"),
    P_SYNC: ("_sync",),
    P_CHUNK: ("run_loop",),
}


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


def _l1_row_write(cfg: MachineConfig, l1, writes):
    """`l1` with `writes` applied, each core editing its own row: the
    write-side twin of `_l1_set_read`. `writes` is a list of
    `(plane, mask, col, val)`: a static plane number of the fused L1
    array, the cores that write [C] (or [C, K]: K writes a core), the
    column within the plane and the word (both broadcast to the mask).

    Every write of core `c` lands in row `c`, so this is no scatter across
    rows: each plane `l1[:, p * FS : (p + 1) * FS]` (a static, tile-aligned
    view of the row; a reshape that put the plane or S1 on an axis of its
    own would re-tile the array) is compared against the columns of its
    own writes alone, a masked lane comparing against -1, and written back
    where it lay by a static `dynamic_update_slice`, which XLA does in
    place: one fusion a plane, the loop's carry edited and never copied
    (a `concatenate` of the planes compiles to a 168 MB copy a step on
    rung 5; one select over the full width compares every column against
    every plane's writes). Dense vector work at the array's bandwidth:
    19.7 us at 1024 cores x 2560 columns, 531 us at 16384, where the one
    element scatter it replaced took 135 and 3777 (`prof_gather.py
    writes`, PERF.md section 6, PR 38). Later writes of a plane win over
    earlier ones; `_l1_writes` hands none that differ on one word."""
    FS = cfg.l1.ways * cfg.l1.sets
    iota = jnp.arange(FS, dtype=jnp.int32)
    for p in sorted({plane for plane, _, _, _ in writes}):
        new = jax.lax.slice_in_dim(l1, p * FS, (p + 1) * FS, axis=1)
        for plane, mask, col, val in writes:
            if plane != p:
                continue
            at = jnp.where(mask, col, -1).reshape(mask.shape[0], -1)  # [C, K]
            val = jnp.broadcast_to(val, mask.shape).reshape(at.shape)
            for k in range(at.shape[1]):
                new = jnp.where(iota == at[:, k, None], val[:, k, None], new)
        l1 = jax.lax.dynamic_update_slice(l1, new, (0, p * FS))
    return l1


def _l1_probe(cfg: MachineConfig, arange_c, l1, dirm, line,
              run_patch=None, step_no=None, mesh=None):
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
    recorded at fill time) — ONE gather of the whole `dirm` rows the W1
    pointers name, the entry's words selected out of each row
    (`_validate_ways`, `_way_record`) — instead of a W2-wide tag search of
    the home set; a stale pointer self-detects by tag mismatch and yields
    exactly the search result (DESIGN.md §7).

    The pointer is decomposed into (row, way) coordinates and the gather
    indexes `dirm` in its NATIVE layout: a `reshape(-1)` flat view of a
    TPU-tiled array is a physical relayout — XLA materializes a full copy
    of the (537 MB at 1024 cores) sharers array every step.

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
        # one row write) patched in-register: silent E->M at wm columns,
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
        cfg, arange_c, tag_rows, state_rows, ptr_rows, eph_rows, dirm, mesh,
    )
    return w1cols, tag_rows, lru_rows, weff


def _way_record(cfg: MachineConfig, rows, pway, core):
    """What the probe reads of the directory rows `rows` [W1, C, DW] its
    way pointers name, at the way `pway` [W1, C] within each row, for the
    cores `core` [C]: the entry's tag, its owner, the core's own sharer
    bit; under the coarse vector the entry's invalidation epoch too. A
    tuple of [W1, C] arrays. The sibling of `_run_record`: a function of
    one row and of values every chip has, so `sharding.read_rows` runs it
    on the chip that holds the row. Every word is a select out of the row
    in hand (`_pick`)."""
    W2, NW = cfg.llc.ways, cfg.n_sharer_words
    MW = llc_meta_width(cfg)
    pairs = rows[..., : 2 * W2]  # (tag, owner) a way
    g_c = (core >> (cfg.sharer_group.bit_length() - 1))[None, :]
    vsh = _pick(rows[..., MW:], pway * NW + (g_c >> 5))
    record = [
        _pick(pairs, 2 * pway),
        _pick(pairs, 2 * pway + 1),
        ((vsh >> (g_c & 31)) & 1) != 0,
    ]
    if cfg.sharer_group > 1:
        record.append(_pick(rows[..., 3 * W2 : 4 * W2], pway))
    return tuple(record)


def _validate_ways(cfg, arange_c, tag_rows, state_rows, ptr_rows, eph_rows,
                   dirm, mesh=None):
    """Pull-validate each way's locally-written state against the
    directory entry its fill-time way pointer names (see `_l1_probe`).

    The W1 entries of a core are read as ONE gather of whole `dirm` rows
    at the pointers' rows, ways first (`[W1, C]` slots: the gather's
    `[W1*C, DW]` result splits into `[W1, C, DW]` as it lies), through
    `sharding.read_rows`: the tag, the owner, the core's sharer bit and
    the epoch are selects out of the row in hand (`_way_record`), and on
    a mesh each chip reduces the rows of its own shard and the 3 or 4
    words a (core, way) cross chips. Until PR 36 they were three (coarse:
    four) ELEMENT gathers at the same rows. On the v5e an element of
    `dirm` costs 15-22 ns whatever the row (14.8 alone in a loop; in the
    step 0.089 ms for 4096 at 1024 cores, 1.17 ms for 65536 on rung 5,
    each of the three or four), a whole row with its selects 12-14 ns at
    768 and 1536 bytes, 21-32 at rung 4's 4608, 35-54 at 8704, 59-99 at
    16896 (cores first 1.7-3.3 times that). The row read grows with the row
    and the elements do not: at 4096 cores and more they cross near rows
    of 7 KB (at 4608 bytes the rows still win x1.4-2.1, at 8704 they lose
    x0.8; at 1024 cores they win there too), a full-map directory of
    about 6500 cores at 8 ways (scripts/prof/prof_gather.py rows, PERF.md
    section 6, PR 36). No shipped machine has such rows: the widest is
    rung 4's 4608 bytes, and rung 5's 16384 cores are coarse, 768. So
    there is no second form.

    Under the coarse sharer vector (sharer_group > 1) the core checks
    its GROUP's bit, which may stay set on a NEIGHBOR's behalf after
    this core was invalidated — so the group-bit path additionally
    requires the entry's INVALIDATION EPOCH (bumped by every sharer-
    clearing transition) to still equal the one this core recorded at
    fill time. Epoch-match + group-bit is exactly eager-golden validity:
    every S grant after the last clearing records the current epoch, and
    anything older was invalidated by that clearing. The owner path
    needs no epoch (owner identity is exact)."""
    W2 = cfg.llc.ways
    ptr_w = ptr_rows.T  # [W1, C]; ptr = (bank*S2 + set)*W2 + way
    vtag, vown, vbit, *veph = (
        v.T for v in read_rows(
            mesh, dirm, ptr_w // W2, functools.partial(_way_record, cfg),
            per_slot=(ptr_w % W2,), whole=(arange_c,),
        )
    )  # [C, W1] each
    if cfg.sharer_group > 1:
        vbit = vbit & (veph[0] == eph_rows)
    return jnp.where(
        (state_rows == I) | (vtag != tag_rows),
        I,
        jnp.where(
            vown == arange_c[:, None],
            state_rows,
            jnp.where(vbit, S, I),
        ),
    )  # [C, W1] effective MESI per way


class Request(NamedTuple):
    """What each core asks of the uncore this step: the event it
    arbitrates with, decoded, and its classification against the core's
    own L1 set and the line's home set as they stood at step entry
    (golden keeps the same per core). Every field is `[C]` unless said.
    `_probe` builds it once; later phases read it by field."""

    et: jax.Array  # the event: type, argument, pre-batched instructions
    earg: jax.Array
    epre: jax.Array
    line: jax.Array  # line address (a barrier's id, a mutex's address)
    l1s: jax.Array  # its L1 set
    bank: jax.Array  # its home bank and (bank, set) id
    slot: jax.Array
    is_ins: jax.Array  # lane classes, `active` already applied
    is_lock: jax.Array
    is_unlock: jax.Array
    is_barrier: jax.Array  # arrivals (frozen waiters excluded)
    read_hit: jax.Array
    write_hit: jax.Array
    upg: jax.Array
    gets: jax.Array
    getm: jax.Array
    hit_way: jax.Array  # the L1 set as probed: hit way, [C, W1] tags, LRU
    tag_rows: jax.Array  # stamps and effective MESI per way
    lru_rows: jax.Array
    weff: jax.Array
    meta_rows: jax.Array  # [C, DW] the home set's directory row
    llc_has: jax.Array  # line resident in the LLC, at which way, its owner
    llc_hway: jax.Array
    owner: jax.Array
    shw: jax.Array  # [C, NW] the line's packed sharer words
    other_sharers: jax.Array
    g_c: jax.Array  # this core's sharer bit: group, word and bit in word
    word_idx: jax.Array
    bit_idx: jax.Array
    # the parsed home row, [C, W2], [C, W2], [C, W2, NW]
    llc_tag_rows: jax.Array
    owner_rows: jax.Array
    sh_rows: jax.Array


class DirOutcome(NamedTuple):
    """What the directory decided about each core's request this step,
    on step-start state: the transition a winner takes, whom it probes
    and invalidates, the victim of a miss, and how the miss is served.
    Every field is `[C]`. `_dir_transition` builds it once."""

    llc_hit: jax.Array  # winners by what the LLC held
    llc_miss: jax.Array
    gets_w: jax.Array  # winners by request class
    write_w: jax.Array
    gets_probe: jax.Array  # GETS grants: owner probed, shared, exclusive
    gets_shared: jax.Array
    gets_excl_hit: jax.Array
    write_probe: jax.Array
    oclamp: jax.Array  # the probed owner (0 where none) and the
    po_lat: jax.Array  # bank -> owner leg
    po_hops: jax.Array
    rr_po: jax.Array | None  # that leg's reroutes (faults_enabled)
    llc_vway: jax.Array  # the miss's victim way, its owner, whether valid
    vic_owner: jax.Array
    vic_valid: jax.Array
    llc_lru_rows: jax.Array  # [C, W2]
    inv_lat: jax.Array  # invalidation fan-out of a write hit: slowest
    inv_count: jax.Array  # round trip, messages, hops
    inv_hops: jax.Array
    back_count: jax.Array  # back-invalidation of the victim's holders
    back_hops: jax.Array
    pf_hit: jax.Array  # miss covered by the stride prefetcher
    miss_dram: jax.Array  # miss that goes to DRAM


def _count(acc: dict, name: str, amount) -> None:
    """Add `amount` [C] to counter or stat row `name` of this step. The
    deltas collect in `acc`, a dict of [C] lanes that lives for one trace
    of `step`, and fold into the [N_BLOCK_ROWS, C] array in ONE stacked add
    at the end of the step (`_counter_deltas`): each `.at[row].add` is its
    own dynamic-update-slice kernel, while the dict adds fuse into the
    surrounding elementwise work for free. A stat row (STAT_NAMES) is
    written here and read by nothing in the step."""
    a = amount.astype(jnp.int32)
    acc[name] = a if name not in acc else acc[name] + a


def _counter_deltas(acc: dict, C: int, n_rows: int):
    # the rows the state's block has: all of BLOCK_NAMES, or the counters
    # alone (a mesh: `init_state(stat_rows=False)`; what was counted into
    # `acc` for a row the block lacks is dead code to the compiler)
    rows = [
        acc[k] if k in acc else jnp.zeros(C, jnp.int32)
        for k in BLOCK_NAMES[:n_rows]
    ]
    return jnp.stack(rows)


def _unpack_bits(words, g_c):
    """[C, NW] packed sharer words -> [C, C] bool per TARGET core."""
    C, NW = words.shape
    b = (words[:, :, None] >> jnp.arange(32, dtype=jnp.int32)[None, None, :]) & 1
    groups = b.reshape(C, NW * 32) != 0
    # target core t is recorded iff its GROUP's bit is set (identity
    # expansion at G=1)
    return jnp.take(groups, g_c, axis=1)


def _is_router(cfg: MachineConfig) -> bool:
    return cfg.noc.contention and cfg.noc.contention_model == "router"


def _fault(cfg: MachineConfig, events, st: MachineState, arange_c, acc):
    """Phase -1: fault injection (DESIGN.md §12) -> the state with this
    step's faults applied (`dirm`, `lock_holder`, `faults`) and `deadb`
    [C], the fail-stopped cores, which leave every later mask.

    Called under the STATIC gate `cfg.faults_enabled` only: faults-off
    programs contain none of this — the faults pytree passes through
    untouched and the step graph is the one without the subsystem (the
    bit-exact / zero-overhead contract). Faults-on, everything is TRACED
    (schedule arrays, counter-based PRNG on (seed, step, site)) so one
    compiled program serves every seed and schedule of a geometry, and
    the fleet vmaps straight through it."""
    with jax.named_scope(P_FAULT):
        from ..faults.inject import ecc_step, fire_events, scrub_dead_cond

        fsf = st.faults
        # only cores that haven't retired END absorb faults: a finished
        # core is powered down, and — critically for the solo-vs-fleet
        # determinism contract — a fleet element keeps stepping after it
        # completes (until the whole batch drains), so any fault counted
        # on an ended core would diverge from the same element run solo
        alive0 = (events.at(st.ptr)[:, 0] != EV_END) & (fsf.core_dead == 0)
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
        _count(acc, "core_failstops", kill_now)
        _count(acc, "ecc_corrected", jnp.where(alive0, ecc_corr, 0))
        _count(acc, "ecc_due", jnp.where(alive0, ecc_due, 0))
        dirm_f, lockh_f, wb_dead = scrub_dead_cond(
            cfg, st.dirm, st.lock_holder, kill_now
        )
        if cfg.fault_dead_policy == "writeback":
            _count(acc, "l1_writebacks", wb_dead)
        fsf = fsf._replace(
            core_dead=fsf.core_dead | kill_now,
            link_dead=link_dead_n,
            link_extra=link_extra_n,
        )
        st = st._replace(dirm=dirm_f, lock_holder=lockh_f, faults=fsf)
        deadb = fsf.core_dead != 0  # [C] — dead cores leave every mask
    return st, deadb


def _run_record(cfg: MachineConfig, rows, line, core):
    """What a local run reads of its candidates' home rows `rows`
    [K, C, DW] (`dirm` rows: metadata AND sharers) for the lines `line`
    [K, C] of the cores `core` [C]: whether a way holds the line, that
    way's owner, the core's own sharer bit; under the coarse vector the
    way's invalidation epoch too, under moesi the number of sharers
    recorded. A tuple of [K, C] arrays. A function of one row and of
    values every chip has, so `sharding.read_rows` runs it on the chip
    that holds the row."""
    W2, NW = cfg.llc.ways, cfg.n_sharer_words
    MW = llc_meta_width(cfg)
    pmeta = rows[:, :, : 2 * W2].reshape(*line.shape, W2, 2)
    pmmatch = pmeta[..., 0] == line[:, :, None]
    pmhas = jnp.any(pmmatch, axis=2)
    pmway = jnp.argmax(pmmatch, axis=2).astype(jnp.int32)
    # way and word picks out of rows already in hand: selects, not
    # gathers (`_pick`); `argmax` keeps first-match order
    pown = _pick(pmeta[..., 1], pmway)
    g_c = (core >> (cfg.sharer_group.bit_length() - 1))[None, :]
    # the self sharer word rides the row gather: in-register select
    pshw = _pick(rows[:, :, MW:], pmway * NW + (g_c >> 5))
    pbit = ((pshw >> (g_c & 31)) & 1) != 0
    record = [pmhas, pown, pbit]
    if cfg.sharer_group > 1:
        record.append(_pick(rows[:, :, 3 * W2 : 4 * W2], pmway))
    if cfg.coherence == "moesi":
        psh_all = rows[:, :, MW:].reshape(*line.shape, W2, NW)
        pwords = _pick(
            jnp.swapaxes(psh_all, 2, 3), pmway[:, :, None]
        )  # [K, C, NW]: the matching way's sharer words
        record.append(jnp.sum(jax.lax.population_count(pwords), axis=2))
    return tuple(record)


def _local(cfg: MachineConfig, events, st: MachineState, arange_c, deadb, acc,
           mesh=None):
    """Phase 0, the quantum barrier, and phase 0.5, the local runs ->
    `quantum_end` (scalar), the clocks and trace pointers after the runs
    `cycles_c`, `ptr_c` [C], the prefetched candidate events `pev`
    [C, rl+1, 4] and the runs' deferred L1 writes `run_patch` = (`hm`,
    `wm`, `cm`) [C, rl]: which candidates refreshed an LRU stamp, which
    wrote E->M silently, and at which column of the core's L1 row. The
    last two are None where `local_run_len` is 0."""
    C, B = cfg.n_cores, cfg.n_banks
    S1 = cfg.l1.sets
    S2 = cfg.llc.sets
    kn = st.knobs
    Q, cpi_vec, l1_lat = kn.quantum, kn.cpi, kn.l1_lat
    with jax.named_scope(P_LOCAL):
        # ---- phase 0: quantum barrier (on step-entry state) ------------------
        # Barrier-frozen cores (arrived, waiting for release) neither bump nor
        # bound the quantum (DESIGN.md §3): they rejoin at release. With local
        # runs enabled the event at ptr is slot 0 of the phase-0.5 prefetch —
        # reuse it instead of a separate gather kernel. The window is the
        # two whole blocks of the core's trace that hold it, read as rows,
        # and moved down to their lane 0 (`DeviceTrace.window`).
        if cfg.local_run_len:
            _pev0 = events.window(st.ptr, cfg.local_run_len + 1)  # [C, rl+1, 4]
            et0 = _pev0[:, 0, 0]
        else:
            et0 = events.at(st.ptr)[:, 0]
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
        # their home-set metadata, and their self-sharer words come in up
        # front (two row gathers, and the select out of the core's own L1
        # row), and the run below is pure lane arithmetic: nothing in it
        # gathers from the multi-hundred-MB directory arrays.
        #
        # The probe validates against the accessed line's HOME entry (W2-wide
        # tag search of the gathered metadata row) rather than through the L1
        # way pointer; DESIGN.md §7 proves search- and pointer-validation
        # observably identical (a stale pointer self-detects to exactly the
        # search result), and the parity suite re-proves it on every workload.
        cycles_c, ptr_c = st.cycles, st.ptr
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
            # the home rows are read where they live (`_run_record`): of a
            # row the run wants the words below, and on a mesh only those
            # cross chips. Candidates first, as `_validate_ways` reads its
            # ways: the gather's `[K*C, DW]` result is `[K, C, DW]` as it lies
            pmhas, pown, pbit, *prest = (
                v.T for v in read_rows(
                    mesh, st.dirm, pslot.T, functools.partial(_run_record, cfg),
                    per_slot=(pline.T,), whole=(arange_c,),
                )
            )  # [C, rl+1] each
            pmatch_l = (ptagr == pline[:, :, None]) & (pstater != I)
            plhit = jnp.any(pmatch_l, axis=2)
            plway = jnp.argmax(pmatch_l, axis=2).astype(jnp.int32)
            plstate = _pick(pstater, plway)
            if cfg.sharer_group > 1:
                # epoch guard (see _validate_ways): the group bit only keeps
                # this core's S line alive if no sharer-clearing transition
                # happened since its fill
                pleph = _pick(pts[:, :, 2], plway)
                pbit = pbit & (prest[0] == pleph)  # the home way's epoch
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
                pothers = (prest[-1] - pbit.astype(jnp.int32)) > 0  # sharers
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
            n_retired = jnp.sum(retire_k, axis=1).astype(jnp.int32)
            ptr_c = ptr_c + n_retired
            _count(acc, "run_events", n_retired)
            _count(acc, "l1_read_hits", jnp.sum(r_hit_k & retire_k, axis=1))
            _count(acc, "l1_write_hits", jnp.sum(w_hit_k & retire_k, axis=1))
            _count(
                acc,
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
            # all the way into phase 4.A's one write of the L1 array
            # (`_l1_row_write`): every plane is read and written once a
            # step. Phase 1 patches the prefetched planes in-register
            # instead.
    if not rl:
        return quantum_end, cycles_c, ptr_c, None, None
    return quantum_end, cycles_c, ptr_c, pev, (hm, wm, cm)


def _probe(cfg: MachineConfig, events, st: MachineState, arange_c, cycles_c,
           ptr_c, quantum_end, pev, run_patch, deadb, acc,
           mesh=None) -> Request:
    """Phases 0.9 and 1: the event each core arbitrates with (the
    candidate after its local run), its L1 probe, the parse of its home
    set's directory row, and its classification -> the `Request`; and
    into `acc` the three stat rows that say where this step's core-steps
    went (the fourth share, cores at END, is what they leave of C)."""
    C, B = cfg.n_cores, cfg.n_banks
    S1 = cfg.l1.sets
    S2, W2 = cfg.llc.sets, cfg.llc.ways
    NW = cfg.n_sharer_words
    MW = llc_meta_width(cfg)
    logB = B.bit_length() - 1
    rl = cfg.local_run_len
    l1_c, step_no = st.l1, st.step
    with jax.named_scope(P_PROBE):
        # ---- phase 0.9 + phase 1: the arbitration event and its L1 probe -----
        # addresses arrive LINE-granular (Trace.line_events normalizes byte
        # traces at ingest; v4 line-addressed traces pass through) — 2^31
        # lines = 128 GiB at 64B lines, 64x the byte-addressed range
        if rl:
            # a lane that retired k local events arbitrates candidate k
            # (the window repeats the final END row, so over-running lanes
            # read END here exactly as a direct read would). Reusing MORE
            # of the prefetch here (classification, L1 planes, home metadata
            # row) was tried and measured slower: the select/patch kernels
            # cost more than the gathers they replaced.
            consumed = (ptr_c - st.ptr)[:, None]
            ev = _pick(jnp.swapaxes(pev, 1, 2), consumed)  # [C, 4]
        else:
            ev = events.at(ptr_c)  # [C, 4]
        et, earg, eaddr, epre = ev[:, 0], ev[:, 1], ev[:, 2], ev[:, 3]
        line = eaddr
        l1s = line & (S1 - 1)
        w1cols, tag_rows, lru_rows, weff = _l1_probe(
            cfg, arange_c, l1_c, st.dirm, line,
            run_patch=run_patch,
            step_no=step_no,
            mesh=mesh,
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
        with jax.named_scope(_STAT):
            _count(acc, "slot_active", active)
            # a core-step that presents no event: frozen at a barrier, or
            # ahead of the quantum window and waiting for the laggards;
            # what is left of C is at END (or fail-stopped)
            idle = not_done if deadb is None else not_done & ~deadb
            _count(acc, "slot_frozen", idle & frozen)
            _count(acc, "slot_quantum",
                   idle & ~frozen & ~(cycles_c < quantum_end))

        is_ins = active & (et == EV_INS)
        is_st_ev = et == EV_ST
        is_mem = active & ((et == EV_LD) | is_st_ev)
        is_lock = active & (et == EV_LOCK)
        is_unlock = active & (et == EV_UNLOCK)
        is_barrier = active & (et == EV_BARRIER)  # arrivals (frozen excluded)

        # (hits are classified below the LLC parse: the moesi derived-O
        # demotion needs the home row's owner + sharer predicates first)

        # LLC lookup for the accessed line (step-start, all lanes — needed both
        # for join eligibility below and the winner transitions in phase 3).
        # ONE full-row gather returns the home set's tags, owners AND LRU
        # stamps; the owner, victim-owner and victim-LRU reads below become
        # in-register row indexing instead of separate element gathers.
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
    return Request(
        et=et, earg=earg, epre=epre, line=line, l1s=l1s, bank=bank, slot=slot,
        is_ins=is_ins, is_lock=is_lock, is_unlock=is_unlock,
        is_barrier=is_barrier, read_hit=read_hit, write_hit=write_hit,
        upg=upg, gets=gets, getm=getm, hit_way=hit_way, tag_rows=tag_rows,
        lru_rows=lru_rows, weff=weff, meta_rows=meta_rows, llc_has=llc_has,
        llc_hway=llc_hway, owner=owner, shw=shw, other_sharers=other_sharers,
        g_c=g_c, word_idx=word_idx, bit_idx=bit_idx,
        llc_tag_rows=llc_tag_rows, owner_rows=owner_rows, sh_rows=sh_rows,
    )


def _arb(cfg: MachineConfig, kn, arange_c, rq: Request, cycles_c,
         quantum_end, acc):
    """Phase 2: read-join coalescing and the per-(bank, set) arbitration
    -> `winner`, `join` [C] bool (disjoint: the requests that retire this
    step) and `key` [C], the (clock, core) order they were judged by,
    which the FIFO ranks of the router and the DRAM queue reuse."""
    C, B, S2 = cfg.n_cores, cfg.n_banks, cfg.llc.sets
    Q = kn.quantum
    gets, getm, upg, slot = rq.gets, rq.getm, rq.upg, rq.slot
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
        join_elig = gets & rq.llc_has & (rq.owner == -1) & rq.other_sharers
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
        _count(acc, "retries", retry)
    return winner, join, key


def _dir_legs(cfg: MachineConfig, kn, faults, arange_c, rq: Request, winner,
              join, has_sync: bool):
    """Phase 3, first part: where each request travels -> the tiles of
    the core, its home bank and its barrier's home (`ctile`, `btile`,
    `htile`), the barrier id `bid`, `home_txn` (the lanes that make a
    round trip to their home bank this step), the NOMINAL request and
    reply legs `req_lat`, `req_hops`, `rep_lat`, `rep_hops`, all [C], and
    `flt`: under `faults_enabled` the legs' link-fault penalties
    (`flt_rt` round-trip extra, `fh_req`, `fh_rep` detour hops, `rr_req`,
    `rr_rep` reroutes), else None."""
    n_tiles = cfg.n_tiles
    with jax.named_scope(P_DIR):
        ctile = arange_c % n_tiles
        btile = rq.bank % n_tiles
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
                cfg, faults, kn, ctile, btile
            )
            fx_rep, fh_rep, rr_rep = leg_fault_penalty(
                cfg, faults, kn, btile, ctile
            )
            flt_rt = fx_req + fx_rep  # round-trip fault extra, home txns
            flt = (flt_rt, fh_req, fh_rep, rr_req, rr_rep)
        else:
            flt = None

        # barrier home tile (bid lives in the addr field; ids validated
        # < barrier_slots at ingest) — shared by the contention count and the
        # phase-2.7 arrival/release paths
        bid = jnp.where(rq.et == EV_BARRIER, rq.line, 0)
        htile = bid % n_tiles

        # this step's uncore transactions at a home bank: memory winners +
        # joins, lock/unlock RMWs (the lock's home == the same btile)
        home_txn = winner | join
        if has_sync:
            home_txn = home_txn | rq.is_lock | rq.is_unlock
    return (ctile, btile, htile, bid, home_txn, req_lat, req_hops, rep_lat,
            rep_hops, flt)


def _noc_counts(cfg: MachineConfig, kn, is_barrier, home_txn, ctile, btile,
                htile, has_sync: bool, acc):
    """NoC contention by counting (`contention_model` "tile" or "link")
    -> `extra_home` (valid where `home_txn`) and `extra_bar` (valid where
    `is_barrier`), the queueing cycles of a home transaction and of a
    barrier arrival, [C].

    This step's uncore transactions: the home transactions and the
    barrier arrivals (at `bid % n_tiles`). Tile model: occupancy count
    per home tile, charge contention_lat * (count - 1). Link model: each
    transaction's XY request+reply path (barrier arrivals: one way)
    claims its links; charge contention_lat * bottleneck (count - 1) over
    the path — mirroring golden's _bump/_contention_extra exactly. (The
    "router" model replaces the analytic request/reply legs wholesale:
    `_router_walk`, once the service components are known.)"""
    n_tiles = cfg.n_tiles
    with jax.named_scope(P_NOC):
        ccl = kn.contention_lat
        if cfg.noc.contention_model == "link":
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
        _count(
            acc,
            "noc_contention_cycles",
            jnp.where(home_txn, extra_home, 0)
            + (jnp.where(is_barrier, extra_bar, 0) if has_sync else 0),
        )
    return extra_home, extra_bar


def _dir_transition(cfg: MachineConfig, st: MachineState, arange_c,
                    rq: Request, winner, join, ctile, btile, acc):
    """Phase 3, second part: the directory transition of every winner on
    step-start state (grant, probe, victim, invalidation and
    back-invalidation fan-outs by the machine's sharer-vector form) and
    the stride prefetcher -> the `DirOutcome`, and the prefetcher's next
    state (`pf_line`, `pf_stride`, `pf_streak`)."""
    C = cfg.n_cores
    W2 = cfg.llc.ways
    NW = cfg.n_sharer_words
    n_tiles = cfg.n_tiles
    logG = cfg.sharer_group.bit_length() - 1
    kn = st.knobs
    line, llc_has, owner = rq.line, rq.llc_has, rq.owner
    gets, getm, upg, other_sharers = rq.gets, rq.getm, rq.upg, rq.other_sharers
    shw, g_c = rq.shw, rq.g_c
    rr_po = None
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
            from ..faults.inject import leg_fault_penalty

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
        llc_state_valid = rq.llc_tag_rows != -1
        llc_lru_rows = rq.meta_rows[:, 2 * W2 : 3 * W2]  # [C, W2], row gather
        vkey = jnp.where(llc_state_valid, llc_lru_rows, -1)
        llc_vway = jnp.argmin(vkey, axis=1).astype(jnp.int32)
        vic_tag = rq.llc_tag_rows[arange_c, llc_vway]
        vic_owner = rq.owner_rows[arange_c, llc_vway]
        vic_shw = jnp.take_along_axis(
            rq.sh_rows, llc_vway[:, None, None], axis=1
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
            with jax.named_scope(_CHUNK):
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
        else:
            ttile = arange_c % n_tiles  # target tiles
            pair_lat, pair_hops = _one_way(btile[:, None], ttile[None, :], cfg, kn)
            sh_bits = _unpack_bits(shw, g_c)
            sh_bits = sh_bits & (arange_c[None, :] != arange_c[:, None])
            inv_pairs = sh_bits & inv_row[:, None]  # [C, C]
            inv_lat = jnp.max(jnp.where(inv_pairs, 2 * pair_lat, 0), axis=1)
            inv_count = jnp.sum(inv_pairs, axis=1).astype(jnp.int32)
            inv_hops = jnp.sum(jnp.where(inv_pairs, 2 * pair_hops, 0), axis=1).astype(jnp.int32)
            vic_sh_bits = _unpack_bits(vic_shw, g_c)
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
            _count(acc, "prefetch_hits", pf_hit)
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
    outcome = DirOutcome(
        llc_hit=llc_hit, llc_miss=llc_miss, gets_w=gets_w, write_w=write_w,
        gets_probe=gets_probe, gets_shared=gets_shared,
        gets_excl_hit=gets_excl_hit, write_probe=write_probe, oclamp=oclamp,
        po_lat=po_lat, po_hops=po_hops, rr_po=rr_po, llc_vway=llc_vway,
        vic_owner=vic_owner, vic_valid=vic_valid, llc_lru_rows=llc_lru_rows,
        inv_lat=inv_lat, inv_count=inv_count, inv_hops=inv_hops,
        back_count=back_count, back_hops=back_hops, pf_hit=pf_hit,
        miss_dram=miss_dram,
    )
    return outcome, (pf_line_n, pf_stride_n, pf_streak_n)


def _fifo_order(cfg: MachineConfig, key):
    """The dense order of the arbitration keys, [C]: one sort that the
    segmented FIFO ranks of the DRAM queue AND the router walk share
    (DESIGN.md §13), billed to the first of the two the machine has."""
    with (jax.named_scope(P_DRAM if cfg.dram_queue else P_NOC),
          jax.named_scope(_RANK)):
        ord_c = lane_order(key)
    return ord_c


def _dram_queue(cfg: MachineConfig, kn, dram_free, bank, epre, cycles_c,
                req_lat, miss_dram, ord_c, acc):
    """The memory-controller queue (cfg.dram_queue, SURVEY §2 #7) ->
    `extra_dram` [C], the cycles a DRAM-bound miss waits at its home
    bank's controller, and the controllers' next-free clocks [B].

    Miss winners queue at their home bank's controller: wait floor =
    max(dram_free[bank], bank's earliest nominal arrival this step) +
    rank*service — the router model's FIFO shape on a per-bank clock.
    Ranks via the shared sort-based segmented-rank primitive, in the key
    order `ord_c`; bit-exact vs golden (tests/test_dram.py)."""
    B = cfg.n_banks
    cpi_vec, l1_lat, llc_lat = kn.cpi, kn.l1_lat, kn.llc_lat
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
        # the where/drop masks below never let escape
        with jax.named_scope(_RANK):
            rd = segmented_rank(dtgt[:, None], n_seg=B, order=ord_c)[:, 0]
        dstart = jnp.maximum(
            a_nom,
            jnp.maximum(dram_free[bank], dbase[bank]) + rd * svc_d,
        )
        extra_dram = jnp.where(miss_dram, dstart - a_nom, 0)
        dram_free_n = dram_free.at[dtgt].max(dstart + svc_d, mode="drop")
        _count(acc, "dram_queue_cycles", extra_dram)
    return extra_dram, dram_free_n


def _commit_service(cfg: MachineConfig, kn, dr: DirOutcome, winner,
                    extra_dram):
    """Latency composition, first part (golden order) -> `service` [C],
    the interval between a request's arrival at its home bank and the
    reply's injection, which the router walk needs before the round trip
    can be composed; and `probe_any` [C], the winners that probe an
    owner."""
    llc_lat = kn.llc_lat
    with jax.named_scope(P_COMMIT):
        probe_any = dr.gets_probe | dr.write_probe
        # service interval between the request's arrival at the home bank and
        # the reply's injection: LLC lookup + probe legs + invalidation waits
        # + controller queueing + DRAM (memory lanes), plain LLC lookup
        # (joins, lock/unlock RMWs)
        dram_term = jnp.where(dr.miss_dram, kn.dram_lat, 0)
        if cfg.prefetcher != "none":
            # prefetch-covered misses pay the (traced) buffer latency instead
            dram_term = dram_term + jnp.where(dr.pf_hit, kn.prefetch_lat, 0)
        service = jnp.where(
            winner,
            llc_lat
            + jnp.where(probe_any, 2 * dr.po_lat, 0)
            + jnp.where(dr.write_w & dr.llc_hit, dr.inv_lat, 0)
            + dram_term
            + extra_dram,
            llc_lat,
        )
    return service, probe_any


def _router_walk(cfg: MachineConfig, kn, link_free, sync_flag, rq: Request,
                 winner, join, home_txn, ctile, btile, htile, req_lat,
                 req_hops, rep_lat, rep_hops, cycles_c, service, ord_c,
                 has_sync: bool, acc):
    """NoC contention hop by hop (`contention_model` "router"; golden
    _route/_route_rt, vectorized) -> `raw_rt` [C], the whole round trip of
    a home transaction, service included; `raw_arr` [C], a barrier
    arrival's one-way trip; what of each is queueing (`extra_home`,
    `extra_bar`); and the links' next-free clocks `link_free_n` [NL].
    `raw_arr` and `extra_bar` are None without sync events.

    Model: every directed link keeps a next-free clock carried across
    steps; a packet waits at link l for
      max(link_free[l], base[l]) + rank_l * link_lat
    (base = the link's earliest NOMINAL same-step arrival, rank = packets
    on l with smaller (clock, core) key — FIFO serialization at link_lat
    per packet), then occupies the link for link_lat and pays router_lat
    at the next router; waits cascade into later hops. The cascade has a
    closed form: with F_k the wait floor at hop k and c = link_lat +
    router_lat,
      t_k = max(t0 + router_lat, cummax_{k'<=k}(F_k' - k'c)) + kc
    so one cummax per path replaces the sequential walk. Everything per
    link (rank, base, the clock's lookup, the departures' max into the
    clocks) is taken in the sorted order of the flattened (link, key)
    entries, key order `ord_c`: ops/ranking.py, DESIGN.md §13, O(E log E)
    and no table indexed entry by entry. Bit-exact vs the golden scalar
    walk (tests/test_router.py)."""
    cpi_vec, l1_lat, llc_lat = kn.cpi, kn.l1_lat, kn.llc_lat
    epre, is_lock, is_unlock, is_barrier = (
        rq.epre, rq.is_lock, rq.is_unlock, rq.is_barrier)
    raw_arr = extra_bar = None
    with jax.named_scope(P_NOC):
        NL = n_links(cfg)
        L_lat = kn.link_lat
        R_lat = kn.router_lat
        c_hop = kn.link_lat + kn.router_lat
        SENT = jnp.int32(-(1 << 30) - (1 << 21))  # < any real wait floor
        # the first leg: a home transaction's request or, on a barrier
        # lane (which makes none), the arrival ctile -> htile: both start
        # at `t0` with the nominal clocks `a_req`, so a walk with sync
        # events sorts the slots of one without
        leg1_dst, leg1_mask, leg1_hops = btile, home_txn, req_hops
        if has_sync:
            arr_lat_a, arr_hops = _one_way(ctile, htile, cfg, kn)
            leg1_dst = jnp.where(is_barrier, htile, btile)
            leg1_mask = home_txn | is_barrier
            leg1_hops = jnp.where(is_barrier, arr_hops, req_hops)
        req_p = _path_links(cfg, ctile, leg1_dst)  # [C, H]
        rep_p = _path_links(cfg, btile, ctile)
        H = req_p.shape[1]
        hidx = jnp.arange(H, dtype=jnp.int32)[None, :]
        first_lock = is_lock & (sync_flag == 0)
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
        # ([C, 2H]: the first leg and the reply), and in the order ONE
        # sort gives them: sorted by (link, key) a link's entries are one
        # contiguous run, so its rank, its earliest nominal arrival and
        # its next-free clock are scans over that run, and a sort back by
        # the carried index returns them to [C, 2H] (ops/ranking.py). A
        # table of NL words indexed entry by entry is the slow form on
        # the chip: an element gather or scatter costs ten times a sort
        # of the same entries (PERF.md §6, PR 31). The per-(lane, segment)
        # uniqueness contract of the rank holds by construction: an XY
        # path crosses a directed link once, first leg and reply traverse
        # reversed DIRECTED links (distinct ids), and a barrier lane has
        # no reply (masked to home-transaction lanes, which are disjoint).
        pth_all, mask_all = _concat_legs(
            [(req_p, leg1_mask), (rep_p, home_txn)]
        )
        a_all = jnp.concatenate([a_req, a_rep], axis=1)
        ok_all = mask_all & (pth_all >= 0)
        tgt_all = jnp.where(ok_all, pth_all, NL)
        # r_all: packets ahead of lane i in each hop's same-step FIFO,
        # ordered by the phase-2 arbitration key; fl_all: the wait
        # floor's first term max(link_free[l], base[l]), base the link's
        # earliest nominal arrival of this step (masked slots carry
        # garbage the SENT select below discards)
        with jax.named_scope(_RANK):
            r_all, fl_all, runs = segmented_rank_floor(
                tgt_all, a_all, link_free, order=ord_c
            )
        F_all = jnp.where(
            ok_all, fl_all + r_all * L_lat, SENT
        )  # [C, 2H] wait floors

        def _cascade(t_start, F, nh):
            G = F - hidx * c_hop
            cum = jax.lax.cummax(G, axis=1)
            t1 = t_start + R_lat
            t_end = jnp.maximum(t1, cum[:, -1]) + nh * c_hop
            departs = (
                jnp.maximum(t1[:, None], cum) + hidx * c_hop + L_lat
            )
            return t_end, departs

        t_req_end, d_req = _cascade(t0, F_all[:, :H], leg1_hops)
        t_rep_end, d_rep = _cascade(
            t_req_end + service, F_all[:, H:], rep_hops
        )
        d_all = jnp.concatenate([d_req, d_rep], axis=1)
        raw_rt = t_rep_end - t0  # valid on home_txn lanes
        extra_home = raw_rt - (req_lat + service + rep_lat)
        if has_sync:
            raw_arr = t_req_end - t0  # valid on barrier lanes
            extra_bar = raw_arr - arr_lat_a
        # every link's clock raised to its latest departure, in the
        # sorted order the rank built (masked slots sit in the sentinel
        # link's run, which no clock reads)
        with jax.named_scope(_RANK):
            link_free_n = segmented_table_max(runs, d_all, link_free)
        _count(
            acc,
            "noc_contention_cycles",
            jnp.where(home_txn, extra_home, 0)
            + (jnp.where(is_barrier, extra_bar, 0) if has_sync else 0),
        )
        with jax.named_scope(_STAT):
            # the real entries of the sort above, lane by lane (a path
            # holds as many links as it has hops: `ok_all.sum(1)`, without
            # the reduction), and the power of two that would have held
            # this step's, all lanes together: their number is where the
            # masked entries' run starts in the sorted order, so it costs
            # no reduction, and on a mesh no collective
            entries = jnp.where(home_txn, req_hops + rep_hops, 0)
            if has_sync:
                entries = entries + jnp.where(is_barrier, arr_hops, 0)
            _count(acc, "noc_entries", entries)
            # lane b holds (2^(b-1), 2^b], lane 0 none or one, the last
            # lane the rest: two constant vectors and one compare of each
            # with the count, no scalar arithmetic
            lane = np.arange(cfg.n_cores, dtype=np.int64)
            above = np.where(lane == 0, -1, 1 << np.clip(lane - 1, 0, 31))
            upto = 1 << np.minimum(lane, 31)
            upto[-1] = INT32_MAX
            above, upto = (np.minimum(x, INT32_MAX) for x in (above, upto))
            n = runs.n_real
            _count(acc, "noc_sort_log2",
                   (n > above.astype(np.int32)) & (n <= upto.astype(np.int32)))
    return raw_rt, raw_arr, extra_home, extra_bar, link_free_n


def _commit_retire(cfg: MachineConfig, kn, rq: Request, dr: DirOutcome,
                   winner, join, cycles_c, ptr_c, service, probe_any,
                   extra_home, raw_rt, req_lat, req_hops, rep_lat, rep_hops,
                   flt, acc):
    """Latency composition, second part, and phase 4.A's retirement: the
    round trip of every winner and join, the granted L1 state, the
    counters of the memory lanes, the clock advance -> `cycles`, `ptr`
    [C] after this step's INS, hit, winner and join lanes (sync lanes
    still to come), `grant` [C] the MESI state a fill takes, `hit` [C],
    and the request and reply hop counts with their fault detours added
    (the nominal ones where `flt` is None)."""
    cpi_vec, l1_lat, llc_lat = kn.cpi, kn.l1_lat, kn.llc_lat
    earg, epre, is_ins = rq.earg, rq.epre, rq.is_ins
    read_hit, write_hit, upg, getm = rq.read_hit, rq.write_hit, rq.upg, rq.getm
    llc_hit, llc_miss, write_w = dr.llc_hit, dr.llc_miss, dr.write_w
    inv_count, back_count = dr.inv_count, dr.back_count
    with jax.named_scope(P_COMMIT):
        if _is_router(cfg):
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
            flt_rt, fh_req, fh_rep, rr_req, rr_rep = flt
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
                jnp.where(dr.gets_probe | dr.gets_shared, S, E),  # GETS: E on excl/miss
            ),
        )

        # ---- counters for winners + joins -----------------------------------
        _count(acc, "l1_read_misses", dr.gets_w | join)
        _count(acc, "l1_write_misses", getm & winner)
        _count(acc, "upgrades", upg & winner)
        _count(acc, "llc_hits", llc_hit | join)
        _count(acc, "llc_misses", llc_miss)
        _count(acc, "dram_accesses", llc_miss)
        _count(acc, "llc_writebacks", llc_miss & dr.vic_valid & (dr.vic_owner >= 0))
        _count(acc, "probes", probe_any)
        _count(acc, "invalidations", jnp.where(write_w & llc_hit, inv_count, 0) + back_count)
        noc_msgs = (
            jnp.where(winner | join, 2, 0)  # request + reply
            + jnp.where(probe_any, 2, 0)
            + jnp.where(write_w & llc_hit, 2 * inv_count, 0)
            + jnp.where(llc_miss, 2, 0)  # DRAM (co-located controller)
            + 2 * back_count
        )
        noc_hops = (
            jnp.where(winner | join, req_hops + rep_hops, 0)
            + jnp.where(probe_any, 2 * dr.po_hops, 0)
            + jnp.where(write_w & llc_hit, dr.inv_hops, 0)
            + dr.back_hops
        )
        _count(acc, "noc_msgs", noc_msgs)
        _count(acc, "noc_hops", noc_hops)
        if cfg.faults_enabled:
            # rerouted messages: one-way legs whose XY path crossed a dead
            # link (invalidation fan-outs keep their analytic group/pair
            # latencies — model scope, like the router walk's)
            _count(
                acc,
                "noc_reroutes",
                jnp.where(winner | join, rr_req + rr_rep, 0)
                + jnp.where(probe_any, 2 * dr.rr_po, 0),
            )

        # ---- phase 4.A: local updates ----------------------------------------
        # retire + clock advance (memory events also charge their pre-batched
        # non-memory instructions: epre * cpi, PriME per-BBL batching)
        hit = read_hit | write_hit
        _count(acc, "l1_read_hits", read_hit)
        _count(acc, "l1_write_hits", write_hit)
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
        _count(
            acc,
            "instructions",
            jnp.where(is_ins, earg, 0) + jnp.where(mem_ret, epre + 1, 0),
        )
    return cycles, ptr, grant, hit, req_hops, rep_hops


def _l1_writes(cfg: MachineConfig, step_no, arange_c, rq: Request,
               dr: DirOutcome, winner, join, grant, hit, run_patch, new_eph,
               acc):
    """Every L1 write of the step as `(plane, mask, col, val)` for
    `_l1_row_write`: the seven of phase 4 (hit refresh, grant or silent
    E->M, the fill's tag, way pointer and post-bump entry epoch `new_eph`,
    the clear of a stale duplicate's tag and state) and, with local runs,
    the runs' deferred LRU stamps and E->M writes (`run_patch`), [C, rl]
    each: 7 + 2*rl words a core, at most. Counts the victim's writeback.

    A core writes at most TWO ways of its accessed set — the retired way,
    and (for fills) a stale duplicate of the filled tag — and the ways its
    run hit; columns are way*S1 + set within a plane."""
    S1, W2 = cfg.l1.sets, cfg.llc.ways
    line, l1s, slot, hit_way = rq.line, rq.l1s, rq.slot, rq.hit_way
    tag_rows, lru_rows, weff = rq.tag_rows, rq.lru_rows, rq.weff
    write_hit, upg, llc_hway = rq.write_hit, rq.upg, rq.llc_hway
    llc_hit, llc_vway = dr.llc_hit, dr.llc_vway
    TAG, STATE, LRU, PTR, EPOCH = range(5)  # planes of the fused L1 array

    # winner L1 update: UPG-in-place vs fill. Victim preference counts
    # directory-invalidated (stale) ways as free, matching eager-MESI's
    # invalid-first rule; the victim writeback fires only on EFFECTIVE M.
    upg_in_place = upg & winner  # upg requires an L1 hit: always in-place
    fill = (winner & ~upg_in_place) | join
    l1_vkey = jnp.where(weff == I, -1, lru_rows)  # lru_rows from the probe
    l1_vway = jnp.argmin(l1_vkey, axis=1).astype(jnp.int32)
    _count(acc, "l1_writebacks", fill & (weff[arange_c, l1_vway] == M))
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
    dup_col = t_way * S1 + l1s

    wj = winner | join
    lru_col = jnp.where(hit, hit_col, upd_col)
    st_own = write_hit | wj  # silent E->M + grants
    st_col = jnp.where(write_hit, hit_col, upd_col)
    st_val = jnp.where(write_hit, M, grant)
    # the filled line's directory entry position (way pointer); joins and
    # LLC hits fill at the line's hit way, misses at the victim
    fill_ptr = slot * W2 + jnp.where(join | llc_hit, llc_hway, llc_vway)
    # Targets are pairwise distinct up to benign identical-value
    # duplicates, so the order of the writes is free: dup_col != upd_col (a
    # duplicate is a different way than the fill target), hit refresh and
    # grant rows are disjoint lane classes, each write addresses its own
    # plane, run-LRU duplicates of phase-4 LRU writes carry the identical
    # step stamp, and a run E->M colliding with a phase-4 state write at the
    # same way is SUPPRESSED (phase 4 wrote after the run in the serialized
    # order, so its value wins).
    writes = [
        (TAG, dup, dup_col, -1),  # stale duplicate tag clear
        (STATE, dup, dup_col, I),  # stale duplicate state clear
        (LRU, hit | wj, lru_col, step_no),  # hit refresh / fill LRU stamp
        (STATE, st_own, st_col, st_val),  # silent E->M + grant state
        (TAG, wj, upd_col, line),  # fill tag
        (PTR, wj, upd_col, fill_ptr),  # fill way pointer
        (EPOCH, wj, upd_col, new_eph),  # fill-time entry epoch (post-bump)
    ]
    if cfg.local_run_len:
        hm, wm, cm = run_patch
        run_m_sup = wm & ~(st_own[:, None] & (st_col[:, None] == cm))
        writes += [(LRU, hm, cm, step_no), (STATE, run_m_sup, cm, M)]
    return writes


def _join_representative(join, entry, key, n):
    """[C] bool: of the lanes with `join` set, the one a directory entry
    (`entry` = slot * W2 + way, `n` entries in all) whose `key` is least:
    a scatter-min of the keys into a table of one word an entry, read back
    at the lane's own entry. Lanes without `join` scatter to the drop
    index `n` and read a clamped entry that `join` masks.

    The table has ONE form: scattered into flat, read as rows of 128
    lanes (the device's tile) at `(i >> 7, i & 127)`. Read flat,
    `jax.vmap` of the step (the fleet) relaid all B x n words from the
    scatter's flat tile into `[1, B, n]` one row a loop trip before the
    gather, every step (rung 2 at B = 16: 64 MB, 1.85 of a 6.48 ms step;
    PERF.md section 6, PR 44). As rows of a tile the scatter's result
    reaches the gather through a bitcast, solo and under a batch axis
    alike. Where `n` is no multiple of 128 the last row is padded: the
    drop index then lands in the padding (else past the table: dropped),
    and no read reaches it."""
    rows = -(-n // 128)
    tab = jnp.full(rows * 128, INT32_MAX, jnp.int32).at[
        jnp.where(join, entry, n)].min(key, mode="drop").reshape(rows, 128)
    rd = jnp.minimum(entry, n - 1)
    return join & (tab[rd >> 7, rd & 127] == key)


def _commit_writes(cfg: MachineConfig, st: MachineState, arange_c,
                   rq: Request, dr: DirOutcome, winner, join, key, grant, hit,
                   run_patch, acc, mesh=None):
    """Phase 4.A's array writes: every L1 write of the step (hit
    refreshes, grants and fills, stale-duplicate clears, the local runs'
    deferred writes: `_l1_writes`) as each core's edit of its own row
    (`_l1_row_write`, a select), and the directory's, a true cross-row
    write, in one row scatter-add -> (`l1_n`, `dirm_n`).

    The L1 write has ONE form. Until PR 38 it was one element scatter of
    all C x (7 + 2*rl) words, which on the v5e costs 5.7-10 ns a WORD and
    draws a flat relayout of the whole array (and from 4096 cores an
    index sort) round it; the select costs what the array's bytes cost.
    `scripts/prof/prof_gather.py writes`, us a write of 23 words a core,
    five planes, select against scatter: FS = W1*S1 512 (every BASELINE
    rung) 19.7 / 135 at 1024 cores, 531 / 3777 at 16384; FS 2048 34.5 /
    190 and 2067 / 8388; FS 8192 (the zoo's IPU tile, 1472 cores) 744 /
    4366, 8236 / 50449 at 16384. The scatter wins nowhere, so no size
    branch (scripts/prof/README.md has the table).

    No phase 4.B: under pull-based coherence the directory update IS the
    invalidations/downgrades — remote L1s re-derive their state on their
    next access (phase 1 validation)."""
    C, B = cfg.n_cores, cfg.n_banks
    S2, W2 = cfg.llc.sets, cfg.llc.ways
    NW = cfg.n_sharer_words
    MW = llc_meta_width(cfg)
    logG = cfg.sharer_group.bit_length() - 1
    step_no = st.step
    line, slot = rq.line, rq.slot
    meta_rows, llc_hway, shw = rq.meta_rows, rq.llc_hway, rq.shw
    word_idx, bit_idx = rq.word_idx, rq.bit_idx
    llc_tag_rows, owner_rows, sh_rows = rq.llc_tag_rows, rq.owner_rows, rq.sh_rows
    llc_hit, llc_miss, write_w = dr.llc_hit, dr.llc_miss, dr.write_w
    gets_probe, gets_shared, gets_excl_hit = (
        dr.gets_probe, dr.gets_shared, dr.gets_excl_hit)
    oclamp, llc_vway, llc_lru_rows = dr.oclamp, dr.llc_vway, dr.llc_lru_rows
    with jax.named_scope(P_COMMIT):
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
        l1_n = _l1_row_write(
            cfg,
            st.l1,
            _l1_writes(cfg, step_no, arange_c, rq, dr, winner, join, grant,
                       hit, run_patch, new_eph, acc),
        )

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
        jrep = least_of_entry(
            mesh, _join_representative, join, slot * W2 + llc_hway, key,
            B * S2 * W2)
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
        return l1_n, dirm_n


def _sync(cfg: MachineConfig, st: MachineState, arange_c, rq: Request, cycles,
          ptr, cycles_c, quantum_end, ctile, htile, bid, req_lat, req_hops,
          rep_lat, rep_hops, flt, extra_home, extra_bar, raw_rt, raw_arr,
          deadb, acc):
    """Phase 2.7: synchronization events (golden/sim.py phase 2.7) ->
    `cycles`, `ptr` [C] with the sync lanes added, and the next
    `lock_holder` [L], `barrier_count`, `barrier_time` [BS], `sync_flag`
    [C].

    Sync lanes (LOCK/UNLOCK/BARRIER) are disjoint from every memory lane
    (classification is by event type), so ordering after phase 4.A is
    immaterial; WITHIN sync the canonical order is unlocks -> lock grants
    -> barrier arrivals -> releases. Called under the STATIC `has_sync`
    only: traces without sync events (checked at ingest) compile none of
    it. It charges the legs phase 3 computed (the lock's home is the
    line's home bank) and the contention extras of whichever NoC model
    the machine has, which is why it takes them all."""
    C = cfg.n_cores
    kn = st.knobs
    Q, cpi_vec, llc_lat = kn.quantum, kn.cpi, kn.llc_lat
    et, earg, epre, line = rq.et, rq.earg, rq.epre, rq.line
    is_lock, is_unlock, is_barrier = rq.is_lock, rq.is_unlock, rq.is_barrier
    if cfg.faults_enabled:
        from ..faults.inject import leg_fault_penalty

        flt_rt, _, _, rr_req, rr_rep = flt
    lock_holder = st.lock_holder
    barrier_count = st.barrier_count
    barrier_time = st.barrier_time
    sync_flag = st.sync_flag
    with jax.named_scope(P_SYNC):
        L = cfg.lock_slots
        BS = cfg.barrier_slots
        # mutex address -> lock slot; its home is the line's home bank, so
        # the phase-3 core<->home-bank latencies/hops apply verbatim
        lslot = line & (L - 1)
        lreq_lat, lreq_hops = req_lat, req_hops
        lrep_lat, lrep_hops = rep_lat, rep_hops
        if _is_router(cfg):
            # raw_rt already reflects this lane's per-class injection
            # time (pre charged on unlocks and first lock attempts only)
            lat_rt = raw_rt
        else:
            lat_rt = lreq_lat + llc_lat + lrep_lat + extra_home
        if cfg.faults_enabled:
            # lock/unlock RMWs ride the same core<->home-bank legs as the
            # memory path: same round-trip fault extra
            lat_rt = lat_rt + flt_rt

        with jax.named_scope(_LOCK):
            # unlocks: every unlock is a charged RMW round trip to the lock's
            # home; the slot is released only if this core actually holds it
            cycles = cycles + jnp.where(is_unlock, epre * cpi_vec + lat_rt, 0)
            ptr = ptr + is_unlock.astype(jnp.int32)
            _count(acc, "instructions", jnp.where(is_unlock, epre + 1, 0))
            _count(acc, "noc_msgs", jnp.where(is_unlock, 2, 0))
            _count(acc, "noc_hops", jnp.where(is_unlock, lreq_hops + lrep_hops, 0))
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
            _count(
                acc,
                "instructions",
                jnp.where(first, epre, 0) + grant.astype(jnp.int32),
            )
            _count(acc, "lock_acquires", grant)
            _count(acc, "lock_spins", spin)
            _count(acc, "noc_msgs", jnp.where(is_lock, 2, 0))
            _count(acc, "noc_hops", jnp.where(is_lock, lreq_hops + lrep_hops, 0))
            if cfg.faults_enabled:
                _count(
                    acc,
                    "noc_reroutes",
                    jnp.where(is_unlock | is_lock, rr_req + rr_rep, 0),
                )
            lock_holder = lock_holder.at[jnp.where(grant, lslot, L)].set(
                arange_c, mode="drop"
            )
            sync_flag = jnp.where(grant, 0, jnp.where(spin, 1, sync_flag))
            ptr = ptr + grant.astype(jnp.int32)

        with jax.named_scope(_BARRIER):
            # barrier arrivals: charge pre + the arrival message, freeze the
            # core, bump the slot's count and max-arrival clock (bid/htile
            # hoisted above the contention block)
            barr_lat, barr_hops = _one_way(ctile, htile, cfg, kn)
            wake_lat, wake_hops = _one_way(htile, ctile, cfg, kn)
            barr_charge = raw_arr if _is_router(cfg) else barr_lat + extra_bar
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
            _count(acc, "instructions", jnp.where(is_barrier, epre, 0))
            _count(acc, "barrier_waits", is_barrier)
            _count(acc, "noc_msgs", is_barrier)
            _count(acc, "noc_hops", jnp.where(is_barrier, barr_hops, 0))
            if cfg.faults_enabled:
                _count(acc, "noc_reroutes", jnp.where(is_barrier, rr_arr, 0)
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
            _count(acc, "instructions", released)
            _count(acc, "noc_msgs", released)
            _count(acc, "noc_hops", jnp.where(released, wake_hops, 0))
            if cfg.faults_enabled:
                _count(acc, "noc_reroutes", jnp.where(released, rr_wk, 0)
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
    return cycles, ptr, lock_holder, barrier_count, barrier_time, sync_flag


def _commit_end(cfg: MachineConfig, st: MachineState, acc):
    """The end-of-step commit, once phase 2.7 has added its counter
    deltas -> the step's counters: the ONE stacked add of every delta in
    `acc`."""
    with jax.named_scope(P_COMMIT):
        counters = st.counters + _counter_deltas(
            acc, cfg.n_cores, st.counters.shape[0])
    return counters


def step(
    cfg: MachineConfig,
    events: DeviceTrace,
    st: MachineState,
    has_sync: bool = True,
    mesh=None,
) -> MachineState:
    """One simulation step: the phases in execution order. The static
    selectors of `cfg` (and `has_sync`) decide which phases a machine
    compiles; everything a phase reads or hands on is in its call. `mesh`
    is the tile mesh the state is sharded over, None on one device: the
    three phases that read it are `_local` and `_probe`, for their reads of
    whole `dirm` rows (`sharding.read_rows`), and `_commit_writes`, for
    its join table (`sharding.least_of_entry`). `events` is the trace as
    the device holds it (`trace/device.py`); the loops that call `step`
    lay a caller's raw `[C, T, 4]` array out once, outside their scan."""
    C = cfg.n_cores
    arange_c = jnp.arange(C, dtype=jnp.int32)
    # TIMING comes from the TRACED knob pytree carried in state, never
    # from cfg (which is a jit-static arg and may be timing-normalized):
    # one compiled program per GEOMETRY serves every timing variant, and
    # the fleet engine vmaps per-simulation knob values over the batch
    # axis. cfg keeps geometry and model selectors only.
    kn = st.knobs
    router = _is_router(cfg)
    acc: dict = {}  # this step's counter deltas (`_count`)

    deadb = None
    if cfg.faults_enabled:
        st, deadb = _fault(cfg, events, st, arange_c, acc)
    quantum_end, cycles_c, ptr_c, pev, run_patch = _local(
        cfg, events, st, arange_c, deadb, acc, mesh)
    rq = _probe(cfg, events, st, arange_c, cycles_c, ptr_c, quantum_end, pev,
                run_patch, deadb, acc, mesh)
    winner, join, key = _arb(cfg, kn, arange_c, rq, cycles_c, quantum_end, acc)
    (ctile, btile, htile, bid, home_txn, req_lat, req_hops, rep_lat, rep_hops,
     flt) = _dir_legs(cfg, kn, st.faults, arange_c, rq, winner, join, has_sync)
    if cfg.noc.contention and not router:
        extra_home, extra_bar = _noc_counts(
            cfg, kn, rq.is_barrier, home_txn, ctile, btile, htile, has_sync,
            acc)
    else:
        extra_home = extra_bar = jnp.zeros(C, jnp.int32)
    dr, (pf_line_n, pf_stride_n, pf_streak_n) = _dir_transition(
        cfg, st, arange_c, rq, winner, join, ctile, btile, acc)
    if cfg.dram_queue or router:
        ord_c = _fifo_order(cfg, key)
    if cfg.dram_queue:
        extra_dram, dram_free_n = _dram_queue(
            cfg, kn, st.dram_free, rq.bank, rq.epre, cycles_c, req_lat,
            dr.miss_dram, ord_c, acc)
    else:
        extra_dram = jnp.zeros(C, jnp.int32)
        dram_free_n = st.dram_free
    service, probe_any = _commit_service(cfg, kn, dr, winner, extra_dram)
    raw_rt = raw_arr = None
    link_free_n = st.link_free
    if router:
        raw_rt, raw_arr, extra_home, extra_bar, link_free_n = _router_walk(
            cfg, kn, st.link_free, st.sync_flag, rq, winner, join, home_txn,
            ctile, btile, htile, req_lat, req_hops, rep_lat, rep_hops,
            cycles_c, service, ord_c, has_sync, acc)
    cycles, ptr, grant, hit, req_hops, rep_hops = _commit_retire(
        cfg, kn, rq, dr, winner, join, cycles_c, ptr_c, service, probe_any,
        extra_home, raw_rt, req_lat, req_hops, rep_lat, rep_hops, flt, acc)
    l1_n, dirm_n = _commit_writes(
        cfg, st, arange_c, rq, dr, winner, join, key, grant, hit, run_patch,
        acc, mesh)
    lock_holder, barrier_count = st.lock_holder, st.barrier_count
    barrier_time, sync_flag = st.barrier_time, st.sync_flag
    if has_sync:
        (cycles, ptr, lock_holder, barrier_count, barrier_time,
         sync_flag) = _sync(
            cfg, st, arange_c, rq, cycles, ptr, cycles_c, quantum_end, ctile,
            htile, bid, req_lat, req_hops, rep_lat, rep_hops, flt, extra_home,
            extra_bar, raw_rt, raw_arr, deadb, acc)
    counters = _commit_end(cfg, st, acc)

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
        step=st.step + 1,
        pf_line=pf_line_n,
        pf_stride=pf_stride_n,
        pf_streak=pf_streak_n,
        counters=counters,
        knobs=kn,
        # post-injection fault state (`_fault` rebound `st`); faults-off
        # this is the untouched input pytree
        faults=st.faults,
    )
