"""Golden reference simulator: slow, scalar, obviously correct.

Implements DESIGN.md's step semantics with plain Python/NumPy loops. This is
the oracle the vectorized JAX engine (`primesim_tpu/sim/step.py`, run by `sim/engine.py`) must
match BIT-EXACTLY on per-core cycles, cache/directory state, and counters
(SURVEY.md §4: the single highest-value test asset the reference lacks).

Semantics map to the reference as: CoreManager per-core cycle accounting
(SURVEY.md §2 #2), Cache set-assoc lookup/LRU (#3), System directory-MESI
(#4), Network XY-hop latency (#6), Dram fixed latency (#7), and the relaxed
quantum barrier (#10) — all serialized here in the canonical deterministic
order DESIGN.md defines.
"""

from __future__ import annotations

import numpy as np

from ..config.machine import MachineConfig
from ..noc import topology as _topo
from ..noc.mesh import bank_tile, core_tile, n_links
from ..stats.counters import zero_counters, zero_stats
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

# MESI encoding shared with the JAX engine; O (MOESI) is DERIVED — never
# stored in l1_state, only classified from the home directory's view
I, S, E, M, O = 0, 1, 2, 3, 4


class GoldenSim:
    def __init__(self, cfg: MachineConfig, trace: Trace):
        assert trace.n_cores == cfg.n_cores
        self.cfg = cfg
        self.trace = trace
        # internal addressing is LINE-granular (same normalization as the
        # engine: byte traces shift at ingest, v4 line traces pass through)
        self.events = trace.line_events(cfg.line_bits)
        C, B = cfg.n_cores, cfg.n_banks
        l1s, l1w = cfg.l1.sets, cfg.l1.ways
        ls, lw = cfg.llc.sets, cfg.llc.ways

        self.cycles = np.zeros(C, dtype=np.int64)
        self.ptr = np.zeros(C, dtype=np.int64)
        self.cpi = np.array(cfg.core.cpi_vector(C), dtype=np.int64)

        self.l1_tag = np.full((C, l1s, l1w), -1, dtype=np.int64)
        self.l1_state = np.full((C, l1s, l1w), I, dtype=np.int64)
        self.l1_lru = np.zeros((C, l1s, l1w), dtype=np.int64)

        self.llc_tag = np.full((B, ls, lw), -1, dtype=np.int64)
        self.llc_owner = np.full((B, ls, lw), -1, dtype=np.int64)
        self.llc_lru = np.zeros((B, ls, lw), dtype=np.int64)
        # sharer bit-vector words, matching the JAX engine's packed layout
        self.sharers = np.zeros((B, ls, lw, cfg.n_sharer_words), dtype=np.uint32)

        self.counters = zero_counters(C)
        # the step's account of its own core-steps: the per-core rows of
        # STAT_NAMES, the engine's specification as `counters` is (the
        # histogram `noc_sort_log2` stays zero here: a test recounts it
        # from the per-step sums of `noc_entries`)
        self.stats = zero_stats(C)
        self.quantum_end = cfg.quantum
        self.step_count = 0

        # hop-by-hop router model: per-directed-link next-free clock,
        # carried across steps (contention_model="router")
        self.link_free = np.zeros(n_links(cfg), dtype=np.int64)
        # memory-controller queueing (cfg.dram_queue): per-bank next-free
        # clock, carried across steps
        self.dram_free = np.zeros(B, dtype=np.int64)

        # stride-prefetcher training state (DESIGN.md §25; idle under
        # prefetcher "none" — mirrors MachineState.pf_*)
        self.pf_line = np.zeros(C, dtype=np.int64)
        self.pf_stride = np.zeros(C, dtype=np.int64)
        self.pf_streak = np.zeros(C, dtype=np.int64)

        # synchronization state (DESIGN.md §3 phase 2.7)
        self.lock_holder = np.full(cfg.lock_slots, -1, dtype=np.int64)
        self.barrier_count = np.zeros(cfg.barrier_slots, dtype=np.int64)
        self.barrier_time = np.zeros(cfg.barrier_slots, dtype=np.int64)
        self.sync_flag = np.zeros(C, dtype=np.int64)
        from ..trace.format import validate_sync

        validate_sync(trace, cfg.barrier_slots)

    # ------------------------------------------------------------ helpers

    def _bank(self, line: int) -> int:
        return line % self.cfg.n_banks

    def _bank_set(self, line: int) -> int:
        return (line // self.cfg.n_banks) % self.cfg.llc.sets

    def _l1_set(self, line: int) -> int:
        return line % self.cfg.l1.sets

    def _victim_way(self, tags, states, lrus):
        """Invalid-first LRU with lowest-index tie break (DESIGN.md §1)."""
        key = [(-1 if states[w] == I else int(lrus[w])) for w in range(len(tags))]
        return int(np.argmin(key))

    def _set_sharer(self, b, s, w, core, val: bool):
        # coarse vector (cfg.sharer_group > 1): the bit covers the whole
        # group of cores `core` belongs to
        g = core // self.cfg.sharer_group
        wi, bit = g // 32, g % 32
        if val:
            self.sharers[b, s, w, wi] |= np.uint32(1 << bit)
        else:
            self.sharers[b, s, w, wi] &= np.uint32(~(1 << bit) & 0xFFFFFFFF)

    def _clear_sharers(self, b, s, w):
        self.sharers[b, s, w, :] = 0

    def _derived_owned(self, c: int, line: int) -> bool:
        """MOESI derived-O test (DESIGN.md §25): core c's stored E/M line
        is effectively Owned when the home directory still names c owner
        WITH other sharers recorded (a GETS left the dirty copy in
        place). O is never stored — reads stay local, stores must
        arbitrate as upgrades to invalidate the sharers. Directory rows
        are unwritten between classification and phase 3, so the live
        read here equals the engine's step-start row."""
        if self.cfg.coherence != "moesi":
            return False
        b, bs = self._bank(line), self._bank_set(line)
        for wy in range(self.cfg.llc.ways):
            if self.llc_tag[b, bs, wy] == line:
                if self.llc_owner[b, bs, wy] != c:
                    return False
                shl = self._sharers_from(self.sharers, b, bs, wy)
                return any(t != c for t in shl)
        return False

    def _pf_hit(self, c: int, line: int) -> bool:
        """Stride-prefetch coverage test on core c's STEP-ENTRY training
        state (DESIGN.md §25): the line sits 1..prefetch_degree confirmed
        strides (streak >= 2) ahead of the last trained access. Safe to
        read live: only c's own winner/join trains c's state, and that
        happens after this test."""
        if self.cfg.prefetcher != "stride":
            return False
        s = int(self.pf_stride[c])
        if s == 0 or int(self.pf_streak[c]) < 2:
            return False
        delta = line - int(self.pf_line[c])
        q, rem = divmod(delta, s)  # floor semantics, same as the engine
        return rem == 0 and 1 <= q <= self.cfg.prefetch_degree

    def _pf_train(self, c: int, line: int) -> None:
        """Train the stride detector on a retired uncore access (winners
        + joins only — retries re-observe the same line and must not
        retrain; local L1 hits never reach the uncore)."""
        if self.cfg.prefetcher != "stride":
            return
        ns = line - int(self.pf_line[c])
        if ns == int(self.pf_stride[c]) and ns != 0:
            self.pf_streak[c] += 1
        else:
            self.pf_streak[c] = 1
        self.pf_stride[c] = ns
        self.pf_line[c] = line

    def _lock_slot(self, line: int) -> int:
        """Mutex LINE index -> lock-table slot (events are line-granular)."""
        return line & (self.cfg.lock_slots - 1)

    def _lock_home_tile(self, line: int) -> int:
        return bank_tile(self._bank(line), self.cfg)

    # topology dispatch (DESIGN.md §25): every hop count, one-way latency
    # and route in the golden model goes through noc/topology.py, so the
    # torus/ring plugins are oracle-checked by the same parity suite
    def _thops(self, tile_a: int, tile_b: int) -> int:
        return int(_topo.hops(self.cfg, tile_a, tile_b, xp=np))

    def _owl(self, tile_a: int, tile_b: int) -> int:
        return int(_topo.one_way_lat(self.cfg, tile_a, tile_b))

    def _links(self, tile_a: int, tile_b: int) -> list[int]:
        return list(_topo.route_links(self.cfg, tile_a, tile_b))

    def _noc(self, c: int, tile_a: int, tile_b: int):
        """Charge one message tile_a->tile_b to core c's NoC counters."""
        lat = self._owl(tile_a, tile_b)
        self.counters["noc_msgs"][c] += 1
        self.counters["noc_hops"][c] += self._thops(tile_a, tile_b)
        return lat

    def _txn_path(self, ctile: int, htile: int, round_trip: bool) -> list[int]:
        p = self._links(ctile, htile)
        if round_trip:
            p = p + self._links(htile, ctile)
        return p

    def _contention_extra(
        self, c: int, ctile: int, htile: int, round_trip: bool = True
    ) -> int:
        """Queueing charge for core c's transaction from `ctile` to home
        `htile` this step (0 when the model is disabled). Tile model:
        occupancy at the home tile; link model: bottleneck occupancy over
        the transaction's XY path links. The router model charges through
        `_route` instead (this returns 0 so analytic compositions stay
        clean and the router surcharge replaces them wholesale)."""
        cfg = self.cfg
        if not cfg.noc.contention:
            return 0
        if cfg.noc.contention_model == "router":
            return 0
        if cfg.noc.contention_model == "tile":
            extra = cfg.noc.contention_lat * (self._tile_txns.get(htile, 1) - 1)
        else:
            worst = 0
            for l in self._txn_path(ctile, htile, round_trip):
                worst = max(worst, self._link_cnt.get(l, 1) - 1)
            extra = cfg.noc.contention_lat * worst
        self.counters["noc_contention_cycles"][c] += extra
        return extra

    # ------------------------------------------ hop-by-hop router model

    @property
    def _router_on(self) -> bool:
        return (
            self.cfg.noc.contention
            and self.cfg.noc.contention_model == "router"
        )

    def _rtr_rank(self, link: int, key) -> int:
        """FIFO position among this step's packets on `link`: how many
        same-step transactions with a smaller (clock, core) key also
        traverse it. Fixed at step entry — every transaction's charge
        depends only on carried link clocks, the step's fixed rank/anchor
        tables, and its own timings, which is what makes the vectorized
        engine bit-exact."""
        return sum(1 for k in self._rtr_users.get(link, ()) if k < key)

    def _route(self, t0: int, path, key) -> int:
        """Walk one packet over `path` hop by hop against the carried
        per-link clocks: at each link wait for
        `max(link_free, base) + rank*link_lat` — `base` is the link's
        EARLIEST NOMINAL (uncontended) arrival among this step's packets,
        so same-step FIFO serialization anchors at when the link's queue
        actually starts forming, not at a long-idle link clock — then
        occupy the link for link_lat and pay router_lat at the next
        router; waits cascade into later hops. Records each departure for
        the end-of-step clock advance. Returns the arrival time;
        uncontended this is exactly t0 + hops*link_lat +
        (hops+1)*router_lat (the analytic one-way)."""
        noc = self.cfg.noc
        t = t0 + noc.router_lat
        for l in path:
            rank = self._rtr_rank(l, key)
            anchor = max(int(self.link_free[l]), self._rtr_base.get(l, 0))
            t = max(t, anchor + rank * noc.link_lat)
            self._rtr_departs.append((l, t + noc.link_lat))
            t += noc.link_lat + noc.router_lat
        return t

    def _route_rt(self, c: int, t0: int, htile: int, service: int) -> int:
        """Round-trip request->service->reply through the router, keyed
        by core c's recorded step-entry key. Returns completion time."""
        ctile = core_tile(c, self.cfg)
        key = self._rtr_key[c]
        t = self._route(t0, self._links(ctile, htile), key)
        return self._route(t + service, self._links(htile, ctile), key)

    def _rtr_end(self) -> None:
        for l, d in self._rtr_departs:
            if d > self.link_free[l]:
                self.link_free[l] = d
        self._rtr_departs = []

    # --------------------------------------------------------------- step

    def done(self) -> bool:
        t = self.events
        return all(
            t[c, min(int(self.ptr[c]), self.trace.max_len - 1), 0] == EV_END
            for c in range(self.cfg.n_cores)
        )

    def step(self) -> None:
        cfg = self.cfg
        C = cfg.n_cores
        ev = self.events

        # --- quantum barrier (DESIGN.md §3): bump quantum_end if nobody
        # active. Barrier-frozen cores neither bump nor bound the quantum.
        cur = [ev[c, min(int(self.ptr[c]), self.trace.max_len - 1)] for c in range(C)]
        not_done = [c for c in range(C) if cur[c][0] != EV_END]
        if not not_done:
            return

        def _frozen(c):
            return cur[c][0] == EV_BARRIER and self.sync_flag[c]

        countable = [c for c in not_done if not _frozen(c)]
        active = [c for c in countable if self.cycles[c] < self.quantum_end]
        if not active and countable:
            m = min(int(self.cycles[c]) for c in countable)
            self.quantum_end = (m // cfg.quantum + 1) * cfg.quantum
            active = [c for c in countable if self.cycles[c] < self.quantum_end]
        # Clock-window invariant (DESIGN.md §3-sync): every active core's
        # clock lies in [quantum_end - Q, quantum_end). The JAX engine's
        # packed arbitration keys (rel*C + core) REQUIRE this; asserting it
        # here makes every golden/parity test also an invariant check.
        assert all(
            self.cycles[c] >= self.quantum_end - cfg.quantum for c in active
        ), "clock-window invariant violated"

        step = self.step_count
        self.step_count += 1

        # --- phase 0.5: local runs (DESIGN.md §3) --------------------------
        # Each active core first retires up to `local_run_len` LOCAL events
        # (INS batches, L1 read hits, L1 write hits in E/M) in order, judged
        # against the live directory (which no run modifies — runs touch only
        # the core's own L1 row: LRU refresh, silent E->M) and the core's own
        # live L1 state. The run stops at the first non-local event, at the
        # quantum boundary, or after local_run_len events. The event then at
        # ptr enters the normal per-step phases below.
        for c in active:
            ptr0 = int(self.ptr[c])
            for _ in range(cfg.local_run_len):
                if self.cycles[c] >= self.quantum_end:
                    break
                e = ev[c, min(int(self.ptr[c]), self.trace.max_len - 1)]
                t, arg, addr = int(e[0]), int(e[1]), int(e[2])
                pre = int(e[3])
                if t == EV_END:
                    break
                if t == EV_INS:
                    self.cycles[c] += arg * int(self.cpi[c])
                    self.counters["instructions"][c] += arg
                    self.ptr[c] += 1
                    continue
                if t not in (EV_LD, EV_ST):
                    break  # sync events are never local: arbitrate below
                line = addr  # line-granular events
                s = self._l1_set(line)
                w = -1
                for wy in range(cfg.l1.ways):
                    if (
                        self.l1_tag[c, s, wy] == line
                        and self.l1_state[c, s, wy] != I
                    ):
                        w = wy
                        break
                if w < 0:
                    break  # miss: stop the run, arbitrate below
                if t == EV_ST and (
                    self.l1_state[c, s, w] not in (E, M)
                    or self._derived_owned(c, line)
                ):
                    break  # held in S (or derived O): upgrade, arbitrate
                self.cycles[c] += pre * int(self.cpi[c]) + cfg.l1.latency
                self.counters["instructions"][c] += pre + 1
                if t == EV_LD:
                    self.counters["l1_read_hits"][c] += 1
                else:
                    self.counters["l1_write_hits"][c] += 1
                    self.l1_state[c, s, w] = M  # silent E->M
                self.l1_lru[c, s, w] = step
                self.ptr[c] += 1
            self.stats["run_events"][c] += int(self.ptr[c]) - ptr0
        if cfg.local_run_len:
            # re-gather events and the active set at the post-run pointers
            cur = [
                ev[c, min(int(self.ptr[c]), self.trace.max_len - 1)]
                for c in range(C)
            ]
            active = [
                c
                for c in range(C)
                if cur[c][0] != EV_END
                and not _frozen(c)
                and self.cycles[c] < self.quantum_end
            ]

        # where this step's core-steps went: a core presents its event,
        # is frozen at a barrier, or waits ahead of the quantum window;
        # the rest are at END
        for c in range(C):
            if cur[c][0] == EV_END:
                continue
            if _frozen(c):
                self.stats["slot_frozen"][c] += 1
            elif self.cycles[c] < self.quantum_end:
                self.stats["slot_active"][c] += 1
            else:
                self.stats["slot_quantum"][c] += 1

        # --- phase 0/1: classify against step-start state ------------------
        # Only the L1 tag/state arrays need step-start snapshots: phase-3
        # reads of OTHER cores' L1 rows (owner probes) must not see this
        # step's phase-A writes. Every other read in the step touches rows
        # that nothing else writes within the step (winners own their
        # (bank,set) exclusively; cores own their L1 row), so live arrays
        # are equivalent and the expensive LLC copies are skipped.
        l1_tag0 = self.l1_tag.copy()
        l1_state0 = self.l1_state.copy()

        # request tuple: (cycles, core, kind, line, pre)
        requests = []
        joins = []  # read-join candidates: (core, line, pre)
        lock_reqs = []  # (cycles, core, addr, pre)
        unlocks = []  # (core, addr, pre)
        barrier_arr = []  # (core, barrier id, n participants, pre)
        GETS, GETM, UPG = 0, 1, 2

        for c in active:
            t, arg, addr = int(cur[c][0]), int(cur[c][1]), int(cur[c][2])
            pre = int(cur[c][3])  # pre-batched non-memory instructions
            if t == EV_INS:
                self.cycles[c] += arg * int(self.cpi[c])
                self.counters["instructions"][c] += arg
                self.ptr[c] += 1
                continue
            if t == EV_LOCK:
                lock_reqs.append((int(self.cycles[c]), c, addr, pre))
                continue
            if t == EV_UNLOCK:
                unlocks.append((c, addr, pre))
                continue
            if t == EV_BARRIER:
                barrier_arr.append((c, addr, arg, pre))
                continue
            line = addr  # line-granular events
            s = self._l1_set(line)
            w = -1
            for wy in range(cfg.l1.ways):
                if l1_tag0[c, s, wy] == line and l1_state0[c, s, wy] != I:
                    w = wy
                    break
            if t == EV_LD:
                if w >= 0:  # read hit
                    self.cycles[c] += pre * int(self.cpi[c]) + cfg.l1.latency
                    self.counters["l1_read_hits"][c] += 1
                    self.counters["instructions"][c] += pre + 1
                    self.l1_lru[c, s, w] = step  # phase A local
                    self.ptr[c] += 1
                elif self._join_eligible(c, line):
                    joins.append((c, line, pre))
                else:
                    requests.append((int(self.cycles[c]), c, GETS, line, pre))
            else:  # EV_ST
                if (
                    w >= 0
                    and l1_state0[c, s, w] in (E, M)
                    and not self._derived_owned(c, line)
                ):  # write hit (E/M exactly — derived O must arbitrate)
                    self.cycles[c] += pre * int(self.cpi[c]) + cfg.l1.latency
                    self.counters["l1_write_hits"][c] += 1
                    self.counters["instructions"][c] += pre + 1
                    self.l1_state[c, s, w] = M  # silent E->M, phase A local
                    self.l1_lru[c, s, w] = step
                    self.ptr[c] += 1
                elif w >= 0:  # held in S (or derived O) -> upgrade
                    requests.append((int(self.cycles[c]), c, UPG, line, pre))
                else:
                    requests.append((int(self.cycles[c]), c, GETM, line, pre))

        # --- phase 2: per-(bank,set) conflict serialization ----------------
        # Read-joins (GETS to a shared, ownerless, already-shared line)
        # coalesce: any number retire in one step, bit-exact to any
        # serialization order because the join path's latency is independent
        # of the sharer set and the sharer-bit updates commute (DESIGN.md
        # §3). A join only proceeds if no arbitrating request targets its
        # home (bank,set) this step; otherwise it demotes to a normal GETS.
        arb_slots = {
            (self._bank(r[3]), self._bank_set(r[3])) for r in requests
        }
        join_go = []
        for c, line, pre in joins:
            if (self._bank(line), self._bank_set(line)) in arb_slots:
                requests.append((int(self.cycles[c]), c, GETS, line, pre))
            else:
                join_go.append((c, line, pre))

        by_bankset: dict[tuple[int, int], list] = {}
        for r in requests:
            key = (self._bank(r[3]), self._bank_set(r[3]))
            by_bankset.setdefault(key, []).append(r)
        winners = []
        for key, rs in by_bankset.items():
            rs.sort(key=lambda r: (r[0], r[1]))  # (cycles, core_id)
            winners.append(rs[0])
            for r in rs[1:]:
                self.counters["retries"][r[1]] += 1

        # --- contention occupancy counts (NocConfig.contention) -----------
        # Tile model: every uncore transaction served at a home tile this
        # step queues behind the others there. Link model: every directed
        # mesh link on a transaction's XY request+reply path (barrier
        # arrivals: one-way) is claimed by it. Counts are fixed BEFORE any
        # charging so the extra is identical for every transaction sharing
        # a tile/link (matching the engine's one-scatter count). The
        # transaction classes: memory winners + joins (home bank),
        # lock/unlock RMWs (lock home), barrier arrivals (barrier home).
        self._tile_txns = {}
        self._link_cnt = {}
        self._rtr_users = {}
        self._rtr_base = {}
        self._rtr_key = {}
        self._rtr_departs = []
        if cfg.noc.contention:
            link_model = cfg.noc.contention_model == "link"
            router = cfg.noc.contention_model == "router"
            c_hop = cfg.noc.link_lat + cfg.noc.router_lat
            r_lat = cfg.noc.router_lat

            def _bump(c, htile, round_trip=True, key=None, t0=0):
                if router:
                    # record this packet's links, canonical key, and
                    # NOMINAL (uncontended) per-link arrival times; ranks
                    # and queue anchors are computed against this fixed
                    # set. Reply-leg nominals assume llc.latency service
                    # (the model's defined anchor — the real service may
                    # be longer; `base` is a min, so early is safe).
                    self._rtr_key[c] = key
                    ctile = core_tile(c, cfg)
                    req = self._links(ctile, htile)
                    legs = [(req, t0)]
                    if round_trip:
                        legs.append(
                            (
                                self._links(htile, ctile),
                                t0
                                + r_lat
                                + len(req) * c_hop
                                + cfg.llc.latency,
                            )
                        )
                    seen = set()
                    self.stats["noc_entries"][c] += sum(
                        len(path) for path, _ in legs)
                    for path, leg_t0 in legs:
                        for k, l in enumerate(path):
                            a = leg_t0 + r_lat + k * c_hop
                            if (b := self._rtr_base.get(l)) is None or a < b:
                                self._rtr_base[l] = a
                            if l not in seen:
                                seen.add(l)
                                self._rtr_users.setdefault(l, []).append(key)
                elif link_model:
                    ctile = core_tile(c, cfg)
                    for l in self._txn_path(ctile, htile, round_trip):
                        self._link_cnt[l] = self._link_cnt.get(l, 0) + 1
                else:
                    self._tile_txns[htile] = self._tile_txns.get(htile, 0) + 1

            l1lat = cfg.l1.latency
            for cyc, c, _, line, pre in winners:
                _bump(
                    c,
                    bank_tile(self._bank(line), cfg),
                    key=(cyc, c),
                    t0=cyc + pre * int(self.cpi[c]) + l1lat,
                )
            for c, line, pre in join_go:
                cy = int(self.cycles[c])
                _bump(
                    c,
                    bank_tile(self._bank(line), cfg),
                    key=(cy, c),
                    t0=cy + pre * int(self.cpi[c]) + l1lat,
                )
            for c, addr, pre in unlocks:
                cy = int(self.cycles[c])
                _bump(
                    c,
                    self._lock_home_tile(addr),
                    key=(cy, c),
                    t0=cy + pre * int(self.cpi[c]),
                )
            for cyc, c, addr, pre in lock_reqs:
                first = self.sync_flag[c] == 0
                _bump(
                    c,
                    self._lock_home_tile(addr),
                    key=(cyc, c),
                    t0=cyc + (pre * int(self.cpi[c]) if first else 0),
                )
            for c, bid, _, pre in barrier_arr:
                cy = int(self.cycles[c])
                _bump(
                    c,
                    bid % cfg.n_tiles,
                    round_trip=False,
                    key=(cy, c),
                    t0=cy + pre * int(self.cpi[c]),
                )

        for c, line, pre in join_go:
            self._do_join(c, line, pre, step)

        # --- memory-controller queue pre-pass (cfg.dram_queue) -------------
        # This step's DRAM transactions (miss winners) and their NOMINAL
        # controller arrivals are fixed BEFORE any winner is processed, so
        # ranks/anchors are step-scoped exactly like the router model's;
        # the per-slot uniqueness of winners makes the hit peek identical
        # to the processing-time lookup.
        self._dram_users = {}
        self._dram_base = {}
        self._dram_arr = {}
        self._dram_starts = []
        if cfg.dram_queue:
            svc = cfg.dram_service or cfg.dram_lat
            for cyc, c, kind, line, pre in winners:
                b, bs = self._bank(line), self._bank_set(line)
                if any(
                    self.llc_tag[b, bs, w] == line
                    for w in range(cfg.llc.ways)
                ):
                    continue  # LLC hit: no controller access
                if self._pf_hit(c, line):
                    continue  # prefetch-covered miss: no controller access
                a = (
                    cyc
                    + pre * int(self.cpi[c])
                    + cfg.l1.latency
                    + self._owl(core_tile(c, cfg), bank_tile(b, cfg))
                    + cfg.llc.latency
                )
                self._dram_users.setdefault(b, []).append((cyc, c))
                self._dram_arr[c] = a
                if b not in self._dram_base or a < self._dram_base[b]:
                    self._dram_base[b] = a

        # --- phase 3: transitions on step-start state; collect phase-B ops -
        # Phase-B op = (core, line, op) with op in {"downgrade","invalidate"}
        phase_b: list[tuple[int, int, str]] = []

        for cyc, c, kind, line, pre in sorted(winners, key=lambda r: r[1]):
            b = self._bank(line)
            bs = self._bank_set(line)
            ctile = core_tile(c, cfg)
            btile = bank_tile(b, cfg)

            lat = cfg.l1.latency
            lat += self._noc(c, ctile, btile)  # request
            lat += cfg.llc.latency

            # LLC lookup (step-start)
            hitw = -1
            for wy in range(cfg.llc.ways):
                if self.llc_tag[b, bs, wy] == line:
                    hitw = wy
                    break

            if kind == GETS:
                self.counters["l1_read_misses"][c] += 1
            elif kind == GETM:
                self.counters["l1_write_misses"][c] += 1
            else:
                self.counters["upgrades"][c] += 1

            if hitw >= 0:
                self.counters["llc_hits"][c] += 1
                w = hitw
                owner = int(self.llc_owner[b, bs, w])
                recorded = self._sharers_from(self.sharers, b, bs, w)
                shl = [t for t in recorded if t != c]
                # coarse vector: "shared" means ANY group bit is set —
                # the requester's own group bit may cover other cores, so
                # exclusivity requires an empty vector
                shared_any = (
                    bool(shl)
                    if cfg.sharer_group == 1
                    else self._any_sharer_bit(b, bs, w)
                )
                if kind == GETS:
                    if owner >= 0 and owner != c:
                        # probe owner (charged regardless of staleness)
                        otile = core_tile(owner, cfg)
                        lat += self._noc(c, btile, otile)
                        lat += self._noc(c, otile, btile)
                        self.counters["probes"][c] += 1
                        if cfg.coherence == "moesi":
                            # dirty sharing: the probed owner KEEPS the
                            # line (derives to O on its next access) and
                            # existing sharers stay recorded — no
                            # downgrade op, no owner clear
                            pass
                        else:
                            phase_b.append((owner, line, "downgrade"))
                            self.llc_owner[b, bs, w] = -1
                            self._clear_sharers(b, bs, w)
                        self._set_sharer(b, bs, w, c, True)
                        # The directory cannot observe silent L1 evictions,
                        # so the probed owner is conservatively re-recorded
                        # as a sharer whether or not it still holds the line
                        # (recorded sharers stay a superset of holders) —
                        # exactly what a real home node does, and it keeps
                        # the home-side transition free of any read of the
                        # owner's private cache state.
                        self._set_sharer(b, bs, w, owner, True)
                        grant = S
                    elif shared_any:
                        # no-op under mesi (owner >= 0 implies an empty
                        # sharer vector there); under moesi the owner's
                        # OWN refetch after a silent eviction lands here
                        # and relinquishes ownership
                        self.llc_owner[b, bs, w] = -1
                        self._set_sharer(b, bs, w, c, True)
                        grant = S
                    else:
                        self.llc_owner[b, bs, w] = c
                        self._clear_sharers(b, bs, w)
                        grant = E
                else:  # GETM or UPG
                    inv_lat = 0
                    if owner >= 0 and owner != c:
                        otile = core_tile(owner, cfg)
                        lat += self._noc(c, btile, otile)
                        lat += self._noc(c, otile, btile)
                        self.counters["probes"][c] += 1
                        phase_b.append((owner, line, "invalidate"))
                    # serialization latency spans every RECORDED core of
                    # flagged groups (coarse mode: including the
                    # requester's own slot — the home node serializes the
                    # whole group broadcast); messages/counters/phase-B
                    # go to the recorded cores minus the requester
                    for tcore in recorded:
                        ttile = core_tile(tcore, cfg)
                        rt = self._owl(btile, ttile) * 2
                        if cfg.sharer_group > 1 or tcore != c:
                            inv_lat = max(inv_lat, rt)
                    for tcore in shl:
                        ttile = core_tile(tcore, cfg)
                        self.counters["invalidations"][c] += 1
                        self.counters["noc_msgs"][c] += 2
                        self.counters["noc_hops"][c] += 2 * self._thops(
                            btile, ttile
                        )
                        phase_b.append((tcore, line, "invalidate"))
                    lat += inv_lat
                    self.llc_owner[b, bs, w] = c
                    self._clear_sharers(b, bs, w)
                    grant = M
                self.llc_lru[b, bs, w] = step
            else:
                # LLC miss -> DRAM + fill (UPG stale corner handled as GETM)
                self.counters["llc_misses"][c] += 1
                self.counters["dram_accesses"][c] += 1
                self.counters["noc_msgs"][c] += 2  # to co-located controller
                if self._pf_hit(c, line):
                    # covered by the stride prefetcher: pay the buffer
                    # latency, skip the controller queue AND dram_lat
                    # (dram_accesses above still counts it — the fetch
                    # happened, just earlier)
                    self.counters["prefetch_hits"][c] += 1
                    lat += cfg.prefetch_lat
                else:
                    if cfg.dram_queue:
                        svc = cfg.dram_service or cfg.dram_lat
                        bkey = (cyc, c)
                        rank = sum(
                            1 for k in self._dram_users.get(b, ()) if k < bkey
                        )
                        a = self._dram_arr[c]
                        start = max(
                            a,
                            max(int(self.dram_free[b]), self._dram_base[b])
                            + rank * svc,
                        )
                        self.counters["dram_queue_cycles"][c] += start - a
                        lat += start - a
                        self._dram_starts.append((b, start + svc))
                    lat += cfg.dram_lat
                # victim selection on step-start state
                w = self._victim_way(
                    self.llc_tag[b, bs],
                    self._llc_valid(self.llc_tag, b, bs),
                    self.llc_lru[b, bs],
                )
                if self.llc_tag[b, bs, w] != -1:
                    vline = int(self.llc_tag[b, bs, w])
                    vowner = int(self.llc_owner[b, bs, w])
                    vtargets = self._sharers_from(self.sharers, b, bs, w)
                    if vowner >= 0:
                        self.counters["llc_writebacks"][c] += 1
                        if vowner not in vtargets:
                            vtargets = vtargets + [vowner]
                    for tcore in vtargets:
                        ttile = core_tile(tcore, cfg)
                        self.counters["invalidations"][c] += 1
                        self.counters["noc_msgs"][c] += 2
                        self.counters["noc_hops"][c] += 2 * self._thops(
                            btile, ttile
                        )
                        phase_b.append((tcore, vline, "invalidate"))
                self.llc_tag[b, bs, w] = line
                self.llc_lru[b, bs, w] = step
                if kind == GETS:
                    self.llc_owner[b, bs, w] = c
                    self._clear_sharers(b, bs, w)
                    grant = E
                else:
                    self.llc_owner[b, bs, w] = c
                    self._clear_sharers(b, bs, w)
                    grant = M

            lat += self._noc(c, btile, ctile)  # reply
            lat += self._contention_extra(c, ctile, btile)

            if self._router_on:
                # replace the analytic request/reply legs with the hop-by
                # -hop walk; everything between them (LLC, probes,
                # invalidations, DRAM) is the service interval
                req_a = self._owl(ctile, btile)
                rep_a = self._owl(btile, ctile)
                service = lat - cfg.l1.latency - req_a - rep_a
                t0 = cyc + pre * int(self.cpi[c]) + cfg.l1.latency
                t_end = self._route_rt(c, t0, btile, service)
                raw = cfg.l1.latency + (t_end - t0)
                self.counters["noc_contention_cycles"][c] += raw - lat
                lat = raw

            # O3-style overlap: hide a fraction of the miss latency
            ov = cfg.core.o3_overlap_256
            if ov:
                lat = lat - ((lat * ov) >> 8)

            # --- phase 4.A for this winner: L1 update ----------------------
            s = self._l1_set(line)
            curw = -1
            for wy in range(cfg.l1.ways):
                if l1_tag0[c, s, wy] == line and l1_state0[c, s, wy] != I:
                    curw = wy
                    break
            if kind == UPG and curw >= 0:
                self.l1_state[c, s, curw] = grant
                self.l1_lru[c, s, curw] = step
            else:
                vw = self._victim_way(
                    l1_tag0[c, s],
                    l1_state0[c, s],
                    self.l1_lru[c, s],
                )
                if l1_state0[c, s, vw] == M:
                    self.counters["l1_writebacks"][c] += 1
                self.l1_tag[c, s, vw] = line
                self.l1_state[c, s, vw] = grant
                self.l1_lru[c, s, vw] = step

            self.cycles[c] += pre * int(self.cpi[c]) + lat
            self.counters["instructions"][c] += pre + 1
            self.ptr[c] += 1
            self._pf_train(c, line)

        # --- phase 4.B: remote ops, tag-conditional against live state -----
        for tcore, line, op in phase_b:
            s = self._l1_set(line)
            for wy in range(cfg.l1.ways):
                if self.l1_tag[tcore, s, wy] == line and self.l1_state[tcore, s, wy] != I:
                    if op == "downgrade":
                        if self.l1_state[tcore, s, wy] in (E, M):
                            self.l1_state[tcore, s, wy] = S
                    else:
                        self.l1_state[tcore, s, wy] = I
                    break

        # --- phase 2.7: synchronization events (DESIGN.md) -----------------
        # Sync and memory phases touch disjoint per-core/table state, so
        # their relative order within the step is immaterial; unlocks ->
        # lock grants -> barrier arrivals -> releases is the canonical
        # order WITHIN sync.
        for c, addr, pre in unlocks:
            s = self._lock_slot(addr)
            h = self._lock_home_tile(addr)
            ctile = core_tile(c, cfg)
            lat = self._noc(c, ctile, h) + cfg.llc.latency + self._noc(c, h, ctile)
            lat += self._contention_extra(c, ctile, h)
            if self._router_on:
                t0 = int(self.cycles[c]) + pre * int(self.cpi[c])
                t_end = self._route_rt(c, t0, h, cfg.llc.latency)
                self.counters["noc_contention_cycles"][c] += (t_end - t0) - lat
                lat = t_end - t0
            self.cycles[c] += pre * int(self.cpi[c]) + lat
            self.counters["instructions"][c] += pre + 1
            if self.lock_holder[s] == c:
                self.lock_holder[s] = -1
            self.ptr[c] += 1

        by_slot: dict[int, list] = {}
        for r in lock_reqs:
            by_slot.setdefault(self._lock_slot(r[2]), []).append(r)
        for s, rs in sorted(by_slot.items()):
            rs.sort(key=lambda r: (r[0], r[1]))  # (cycles, core_id)
            for i, (cyc, c, addr, pre) in enumerate(rs):
                h = self._lock_home_tile(addr)
                ctile = core_tile(c, cfg)
                # every attempt (grant or spin) is a charged RMW round trip
                lat = (
                    self._noc(c, ctile, h)
                    + cfg.llc.latency
                    + self._noc(c, h, ctile)
                )
                lat += self._contention_extra(c, ctile, h)
                if self._router_on:
                    t0 = int(self.cycles[c]) + (
                        pre * int(self.cpi[c]) if self.sync_flag[c] == 0 else 0
                    )
                    t_end = self._route_rt(c, t0, h, cfg.llc.latency)
                    self.counters["noc_contention_cycles"][c] += (
                        t_end - t0
                    ) - lat
                    lat = t_end - t0
                if self.sync_flag[c] == 0:  # first attempt: charge pre batch
                    self.cycles[c] += pre * int(self.cpi[c])
                    self.counters["instructions"][c] += pre
                self.cycles[c] += lat
                holder = int(self.lock_holder[s])
                if holder == c or (i == 0 and holder == -1):
                    self.lock_holder[s] = c
                    self.counters["lock_acquires"][c] += 1
                    self.counters["instructions"][c] += 1
                    self.sync_flag[c] = 0
                    self.ptr[c] += 1
                else:
                    self.counters["lock_spins"][c] += 1
                    self.sync_flag[c] = 1

        for c, bid, n, pre in barrier_arr:
            h = bid % cfg.n_tiles
            ctile = core_tile(c, cfg)
            self.cycles[c] += pre * int(self.cpi[c])
            self.counters["instructions"][c] += pre
            arr_lat = self._noc(c, ctile, h)  # arrival message
            if self._router_on:
                t0 = int(self.cycles[c])
                t_end = self._route(
                    t0,
                    self._links(ctile, h),
                    self._rtr_key[c],
                )
                self.counters["noc_contention_cycles"][c] += (
                    t_end - t0
                ) - arr_lat
                arr_lat = t_end - t0
            self.cycles[c] += arr_lat
            self.cycles[c] += self._contention_extra(c, ctile, h, round_trip=False)
            self.counters["barrier_waits"][c] += 1
            self.sync_flag[c] = 1
            self.barrier_count[bid] += 1
            self.barrier_time[bid] = max(
                int(self.barrier_time[bid]), int(self.cycles[c])
            )

        # releases: every waiter whose slot count reached ITS participant
        # count resumes at the slot's max arrival time + wake-up message
        waiting: dict[int, list] = {}
        for c in range(C):
            e = ev[c, min(int(self.ptr[c]), self.trace.max_len - 1)]
            if int(e[0]) == EV_BARRIER and self.sync_flag[c]:
                waiting.setdefault(int(e[2]), []).append((c, int(e[1])))
        for bid, ws in sorted(waiting.items()):
            rel = [c for c, n in ws if self.barrier_count[bid] >= n]
            for c in rel:
                h = bid % cfg.n_tiles
                ctile = core_tile(c, cfg)
                self.cycles[c] = int(self.barrier_time[bid]) + self._noc(
                    c, h, ctile
                )
                self.counters["instructions"][c] += 1
                self.sync_flag[c] = 0
                self.ptr[c] += 1
            self.barrier_count[bid] -= len(rel)
            if self.barrier_count[bid] <= 0:
                self.barrier_count[bid] = 0
                self.barrier_time[bid] = 0

        # hop-by-hop router: advance each touched link's clock to its
        # last departure (deferred to step end so every transaction
        # charged this step saw the same carried link state)
        if self._router_on:
            self._rtr_end()
        for b, d in self._dram_starts:
            if d > self.dram_free[b]:
                self.dram_free[b] = d

    # ------------------------------------------------------ read-join path

    def _join_eligible(self, c: int, line: int) -> bool:
        """GETS may coalesce iff the line is LLC-resident, ownerless, and
        already shared by someone else (DESIGN.md §3 'plain join' case —
        the only transition whose outcome and latency are independent of
        concurrent same-line readers). Disabled under the coarse sharer
        vector: two same-group joiners' bit updates would collide in the
        engine's single fused scatter-add (and coarse 'shared' cannot
        distinguish self-only anyway)."""
        if self.cfg.sharer_group > 1:
            return False
        b, bs = self._bank(line), self._bank_set(line)
        for wy in range(self.cfg.llc.ways):
            if self.llc_tag[b, bs, wy] == line:
                if self.llc_owner[b, bs, wy] >= 0:
                    return False
                shl = self._sharers_from(self.sharers, b, bs, wy)
                return any(t != c for t in shl)
        return False

    def _do_join(self, c: int, line: int, pre: int, step: int) -> None:
        """Retire one coalesced read-join (same outcome as the serialized
        'sharers non-empty -> S, sharers |= {c}' path)."""
        cfg = self.cfg
        b, bs = self._bank(line), self._bank_set(line)
        ctile, btile = core_tile(c, cfg), bank_tile(b, cfg)
        w = -1
        for wy in range(cfg.llc.ways):
            if self.llc_tag[b, bs, wy] == line:
                w = wy
                break
        self.counters["l1_read_misses"][c] += 1
        self.counters["llc_hits"][c] += 1
        lat = cfg.l1.latency
        lat += self._noc(c, ctile, btile)
        lat += cfg.llc.latency
        self._set_sharer(b, bs, w, c, True)
        self.llc_lru[b, bs, w] = step
        lat += self._noc(c, btile, ctile)
        lat += self._contention_extra(c, ctile, btile)
        if self._router_on:
            req_a = self._owl(ctile, btile)
            rep_a = self._owl(btile, ctile)
            service = lat - cfg.l1.latency - req_a - rep_a  # llc.latency
            t0 = int(self.cycles[c]) + pre * int(self.cpi[c]) + cfg.l1.latency
            t_end = self._route_rt(c, t0, btile, service)
            raw = cfg.l1.latency + (t_end - t0)
            self.counters["noc_contention_cycles"][c] += raw - lat
            lat = raw
        ov = cfg.core.o3_overlap_256
        if ov:
            lat = lat - ((lat * ov) >> 8)
        # L1 fill (victim on step-start state == live state for this set:
        # joins are this core's only action this step)
        s = self._l1_set(line)
        vw = self._victim_way(
            self.l1_tag[c, s], self.l1_state[c, s], self.l1_lru[c, s]
        )
        if self.l1_state[c, s, vw] == M:
            self.counters["l1_writebacks"][c] += 1
        self.l1_tag[c, s, vw] = line
        self.l1_state[c, s, vw] = S
        self.l1_lru[c, s, vw] = step
        self.cycles[c] += pre * int(self.cpi[c]) + lat
        self.counters["instructions"][c] += pre + 1
        self.ptr[c] += 1
        self._pf_train(c, line)

    # ----------------------------------------------------- static helpers

    def _llc_valid(self, llc_tag0, b, bs):
        """Map tags to pseudo-states for victim selection (valid=1, I=0)."""
        return [I if llc_tag0[b, bs, w] == -1 else S for w in range(self.cfg.llc.ways)]

    def _sharers_from(self, sharers0, b, s, w) -> list[int]:
        """RECORDED sharer cores of an entry: with the full-map vector,
        exactly the cores whose bits are set; with a coarse vector
        (sharer_group > 1), every core of every flagged group — the
        conservative superset the directory actually knows."""
        G = self.cfg.sharer_group
        C = self.cfg.n_cores
        out = []
        for wi in range(sharers0.shape[3]):
            word = int(sharers0[b, s, w, wi])
            for bit in range(32):
                if word & (1 << bit):
                    g = wi * 32 + bit
                    out.extend(
                        t for t in range(g * G, min((g + 1) * G, C))
                    )
        return out

    def _any_sharer_bit(self, b, s, w) -> bool:
        return bool(self.sharers[b, s, w].any())

    # ----------------------------------------------------------------- run

    def run(self, max_steps: int = 10_000_000) -> None:
        for _ in range(max_steps):
            if self.done():
                return
            self.step()
        raise RuntimeError("golden: max_steps exceeded (deadlock?)")
