"""Plain reference of a machine that runs a pthread program: the stock
machine of `benchmark/reference.py` plus the synchronisation events LOCK,
UNLOCK and BARRIER (phase 2.7 of the repo's DESIGN.md §3, which
`primesim_tpu/golden/sim.py` follows too), written here on its own.

The model, a step at a time:

- a core whose event is a sync event stops its local run there and takes
  no part in the memory phases of the step; sync events never overlap
  (the O3 window hides nothing of them);
- UNLOCK: a round trip to the lock's home (the tile of the mutex line's
  home bank, `(line % n_banks) % n_tiles`) of request + LLC latency +
  reply, after the `pre` instructions; the slot (`line % lock_slots`)
  is freed if this core holds it;
- LOCK: every attempt is the same round trip. The attempts on one slot are
  taken in (clock, core id) order, after the step's unlocks: the first is
  granted if the slot is free, a core that already holds it is granted
  again, every other attempt is a spin and the core tries again next step
  (one grant a slot a step). `pre` is charged on the first attempt only;
- BARRIER: after `pre`, an arrival message one way to the barrier's home
  tile (`id % n_tiles`); the core freezes, the slot's count goes up and
  its clock becomes the latest arrival. Every frozen core whose slot has
  reached the participant count its own event names resumes at the slot's
  clock + the wake-up message from the home tile (analytic, also under the
  router), and the slot is cleared when its count returns to 0;
- a frozen core neither bounds nor bumps the quantum barrier of phase 0;
- under the `router` model the round trips and the arrival legs claim the
  links of their XY paths in the step's FIFO order, beside the home
  transactions' legs, and are walked hop by hop like them.

`lock_slots` and `barrier_slots` are stated by the configuration and
modelled: a barrier id at or above `barrier_slots` is refused, and two
mutex lines that meet in one slot are one lock.

The stock `RefSim.step` is one method with no seam between its phases
(ROADMAP D14), so this subclass states `step` again in the stock one's
order and with its helpers (as `coarse_dir.py` and `biglittle.py` do), and
adds the sync events where the design puts them. Plain before fast:
scalar, dictionary rows.

numpy and the standard library only; never imports the program or JAX.
"""

from __future__ import annotations

import numpy as np

from reference import COUNTERS as _STOCK_COUNTERS
from reference import E, EV_END, EV_INS, EV_LD, EV_ST, GETM, GETS, I, M, S, UPG, UnsupportedMachine
from reference import RefSim as Stock

EV_LOCK, EV_UNLOCK, EV_BARRIER = 4, 5, 6

COUNTERS = _STOCK_COUNTERS + ("barrier_waits", "lock_acquires", "lock_spins")


def _slots(m: dict, key: str) -> int:
    v = m.pop(key, None)
    if not isinstance(v, int) or isinstance(v, bool) or v < 1 or v & (v - 1):
        raise UnsupportedMachine(f"{key} must be stated: a power of two, 1 or more")
    return v


class RefSim(Stock):
    """The stock machine, and LOCK / UNLOCK / BARRIER events in its trace."""

    def __init__(self, machine: dict, events):
        m = dict(machine)
        self.lock_slots = _slots(m, "lock_slots")
        self.barrier_slots = _slots(m, "barrier_slots")
        # the machine goes through the stock constructor, which refuses every
        # key it does not model, on a trace of END alone; the events are read here
        C = m.get("n_cores")
        super().__init__(m, np.full((C if isinstance(C, int) and C > 0 else 1, 1, 4),
                                    EV_END, np.int32))
        ev = np.asarray(events)
        if ev.ndim != 3 or ev.shape[0] != C or ev.shape[2] != 4:
            raise UnsupportedMachine("events must be [n_cores, T, 4]")
        t = ev[:, :, 0]
        if ((t < EV_INS) | (t > EV_BARRIER)).any():
            raise UnsupportedMachine("only INS/LD/ST/END/LOCK/UNLOCK/BARRIER events are modelled")
        if (t[:, -1] != EV_END).any():
            raise UnsupportedMachine("every core's row must end with END")
        barrier = t == EV_BARRIER
        if ((ev[:, :, 2] < 0) | (ev[:, :, 2] >= self.barrier_slots))[barrier].any():
            raise UnsupportedMachine(f"barrier ids must lie in [0, barrier_slots={self.barrier_slots})")
        ev = ev.astype(np.int64)
        line_bits = m["l1"]["line"].bit_length() - 1
        addressed = (t == EV_LD) | (t == EV_ST) | (t == EV_LOCK) | (t == EV_UNLOCK)
        ev[:, :, 2] = np.where(addressed, ev[:, :, 2] >> line_bits, ev[:, :, 2])
        self.T = ev.shape[1]
        self.ev = ev.tolist()
        self.counters = {k: [0] * C for k in COUNTERS}
        self.lock_holder: dict = {}  # slot -> core
        self.barrier_count: dict = {}  # barrier id -> arrivals of this round
        self.barrier_time: dict = {}  # barrier id -> latest arrival
        self.waiting = [False] * C  # frozen at a barrier, or spun on a lock

    def _claim_arrival(self, c: int, htile: int, key, t0: int) -> None:
        """Record a barrier arrival's one leg: its links (an XY path takes
        none twice), key and nominal arrival times, beside the round trips
        that the stock `_claim` records."""
        self._key[c] = key
        c_hop = self.link_lat + self.router_lat
        base, users = self._base, self._users
        a = t0 + self.router_lat  # nominal arrival at the leg's first link
        for l in self._links(c % self.n_tiles, htile):
            b = base.get(l)
            if b is None or a < b:
                base[l] = a
            users.setdefault(l, []).append(key)
            a += c_hop

    def _lock_round_trip(self, c: int, line: int, t0: int) -> int:
        """Cycles of one RMW round trip to the lock's home, injected at `t0`."""
        cnt = self.counters
        ctile, h = c % self.n_tiles, (line % self.B) % self.n_tiles
        lat = self._noc(c, ctile, h) + self.llc_lat + self._noc(c, h, ctile)
        if self.router:
            raw = self._route_rt(c, t0, h, self.llc_lat) - t0
            cnt["noc_contention_cycles"][c] += raw - lat
            lat = raw
        return lat

    def step(self) -> None:
        C, ev, T = self.C, self.ev, self.T
        cyc, ptr, cnt, cpi = self.cycles, self.ptr, self.counters, self.cpi
        l1_lat, llc_lat = self.l1_lat, self.llc_lat
        waiting = self.waiting

        def frozen(c):
            return waiting[c] and ev[c][min(ptr[c], T - 1)][0] == EV_BARRIER

        not_done = [c for c in range(C) if ev[c][min(ptr[c], T - 1)][0] != EV_END]
        if not not_done:
            return
        # a core frozen at a barrier neither bounds nor bumps the quantum
        countable = [c for c in not_done if not frozen(c)]
        active = [c for c in countable if cyc[c] < self.quantum_end]
        if not active and countable:
            m = min(cyc[c] for c in countable)
            self.quantum_end = (m // self.quantum + 1) * self.quantum
            active = [c for c in countable if cyc[c] < self.quantum_end]
        assert all(cyc[c] >= self.quantum_end - self.quantum for c in active)
        step = self.step_count
        self.step_count += 1

        # local runs: up to local_run_len events that need no other core
        # (INS batches, L1 read hits, L1 write hits in E/M); a sync event ends one
        for c in active:
            for _ in range(self.local_run_len):
                if cyc[c] >= self.quantum_end:
                    break
                t, arg, line, pre = ev[c][min(ptr[c], T - 1)]
                if t == EV_END:
                    break
                if t == EV_INS:
                    cyc[c] += arg * cpi
                    cnt["instructions"][c] += arg
                    ptr[c] += 1
                    continue
                if t > EV_END:
                    break
                way = next((w for w in self._l1_row(c, line)
                            if w[0] == line and w[1] != I), None)
                if way is None or (t == EV_ST and way[1] not in (E, M)):
                    break
                cyc[c] += pre * cpi + l1_lat
                cnt["instructions"][c] += pre + 1
                if t == EV_LD:
                    cnt["l1_read_hits"][c] += 1
                else:
                    cnt["l1_write_hits"][c] += 1
                    way[1] = M
                way[2] = step
                ptr[c] += 1
        if self.local_run_len:
            active = [
                c for c in range(C)
                if ev[c][min(ptr[c], T - 1)][0] != EV_END and not frozen(c)
                and cyc[c] < self.quantum_end
            ]

        # classify the event each active core stands on
        requests, joins = [], []  # (cycles, core, kind, line, pre) / (core, line, pre)
        lock_reqs, unlocks, arrivals = [], [], []  # (cycles, core, line | id[, n], pre)
        for c in active:
            t, arg, line, pre = ev[c][min(ptr[c], T - 1)]
            if t == EV_INS:
                cyc[c] += arg * cpi
                cnt["instructions"][c] += arg
                ptr[c] += 1
                continue
            if t == EV_LOCK:
                lock_reqs.append((cyc[c], c, line, pre))
                continue
            if t == EV_UNLOCK:
                unlocks.append((cyc[c], c, line, pre))
                continue
            if t == EV_BARRIER:
                arrivals.append((cyc[c], c, line, arg, pre))
                continue
            way = next((w for w in self._l1_row(c, line)
                        if w[0] == line and w[1] != I), None)
            if way is not None and (t == EV_LD or way[1] in (E, M)):
                cyc[c] += pre * cpi + l1_lat
                cnt["instructions"][c] += pre + 1
                if t == EV_LD:
                    cnt["l1_read_hits"][c] += 1
                else:
                    cnt["l1_write_hits"][c] += 1
                    way[1] = M
                way[2] = step
                ptr[c] += 1
            elif t == EV_LD:
                if self._join_eligible(c, line):
                    joins.append((c, line, pre))
                else:
                    requests.append((cyc[c], c, GETS, line, pre))
            else:
                requests.append((cyc[c], c, UPG if way is not None else GETM, line, pre))

        # one winner per (bank, set): lowest (cycles, core); losers retry.
        # A read-join goes ahead only if nobody arbitrates for its set.
        def slot(line):
            return (line % self.B, (line // self.B) % self.llc_sets)

        arb = {slot(r[3]) for r in requests}
        join_go = []
        for c, line, pre in joins:
            if slot(line) in arb:
                requests.append((cyc[c], c, GETS, line, pre))
            else:
                join_go.append((c, line, pre))
        by_slot: dict = {}
        for r in requests:
            by_slot.setdefault(slot(r[3]), []).append(r)
        winners = []
        for rs in by_slot.values():
            rs.sort(key=lambda r: (r[0], r[1]))
            winners.append(rs[0])
            for r in rs[1:]:
                cnt["retries"][r[1]] += 1

        # the step's packets, their links and their nominal arrivals are
        # fixed before any of them is walked: the home transactions' round
        # trips, the locks' and unlocks' round trips, the barriers' arrivals
        self._users, self._base, self._key, self._departs = {}, {}, {}, []
        if self.router:
            for cy, c, _, line, pre in winners:
                self._claim(c, (line % self.B) % self.n_tiles, (cy, c),
                            cy + pre * cpi + l1_lat)
            for c, line, pre in join_go:
                self._claim(c, (line % self.B) % self.n_tiles, (cyc[c], c),
                            cyc[c] + pre * cpi + l1_lat)
            for cy, c, line, pre in unlocks:
                self._claim(c, (line % self.B) % self.n_tiles, (cy, c), cy + pre * cpi)
            for cy, c, line, pre in lock_reqs:
                self._claim(c, (line % self.B) % self.n_tiles, (cy, c),
                            cy + (0 if waiting[c] else pre * cpi))
            for cy, c, bid, _, pre in arrivals:
                self._claim_arrival(c, bid % self.n_tiles, (cy, c), cy + pre * cpi)
            for users in self._users.values():
                users.sort()

        for c, line, pre in join_go:
            self._do_join(c, line, pre, step)

        # DRAM controller queue: this step's LLC-miss winners and their
        # nominal arrivals are fixed before any winner is processed
        dram_users, dram_base, dram_arr, dram_starts = {}, {}, {}, []
        if self.dram_queue:
            for cy, c, _, line, pre in winners:
                if any(w[0] == line for w in self._llc_row(line)):
                    continue
                b = line % self.B
                a = (cy + pre * cpi + l1_lat
                     + self._owl(c % self.n_tiles, b % self.n_tiles) + llc_lat)
                dram_users.setdefault(b, []).append((cy, c))
                dram_arr[c] = a
                if b not in dram_base or a < dram_base[b]:
                    dram_base[b] = a

        phase_b = []  # (core, line, downgrade?) applied after every winner
        for cy, c, kind, line, pre in sorted(winners, key=lambda r: r[1]):
            b = line % self.B
            ctile, btile = c % self.n_tiles, b % self.n_tiles
            lat = l1_lat + self._noc(c, ctile, btile) + llc_lat
            row = self._llc_row(line)
            hit = next((w for w in row if w[0] == line), None)
            cnt[("l1_read_misses", "l1_write_misses", "upgrades")[kind]][c] += 1
            if hit is not None:
                cnt["llc_hits"][c] += 1
                owner = hit[1]
                recorded = sorted(hit[3])
                others = [t for t in recorded if t != c]
                if kind == GETS:
                    if owner >= 0 and owner != c:
                        otile = owner % self.n_tiles
                        lat += self._noc(c, btile, otile) + self._noc(c, otile, btile)
                        cnt["probes"][c] += 1
                        phase_b.append((owner, line, True))
                        hit[1] = -1
                        hit[3] = {c, owner}
                        grant = S
                    elif others:
                        hit[1] = -1
                        hit[3].add(c)
                        grant = S
                    else:
                        hit[1] = c
                        hit[3] = set()
                        grant = E
                else:
                    if owner >= 0 and owner != c:
                        otile = owner % self.n_tiles
                        lat += self._noc(c, btile, otile) + self._noc(c, otile, btile)
                        cnt["probes"][c] += 1
                        phase_b.append((owner, line, False))
                    inv_lat = 0
                    for t in others:
                        ttile = t % self.n_tiles
                        inv_lat = max(inv_lat, 2 * self._owl(btile, ttile))
                        cnt["invalidations"][c] += 1
                        cnt["noc_msgs"][c] += 2
                        cnt["noc_hops"][c] += 2 * self._hops(btile, ttile)
                        phase_b.append((t, line, False))
                    lat += inv_lat
                    hit[1] = c
                    hit[3] = set()
                    grant = M
                hit[2] = step
            else:
                cnt["llc_misses"][c] += 1
                cnt["dram_accesses"][c] += 1
                cnt["noc_msgs"][c] += 2  # to the co-located controller
                if self.dram_queue:
                    rank = sum(1 for k in dram_users.get(b, ()) if k < (cy, c))
                    a = dram_arr[c]
                    start = max(a, max(self.dram_free.get(b, 0), dram_base[b])
                                + rank * self.dram_svc)
                    cnt["dram_queue_cycles"][c] += start - a
                    lat += start - a
                    dram_starts.append((b, start + self.dram_svc))
                lat += self.dram_lat
                way = row[self._victim(row, lambda w: w[0] != -1)]
                if way[0] != -1:
                    targets = sorted(way[3])
                    if way[1] >= 0:
                        cnt["llc_writebacks"][c] += 1
                        if way[1] not in way[3]:
                            targets.append(way[1])
                    for t in targets:
                        cnt["invalidations"][c] += 1
                        cnt["noc_msgs"][c] += 2
                        cnt["noc_hops"][c] += 2 * self._hops(btile, t % self.n_tiles)
                        phase_b.append((t, way[0], False))
                way[0], way[1], way[2], way[3] = line, c, step, set()
                grant = E if kind == GETS else M
            lat += self._noc(c, btile, ctile)

            if self.router:
                # the hop-by-hop walk replaces the analytic request and
                # reply legs; all between them is the service interval
                service = lat - l1_lat - self._owl(ctile, btile) - self._owl(btile, ctile)
                t0 = cy + pre * cpi + l1_lat
                raw = l1_lat + self._route_rt(c, t0, btile, service) - t0
                cnt["noc_contention_cycles"][c] += raw - lat
                lat = raw
            if self.o3:
                lat -= (lat * self.o3) >> 8

            l1row = self._l1_row(c, line)
            cur = next((w for w in l1row if w[0] == line and w[1] != I), None)
            if kind == UPG and cur is not None:
                cur[1], cur[2] = grant, step
            else:
                v = l1row[self._victim(l1row, lambda w: w[1] != I)]
                if v[1] == M:
                    cnt["l1_writebacks"][c] += 1
                v[0], v[1], v[2] = line, grant, step
            cyc[c] += pre * cpi + lat
            cnt["instructions"][c] += pre + 1
            ptr[c] += 1

        for t, line, downgrade in phase_b:
            for w in self._l1_row(t, line):
                if w[0] == line and w[1] != I:
                    if not downgrade:
                        w[1] = I
                    elif w[1] in (E, M):
                        w[1] = S
                    break

        # ---- synchronisation: unlocks, lock grants, arrivals, releases ----
        holder = self.lock_holder
        for cy, c, line, pre in unlocks:
            lat = self._lock_round_trip(c, line, cy + pre * cpi)
            cyc[c] += pre * cpi + lat
            cnt["instructions"][c] += pre + 1
            if holder.get(line % self.lock_slots) == c:
                del holder[line % self.lock_slots]
            ptr[c] += 1

        by_lock: dict = {}
        for r in lock_reqs:
            by_lock.setdefault(r[2] % self.lock_slots, []).append(r)
        for s, rs in by_lock.items():
            rs.sort(key=lambda r: (r[0], r[1]))
            for i, (cy, c, line, pre) in enumerate(rs):
                first = not waiting[c]
                lat = self._lock_round_trip(c, line, cy + (pre * cpi if first else 0))
                if first:  # the batch before the lock is charged once
                    cyc[c] += pre * cpi
                    cnt["instructions"][c] += pre
                cyc[c] += lat  # a spin costs what a grant costs
                if holder.get(s) == c or (i == 0 and s not in holder):
                    holder[s] = c
                    cnt["lock_acquires"][c] += 1
                    cnt["instructions"][c] += 1
                    waiting[c] = False
                    ptr[c] += 1
                else:
                    cnt["lock_spins"][c] += 1
                    waiting[c] = True

        count, latest = self.barrier_count, self.barrier_time
        for cy, c, bid, _, pre in arrivals:
            ctile, h = c % self.n_tiles, bid % self.n_tiles
            cyc[c] += pre * cpi
            cnt["instructions"][c] += pre
            lat = self._noc(c, ctile, h)  # the arrival message, one way
            if self.router:
                raw = self._route(cyc[c], self._links(ctile, h), self._key[c]) - cyc[c]
                cnt["noc_contention_cycles"][c] += raw - lat
                lat = raw
            cyc[c] += lat
            cnt["barrier_waits"][c] += 1
            waiting[c] = True
            count[bid] = count.get(bid, 0) + 1
            latest[bid] = max(latest.get(bid, 0), cyc[c])

        # every frozen core, arrived now or earlier, whose slot holds as
        # many arrivals as its own event asks for
        at_barrier: dict = {}
        for c in range(C):
            t, arg, bid, _ = ev[c][min(ptr[c], T - 1)]
            if t == EV_BARRIER and waiting[c]:
                at_barrier.setdefault(bid, []).append((c, arg))
        for bid, ws in at_barrier.items():
            released = [c for c, n in ws if count.get(bid, 0) >= n]
            h = bid % self.n_tiles
            for c in released:
                cyc[c] = latest[bid] + self._noc(c, h, c % self.n_tiles)  # the wake-up message
                cnt["instructions"][c] += 1
                waiting[c] = False
                ptr[c] += 1
            if released:
                count[bid] -= len(released)
                if count[bid] <= 0:
                    count[bid], latest[bid] = 0, 0

        for l, d in self._departs:
            if d > self.link_free.get(l, 0):
                self.link_free[l] = d
        for b, d in dram_starts:
            if d > self.dram_free.get(b, 0):
                self.dram_free[b] = d
