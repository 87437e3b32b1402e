"""Plain reference of a machine with a coarse sharer vector (Dir-G: Gupta,
Weber, Mowry, ICPP 1990): the stock machine of `benchmark/reference.py`
whose directory keeps one sharer bit to `sharer_group` consecutive cores
instead of one to a core. The model, as `primesim_tpu/config/machine.py`
states it for `sharer_group` > 1 and as this file implements it on its own:

- the bit a core sets, and the bit that stands for it, is its group's
  (`core // G`); the owner of a line is still one exact core;
- a line is exclusive (E on a read) only while NO group bit is set: the
  requester's own group bit may stand for a neighbour;
- read-join coalescing is off: every read miss arbitrates;
- an invalidation (a write to a shared line) and a back-invalidation (an
  LLC victim) go to EVERY core of every flagged group, whether it holds
  the line or not: the messages, their hops and the `invalidations`
  counter count them all. The requester is skipped as a message of its
  own write's invalidation, but its slot stays inside the serialisation
  latency: the home node serialises the whole group's broadcast, so the
  latency is the round trip to the farthest core of the flagged groups.

A core's line therefore dies with the first sharer-clearing transition
after its fill, whatever its group's bit says afterwards. Here that is
the broadcast itself, applied to every member at the end of the step; the
program reaches the same state lazily, by an epoch it stamps on a fill and
bumps on a clearing (`sim/engine.py::_validate_ways`).

The stock `RefSim.step` is one method and keeps a way's sharers as a set
of cores, read and written in place, so there is no seam to override the
bookkeeping alone: this subclass states `step` again, in the stock one's
order and with its helpers (`_l1_row`, `_llc_row`, `_noc`, `_owl`,
`_claim`, `_route_rt`, `_victim`), and differs from it only where a way's
sharers are read or written (`_broadcast`, the three grants).
Everything else of the machine, the constructor's refusals included, is
the stock one's. Plain before fast: scalar, dictionary rows, a loop over
the G members of a flagged group; no index of which cores hold a line.

numpy and the standard library only; never imports the program or JAX.
"""

from __future__ import annotations

from reference import (COUNTERS, E, EV_END, EV_INS, EV_LD, EV_ST, GETM, GETS, I, M, S, UPG,
                       UnsupportedMachine)
from reference import RefSim as Stock


class RefSim(Stock):
    """The stock machine with `sharer_group` = G > 1. A way of an LLC row
    is `[tag, owner, lru, groups]`: `groups` is the set of flagged group
    numbers where the stock one keeps a set of cores."""

    def __init__(self, machine: dict, events):
        m = dict(machine)
        G = m.pop("sharer_group", None)
        if not isinstance(G, int) or isinstance(G, bool) or G < 2 or G & (G - 1):
            raise UnsupportedMachine("sharer_group must be a power of two above 1")
        super().__init__(m, events)  # refuses every other key it does not model
        if self.C % G:
            raise UnsupportedMachine("sharer_group must divide n_cores")
        self.G = G

    def _broadcast(self, c: int, btile: int, groups, line: int, phase_b: list,
                   skip: int = -1, owner: int = -1) -> int:
        """Invalidate `line` in every core the directory has to assume
        holds it: all G members of each flagged group, and the line's
        `owner` where no bit stands for it. One message and its
        acknowledgement per target, charged to the requester `c`; `skip`
        gets none; the L1 copies die in phase B. Returns the hops to the
        farthest member, `skip` included."""
        G = self.G
        targets = [t for g in sorted(groups) for t in range(g * G, (g + 1) * G)]
        if owner >= 0 and owner // G not in groups:
            targets.append(owner)
        far = hops = n = 0
        for t in targets:
            h = self._hops(btile, t % self.n_tiles)
            if h > far:
                far = h
            if t != skip:
                n += 1
                hops += h
                phase_b.append((t, line, False))
        cnt = self.counters
        cnt["invalidations"][c] += n
        cnt["noc_msgs"][c] += 2 * n
        cnt["noc_hops"][c] += 2 * hops
        return far

    def step(self) -> None:
        C, ev, T, G = self.C, self.ev, self.T, self.G
        cyc, ptr, cnt, cpi = self.cycles, self.ptr, self.counters, self.cpi
        l1_lat, llc_lat = self.l1_lat, self.llc_lat

        not_done = [c for c in range(C) if ev[c][min(ptr[c], T - 1)][0] != EV_END]
        if not not_done:
            return
        active = [c for c in not_done if cyc[c] < self.quantum_end]
        if not active:
            m = min(cyc[c] for c in not_done)
            self.quantum_end = (m // self.quantum + 1) * self.quantum
            active = [c for c in not_done if cyc[c] < self.quantum_end]
        step = self.step_count
        self.step_count += 1

        # local runs: up to local_run_len events that need no other core
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
                if ev[c][min(ptr[c], T - 1)][0] != EV_END and cyc[c] < self.quantum_end
            ]

        # classify the event each active core stands on; no read joins
        requests = []  # (cycles, core, kind, line, pre)
        for c in active:
            t, arg, line, pre = ev[c][min(ptr[c], T - 1)]
            if t == EV_INS:
                cyc[c] += arg * cpi
                cnt["instructions"][c] += arg
                ptr[c] += 1
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
                requests.append((cyc[c], c, GETS, line, pre))
            else:
                requests.append((cyc[c], c, UPG if way is not None else GETM, line, pre))

        # one winner per (bank, set): lowest (cycles, core); losers retry
        by_slot: dict = {}
        for r in requests:
            by_slot.setdefault((r[3] % self.B, (r[3] // self.B) % self.llc_sets), []).append(r)
        winners = []
        for rs in by_slot.values():
            rs.sort(key=lambda r: (r[0], r[1]))
            winners.append(rs[0])
            for r in rs[1:]:
                cnt["retries"][r[1]] += 1

        self._users, self._base, self._key, self._departs = {}, {}, {}, []
        if self.router:
            for cy, c, _, line, pre in winners:
                self._claim(c, (line % self.B) % self.n_tiles, (cy, c),
                            cy + pre * cpi + l1_lat)
            for users in self._users.values():
                users.sort()

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
                if kind == GETS:
                    if owner >= 0 and owner != c:
                        otile = owner % self.n_tiles
                        lat += self._noc(c, btile, otile) + self._noc(c, otile, btile)
                        cnt["probes"][c] += 1
                        phase_b.append((owner, line, True))
                        hit[1] = -1
                        hit[3] = {c // G, owner // G}
                        grant = S
                    elif hit[3]:  # any bit: it may stand for a neighbour
                        hit[1] = -1
                        hit[3].add(c // G)
                        grant = S
                    else:
                        hit[1] = c
                        grant = E
                else:
                    if owner >= 0 and owner != c:
                        otile = owner % self.n_tiles
                        lat += self._noc(c, btile, otile) + self._noc(c, otile, btile)
                        cnt["probes"][c] += 1
                        phase_b.append((owner, line, False))
                    far = self._broadcast(c, btile, hit[3], line, phase_b, skip=c)
                    if hit[3]:
                        # the home node serialises the whole broadcast, the
                        # requester's slot too: the farthest member's round
                        # trip bounds it
                        lat += 2 * (far * self.link_lat + (far + 1) * self.router_lat)
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
                    if way[1] >= 0:
                        cnt["llc_writebacks"][c] += 1
                    self._broadcast(c, btile, way[3], way[0], phase_b, owner=way[1])
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

        l1, l1_sets = self.l1, self.l1_sets
        for t, line, downgrade in phase_b:
            # most targets of a broadcast never touched the line's set
            for w in l1.get((t, line % l1_sets), ()):
                if w[0] == line and w[1] != I:
                    if not downgrade:
                        w[1] = I
                    elif w[1] in (E, M):
                        w[1] = S
                    break

        for l, d in self._departs:
            if d > self.link_free.get(l, 0):
                self.link_free[l] = d
        for b, d in dram_starts:
            if d > self.dram_free.get(b, 0):
                self.dram_free[b] = d
