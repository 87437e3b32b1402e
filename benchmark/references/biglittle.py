"""Plain reference of a heterogeneous (big.LITTLE) machine: the stock
machine of `benchmark/reference.py` whose cores do not all retire a
non-memory instruction in the same number of cycles. The model, as
`primesim_tpu/config/machine.py::CoreConfig.cpi_vector` states it for
`core.cpi_pattern` and as this file implements it on its own:

- core `c` has the CPI `cpi_pattern[c % len(cpi_pattern)]`: the pattern is
  tiled over the cores in core order, so (1, 1, 1, 1, 3, 3, 3, 3) is four
  big cores, then four LITTLE ones, and so on; where a pattern is given,
  the scalar `core.cpi` beside it says nothing;
- every cycle count that the stock machine takes from the one CPI takes it
  from the core's own: an INS batch of n instructions costs n * cpi[c],
  the `pre` instructions folded into a memory event cost pre * cpi[c]
  before the access, and the nominal arrival times that order the router's
  and the DRAM queue's FIFOs start from the core's own clock after its own
  `pre`. Memory latencies (L1, LLC, NoC, DRAM) are the machine's, the same
  for a big and a LITTLE core.

`sharer_chunk_words` is accepted and ignored: it bounds the program's
temporaries (the invalidation-target reductions of phase 3 walk the sharer
words in blocks of that many, `sim/step.py::_dir_transition`, so that a
4096-core machine never expands a [cores, cores] matrix) and changes no
simulated count: a machine with it and one without it give the same cycles
and counters to the bit (ROADMAP D4). A full-map sharer vector is what the
stock reference models already: a set of cores a way.

The stock `RefSim.step` is one method that reads the CPI into a local, so
there is no seam to override the CPI alone (ROADMAP D14): this subclass
states `step` and `_do_join` again, in the stock one's order and with its
helpers, and differs from them only where the CPI is read: `cpi[c]` for
`cpi`. Everything else of the machine, the constructor's refusals
included, is the stock one's. Plain before fast: scalar, dictionary rows.

numpy and the standard library only; never imports the program or JAX.
"""

from __future__ import annotations

from reference import (COUNTERS, E, EV_END, EV_INS, EV_LD, EV_ST, GETM, GETS, I, M, S, UPG,
                       UnsupportedMachine)
from reference import RefSim as Stock


def _whole(v) -> bool:
    return isinstance(v, int) and not isinstance(v, bool)


class RefSim(Stock):
    """The stock machine with a CPI a core: `self.cpi` is a list, one
    entry a core, where the stock one keeps a number."""

    def __init__(self, machine: dict, events):
        m = dict(machine)
        chunk = m.pop("sharer_chunk_words", 0)
        if not _whole(chunk) or chunk < 0:
            raise UnsupportedMachine("sharer_chunk_words must be a whole number, 0 or more")
        core = dict(m.get("core") or {})
        pattern = core.pop("cpi_pattern", None)
        if (not isinstance(pattern, (list, tuple)) or not pattern
                or not all(_whole(p) and p >= 1 for p in pattern)):
            raise UnsupportedMachine("core.cpi_pattern must be a list of whole numbers, 1 or more")
        m["core"] = core
        super().__init__(m, events)  # refuses every other key it does not model
        self.cpi = [pattern[c % len(pattern)] for c in range(self.C)]

    def step(self) -> None:
        C, ev, T = self.C, self.ev, self.T
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
        assert all(cyc[c] >= self.quantum_end - self.quantum for c in active)
        step = self.step_count
        self.step_count += 1

        # local runs: up to local_run_len events that need no other core
        # (INS batches, L1 read hits, L1 write hits in E/M)
        for c in active:
            for _ in range(self.local_run_len):
                if cyc[c] >= self.quantum_end:
                    break
                t, arg, line, pre = ev[c][min(ptr[c], T - 1)]
                if t == EV_END:
                    break
                if t == EV_INS:
                    cyc[c] += arg * cpi[c]
                    cnt["instructions"][c] += arg
                    ptr[c] += 1
                    continue
                way = next((w for w in self._l1_row(c, line)
                            if w[0] == line and w[1] != I), None)
                if way is None or (t == EV_ST and way[1] not in (E, M)):
                    break
                cyc[c] += pre * cpi[c] + l1_lat
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

        # classify the event each active core stands on
        requests, joins = [], []  # (cycles, core, kind, line, pre) / (core, line, pre)
        for c in active:
            t, arg, line, pre = ev[c][min(ptr[c], T - 1)]
            if t == EV_INS:
                cyc[c] += arg * cpi[c]
                cnt["instructions"][c] += arg
                ptr[c] += 1
                continue
            way = next((w for w in self._l1_row(c, line)
                        if w[0] == line and w[1] != I), None)
            if way is not None and (t == EV_LD or way[1] in (E, M)):
                cyc[c] += pre * cpi[c] + l1_lat
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

        self._users, self._base, self._key, self._departs = {}, {}, {}, []
        if self.router:
            for cy, c, _, line, pre in winners:
                self._claim(c, (line % self.B) % self.n_tiles, (cy, c),
                            cy + pre * cpi[c] + l1_lat)
            for c, line, pre in join_go:
                self._claim(c, (line % self.B) % self.n_tiles, (cyc[c], c),
                            cyc[c] + pre * cpi[c] + l1_lat)
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
                a = (cy + pre * cpi[c] + l1_lat
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
                t0 = cy + pre * cpi[c] + l1_lat
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
            cyc[c] += pre * cpi[c] + lat
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

        for l, d in self._departs:
            if d > self.link_free.get(l, 0):
                self.link_free[l] = d
        for b, d in dram_starts:
            if d > self.dram_free.get(b, 0):
                self.dram_free[b] = d

    def _do_join(self, c: int, line: int, pre: int, step: int) -> None:
        cnt, cpi, l1_lat = self.counters, self.cpi, self.l1_lat
        ctile, btile = c % self.n_tiles, (line % self.B) % self.n_tiles
        hit = next(w for w in self._llc_row(line) if w[0] == line)
        cnt["l1_read_misses"][c] += 1
        cnt["llc_hits"][c] += 1
        lat = l1_lat + self._noc(c, ctile, btile) + self.llc_lat
        hit[3].add(c)
        hit[2] = step
        lat += self._noc(c, btile, ctile)
        if self.router:
            service = lat - l1_lat - self._owl(ctile, btile) - self._owl(btile, ctile)
            t0 = self.cycles[c] + pre * cpi[c] + l1_lat
            raw = l1_lat + self._route_rt(c, t0, btile, service) - t0
            cnt["noc_contention_cycles"][c] += raw - lat
            lat = raw
        if self.o3:
            lat -= (lat * self.o3) >> 8
        l1row = self._l1_row(c, line)
        v = l1row[self._victim(l1row, lambda w: w[1] != I)]
        if v[1] == M:
            cnt["l1_writebacks"][c] += 1
        v[0], v[1], v[2] = line, S, step
        self.cycles[c] += pre * cpi[c] + lat
        cnt["instructions"][c] += pre + 1
        self.ptr[c] += 1
