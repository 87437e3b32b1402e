"""Plain reference simulator: the yardstick `correct` is held to.

A scalar, dictionary-based model of the machine the benchmark's
configurations describe: directory MESI over private L1s and a banked
LLC, an XY mesh with fixed per-hop latency or the hop-by-hop router
contention model, an optional DRAM controller queue, O3 overlap, local
runs and the relaxed quantum barrier. It follows the step semantics of
the repo's DESIGN.md (as `primesim_tpu/golden/sim.py` does) but imports
nothing of the program, takes the machine as the plain dict of a
configuration file, and models only what those files may state: any
other key, value or event type raises `UnsupportedMachine`, so a
configuration cannot silently leave the reference behind.

numpy only; never touches JAX.
"""

from __future__ import annotations

from bisect import bisect_left

import numpy as np

EV_INS, EV_LD, EV_ST, EV_END = 0, 1, 2, 3
I, S, E, M = 0, 1, 2, 3
GETS, GETM, UPG = 0, 1, 2

COUNTERS = (
    "instructions", "l1_read_hits", "l1_read_misses", "l1_write_hits",
    "l1_write_misses", "upgrades", "llc_hits", "llc_misses",
    "dram_accesses", "l1_writebacks", "llc_writebacks", "probes",
    "invalidations", "noc_msgs", "noc_hops", "retries",
    "noc_contention_cycles", "dram_queue_cycles",
)

_MACHINE_KEYS = {
    "n_cores", "n_banks", "core", "l1", "llc", "noc", "dram_lat",
    "dram_queue", "dram_service", "quantum", "local_run_len",
}
_NOC_KEYS = {
    "mesh_x", "mesh_y", "link_lat", "router_lat", "contention",
    "contention_model", "contention_lat", "topology",
}


class UnsupportedMachine(ValueError):
    """The machine or trace asks for something this reference does not model."""


def _cache(d: dict, name: str) -> tuple[int, int, int, int]:
    if set(d) != {"size", "ways", "line", "latency"}:
        raise UnsupportedMachine(f"{name}: keys must be size/ways/line/latency")
    sets = d["size"] // (d["ways"] * d["line"])
    if sets < 1 or sets & (sets - 1):
        raise UnsupportedMachine(f"{name}: sets must be a power of two")
    return sets, d["ways"], d["line"], d["latency"]


class RefSim:
    """One machine, one trace. `run()` to completion, or `step()` by hand;
    results are `cycles` (per core) and `counters[name]` (per core)."""

    def __init__(self, machine: dict, events: np.ndarray):
        m = {k: v for k, v in machine.items() if not k.startswith("_")}
        if set(m) - _MACHINE_KEYS:
            raise UnsupportedMachine(f"unmodelled keys {sorted(set(m) - _MACHINE_KEYS)}")
        if _MACHINE_KEYS - set(m):
            raise UnsupportedMachine(f"missing keys {sorted(_MACHINE_KEYS - set(m))}")
        noc = m["noc"]
        if set(noc) - _NOC_KEYS or noc.get("topology", "mesh") != "mesh":
            raise UnsupportedMachine("noc: only the XY mesh is modelled")
        if set(m["core"]) - {"cpi", "o3_overlap_256"}:
            raise UnsupportedMachine("core: only cpi and o3_overlap_256")
        self.C = C = m["n_cores"]
        self.B = m["n_banks"]
        self.l1_sets, self.l1_ways, line, self.l1_lat = _cache(m["l1"], "l1")
        self.llc_sets, self.llc_ways, line2, self.llc_lat = _cache(m["llc"], "llc")
        if line != line2 or line & (line - 1):
            raise UnsupportedMachine("l1 and llc need one power-of-two line size")
        self.cpi = int(m["core"].get("cpi", 1))
        self.o3 = int(m["core"].get("o3_overlap_256", 0))
        self.mesh_x, self.mesh_y = noc["mesh_x"], noc["mesh_y"]
        self.n_tiles = self.mesh_x * self.mesh_y
        self.link_lat, self.router_lat = noc["link_lat"], noc["router_lat"]
        contention = bool(noc.get("contention", False))
        if contention and noc.get("contention_model") != "router":
            raise UnsupportedMachine("noc: only contention_model 'router'")
        self.router = contention
        self.dram_lat = m["dram_lat"]
        self.dram_queue = bool(m["dram_queue"])
        self.dram_svc = m["dram_service"] or m["dram_lat"]
        self.quantum = m["quantum"]
        self.local_run_len = m["local_run_len"]

        ev = np.asarray(events)
        if ev.ndim != 3 or ev.shape[0] != C or ev.shape[2] != 4:
            raise UnsupportedMachine("events must be [n_cores, T, 4]")
        t = ev[:, :, 0]
        if ((t < EV_INS) | (t > EV_END)).any():
            raise UnsupportedMachine("only INS/LD/ST/END events are modelled")
        if (t[:, -1] != EV_END).any():
            raise UnsupportedMachine("every core's row must end with END")
        ev = ev.astype(np.int64)
        mem = (t == EV_LD) | (t == EV_ST)
        ev[:, :, 2] = np.where(mem, ev[:, :, 2] >> (line.bit_length() - 1), ev[:, :, 2])
        self.T = ev.shape[1]
        self.ev = ev.tolist()  # python ints: scalar access is the hot path

        self.cycles = [0] * C
        self.ptr = [0] * C
        self.counters = {k: [0] * C for k in COUNTERS}
        self.quantum_end = self.quantum
        self.step_count = 0
        # l1[(core, set)] -> ways of [tag, state, lru]; llc[(bank, set)] ->
        # ways of [tag, owner, lru, sharers:set]; rows appear on first touch
        self.l1: dict = {}
        self.llc: dict = {}
        self.link_free: dict = {}  # directed link -> next-free clock
        self.dram_free: dict = {}  # bank -> next-free clock
        self._routes: dict = {}

    # ------------------------------------------------------------ geometry

    def _l1_row(self, c: int, line: int):
        key = (c, line % self.l1_sets)
        row = self.l1.get(key)
        if row is None:
            row = self.l1[key] = [[-1, I, 0] for _ in range(self.l1_ways)]
        return row

    def _llc_row(self, line: int):
        key = (line % self.B, (line // self.B) % self.llc_sets)
        row = self.llc.get(key)
        if row is None:
            row = self.llc[key] = [[-1, -1, 0, set()] for _ in range(self.llc_ways)]
        return row

    def _hops(self, a: int, b: int) -> int:
        mx = self.mesh_x
        return abs(a % mx - b % mx) + abs(a // mx - b // mx)

    def _owl(self, a: int, b: int) -> int:
        h = self._hops(a, b)
        return h * self.link_lat + (h + 1) * self.router_lat

    def _links(self, a: int, b: int) -> tuple:
        """Directed links of the XY route a -> b: id = tile*4 + dir, dir
        0=+x 1=-x 2=+y 3=-y; x phase on the source row, then y phase."""
        r = self._routes.get((a, b))
        if r is None:
            mx = self.mesh_x
            x, y, bx, by = a % mx, a // mx, b % mx, b // mx
            out = []
            while x != bx:
                out.append((y * mx + x) * 4 + (0 if bx > x else 1))
                x += 1 if bx > x else -1
            while y != by:
                out.append((y * mx + x) * 4 + (2 if by > y else 3))
                y += 1 if by > y else -1
            r = self._routes[(a, b)] = tuple(out)
        return r

    def _noc(self, c: int, a: int, b: int) -> int:
        self.counters["noc_msgs"][c] += 1
        self.counters["noc_hops"][c] += self._hops(a, b)
        return self._owl(a, b)

    @staticmethod
    def _victim(ways, valid) -> int:
        """Invalid first, then least recently used; lowest index on a tie."""
        best, best_key = 0, None
        for w, way in enumerate(ways):
            key = way[2] if valid(way) else -1
            if best_key is None or key < best_key:
                best, best_key = w, key
        return best

    # ------------------------------------------------- router contention

    def _route(self, t0: int, path, key) -> int:
        """One packet over `path`, hop by hop: at each link wait for
        max(link clock, earliest nominal arrival of this step's packets)
        + FIFO rank * link_lat, hold the link for link_lat, pay router_lat
        at the next router. Uncontended this is the analytic one-way."""
        ll, rl = self.link_lat, self.router_lat
        users, base, free, depart = self._users, self._base, self.link_free.get, self._departs.append
        t = t0 + rl
        for l in path:
            wait = max(free(l, 0), base[l]) + bisect_left(users[l], key) * ll
            if wait > t:
                t = wait
            depart((l, t + ll))
            t += ll + rl
        return t

    def _route_rt(self, c: int, t0: int, htile: int, service: int) -> int:
        ctile = c % self.n_tiles
        key = self._key[c]
        t = self._route(t0, self._links(ctile, htile), key)
        return self._route(t + service, self._links(htile, ctile), key)

    def _claim(self, c: int, htile: int, key, t0: int) -> None:
        """Record a round trip's links, key and nominal arrival times; the
        step's ranks and anchors are taken against this fixed set."""
        self._key[c] = key
        ctile = c % self.n_tiles
        req = self._links(ctile, htile)
        c_hop = self.link_lat + self.router_lat
        legs = (
            (req, t0 + self.router_lat),
            (self._links(htile, ctile),
             t0 + 2 * self.router_lat + len(req) * c_hop + self.llc_lat),
        )
        base, users = self._base, self._users
        seen = set()
        for path, a in legs:  # a: nominal arrival at the leg's next link
            for l in path:
                b = base.get(l)
                if b is None or a < b:
                    base[l] = a
                if l not in seen:
                    seen.add(l)
                    u = users.get(l)
                    if u is None:
                        users[l] = [key]
                    else:
                        u.append(key)
                a += c_hop

    # ---------------------------------------------------------------- step

    def done(self) -> bool:
        ev, T = self.ev, self.T
        return all(ev[c][min(self.ptr[c], T - 1)][0] == EV_END for c in range(self.C))

    def run(self, max_steps: int = 10_000_000) -> None:
        for _ in range(max_steps):
            if self.done():
                return
            self.step()
        raise RuntimeError("reference: max_steps exceeded")

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

        # classify the event each active core stands on
        requests, joins = [], []  # (cycles, core, kind, line, pre) / (core, line, pre)
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
                            cy + pre * cpi + l1_lat)
            for c, line, pre in join_go:
                self._claim(c, (line % self.B) % self.n_tiles, (cyc[c], c),
                            cyc[c] + pre * cpi + l1_lat)
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

        for l, d in self._departs:
            if d > self.link_free.get(l, 0):
                self.link_free[l] = d
        for b, d in dram_starts:
            if d > self.dram_free.get(b, 0):
                self.dram_free[b] = d

    # ------------------------------------------------------ read-join path

    def _join_eligible(self, c: int, line: int) -> bool:
        """A GETS may coalesce iff the line is LLC-resident, ownerless and
        already shared by someone else: that transition's outcome and
        latency do not depend on concurrent readers of the same line."""
        for w in self._llc_row(line):
            if w[0] == line:
                return w[1] < 0 and any(t != c for t in w[3])
        return False

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
            t0 = self.cycles[c] + pre * cpi + l1_lat
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
        self.cycles[c] += pre * cpi + lat
        cnt["instructions"][c] += pre + 1
        self.ptr[c] += 1
