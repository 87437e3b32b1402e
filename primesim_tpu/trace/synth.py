"""Synthetic workload trace generators.

Stand-ins for the reference's benchmark inputs (SPLASH-2 / PARSEC binaries run
under Pin, SURVEY.md §4). Each generator emits the access *pattern class* of a
benchmark family so cache/coherence/NoC behavior is representative and the
expected statistics are analyzable:

- ``uniform_random``  — uncorrelated loads/stores over a working set
- ``stream``          — sequential streaming (stride = line), low reuse
- ``pointer_chase``   — dependent chain, one hot line at a time per core
- ``false_sharing``   — all cores hammer distinct words of the SAME lines
                        (coherence ping-pong; the MESI stress test)
- ``fft_like``        — phases of private strided work + butterfly exchange
                        with partner cores (SPLASH-2 FFT communication shape)
- ``readers_writer``  — one producer writes a block, all others read it
                        (invalidation broadcast shape)
- ``lock_contention`` — cores hammer a small set of mutexes around short
                        critical sections (pthread_mutex shape; LOCK/UNLOCK)
- ``barrier_phases``  — bulk-synchronous phases of private work separated
                        by global (or subset) barriers (SPLASH-2 phase shape)
- ``ocean_like``      — SPLASH-2 OCEAN's multigrid solver: square subgrids,
                        border exchange with four neighbours, red-black
                        relaxations on a V-cycle, a barrier after every phase
- ``ycsb_like``       — YCSB core workload A on a shared in-memory hash
                        table: zipfian keys, whole-record reads, one-field
                        updates in place (hot records serialise at their homes)
- ``moe_decode_like`` — one decode step of one routed-expert layer of a
                        sparse-expert model (DeepSeek-V3's widths), an expert to
                        64 cores: private cold weight streams, activations
                        read across experts, producer-consumer hand-offs,
                        and most experts with no token at all

All generators are deterministic given ``seed``.
"""

from __future__ import annotations

import numpy as np

from .format import (
    EV_BARRIER,
    EV_INS,
    EV_LD,
    EV_LOCK,
    EV_ST,
    EV_UNLOCK,
    Trace,
    from_event_lists,
)


def _rng(seed: int) -> np.random.Generator:
    return np.random.default_rng(seed)


def _interleave(rng, mem_events, ins_per_mem: int):
    """Weave INS batches between memory events (~ins_per_mem each, >=1)."""
    out = []
    for ev in mem_events:
        k = int(rng.integers(1, 2 * ins_per_mem + 1)) if ins_per_mem > 0 else 0
        if k:
            out.append((EV_INS, k, 0))
        out.append(ev)
    return out


def uniform_random(
    n_cores: int,
    n_mem_ops: int = 256,
    working_set: int = 1 << 20,
    write_frac: float = 0.3,
    ins_per_mem: int = 3,
    shared_frac: float = 0.2,
    seed: int = 0,
    line: int = 64,
) -> Trace:
    """Random accesses; a `shared_frac` of them hit a common shared region."""
    rng = _rng(seed)
    shared_base = 0
    shared_size = max(line * 16, working_set // 8)
    per_core = []
    for c in range(n_cores):
        priv_base = (1 + c) * working_set
        n = n_mem_ops
        is_shared = rng.random(n) < shared_frac
        is_write = rng.random(n) < write_frac
        offs = rng.integers(0, working_set, n)
        sh_offs = rng.integers(0, shared_size, n)
        addrs = np.where(is_shared, shared_base + sh_offs, priv_base + offs)
        addrs = (addrs // 4) * 4
        evs = [
            (EV_ST if w else EV_LD, 4, int(a))
            for w, a in zip(is_write, addrs)
        ]
        per_core.append(_interleave(rng, evs, ins_per_mem))
    return from_event_lists(per_core)


def stream(
    n_cores: int,
    n_mem_ops: int = 256,
    ins_per_mem: int = 2,
    seed: int = 0,
    line: int = 64,
) -> Trace:
    """Each core streams sequentially through its own region (cold misses)."""
    rng = _rng(seed)
    per_core = []
    for c in range(n_cores):
        base = (1 + c) * (n_mem_ops * line + (1 << 12))
        evs = [(EV_LD, 4, base + i * line) for i in range(n_mem_ops)]
        per_core.append(_interleave(rng, evs, ins_per_mem))
    return from_event_lists(per_core)


def pointer_chase(
    n_cores: int,
    n_mem_ops: int = 256,
    n_nodes: int = 64,
    ins_per_mem: int = 1,
    seed: int = 0,
    line: int = 64,
) -> Trace:
    """Dependent-chain loads over a private ring of nodes (latency-bound)."""
    rng = _rng(seed)
    per_core = []
    for c in range(n_cores):
        base = (1 + c) * (n_nodes * line * 4)
        perm = rng.permutation(n_nodes)
        node = 0
        evs = []
        for _ in range(n_mem_ops):
            evs.append((EV_LD, 8, base + int(perm[node]) * line))
            node = (node + 1) % n_nodes
        per_core.append(_interleave(rng, evs, ins_per_mem))
    return from_event_lists(per_core)


def false_sharing(
    n_cores: int,
    n_mem_ops: int = 256,
    n_hot_lines: int = 4,
    ins_per_mem: int = 1,
    seed: int = 0,
    line: int = 64,
) -> Trace:
    """All cores read-modify-write distinct words of the same few lines."""
    rng = _rng(seed)
    per_core = []
    for c in range(n_cores):
        evs = []
        word = (c * 4) % line
        for i in range(n_mem_ops // 2):
            ln = int(rng.integers(0, n_hot_lines))
            addr = ln * line + word
            evs.append((EV_LD, 4, addr))
            evs.append((EV_ST, 4, addr))
        per_core.append(_interleave(rng, evs, ins_per_mem))
    return from_event_lists(per_core)


def fft_like(
    n_cores: int,
    n_phases: int = 4,
    points_per_core: int = 64,
    ins_per_mem: int = 4,
    seed: int = 0,
    line: int = 64,
) -> Trace:
    """SPLASH-2 FFT shape: local strided compute, then butterfly exchange.

    Phase p: each core loads/stores its own `points_per_core` elements
    (stride grows with phase), then reads the block of its butterfly partner
    (c XOR 2^p) — cross-tile communication whose distance doubles each phase.
    """
    rng = _rng(seed)
    block = points_per_core * 8  # 8-byte points
    per_core_evs: list[list] = [[] for _ in range(n_cores)]
    for p in range(n_phases):
        stride = 8 << p
        for c in range(n_cores):
            base = (1 + c) * (block * 8)
            evs = []
            for i in range(points_per_core):
                a = base + (i * stride) % block
                evs.append((EV_LD, 8, a))
                evs.append((EV_ST, 8, a))
            partner = c ^ (1 << (p % max(1, (n_cores - 1).bit_length())))
            partner %= n_cores
            pbase = (1 + partner) * (block * 8)
            for i in range(0, points_per_core, max(1, line // 8)):
                evs.append((EV_LD, 8, pbase + i * 8))
            per_core_evs[c].extend(_interleave(rng, evs, ins_per_mem))
    return from_event_lists(per_core_evs)


def readers_writer(
    n_cores: int,
    n_rounds: int = 8,
    block_lines: int = 8,
    ins_per_mem: int = 2,
    seed: int = 0,
    line: int = 64,
) -> Trace:
    """Core 0 writes a shared block; all others read it (each round)."""
    rng = _rng(seed)
    per_core_evs: list[list] = [[] for _ in range(n_cores)]
    for r in range(n_rounds):
        base = r * block_lines * line
        w = [(EV_ST, 4, base + i * line) for i in range(block_lines)]
        per_core_evs[0].extend(_interleave(rng, w, ins_per_mem))
        for c in range(1, n_cores):
            rd = [(EV_LD, 4, base + i * line) for i in range(block_lines)]
            per_core_evs[c].extend(_interleave(rng, rd, ins_per_mem))
    return from_event_lists(per_core_evs)


def lock_contention(
    n_cores: int,
    n_critical: int = 16,
    n_locks: int = 2,
    ins_per_mem: int = 2,
    seed: int = 0,
    line: int = 64,
) -> Trace:
    """Cores repeatedly acquire a few shared mutexes, touch the protected
    data (load + store), and release — the pthread_mutex critical-section
    shape the reference captures by interception (SURVEY.md §2 #1)."""
    rng = _rng(seed)
    per_core = []
    for c in range(n_cores):
        evs = []
        for _ in range(n_critical):
            lk = int(rng.integers(0, n_locks))
            mtx = 0x10000 + lk * 4 * line  # mutex addresses, distinct lines
            data = 0x80000 + lk * line  # protected data, one line per lock
            evs.append((EV_LOCK, 0, mtx))
            evs.append((EV_LD, 4, data))
            evs.append((EV_ST, 4, data))
            evs.append((EV_UNLOCK, 0, mtx))
        per_core.append(_interleave(rng, evs, ins_per_mem))
    return from_event_lists(per_core)


def barrier_phases(
    n_cores: int,
    n_phases: int = 4,
    work_per_phase: int = 12,
    ins_per_mem: int = 2,
    subset: bool = False,
    seed: int = 0,
    line: int = 64,
) -> Trace:
    """Bulk-synchronous phases: private strided work, then a barrier.

    Barrier ids alternate over two slots to exercise slot reuse (count
    reset + re-arm). With ``subset=True`` only the first half of the cores
    participate (participant count = n_cores // 2), the rest free-run —
    exercising per-waiter participant counts.
    """
    rng = _rng(seed)
    half = max(1, n_cores // 2)
    per_core: list[list] = [[] for _ in range(n_cores)]
    for p in range(n_phases):
        for c in range(n_cores):
            base = (1 + c) * (1 << 14) + p * work_per_phase * line
            evs = [(EV_LD, 4, base + i * line) for i in range(work_per_phase)]
            evs.append((EV_ST, 4, base))
            w = _interleave(rng, evs, ins_per_mem)
            if subset:
                if c < half:
                    w.append((EV_BARRIER, half, p % 2))
            else:
                w.append((EV_BARRIER, n_cores, p % 2))
            per_core[c].extend(w)
    return from_event_lists(per_core)


def _ocean_visits(levels: int, visits: int) -> list[int]:
    """The levels one V-cycle visits, cut to its first `visits`."""
    cycle = list(range(levels)) + list(range(levels - 2, -1, -1))
    if not 1 <= visits <= len(cycle):
        raise ValueError(f"visits must be 1..{len(cycle)} at {levels} levels")
    return cycle[:visits]


def ocean_like(
    n_cores: int,
    seed: int = 0,
    grid_n: int = 258,
    levels: int = 4,
    visits: int = 7,
    ins_per_mem: int = 3,
    barrier_ids: int = 8,
    lock_reductions: int = 0,
    line: int = 64,
) -> Trace:
    """SPLASH-2 OCEAN's multigrid solver (`multig.c`, `slave2.c`,
    contiguous partitions) on a `grid_n` x `grid_n` grid.

    The cores form a square; core (px, py) owns a square subgrid of side
    s0 = (grid_n - 2) / sqrt(n_cores) at level 0 and s0 >> l at level l.
    A level has two arrays of 8-byte elements, `q` and `rhs`; a core's
    block of (s + 2)^2 elements holds its points and their ghost border,
    is rounded to lines and padded by one, and the blocks lie core after
    core. A visit to a level is, for red then black: copy the borders (LD
    the neighbour's edge element, ST the own ghost element, s times a
    neighbour), BARRIER, relax the colour's own points (LD `rhs`, LD the
    four neighbours, ST the point; every point where s = 1), BARRIER; then
    the error phase (with a global lock reduction in the first
    `lock_reductions` visits: LOCK, LD, ST, UNLOCK) and a BARRIER. One
    V-cycle visits the levels 0 .. levels-1 .. 0 with a restrict (LD four
    fine `q`, ST the coarse `rhs`) before each step down and an
    interpolate (LD the coarse `q`, LD and ST four fine `q`) before each
    step up, a BARRIER after either; `visits` takes the first n of its
    visits. Every barrier is global, ids cycling over `barrier_ids`.
    Before each LD, ST, LOCK and UNLOCK a batch of `ins_per_mem` - 1 .. + 1
    instructions is drawn from the seed, which changes nothing else.
    """
    side = int(round(n_cores ** 0.5))
    if side * side != n_cores:
        raise ValueError("ocean_like needs a square number of cores")
    s0, rem = divmod(grid_n - 2, side)
    if rem or s0 < 1 or s0 % (1 << (levels - 1)):
        raise ValueError(
            f"a {grid_n} x {grid_n} grid does not give {side} x {side} cores "
            f"square subgrids that halve {levels - 1} times")
    if ins_per_mem < 1 or barrier_ids < 1 or lock_reductions < 0:
        raise ValueError("ins_per_mem, barrier_ids >= 1; lock_reductions >= 0")
    rng = _rng(seed)
    lock_addr, err_addr = 0x1000, 0x2000
    sides = [s0 >> l for l in range(levels)]
    # level -> (base of q, base of rhs, bytes a core's block takes)
    layout, top = [], 0x10000
    for s in sides:
        block = -(-(s + 2) ** 2 * 8 // line) * line + line
        layout.append((top, top + n_cores * block, block))
        top += 2 * n_cores * block

    def at(level, array, core, i, j):
        return (layout[level][array] + core * layout[level][2]
                + (i * (sides[level] + 2) + j) * 8)

    per_core = []
    for c in range(n_cores):
        px, py = c % side, c // side
        evs: list[tuple] = []
        n_bar = 0

        def mem(t, addr):
            k = int(rng.integers(ins_per_mem - 1, ins_per_mem + 2))
            if k:
                evs.append((EV_INS, k, 0))
            evs.append((t, 8 if t in (EV_LD, EV_ST) else 0, addr))

        def barrier():
            nonlocal n_bar
            evs.append((EV_BARRIER, n_cores, n_bar % barrier_ids))
            n_bar += 1

        def transfer(fine, coarse, restrict):
            sc = sides[coarse]
            for ci in range(1, sc + 1):
                for cj in range(1, sc + 1):
                    pts = [(2 * ci - 1 + a, 2 * cj - 1 + b)
                           for a in (0, 1) for b in (0, 1)]
                    if restrict:
                        for i, j in pts:
                            mem(EV_LD, at(fine, 0, c, i, j))
                        mem(EV_ST, at(coarse, 1, c, ci, cj))
                    else:
                        mem(EV_LD, at(coarse, 0, c, ci, cj))
                        for i, j in pts:
                            mem(EV_LD, at(fine, 0, c, i, j))
                            mem(EV_ST, at(fine, 0, c, i, j))
            barrier()

        order = _ocean_visits(levels, visits)
        for v, l in enumerate(order):
            if v:
                prev = order[v - 1]
                transfer(min(prev, l), max(prev, l), restrict=l > prev)
            s = sides[l]
            # (the neighbour, its edge element, the own ghost element) at k = 1..s
            borders = []
            if px > 0:
                borders.append((c - 1, lambda k: (k, s), lambda k: (k, 0)))
            if px < side - 1:
                borders.append((c + 1, lambda k: (k, 1), lambda k: (k, s + 1)))
            if py > 0:
                borders.append((c - side, lambda k: (s, k), lambda k: (0, k)))
            if py < side - 1:
                borders.append((c + side, lambda k: (1, k), lambda k: (s + 1, k)))
            for colour in (0, 1):
                for nb, edge, ghost in borders:
                    for k in range(1, s + 1):
                        mem(EV_LD, at(l, 0, nb, *edge(k)))
                        mem(EV_ST, at(l, 0, c, *ghost(k)))
                barrier()
                for i in range(1, s + 1):
                    for j in range(1, s + 1):
                        if s > 1 and (i + j) % 2 != colour:
                            continue
                        mem(EV_LD, at(l, 1, c, i, j))
                        for di, dj in ((-1, 0), (1, 0), (0, -1), (0, 1)):
                            mem(EV_LD, at(l, 0, c, i + di, j + dj))
                        mem(EV_ST, at(l, 0, c, i, j))
                barrier()
            if v < lock_reductions:
                mem(EV_LOCK, lock_addr)
                mem(EV_LD, err_addr)
                mem(EV_ST, err_addr)
                mem(EV_UNLOCK, lock_addr)
            barrier()
        per_core.append(evs)
    return from_event_lists(per_core)


_FNV_OFFSET_64, _FNV_PRIME_64 = 0xCBF29CE484222325, 0x100000001B3


def _fnv1a_64(x: int) -> int:
    """FNV-1a over the eight bytes of `x`, low byte first (YCSB's
    `Utils.fnvhash64`, kept unsigned)."""
    h = _FNV_OFFSET_64
    for _ in range(8):
        h = ((h ^ (x & 0xFF)) * _FNV_PRIME_64) & 0xFFFFFFFFFFFFFFFF
        x >>= 8
    return h


_YCSB_ITEMS = 10_000_000_000  # `ScrambledZipfianGenerator.ITEM_COUNT`


def _zeta(n: int, theta: float) -> float:
    """The sum of i^-theta over 1 .. n: the first 2^20 terms added up, the
    others by Euler-Maclaurin (to 1e-14). `_zeta(10**10, 0.99)` is YCSB's
    `ScrambledZipfianGenerator.ZETAN`, 26.46902820178302."""
    m = min(n, 1 << 20)
    head = float(np.sum(np.arange(1, m + 1, dtype=np.float64) ** -theta))
    if n == m:
        return head
    n, m = float(n), float(m)
    return (head + (n ** (1.0 - theta) - m ** (1.0 - theta)) / (1.0 - theta)
            + (n ** -theta - m ** -theta) / 2.0
            - theta * (n ** (-theta - 1.0) - m ** (-theta - 1.0)) / 12.0)


def _zipfian_ranks(u: np.ndarray, n: int, theta: float) -> np.ndarray:
    """Ranks in [0, n) for uniform draws `u`, rank i with probability
    near (i+1)^-theta / zetan: Gray et al.'s method (SIGMOD 1994) as
    YCSB's `ZipfianGenerator.nextLong` computes it."""
    zetan = _zeta(n, theta)
    zeta2 = 1.0 + 0.5 ** theta
    alpha = 1.0 / (1.0 - theta)
    eta = (1.0 - (2.0 / n) ** (1.0 - theta)) / (1.0 - zeta2 / zetan)
    tail = np.floor(n * (eta * u - eta + 1.0) ** alpha).astype(np.int64)
    uz = u * zetan
    return np.where(uz < 1.0, 0, np.where(uz < zeta2, 1, np.minimum(tail, n - 1)))


def ycsb_like(
    n_cores: int,
    seed: int = 0,
    ops_per_core: int = 16,
    recordcount: int = 1000,
    theta: float = 0.99,
    read_frac: float = 0.5,
    fieldcount: int = 10,
    fieldlength: int = 100,
    ins_per_mem: int = 3,
    op_ins: int = 30,
    line: int = 64,
) -> Trace:
    """YCSB core workload A ("update heavy": Cooper et al., SoCC 2010;
    `workloads/workloada`, `CoreWorkload`) against a shared in-memory hash
    table with in-place updates, every core a worker thread that serves
    `ops_per_core` operations in a closed loop.

    The key of an operation is drawn as `ScrambledZipfianGenerator` draws
    it: a zipfian rank (`_zipfian_ranks`, constant `theta`) over that
    class's fixed 10^10 items, whatever the table's size, scattered over
    the records by record = FNV-1a-64(rank) mod `recordcount`; its bucket
    is FNV-1a-64(record) mod 2 * `recordcount`. The store: an index of
    2 * `recordcount` 8-byte bucket heads from address 0, then, from the
    next 4 KB boundary, the records at a stride of 1 + ceil(`fieldcount` *
    `fieldlength` / line) lines: line 0 the header (the key, a
    sequence-lock version), then the fields packed. A READ (a draw below
    `read_frac`; `readallfields`) is LD the bucket head, LD the header, LD
    every field line in rising order; an UPDATE (`writeallfields` false)
    is LD the bucket head, LD the header, ST the header (the version), ST
    every line that one field, uniform over `fieldcount`, lies on. No
    record lock is taken: the trace holds LD and ST only. Before every
    reference a batch of 1 .. 2 * `ins_per_mem` instructions, and `op_ins`
    more before an operation's first (the hash and the dispatch).
    """
    n, fl = recordcount, fieldlength
    if ops_per_core < 1 or n < 3 or not 0.0 < theta < 1.0 or not 0.0 <= read_frac <= 1.0:
        raise ValueError("ops_per_core >= 1, recordcount >= 3, 0 < theta < 1, 0 <= read_frac <= 1")
    if fieldcount < 1 or fl < 1 or ins_per_mem < 1 or op_ins < 0:
        raise ValueError("fieldcount, fieldlength, ins_per_mem >= 1; op_ins >= 0")
    field_lines = -(-fieldcount * fl // line)
    stride = (1 + field_lines) * line
    records = -(-2 * n * 8 // 4096) * 4096
    if records + n * stride > 2**31:
        raise ValueError(f"{n} records of {stride} bytes do not fit under 2^31")
    spans = [(f * fl + fl - 1) // line - f * fl // line + 1 for f in range(fieldcount)]
    slots = 2 + max(field_lines, 1 + max(spans))  # the references of the longest operation
    rng = _rng(seed)
    shape = (n_cores, ops_per_core)
    ranks = _zipfian_ranks(rng.random(shape), _YCSB_ITEMS, theta)
    is_read = rng.random(shape) < read_frac
    field = rng.integers(0, fieldcount, shape)
    batch = rng.integers(1, 2 * ins_per_mem + 1, shape + (slots,))

    per_core = []
    for c in range(n_cores):
        evs: list[tuple] = []
        for o in range(ops_per_core):
            record = _fnv1a_64(int(ranks[c, o])) % n
            base = records + record * stride
            refs = [(EV_LD, 8 * (_fnv1a_64(record) % (2 * n))), (EV_LD, base)]
            if is_read[c, o]:
                refs += [(EV_LD, base + l * line) for l in range(1, field_lines + 1)]
            else:
                lo = int(field[c, o]) * fl
                refs.append((EV_ST, base))
                refs += [(EV_ST, base + line + max(lo, l * line) // 4 * 4)
                         for l in range(lo // line, (lo + fl - 1) // line + 1)]
            for j, (t, addr) in enumerate(refs):
                evs.append((EV_INS, int(batch[c, o, j]) + (0 if j else op_ins), 0))
                evs.append((t, 4, addr))
        per_core.append(evs)
    return from_event_lists(per_core)


def _moe_plan(n_cores, seed, tokens, hidden, inter, experts, top_k, n_group, topk_group,
              skew_milli, gate_rows, up_rows, down_rows, ins_per_mem, line):
    """What `moe_decode_like` and `moe_decode_describe` share: the checks,
    the layout, the routing drawn from the seed, and every core's count
    of references."""
    per = experts // n_group if n_group >= 1 and experts % n_group == 0 else 0
    if tokens < 1 or experts < 1 or n_cores != 64 * experts:
        raise ValueError("tokens >= 1, and an expert is 64 cores: n_cores = 64 * experts")
    if not per or not 1 <= topk_group <= n_group or not 1 <= top_k <= topk_group * per:
        raise ValueError("n_group divides experts; topk_group of them hold top_k experts")
    if hidden < 1 or inter < 1 or hidden % (8 * line) or inter % (8 * line):
        raise ValueError("an eighth of a gate row and of a down row is whole lines")
    gseg, dseg = hidden // (8 * line), inter // (8 * line)  # lines a row segment
    full = (inter // 8, inter // 8, hidden // 8)  # row segments a core holds: gate, up, down
    rows = (gate_rows, up_rows, down_rows)
    if skew_milli < 0 or ins_per_mem < 1 or any(not 1 <= r <= f for r, f in zip(rows, full)):
        raise ValueError("skew_milli >= 0, ins_per_mem >= 1, 1 <= rows <= what a core holds")
    # only what a step can touch is laid out: of every kind of row the
    # segments that `tokens` visits reach, rounded to an odd count of lines
    held = [min(f, tokens * r) for r, f in zip(rows, full)]
    up_at, down_at = held[0] * gseg, (held[0] + held[1]) * gseg
    stride = (down_at + held[2] * dseg) | 1
    inter_base = tokens * hidden
    out_base = inter_base + experts * inter
    w_base = -(-(out_base + experts * 2 * hidden) // (4096 * line)) * 4096 * line
    if w_base + n_cores * stride * line > 2**31:
        raise ValueError("the weights a step touches do not fit under 2^31: fewer tokens or rows")

    rng = _rng(seed)
    weight = np.empty(experts)
    weight[rng.permutation(experts)] = np.arange(1, experts + 1) ** (-skew_milli / 1000.0)
    # a weighted draw without replacement takes the smallest of Exp(1) / weight
    group_key = -np.log1p(-rng.random((tokens, n_group))) / weight.reshape(n_group, per).sum(1)
    expert_key = -np.log1p(-rng.random((tokens, experts))) / weight
    route, home = [], []
    for t in range(tokens):
        groups = np.argsort(group_key[t], kind="stable")[:topk_group]
        key = np.where(np.isin(np.arange(experts) // per, groups), expert_key[t], np.inf)
        chosen = np.argsort(key, kind="stable")[:top_k]
        home.append(int(chosen[0]))
        route.append(sorted(int(e) for e in chosen))
    visits = [[t for t in range(tokens) if e in route[t]] for e in range(experts)]
    classes = {"activation": gseg, "weight": (gate_rows + up_rows) * gseg + down_rows * dseg,
               "intermediate": 1 + dseg, "output": 1}
    refs = np.repeat([sum(classes.values()) * len(v) for v in visits], 64)
    for t in range(tokens):
        refs[64 * home[t] + t % 64] += top_k
    return {
        "rng": rng, "gseg": gseg, "dseg": dseg, "held": held, "up_at": up_at,
        "down_at": down_at, "stride": stride, "inter_base": inter_base, "out_base": out_base,
        "w_base": w_base, "route": route, "home": home, "visits": visits, "classes": classes,
        "refs": refs,
    }


def moe_decode_like(
    n_cores: int,
    seed: int = 0,
    tokens: int = 8,
    hidden: int = 7168,
    inter: int = 2048,
    experts: int = 256,
    top_k: int = 8,
    n_group: int = 8,
    topk_group: int = 4,
    skew_milli: int = 500,
    gate_rows: int = 1,
    up_rows: int = 1,
    down_rows: int = 4,
    ins_per_mem: int = 8,
    line: int = 64,
) -> Trace:
    """One decode step of one routed-expert layer, `y = W_down (silu(W_gate
    x) * (W_up x))` an expert, for a batch of `tokens`, as the memory
    references of its cores. The defaults are DeepSeek-V3's published
    widths (`hidden_size`, `moe_intermediate_size`, `n_routed_experts`,
    `num_experts_per_tok`, `n_group`, `topk_group`), weights, activations
    and the intermediate at one byte a value (FP8), the output at two.

    Expert e is the cores 64 e .. 64 e + 63, core 8 i + j of them block
    (i, j) of an 8 x 8 blocking of each matrix: `inter` / 8 rows of
    `hidden` / 8 bytes of gate and of up, `hidden` / 8 rows of `inter` / 8
    bytes of down (a row segment: 14 and 4 lines at the defaults).

    Routing: expert popularity is rank ** -(`skew_milli` / 1000) over a
    seeded permutation of the experts; the expert groups are `experts` /
    `n_group` consecutive experts. A token draws `topk_group` groups by
    their summed popularity, then `top_k` experts inside them by their
    own, both without replacement; the first expert drawn is its home.

    A visit (a token at an expert, on each of its 64 cores; an expert
    takes its tokens in rising order): LD the token's activation block j;
    LD `gate_rows` gate and `up_rows` up row segments; ST its 1/64 of the
    expert's intermediate; LD block j of the intermediate; LD `down_rows`
    down row segments; ST the first word of its 1/64 of the expert's
    output. Visit v streams the row segments v * rows .. (v + 1) * rows - 1
    (mod what the core holds), so as at full size no weight line is met
    twice. After its visits, core t mod 64 of token t's home combines it:
    LD that slice of the output of each of its experts, in rising order.
    The intermediate and the output are an expert's scratch, written anew
    each visit. Before every reference a batch of 1 .. 2 * `ins_per_mem`
    instructions.

    Addresses: the tokens' activations from 0, the experts' intermediates,
    their outputs, then from the next 256 KB every core's weights, core
    after core: only the segments `tokens` visits can reach, rounded up to
    an odd number of lines, so the cores' streams spread over every bank.
    """
    p = _moe_plan(n_cores, seed, tokens, hidden, inter, experts, top_k, n_group, topk_group,
                  skew_milli, gate_rows, up_rows, down_rows, ins_per_mem, line)
    gseg, dseg, held = p["gseg"], p["dseg"], p["held"]
    batch = p["rng"].integers(1, 2 * ins_per_mem + 1, (n_cores, int(p["refs"].max())))
    own_inter, own_out = inter // 64, 2 * hidden // 64  # bytes of an expert's scratch a core writes

    def segments(base, seg_lines, first, n, of):
        """LD the lines of `n` row segments from segment `first` (mod `of`)."""
        return [(EV_LD, base + (((first + r) % of) * seg_lines + l) * line)
                for r in range(n) for l in range(seg_lines)]

    per_core = []
    for c in range(n_cores):
        e, q = divmod(c, 64)
        j = q % 8
        w = p["w_base"] + c * p["stride"] * line
        scratch, out = p["inter_base"] + e * inter, p["out_base"] + e * 2 * hidden
        refs: list[tuple] = []
        for v, t in enumerate(p["visits"][e]):
            refs += [(EV_LD, t * hidden + (j * gseg + l) * line) for l in range(gseg)]
            refs += segments(w, gseg, v * gate_rows, gate_rows, held[0])
            refs += segments(w + p["up_at"] * line, gseg, v * up_rows, up_rows, held[1])
            refs.append((EV_ST, scratch + q * own_inter))
            refs += [(EV_LD, scratch + (j * dseg + l) * line) for l in range(dseg)]
            refs += segments(w + p["down_at"] * line, dseg, v * down_rows, down_rows, held[2])
            refs.append((EV_ST, out + q * own_out))
        for t in range(q, tokens, 64):
            if p["home"][t] == e:
                refs += [(EV_LD, p["out_base"] + x * 2 * hidden + q * own_out) for x in p["route"][t]]
        evs: list[tuple] = []
        for k, (kind, addr) in enumerate(refs):
            evs.append((EV_INS, int(batch[c, k]), 0))
            evs.append((kind, 4, addr))
        per_core.append(evs)
    return from_event_lists(per_core)


def moe_decode_describe(
    n_cores: int,
    seed: int = 0,
    tokens: int = 8,
    hidden: int = 7168,
    inter: int = 2048,
    experts: int = 256,
    top_k: int = 8,
    n_group: int = 8,
    topk_group: int = 4,
    skew_milli: int = 500,
    gate_rows: int = 1,
    up_rows: int = 1,
    down_rows: int = 4,
    ins_per_mem: int = 8,
    line: int = 64,
) -> dict:
    """What `moe_decode_like` of the same arguments holds, without building
    it: visits an expert, references a class, and the events of the
    fullest and of the mean core (a reference is two events unfolded, an
    INS batch and the LD or ST; END not counted)."""
    p = _moe_plan(n_cores, seed, tokens, hidden, inter, experts, top_k, n_group, topk_group,
                  skew_milli, gate_rows, up_rows, down_rows, ins_per_mem, line)
    per_expert = [len(v) for v in p["visits"]]
    n_visits = sum(per_expert)
    references = {k: 64 * n * n_visits for k, n in p["classes"].items()}
    references["output"] += top_k * tokens  # the combiners' loads
    return {
        "visits": {"all": n_visits, "fullest_expert": max(per_expert),
                   "mean_expert": n_visits / experts,
                   "experts_without": per_expert.count(0)},
        "references_a_visit": p["classes"],
        "references": references,
        "events": {"fullest_core": 2 * int(p["refs"].max()),
                   "mean_core": 2 * float(p["refs"].mean())},
        "bytes_laid_out": p["w_base"] + n_cores * p["stride"] * line,
    }


GENERATORS = {
    "uniform_random": uniform_random,
    "stream": stream,
    "pointer_chase": pointer_chase,
    "false_sharing": false_sharing,
    "fft_like": fft_like,
    "readers_writer": readers_writer,
    "lock_contention": lock_contention,
    "barrier_phases": barrier_phases,
    "ocean_like": ocean_like,
    "ycsb_like": ycsb_like,
    "moe_decode_like": moe_decode_like,
}
