"""Traffic shape `ocean_like`: the access pattern of
`primesim_tpu/trace/synth.py::ocean_like` (SPLASH-2 OCEAN's multigrid
solver, contiguous partitions), written here on its own: one core's
events as a template of (array, whose block, element) built once, every
core's addresses from it in array calls, and the instruction batches
drawn from the same random stream in one call instead of one an event;
`tests/test_synth_ocean.py` holds the two equal, event for event."""

import numpy as np

from trafficgen import EV_END, EV_LD, EV_ST, LINE, finish

EV_LOCK, EV_UNLOCK, EV_BARRIER = 4, 5, 6
LOCK_ADDR, ERR_ADDR, ARRAYS = 0x1000, 0x2000, 0x10000
OWN, WEST, EAST, NORTH, SOUTH = -1, 0, 1, 2, 3


def visit_order(levels: int, visits: int) -> list:
    """The levels one V-cycle visits (0 .. levels-1 .. 0), its first `visits`."""
    cycle = list(range(levels)) + list(range(levels - 2, -1, -1))
    if not 1 <= visits <= len(cycle):
        raise ValueError(f"visits must be 1..{len(cycle)} at {levels} levels")
    return cycle[:visits]


def _template(sides: list, order: list, lock_reductions: int) -> np.ndarray:
    """One interior core's events, a row each: type, base (index into the
    table of array bases, -1 for a fixed address), whose block (OWN or a
    direction), element offset or fixed address, the neighbour the event
    needs (OWN: none)."""
    rows = []

    def ref(t, level, array, who, i, j, needs=OWN):
        rows.append((t, 2 * level + array, who, i * (sides[level] + 2) + j, needs))

    def fixed(t, addr):
        rows.append((t, -1, OWN, addr, OWN))

    def barrier():
        rows.append((EV_BARRIER, -1, OWN, 0, OWN))

    for v, level in enumerate(order):
        if v:
            fine, coarse = sorted((order[v - 1], level))
            for ci in range(1, sides[coarse] + 1):
                for cj in range(1, sides[coarse] + 1):
                    pts = [(2 * ci - 1 + a, 2 * cj - 1 + b) for a in (0, 1) for b in (0, 1)]
                    if level > order[v - 1]:  # restrict: four fine q -> the coarse rhs
                        for i, j in pts:
                            ref(EV_LD, fine, 0, OWN, i, j)
                        ref(EV_ST, coarse, 1, OWN, ci, cj)
                    else:  # interpolate: the coarse q -> four fine q
                        ref(EV_LD, coarse, 0, OWN, ci, cj)
                        for i, j in pts:
                            ref(EV_LD, fine, 0, OWN, i, j)
                            ref(EV_ST, fine, 0, OWN, i, j)
            barrier()
        s = sides[level]
        for colour in (0, 1):
            # (neighbour, its edge element, the own ghost element) at k = 1..s
            for d, edge, ghost in ((WEST, lambda k: (k, s), lambda k: (k, 0)),
                                   (EAST, lambda k: (k, 1), lambda k: (k, s + 1)),
                                   (NORTH, lambda k: (s, k), lambda k: (0, k)),
                                   (SOUTH, lambda k: (1, k), lambda k: (s + 1, k))):
                for k in range(1, s + 1):
                    ref(EV_LD, level, 0, d, *edge(k), needs=d)
                    ref(EV_ST, level, 0, OWN, *ghost(k), needs=d)
            barrier()
            for i in range(1, s + 1):
                for j in range(1, s + 1):
                    if s > 1 and (i + j) % 2 != colour:
                        continue
                    ref(EV_LD, level, 1, OWN, i, j)
                    for di, dj in ((-1, 0), (1, 0), (0, -1), (0, 1)):
                        ref(EV_LD, level, 0, OWN, i + di, j + dj)
                    ref(EV_ST, level, 0, OWN, i, j)
            barrier()
        if v < lock_reductions:  # the global error sum, under the one lock
            fixed(EV_LOCK, LOCK_ADDR)
            fixed(EV_LD, ERR_ADDR)
            fixed(EV_ST, ERR_ADDR)
            fixed(EV_UNLOCK, LOCK_ADDR)
        barrier()
    return np.asarray(rows, np.int64)


def generate(n_cores: int, seed: int, grid_n: int, levels: int, visits: int,
             ins_per_mem: int, barrier_ids: int, lock_reductions: int) -> np.ndarray:
    """OCEAN's shape: sqrt(C) x sqrt(C) cores, each a square subgrid of
    side (grid_n - 2) / sqrt(C) that halves a level; a visit to a level
    copies borders from the four neighbours and relaxes red then black,
    a global barrier after every phase; restricts and interpolates between
    the levels of one V-cycle, the first `visits` visits of it."""
    C = n_cores
    side = int(round(C ** 0.5))
    if side * side != C:
        raise ValueError("ocean_like needs a square number of cores")
    s0, rem = divmod(grid_n - 2, side)
    if rem or s0 < 1 or s0 % (1 << (levels - 1)):
        raise ValueError(f"a {grid_n} x {grid_n} grid does not give {side} x {side} cores "
                         f"square subgrids that halve {levels - 1} times")
    if ins_per_mem < 1 or barrier_ids < 1 or lock_reductions < 0:
        raise ValueError("ins_per_mem, barrier_ids >= 1; lock_reductions >= 0")
    sides = [s0 >> l for l in range(levels)]
    # a level's q, then its rhs: C blocks each, a block (s + 2)^2 doubles
    # rounded to lines and one line more
    blocks = np.repeat([-(-(s + 2) ** 2 * 8 // LINE) * LINE + LINE for s in sides], 2)
    bases = ARRAYS + np.concatenate([[0], np.cumsum(blocks * C)[:-1]])

    t, base, who, off, needs = _template(sides, visit_order(levels, visits), lock_reductions).T
    cores = np.arange(C, dtype=np.int64)
    px, py = cores % side, cores // side
    has = np.stack([px > 0, px < side - 1, py > 0, py < side - 1], axis=1)  # [C, 4]
    step = np.array([-1, 1, -side, side], np.int64)

    barrier = t == EV_BARRIER
    in_array = base >= 0
    owner = cores[:, None] + np.where(who == OWN, 0, step[who])[None, :]  # [C, n]
    addrs = np.where(in_array[None, :],
                     bases[base][None, :] + owner * blocks[base][None, :] + 8 * off[None, :],
                     off[None, :])
    addrs[:, barrier] = (np.arange(barrier.sum()) % barrier_ids)[None, :]
    args = np.where(barrier, C, np.where((t == EV_LD) | (t == EV_ST), 8, 0))
    valid = np.where(needs == OWN, True, has[:, needs])  # [C, n]

    pre = np.zeros((C, len(t)), np.int64)
    drawn = valid & ~barrier[None, :]
    pre[drawn] = np.random.default_rng(seed).integers(
        ins_per_mem - 1, ins_per_mem + 2, size=int(drawn.sum()))

    # a core on the machine's edge has no such neighbour: its events close up
    order = np.argsort(~valid, axis=1, kind="stable")
    kept = np.take_along_axis(valid, order, axis=1)
    return finish(np.where(kept, t[order], EV_END), np.where(kept, args[order], 0),
                  np.where(kept, np.take_along_axis(addrs, order, axis=1), 0),
                  np.where(kept, np.take_along_axis(pre, order, axis=1), 0))
