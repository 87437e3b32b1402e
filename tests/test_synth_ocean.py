"""The trace shape `ocean_like` (SPLASH-2 OCEAN's multigrid solver;
`trace/synth.py`, and the benchmark's own `benchmark/generators/
ocean_like.py`): its counts by formula, where its border loads point, and
the two generators equal event for event."""

import numpy as np
import pytest

from benchmark_modules import ROOT  # puts benchmark/ on the path

import cells
import trafficgen
from primesim_tpu.trace import synth
from primesim_tpu.trace.format import (EV_BARRIER, EV_END, EV_INS, EV_LD, EV_LOCK, EV_ST,
                                       EV_UNLOCK, fold_ins)

CELL = dict(grid_n=258, levels=4, visits=4, ins_per_mem=3, barrier_ids=8, lock_reductions=0)


def _visit(s, neighbours=4):
    """Events of one visit to a level of side s: red and black, each the
    borders (LD + ST, s times a neighbour), a barrier, the colour's points
    (six references each; the one point both times where s = 1), a
    barrier; then the error phase's barrier."""
    points = s * s // 2 if s > 1 else 1
    return 2 * (2 * s * neighbours + 1 + 6 * points + 1) + 1


def _restrict(s_coarse):
    return 5 * s_coarse ** 2 + 1


def _interpolate(s_coarse):
    return 9 * s_coarse ** 2 + 1


def _mem_and_sync(ev):
    return ev[:, :, 0] != EV_END


@pytest.mark.parametrize("visits,barriers,events", [
    (7, 41, 2 * (_visit(8) + _visit(4) + _visit(2)) + _visit(1)
     + _restrict(4) + _restrict(2) + _restrict(1)
     + _interpolate(1) + _interpolate(2) + _interpolate(4)),
    (4, 23, _visit(8) + _visit(4) + _visit(2) + _visit(1)
     + _restrict(4) + _restrict(2) + _restrict(1)),
    (1, 5, _visit(8)),
])
def test_counts_of_the_cells_grid(visits, barriers, events):
    """258 x 258 over 1024 cores: sides 8, 4, 2, 1."""
    ev = cells.load_generator("ocean_like")(1024, 404, **{**CELL, "visits": visits})
    t = ev[:, :, 0]
    assert ev.shape == (1024, events + 1, 4)
    assert events == {7: 1819, 4: 884, 1: 517}[visits]
    per_core = (t == EV_BARRIER).sum(1)
    assert per_core.min() == per_core.max() == barriers  # every core, every barrier
    side = 32
    px, py = np.arange(1024) % side, np.arange(1024) // side
    neighbours = sum(m.astype(int) for m in (px > 0, px < side - 1, py > 0, py < side - 1))
    assert sorted(set(neighbours.tolist())) == [2, 3, 4]
    # a core on the machine's edge copies fewer borders and nothing else differs
    order = synth._ocean_visits(4, visits)
    missing = (4 - neighbours) * sum(2 * 2 * (8 >> l) for l in order)
    assert np.array_equal(_mem_and_sync(ev).sum(1), events - missing)
    bar = t == EV_BARRIER
    assert (ev[:, :, 1][bar] == 1024).all()  # every barrier is global
    ids = ev[0, :, 2][bar[0]]
    assert np.array_equal(ids, np.arange(barriers) % 8)
    assert np.array_equal(ev[:, :, 2][bar].reshape(1024, barriers), np.tile(ids, (1024, 1)))
    assert not ((t == EV_LOCK) | (t == EV_UNLOCK) | (t == EV_INS)).any()
    pre = ev[:, :, 3]
    assert (pre[bar] == 0).all() and set(np.unique(pre[(t == EV_LD) | (t == EV_ST)])) == {2, 3, 4}
    if visits == 4:
        assert trafficgen.total_instructions(ev) == 3518067


def test_the_parity_jobs_arguments():
    """66 x 66 over 1024 cores: 2 x 2 points a core, then one."""
    gen = cells.load_generator("ocean_like")
    spec = cells.load_cell("rung3.ocean-n258")
    assert spec["traffic"]["args"] == CELL and spec["traffic"]["panel_seeds"] == [404]
    two = gen(1024, 5, **{**CELL, "grid_n": 66, "levels": 2, "visits": 2})
    assert two.shape == (1024, _visit(2) + _restrict(1) + _visit(1) + 1, 4)
    assert ((two[:, :, 0] == EV_BARRIER).sum(1) == 11).all()
    ev = trafficgen.make_trace(spec["traffic"], 1024, 5, parity=True)
    assert np.array_equal(ev, gen(1024, 5, **{**CELL, **spec["traffic"]["parity_args"]}))
    assert ((ev[:, :, 0] == EV_BARRIER).sum(1) == 5 * spec["traffic"]["parity_args"]["visits"]).all()
    assert ev.shape[1] <= 885


def test_the_seed_draws_the_batches_and_nothing_else():
    gen = cells.load_generator("ocean_like")
    args = dict(grid_n=34, levels=3, visits=5, ins_per_mem=3, barrier_ids=3, lock_reductions=2)
    a, b = gen(16, 1, **args), gen(16, 2**31 + 9, **args)
    assert a.shape == b.shape
    assert np.array_equal(a[:, :, :3], b[:, :, :3]) and not np.array_equal(a[:, :, 3], b[:, :, 3])
    t = a[:, :, 0]
    assert ((t == EV_LOCK).sum(1) == 2).all() and ((t == EV_UNLOCK).sum(1) == 2).all()
    assert len(set(a[:, :, 2][(t == EV_LOCK) | (t == EV_UNLOCK)])) == 1  # one global lock
    assert (a[:, :, 2][t == EV_BARRIER] < 3).all()


def test_a_border_load_reads_the_neighbours_edge_element():
    """Core 5 of a 4 x 4 machine (px 1, py 1) on a 34 x 34 grid, side 8:
    its first events copy the west border. The load is element (k, 8) of
    core 4's block of `q`, the last own column of the neighbour; the store
    is the own ghost element (k, 0). Core 4 stores that very element when
    it relaxes: the line is shared by producer and consumer."""
    ev = cells.load_generator("ocean_like")(16, 3, grid_n=34, levels=1, visits=1, ins_per_mem=3,
                                            barrier_ids=8, lock_reductions=0)
    block = -(-(10 * 10 * 8) // 64) * 64 + 64  # 100 doubles to lines, one line of padding
    assert block == 896

    def q(core, i, j):
        return 0x10000 + core * block + (i * 10 + j) * 8

    for k in range(1, 9):
        ld, st = ev[5, 2 * (k - 1)], ev[5, 2 * (k - 1) + 1]
        assert (ld[0], ld[1], ld[2]) == (EV_LD, 8, q(4, k, 8))
        assert (st[0], st[1], st[2]) == (EV_ST, 8, q(5, k, 0))
    # then east (core 6, its first own column), north (core 1, its last own row), south
    assert ev[5, 16, 2] == q(6, 1, 1) and ev[5, 32, 2] == q(1, 8, 1) and ev[5, 48, 2] == q(9, 1, 1)
    assert ev[5, 64, 0] == EV_BARRIER
    stores_of_4 = set(ev[4, :, 2][ev[4, :, 0] == EV_ST])
    assert {q(4, k, 8) for k in range(1, 9)} <= stores_of_4
    # core 0 has no west and no north neighbour: its first load is from the east
    assert ev[0, 0, 2] == q(1, 1, 1)
    # rhs lies behind q: 16 blocks further
    rhs = 0x10000 + 16 * block
    assert rhs + 5 * block + (1 * 10 + 1) * 8 in set(ev[5, :, 2][ev[5, :, 0] == EV_LD])


@pytest.mark.parametrize("n_cores,seed,args", [
    (16, 7, dict(grid_n=34, levels=3, visits=5, ins_per_mem=3, barrier_ids=8, lock_reductions=0)),
    (64, 2**31 + 11, dict(grid_n=66, levels=4, visits=7, ins_per_mem=1, barrier_ids=3,
                          lock_reductions=1)),
    (1024, 404, dict(CELL, grid_n=66, levels=2, visits=2)),
])
def test_generator_equals_the_programs(n_cores, seed, args):
    mine = cells.load_generator("ocean_like")(n_cores, seed, **args)
    theirs = fold_ins(synth.ocean_like(n_cores, seed=seed, **args))
    assert np.array_equal(mine, theirs.events)
    assert trafficgen.total_instructions(mine) == theirs.total_instructions()
    assert "ocean_like" in synth.GENERATORS


def test_what_the_shape_refuses():
    gen = cells.load_generator("ocean_like")
    for bad in (dict(n=15), dict(grid_n=35), dict(levels=5), dict(visits=8), dict(visits=0),
                dict(ins_per_mem=0), dict(barrier_ids=0)):
        args = dict(grid_n=34, levels=3, visits=5, ins_per_mem=3, barrier_ids=8, lock_reductions=0)
        n = bad.pop("n", 16)
        args.update(bad)
        with pytest.raises(ValueError):
            gen(n, 1, **args)
        with pytest.raises(ValueError):
            synth.ocean_like(n, seed=1, **args)


def test_the_cli_names_it():
    from primesim_tpu.cli import _parse_synth

    tr = _parse_synth("ocean_like:seed=3,grid_n=34,levels=2,visits=2", 16, True)
    mine = cells.load_generator("ocean_like", ROOT)(16, 3, grid_n=34, levels=2, visits=2,
                                                    ins_per_mem=3, barrier_ids=8, lock_reductions=0)
    assert np.array_equal(tr.events, mine)
