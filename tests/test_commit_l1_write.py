"""Phase 4.A's L1 write (`sim/step.py::_l1_writes` -> `_l1_row_write`):
each core edits its own row of the fused L1 array by a select over the
row's planes. Held to the expression it replaced in PR 38, ONE element
scatter of every (row, column, word) with a masked lane's row dropped,
kept here as a numpy oracle, on lanes shaped by construction to each
situation the write has to get right; with and without local runs, the
full map and the coarse vector, MOESI, on one device and with the rows
over four.
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from primesim_tpu.config.machine import CacheConfig, small_test_config
from primesim_tpu.parallel.sharding import state_shardings, tile_mesh
from primesim_tpu.sim.state import E, I, M, S, dirm_width, init_state
from primesim_tpu.sim.step import DirOutcome, Request, _l1_row_write, _l1_writes

TAG, STATE, LRU, PTR, EPOCH = range(5)
STEP_NO = 1000
_L1 = CacheConfig(size=2048, ways=4, line=64, latency=2)  # 8 sets of 4 ways
MACHINES = {
    "plain": dict(local_run_len=4),  # the probe reads four planes
    "coarse": dict(local_run_len=4, sharer_group=4),  # five: the epoch too
    "moesi": dict(local_run_len=4, coherence="moesi"),
    "norun": dict(local_run_len=0),  # the seven phase-4 writes alone
}
# the situation every core's lane is shaped to -> whether it needs a run
CASES = {
    "refresh_on_run_stamp": True,  # a hit refresh and a run stamp, one word
    "grant_over_run_m": True,  # a run's E->M on the word a grant writes
    "run_m_beside_grant": True,  # and on another way: both land
    "fill_clears_stale_duplicate": False,
    "join": False,
    "all_masks_false": False,
    "silent_e_to_m": False,
    "upgrade_in_place": False,
    "mixed": False,  # core c takes the c-th of the above, so an idle lane
    #                  lies between busy ones
}
GRID = [(m, c) for m in sorted(MACHINES) for c in sorted(CASES)
        if MACHINES[m]["local_run_len"] or not CASES[c]]


def machine(name: str):
    return small_test_config(n_cores=16, n_banks=8, l1=_L1, **MACHINES[name])


def scatter_oracle(cfg, l1, planes, masks, cols, vals):
    """numpy: the write as `_commit_writes` made it until PR 38,
    `l1.at[rows, cols].set(vals, mode="drop")` with `rows` the core's own
    or, masked, the dropped row C. Also what made its order free: words
    written twice carry one value."""
    C, FS = cfg.n_cores, cfg.l1.ways * cfg.l1.sets
    rows = np.where(masks, np.arange(C)[:, None], C)
    cols = cols + np.asarray(planes)[None, :] * FS
    keep = rows < C
    seen = {}
    for r, c, v in zip(rows[keep], cols[keep], vals[keep]):
        assert seen.setdefault((r, c), v) == v, (r, c, seen[(r, c)], v)
    out = l1.copy()
    out[rows[keep], cols[keep]] = vals[keep]
    return out


def lanes(cfg, case: str, rng):
    """Inputs of `_l1_writes` for C cores, every mask false, then each core
    shaped to `case`; and the words the case must leave: a list of
    (cores, plane, column in plane, value)."""
    C, S1, W1, W2 = cfg.n_cores, cfg.l1.sets, cfg.l1.ways, cfg.llc.ways
    rl = cfg.local_run_len
    l1s = rng.integers(0, S1, C).astype(np.int32)
    f = dict(
        l1s=l1s,
        line=(rng.integers(1, 1 << 16, C) * S1 + l1s).astype(np.int32),
        slot=rng.integers(0, cfg.n_banks * cfg.llc.sets, C).astype(np.int32),
        hit_way=rng.integers(0, W1, C).astype(np.int32),
        llc_hway=rng.integers(0, W2, C).astype(np.int32),
        llc_vway=rng.integers(0, W2, C).astype(np.int32),
        weff=rng.choice(np.array([S, E, M], np.int32), (C, W1)),
        # every way another line of the set, stamps distinct and old
        lru_rows=np.argsort(rng.random((C, W1)), axis=1).astype(np.int32) + 7,
        new_eph=rng.integers(0, 999, C).astype(np.int32),
        grant=np.full(C, S, np.int32),
        hm=np.zeros((C, rl), bool), wm=np.zeros((C, rl), bool),
        cm=rng.integers(0, W1 * S1, (C, rl)).astype(np.int32),
    )
    f["tag_rows"] = (f["line"][:, None]
                     + (1 + np.arange(W1, dtype=np.int32)) * S1).astype(np.int32)
    for name in ("hit", "winner", "join", "write_hit", "upg", "llc_hit",
                 "llc_miss", "write_w", "gets_excl_hit"):
        f[name] = np.zeros(C, bool)
    want = []
    names = sorted(k for k in CASES if k != "mixed" and (rl or not CASES[k]))
    for c in range(C):
        this = names[c % len(names)] if case == "mixed" else case
        at = lambda way: way * S1 + l1s[c]  # noqa: E731
        hw = f["hit_way"][c]
        if this == "refresh_on_run_stamp":
            f["hit"][c] = True
            f["tag_rows"][c, hw] = f["line"][c]
            f["hm"][c, 1], f["cm"][c, 1] = True, at(hw)
            want += [(c, LRU, at(hw), STEP_NO)]
        elif this in ("grant_over_run_m", "run_m_beside_grant"):
            # the run hit every way of the set (stamps equal: way 0 is the
            # victim) and wrote way 0, which the miss now refills Shared
            f["winner"][c] = f["llc_hit"][c] = True
            f["lru_rows"][c], f["weff"][c, 0] = STEP_NO, M
            way = 0 if this == "grant_over_run_m" else 3
            f["wm"][c, 0] = f["hm"][c, 0] = True
            f["cm"][c, 0] = at(way)
            want += [(c, STATE, at(0), S), (c, TAG, at(0), f["line"][c]),
                     (c, STATE, at(way), S if way == 0 else M),
                     (c, LRU, at(way), STEP_NO), (c, LRU, at(0), STEP_NO),
                     (c, PTR, at(0), f["slot"][c] * W2 + f["llc_hway"][c])]
        elif this == "fill_clears_stale_duplicate":
            # way 0 empty (the victim), way 2 a stale copy of the line
            f["winner"][c] = f["llc_miss"][c] = True
            f["grant"][c] = E
            f["weff"][c, [0, 2]] = I
            f["tag_rows"][c, 0], f["tag_rows"][c, 2] = -1, f["line"][c]
            want += [(c, TAG, at(2), -1), (c, STATE, at(2), I),
                     (c, TAG, at(0), f["line"][c]), (c, STATE, at(0), E),
                     (c, LRU, at(0), STEP_NO),
                     (c, PTR, at(0), f["slot"][c] * W2 + f["llc_vway"][c]),
                     (c, EPOCH, at(0), f["new_eph"][c])]
        elif this == "join":
            f["join"][c] = True
            v = int(np.argmin(f["lru_rows"][c]))
            want += [(c, TAG, at(v), f["line"][c]), (c, STATE, at(v), S),
                     (c, LRU, at(v), STEP_NO),
                     (c, PTR, at(v), f["slot"][c] * W2 + f["llc_hway"][c]),
                     (c, EPOCH, at(v), f["new_eph"][c])]
        elif this == "silent_e_to_m":
            f["hit"][c] = f["write_hit"][c] = True
            f["tag_rows"][c, hw], f["weff"][c, hw] = f["line"][c], E
            want += [(c, STATE, at(hw), M), (c, LRU, at(hw), STEP_NO)]
        elif this == "upgrade_in_place":
            f["winner"][c] = f["upg"][c] = f["write_w"][c] = True
            f["llc_hit"][c] = True
            f["tag_rows"][c, hw], f["weff"][c, hw] = f["line"][c], S
            f["grant"][c] = M
            want += [(c, STATE, at(hw), M), (c, TAG, at(hw), f["line"][c]),
                     (c, LRU, at(hw), STEP_NO)]
        else:
            assert this == "all_masks_false"
    return f, want


@functools.lru_cache(maxsize=None)
def program(name: str):
    """`_l1_writes` then `_l1_row_write` on arrays, jitted once a machine:
    -> the new L1 array, the writes stacked [C, K] (masks, columns, words;
    their static planes are left in `planes`), the writeback count."""
    cfg = machine(name)
    C = cfg.n_cores
    planes = []

    def run(l1, f):
        blank = lambda cls: cls(*(None,) * len(cls._fields))  # noqa: E731
        rq = blank(Request)._replace(**{k: f[k] for k in (
            "line", "l1s", "slot", "hit_way", "tag_rows", "lru_rows", "weff",
            "write_hit", "upg", "llc_hway")})
        dr = blank(DirOutcome)._replace(**{k: f[k] for k in (
            "llc_hit", "llc_miss", "write_w", "gets_excl_hit", "llc_vway")})
        acc = {}
        writes = _l1_writes(
            cfg, jnp.int32(STEP_NO), jnp.arange(C, dtype=jnp.int32), rq, dr,
            f["winner"], f["join"], f["grant"], f["hit"],
            (f["hm"], f["wm"], f["cm"]), f["new_eph"], acc)
        wide = lambda x, m: jnp.broadcast_to(x, m.shape).reshape(C, -1)  # noqa: E731
        planes[:] = [p for p, m, _, _ in writes for _ in range(m[0].size)]
        stacked = [jnp.concatenate([wide(w[i], w[1]) for w in writes], axis=1)
                   for i in (1, 2, 3)]
        return _l1_row_write(cfg, l1, writes), stacked, acc["l1_writebacks"]

    return cfg, jax.jit(run), planes


@pytest.mark.parametrize("devices", [1, 4])
@pytest.mark.parametrize("name,case", GRID)
def test_row_write_is_the_element_scatter(name, case, devices):
    """The select over the row against the scatter it replaced, to the
    word, on a random L1 array; the words the case is about; rows whose
    every mask is false untouched. On four devices the array lies by rows
    over the chips (four cores each) and comes back so."""
    cfg, run, planes = program(name)
    C, FS = cfg.n_cores, cfg.l1.ways * cfg.l1.sets
    rng = np.random.default_rng(38 + sorted(CASES).index(case))
    f, want = lanes(cfg, case, rng)
    l1 = rng.integers(-5, 1 << 20, (C, 5 * FS)).astype(np.int32)
    assert init_state(cfg).l1.shape == l1.shape
    table = jnp.asarray(l1)
    if devices > 1:
        table = jax.device_put(table, state_shardings(tile_mesh(devices)).l1)
    got, (masks, cols, vals), writebacks = run(
        table, {k: jnp.asarray(v) for k, v in f.items()})
    masks, cols, vals = (np.asarray(x) for x in (masks, cols, vals))
    assert masks.shape == (C, 7 + 2 * cfg.local_run_len) and len(planes) == masks.shape[1]
    assert sorted(set(planes)) == [TAG, STATE, LRU, PTR, EPOCH]
    assert got.sharding.shard_shape(got.shape) == (C // devices, 5 * FS)
    got = np.asarray(got)
    np.testing.assert_array_equal(
        got, scatter_oracle(cfg, l1, planes, masks, cols, vals))
    for c, plane, col, val in want:
        assert got[c, plane * FS + col] == val, (c, plane, col, val)
    idle = ~masks.any(axis=1)
    np.testing.assert_array_equal(got[idle], l1[idle])
    if case in ("all_masks_false", "mixed"):
        assert idle.any()
    if case != "all_masks_false":
        assert want and (got != l1).any()
    if case == "grant_over_run_m":
        # the run's E->M is suppressed under the grant, and the victim it
        # had made Modified is written back
        assert not masks[:, -cfg.local_run_len:].any()
        np.testing.assert_array_equal(np.asarray(writebacks), 1)


@pytest.mark.parametrize("name", ["plain", "coarse"])
def test_sharded_commit_keeps_every_l1_row_on_its_chip(name):
    """The compiled chunk of a machine whose cores lie over four devices:
    the L1 array is edited where it lies. No collective under `s.commit`
    has an operand of the L1 array's width, and none serves a `scatter`:
    as one element scatter the write had every core's 7 + 2*rl rows,
    columns and words all-gathered to every chip, which then dropped
    three quarters of them. What crosses chips under `s.commit` is the
    directory's (`scatter-add`: the row deltas) and a few `[C]` words."""
    from primesim_tpu.parallel.sharding import shard_events, shard_state
    from primesim_tpu.sim.engine import run_chunk
    from primesim_tpu.trace import synth

    cfg = machine(name)
    mesh = tile_mesh(4)
    tr = synth.false_sharing(cfg.n_cores, n_mem_ops=8, seed=38)
    events = shard_events(mesh, jnp.asarray(tr.line_events(cfg.line_bits)))
    st = shard_state(mesh, init_state(cfg))
    text = run_chunk.lower(cfg, 4, events, st, has_sync=False).compile().as_text()
    width = st.l1.shape[1]
    assert width != dirm_width(cfg)
    collective = re.compile(
        r" = (.*?) (?:all-reduce|all-gather|reduce-scatter|all-to-all"
        r"|collective-permute)(?:-start)?\(.*op_name=\"([^\"]*)\"")
    served, bad = set(), []
    for line in text.splitlines():
        found = collective.search(line)
        if not found or "/s.commit/" not in found.group(2):
            continue
        served.add(found.group(2).rsplit("/", 1)[1])
        if (re.search(rf"\[\d+,{width}\]", found.group(1))
                or found.group(2).endswith("/scatter")):
            bad.append(line.strip()[:200])
    assert "scatter-add" in served, served  # the walk sees the scope
    assert not bad, "\n".join(bad)
