"""Multi-chip sharding parity: the sharded engine must be bit-exact.

Runs the vectorized engine over the 8-virtual-device CPU mesh (conftest
forces ``xla_force_host_platform_device_count=8``) with cores/banks sharded
over the tile axis, and asserts cycle counts and every stat counter match
the single-device run and the golden scalar model. This is the
single-host stand-in for PriME's multi-node MPI runs (SURVEY.md §4d).
"""

import functools
import re

import jax
import numpy as np
import pytest

from primesim_tpu.config.machine import (
    CacheConfig,
    CoreConfig,
    MachineConfig,
    NocConfig,
    small_test_config,
)
from primesim_tpu.golden.sim import GoldenSim
from primesim_tpu.parallel.sharding import AXIS, tile_mesh
from primesim_tpu.sim.engine import Engine
from primesim_tpu.trace import synth


def _run_all(cfg, trace, mesh):
    g = GoldenSim(cfg, trace)
    g.run()
    e1 = Engine(cfg, trace, chunk_steps=64)
    e1.run()
    e8 = Engine(cfg, trace, chunk_steps=64, mesh=mesh)
    e8.run()
    return g, e1, e8


def test_eight_device_mesh_exists():
    assert len(jax.devices()) == 8


# the sharded parity matrix: machine x trace shape. `chunked` is rung 4's
# pair of selectors (a CPI a core, the full sharer map reduced in blocks:
# two words, blocks of one), `coarse` rung 5's (one sharer bit to four
# cores), `moesi` the protocol whose local run also counts a line's sharers.
# All with local runs, as every shipped machine has them: the run's
# directory rows are the one read the step places by hand on a mesh
# (`sharding.read_rows`)
MACHINES = {
    "plain": lambda: small_test_config(n_cores=16, n_banks=8, local_run_len=4),
    "chunked": lambda: small_test_config(
        n_cores=64, n_banks=16, sharer_chunk_words=1, local_run_len=4,
        core=CoreConfig(cpi_pattern=(1, 1, 3, 3), o3_overlap_256=64),
        noc=NocConfig(mesh_x=8, mesh_y=8, link_lat=1, router_lat=1),
    ),
    "coarse": lambda: small_test_config(
        n_cores=16, n_banks=8, sharer_group=4, local_run_len=4
    ),
    "moesi": lambda: small_test_config(
        n_cores=16, n_banks=8, coherence="moesi", local_run_len=4
    ),
}
GENERATORS = {
    "uniform_random": lambda n: synth.uniform_random(n, n_mem_ops=80, seed=7),
    "false_sharing": lambda n: synth.false_sharing(n, n_mem_ops=40, seed=3),
    "fft_like": lambda n: synth.fft_like(n, seed=5),
}


@pytest.mark.parametrize("gen", sorted(GENERATORS))
@pytest.mark.parametrize("machine", sorted(MACHINES))
def test_sharded_parity(machine, gen):
    cfg = MACHINES[machine]()
    trace = GENERATORS[gen](cfg.n_cores)
    mesh = tile_mesh(8)
    g, e1, e8 = _run_all(cfg, trace, mesh)
    np.testing.assert_array_equal(e8.cycles, g.cycles)
    np.testing.assert_array_equal(e8.cycles, e1.cycles)
    c_g, c_1, c_8 = g.counters, e1.counters, e8.counters
    for k in c_g:
        np.testing.assert_array_equal(c_8[k], c_g[k], err_msg=k)
        np.testing.assert_array_equal(c_8[k], c_1[k], err_msg=k)


def test_sharded_parity_with_barriers():
    """A `has_sync` machine on the mesh: rung 3's selectors (router walk,
    DRAM queue, O3 window, local runs) at 16 cores, sharded over four
    devices, on `ocean_like`: the lock table and the barrier slots are
    replicated, the lanes that read and write them are sharded by core,
    and every arrival rides the first leg of the link walk. No cell of the
    benchmark shards a program with sync events: this test is the guard."""
    from primesim_tpu.trace.format import EV_BARRIER, EV_LOCK, fold_ins

    cfg = small_test_config(
        n_cores=16, n_banks=8, local_run_len=4, dram_queue=True,
        core=CoreConfig(o3_overlap_256=128),
        noc=NocConfig(mesh_x=4, mesh_y=4, link_lat=1, router_lat=1, contention=True,
                      contention_model="router", contention_lat=1),
    )
    trace = fold_ins(synth.ocean_like(16, seed=5, grid_n=34, levels=2, visits=2,
                                      lock_reductions=1))
    g, e1, e4 = _run_all(cfg, trace, tile_mesh(4))
    assert e4.has_sync and len(e4.state.cycles.devices()) == 4
    np.testing.assert_array_equal(e4.cycles, g.cycles)
    np.testing.assert_array_equal(e4.cycles, e1.cycles)
    for k in g.counters:
        np.testing.assert_array_equal(e4.counters[k], g.counters[k], err_msg=k)
        np.testing.assert_array_equal(e4.counters[k], e1.counters[k], err_msg=k)
    t = trace.events[:, :, 0]
    assert int(g.counters["barrier_waits"].sum()) == int((t == EV_BARRIER).sum()) == 11 * 16
    assert int(g.counters["lock_acquires"].sum()) == int((t == EV_LOCK).sum()) == 16


def test_sharded_parity_in_the_retry_regime():
    """`ycsb_like` on rung 3's machine cut to 64 cores, sharded over four
    devices, in the regime `retry_regime.py` asserts (over a fifth of the
    requests retried, join-eligible reads demoted and beaten by writers):
    the arbitration table's minima and the demotion's view of the step's
    arbitrating (bank, set)s are reduced over the chips. The one-chip cell
    `rung3.ycsb-a` runs this load unsharded; this test is the sharded guard."""
    from retry_regime import golden_in_the_regime

    machine, trace, _ = golden_in_the_regime(64, True)
    g, e1, e4 = _run_all(MachineConfig.from_dict(machine), trace, tile_mesh(4))
    assert not e4.has_sync and len(e4.state.cycles.devices()) == 4
    np.testing.assert_array_equal(e4.cycles, g.cycles)
    np.testing.assert_array_equal(e4.cycles, e1.cycles)
    for k in g.counters:
        np.testing.assert_array_equal(e4.counters[k], g.counters[k], err_msg=k)
        np.testing.assert_array_equal(e4.counters[k], e1.counters[k], err_msg=k)


def _run_record_oracle(cfg, dirm, pslot, pline):
    """numpy: `dirm[pslot]` and what the local run reads of those rows
    (`sim/step.py::_run_record`), an element gather a field."""
    from primesim_tpu.sim.state import llc_meta_width

    C, K = pline.shape
    W2, NW, MW = cfg.llc.ways, cfg.n_sharer_words, llc_meta_width(cfg)
    rows = dirm[pslot]
    meta = rows[:, :, : 2 * W2].reshape(C, K, W2, 2)
    match = meta[..., 0] == pline[:, :, None]
    way = match.argmax(2)
    pick = lambda x, i: np.take_along_axis(x, i[..., None], 2)[..., 0]  # noqa: E731
    g = (np.arange(C) >> (cfg.sharer_group.bit_length() - 1))[:, None]
    word = pick(rows[:, :, MW:], way * NW + (g >> 5))
    out = [match.any(2), pick(meta[..., 1], way), ((word >> (g & 31)) & 1) != 0]
    if cfg.sharer_group > 1:
        out.append(pick(rows[:, :, 3 * W2 : 4 * W2], way))
    if cfg.coherence == "moesi":
        words = np.stack(
            [pick(rows[:, :, MW:], way * NW + w) for w in range(NW)], axis=2)
        out.append(np.bitwise_count(words.view(np.uint32)).sum(2).astype(np.int32))
    return out


@pytest.mark.parametrize("devices", [1, 4, 8])
@pytest.mark.parametrize("machine", ["plain", "chunked", "coarse", "moesi"])
def test_run_record_on_the_rows_own_chip(machine, devices):
    """`read_rows` + `_run_record` alone, with and without a mesh, against
    the numpy oracle on a random directory: `read_rows` has one order,
    `[K, C]` slots, and the record of `[K, C, DW]` rows is the oracle's
    `[C, K]` record transposed, field by field (under moesi the sharer
    count too). Core 0's candidates ask for line 0 in a slot of the LAST
    shard whose row holds other tags, while every other shard's row at
    the clamped index is all zeros: a chip that let such a row answer
    (masking the row and not the record) would find a tag 0 there and
    report a hit."""
    import functools

    import jax.numpy as jnp

    from primesim_tpu.parallel.sharding import read_rows, state_shardings
    from primesim_tpu.sim.state import dirm_width
    from primesim_tpu.sim.step import _run_record

    cfg = MACHINES[machine]()
    C, K, W2 = cfg.n_cores, cfg.local_run_len + 1, cfg.llc.ways
    R, DW = cfg.n_banks * cfg.llc.sets, dirm_width(cfg)
    rng = np.random.default_rng(34)
    dirm = rng.integers(0, 2**31 - 1, (R, DW), dtype=np.int32)
    pslot = rng.integers(0, R, (C, K), dtype=np.int32)
    # half the candidates ask for a tag their row holds, half for another
    held = dirm[pslot][:, :, 0 : 2 * W2 : 2]
    way = rng.integers(0, W2, (C, K))
    pline = np.where(
        rng.random((C, K)) < 0.5,
        np.take_along_axis(held, way[..., None], 2)[..., 0],
        rng.integers(0, 2**31 - 1, (C, K), dtype=np.int32),
    ).astype(np.int32)
    per = R // 8  # a shard of eight; every second one ends a shard of four
    dirm[per - 1 :: per] = 0  # the row every chip reads past its own shard
    dirm[0::per] = 0  # ... and before it
    dirm[R - 2, 0 : 2 * W2 : 2] = np.arange(1, W2 + 1)  # tags, none of them 0
    pslot[0], pline[0] = R - 2, 0
    want = _run_record_oracle(cfg, dirm, pslot, pline)
    assert len(want) == 3 + (machine in ("coarse", "moesi"))
    assert not want[0][0].any()  # line 0 is in no way of its home row

    mesh = tile_mesh(devices) if devices > 1 else None
    table = jnp.asarray(dirm)
    if mesh is not None:
        table = jax.device_put(table, state_shardings(mesh).dirm)
    reduce_rows = functools.partial(_run_record, cfg)
    core = jnp.arange(C, dtype=jnp.int32)
    got = jax.jit(lambda t, sl, ln: read_rows(
        mesh, t, sl, reduce_rows, per_slot=(ln,), whole=(core,)
    ))(table, jnp.asarray(pslot.T), jnp.asarray(pline.T))
    assert len(got) == len(want)
    for i, (g, w) in enumerate(zip(got, want)):
        assert g.shape == (K, C)
        assert g.dtype == (jnp.bool_ if w.dtype == bool else jnp.int32)
        np.testing.assert_array_equal(np.asarray(g).T, w, err_msg=f"field {i}")


def _validate_ways_oracle(cfg, dirm, core, tag_rows, state_rows, ptr_rows,
                          eph_rows):
    """numpy: the record `_way_record` reads and `_validate_ways`' effective
    states as the step computed them until PR 36, an element gather of
    `dirm` a field at the rows and ways the pointers `ptr_rows` [C, W1]
    name."""
    from primesim_tpu.sim.state import I, S, llc_meta_width

    W2, NW, MW = cfg.llc.ways, cfg.n_sharer_words, llc_meta_width(cfg)
    g_c = (core >> (cfg.sharer_group.bit_length() - 1))[:, None]
    pway, pslot = ptr_rows % W2, ptr_rows // W2
    vtag = dirm[pslot, 2 * pway]
    vown = dirm[pslot, 2 * pway + 1]
    vsh = dirm[pslot, MW + pway * NW + (g_c >> 5)]
    vbit = ((vsh >> (g_c & 31)) & 1) != 0
    record = [vtag, vown, vbit]
    if cfg.sharer_group > 1:
        veph = dirm[pslot, 3 * W2 + pway]
        record.append(veph)
        vbit = vbit & (veph == eph_rows)
    weff = np.where(
        (state_rows == I) | (vtag != tag_rows), I,
        np.where(vown == core[:, None], state_rows, np.where(vbit, S, I)))
    return record, weff


# what the entry a way pointer names holds -> the state the way validates to
# ("own": what the core wrote itself)
WAY_CASES = {
    "owner": "own",  # the directory names the core owner: no epoch asked
    "sharer_bit_set": "S",
    "sharer_bit_clear": "I",
    # the coarse vector: the group's bit stands for a neighbour, the entry's
    # epoch has moved on since the core's fill; a full map: the neighbour's
    # bit beside the core's own, which is clear
    "neighbours_bit": "I",
    "stale_pointer": "I",  # the way was refilled: another tag
    # the core holds line 0 and its pointer names an all-zero row (tag 0,
    # owner 0: core 0 alone keeps its state), while the rows every other
    # chip reads at its clamped index hold words
    "line0_zero_row": "own0",
}


@pytest.mark.parametrize("devices", [1, 4])
@pytest.mark.parametrize("case", sorted(WAY_CASES))
@pytest.mark.parametrize("machine", ["plain", "coarse", "moesi"])
def test_validate_ways_reads_rows_like_elements(machine, case, devices):
    """`_validate_ways`' one row read (`read_rows` + `_way_record`, the
    words picked out of the row in hand) against the element gathers it
    replaced, on a random directory in which every (core, way) entry is
    shaped to `case`: the record to the bit, the effective states, and the
    state the case must give. On four devices the pointers name rows of
    every chip and each chip's first and last row (what the others read at
    their clamped index) are never zero."""
    import functools

    import jax.numpy as jnp

    from primesim_tpu.parallel.sharding import read_rows, state_shardings
    from primesim_tpu.sim.state import E, I, M, S, dirm_width, llc_meta_width
    from primesim_tpu.sim.step import _validate_ways, _way_record

    cfg = MACHINES[machine]()
    C, W1, W2, NW = cfg.n_cores, cfg.l1.ways, cfg.llc.ways, cfg.n_sharer_words
    R, DW, MW = cfg.n_banks * cfg.llc.sets, dirm_width(cfg), llc_meta_width(cfg)
    G = cfg.sharer_group
    rng = np.random.default_rng(36 + sorted(WAY_CASES).index(case))
    dirm = rng.integers(1, 2**31 - 1, (R, DW), dtype=np.int32)
    core = np.arange(C, dtype=np.int32)
    # distinct rows, none a shard's first or last, some in every quarter
    per = R // 4
    seeded = [q * per + 1 for q in range(4)]
    inner = np.setdiff1d(
        np.arange(R), [0, *range(per - 1, R, per), *range(per, R, per), *seeded])
    pslot = rng.permutation(inner)[: C * W1].reshape(C, W1).astype(np.int32)
    pslot[:4, 0] = seeded
    assert len(np.unique(pslot)) == C * W1
    assert set(np.unique(pslot // per)) == {0, 1, 2, 3}
    pway = rng.integers(0, W2, (C, W1), dtype=np.int32)
    ptr_rows = pslot * W2 + pway
    state_rows = rng.choice(np.array([S, E, M], np.int32), (C, W1))
    state_rows[:, -1] = I  # a way the core does not hold stays I
    tag_rows = dirm[pslot, 2 * pway].copy()
    eph_rows = dirm[pslot, 3 * W2 + pway].copy() if G > 1 else None
    g = (core // G)[:, None]
    word = MW + pway * NW + (g >> 5)
    bit = (np.int32(1) << (g & 31)).astype(np.int32)
    other = (core[:, None] + 1) % C
    if case == "owner":
        dirm[pslot, 2 * pway + 1] = core[:, None]
        dirm[pslot, word] &= ~bit  # nor is the bit asked
    else:
        dirm[pslot, 2 * pway + 1] = other
    if case == "sharer_bit_set":
        dirm[pslot, word] |= bit
    elif case in ("sharer_bit_clear", "stale_pointer"):
        dirm[pslot, word] &= ~bit
    elif case == "neighbours_bit" and G > 1:
        dirm[pslot, word] |= bit
        dirm[pslot, 3 * W2 + pway] += 1  # a clearing since the fill
    elif case == "neighbours_bit":
        ng = (other // G)
        dirm[pslot, MW + pway * NW + (ng >> 5)] |= np.int32(1) << (ng & 31)
        dirm[pslot, word] &= ~bit
    if case == "stale_pointer":
        tag_rows = tag_rows + 1
    if case == "line0_zero_row":
        dirm[pslot] = 0  # tag 0, owner 0, no sharer, epoch 0
        tag_rows[:] = 0
        if G > 1:
            eph_rows[:] = 0
    record, weff = _validate_ways_oracle(
        cfg, dirm, core, tag_rows, state_rows, ptr_rows, eph_rows)
    want = {"own": state_rows, "S": np.full_like(state_rows, S),
            "I": np.full_like(state_rows, I),
            "own0": np.where(core[:, None] == 0, state_rows, I),
            }[WAY_CASES[case]]
    want = np.where(state_rows == I, I, want)
    np.testing.assert_array_equal(weff, want)

    mesh = tile_mesh(devices) if devices > 1 else None
    table = jnp.asarray(dirm)
    if mesh is not None:
        table = jax.device_put(table, state_shardings(mesh).dirm)
    args = (tag_rows, state_rows, ptr_rows) + ((eph_rows,) if G > 1 else ())

    def both(table, tag_rows, state_rows, ptr_rows, eph_rows=None):
        rec = read_rows(
            mesh, table, ptr_rows.T // W2, functools.partial(_way_record, cfg),
            per_slot=(ptr_rows.T % W2,), whole=(jnp.asarray(core),))
        return rec, _validate_ways(
            cfg, jnp.asarray(core), tag_rows, state_rows, ptr_rows, eph_rows,
            table, mesh)

    got_rec, got = jax.jit(both)(table, *map(jnp.asarray, args))
    np.testing.assert_array_equal(np.asarray(got), weff)
    assert len(got_rec) == len(record) == (4 if G > 1 else 3)
    for i, (g_, w_) in enumerate(zip(got_rec, record)):
        assert g_.shape == (W1, C)  # `read_rows`' one order, on one device too
        assert g_.dtype == (jnp.bool_ if w_.dtype == bool else jnp.int32)
        np.testing.assert_array_equal(np.asarray(g_).T, w_, err_msg=f"field {i}")


def _join_cases():
    """(join, entry, key, n) of each case of the join table's seam, C = 64
    lanes, four chips' quarters of `n` entries. Keys are distinct, as the
    step's are ((clock, core) packed), and wherever lanes join an entry a
    lane that does not join it holds a lesser key there."""
    C = 64
    lane = np.arange(C)

    def keys(seed):
        return np.random.default_rng(seed).permutation(C * 8)[:C].astype(np.int32)

    n = 2048
    per = n // 4
    cases = {}
    cases["no_lane_joins"] = (
        np.zeros(C, bool), (lane * 37 % n).astype(np.int32), keys(1), n)
    cases["every_lane_joins_one_entry"] = (
        np.ones(C, bool), np.full(C, 2 * per + 3, np.int32), keys(2), n)
    # every chip holds four entries, of two, three, four and five lanes
    entry = np.concatenate([
        np.repeat(q * per + np.array([5, 130, 131, per - 7]), [2, 3, 4, 5])
        for q in range(4)
    ] + [np.array([5, per + 130, 2 * per + 131, 4 * per - 7] * 2)])
    key = keys(3)
    key[-8:] = -1 - np.arange(8)  # the lanes that do not join: least of all
    cases["two_to_five_lanes_an_entry_on_every_chip"] = (
        lane < 56, entry.astype(np.int32), key, n)
    # each quarter's first and last entry: two joiners and a lane that is none
    edge = np.array([[q * per, (q + 1) * per - 1] for q in range(4)]).ravel()
    key = keys(4)
    key[16:24] = -1 - np.arange(8)
    cases["each_quarters_first_and_last_entry"] = (
        lane < 16, np.tile(edge, 8).astype(np.int32), key, n)
    rng = np.random.default_rng(5)
    ragged = 4 * 200  # a quarter of 200 entries: its last row of 128 is padded
    entry = rng.integers(0, ragged, C)
    entry[:8] = [0, 199, 200, 399, 400, 599, 600, 799]
    entry[8:16] = entry[:8]
    cases["a_quarter_no_multiple_of_128"] = (
        rng.random(C) < 0.8, entry.astype(np.int32), keys(6), ragged)
    rng = np.random.default_rng(7)
    cases["under_vmap"] = (
        rng.random((2, C)) < 0.7, rng.integers(0, 96, (2, C)).astype(np.int32) * 21,
        np.stack([keys(8), keys(9)]), n)
    return cases


JOIN_CASES = _join_cases()


def _least_of_entry_oracle(join, entry, key):
    """Lane by lane: a joiner whose key no other joiner of its entry beats."""
    return np.array([
        bool(join[c]) and key[c] == min(
            key[d] for d in range(len(key)) if join[d] and entry[d] == entry[c])
        for c in range(len(key))
    ])


@pytest.mark.parametrize("devices", [1, 4])
@pytest.mark.parametrize("case", list(JOIN_CASES))
def test_least_of_entry_is_the_join_table_on_the_entrys_own_chip(case, devices):
    """`sharding.least_of_entry` against the plain expression it is without
    a mesh, `_join_representative` on the whole arrays, and against the
    lanes counted one by one: the `[C]` answer to the bit. On four devices
    the lanes arrive sharded by core and each chip holds a quarter of the
    table."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from primesim_tpu.parallel.sharding import least_of_entry
    from primesim_tpu.sim.step import _join_representative

    join, entry, key, n = JOIN_CASES[case]
    batched = join.ndim == 2
    mesh = tile_mesh(devices) if devices > 1 else None
    assert (n // 4) % 128 == (72 if case == "a_quarter_no_multiple_of_128" else 0)

    def seam(join, entry, key):
        return least_of_entry(mesh, _join_representative, join, entry, key, n)

    def plain(join, entry, key):
        return _join_representative(join, entry, key, n)

    args = [jnp.asarray(a) for a in (join, entry, key)]
    want = (jax.vmap(plain) if batched else plain)(*args)
    if mesh is not None:
        lanes = NamedSharding(mesh, P(None, AXIS) if batched else P(AXIS))
        args = [jax.device_put(a, lanes) for a in args]
    got = jax.jit(jax.vmap(seam) if batched else seam)(*args)
    assert got.dtype == jnp.bool_ and got.shape == join.shape
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    rows = list(zip(join, entry, key)) if batched else [(join, entry, key)]
    oracle = np.array([_least_of_entry_oracle(*row) for row in rows])
    np.testing.assert_array_equal(np.asarray(got), oracle.reshape(join.shape))
    # one lane an entry that is joined; but for the first two cases, some
    # such entries on every chip
    joined = [np.unique(e[j]) for j, e, _ in rows]
    assert oracle.sum() == sum(map(len, joined))
    if case == "no_lane_joins":
        assert not oracle.any()
    elif case == "every_lane_joins_one_entry":
        assert oracle.sum() == 1
    else:
        assert all(set(e // (n // 4)) == {0, 1, 2, 3} for e in joined)


@pytest.mark.parametrize("engine", ["fleet", "stream"])
def test_sharded_local_runs_under_vmap_and_windows(engine):
    """The run's row read is a `shard_map`: it has to compose with the
    fleet's `vmap` of `step` and with the windowed loop, bit for bit. A
    fleet hands `step` its mesh only where it is ONE machine cut over the
    chips (`sharding.fleet_is_cut`: the pool's unit); a fleet of more lies
    with its machines whole (tests/test_fleet_on_chips.py)."""
    cfg = small_test_config(16, n_banks=8, quantum=200, local_run_len=4)
    if engine == "fleet":
        from primesim_tpu.sim.fleet import FleetEngine

        traces = [synth.fft_like(16, n_phases=2, points_per_core=8, seed=14)]
        ovs = [{"dram_lat": 140}]
        make = lambda mesh: FleetEngine(  # noqa: E731
            cfg, traces, ovs, chunk_steps=32, mesh=mesh
        )
    else:
        from primesim_tpu.ingest.stream import StreamEngine

        tr = synth.fft_like(16, n_phases=2, points_per_core=12, seed=31)
        make = lambda mesh: StreamEngine(  # noqa: E731
            cfg, tr, window_events=32, mesh=mesh
        )
    plain, sharded = make(None), make(tile_mesh(8))
    for e in (plain, sharded):
        e.run()
    np.testing.assert_array_equal(sharded.cycles, plain.cycles)
    assert int(plain.counters["l1_read_hits"].sum()) > 0  # runs did retire
    for k, v in plain.counters.items():
        np.testing.assert_array_equal(sharded.counters[k], v, err_msg=k)


def test_device_loops_key_on_the_mesh_of_their_arguments():
    """`run_loop` is told no mesh: `mesh_jit` reads it off the state's
    layout. One program without a mesh, one a mesh, and a direct call on
    an engine's arrays (the benchmark's warm-up) is the engine's program."""
    import jax.numpy as jnp

    from primesim_tpu.parallel.sharding import mesh_of
    from primesim_tpu.sim.engine import run_loop

    cfg = small_test_config(n_cores=16, n_banks=8, local_run_len=2)
    trace = synth.stream(16)
    n0 = run_loop._cache_size()
    engines = [
        Engine(cfg, trace, chunk_steps=8, mesh=mesh)
        for mesh in (None, tile_mesh(8), tile_mesh(4))
    ]
    assert [mesh_of(e.events, e.state) for e in engines] == [
        e.mesh for e in engines
    ]
    for i, e in enumerate(engines):
        e.run()
        assert run_loop._cache_size() == n0 + i + 1
    for e in engines:  # fresh arrays of the same layouts: nothing compiles
        again = Engine(cfg, trace, chunk_steps=8, mesh=e.mesh)
        handed = again.state  # the loop owns it (PR 54): `again` has none to read now
        out = run_loop(cfg, 8, again.events, handed, jnp.asarray(1, jnp.int32),
                       has_sync=again.has_sync)
        assert handed.dirm.is_deleted() and not out[0].dirm.is_deleted()
        assert mesh_of(out[0]) == e.mesh
    assert run_loop._cache_size() == n0 + 3
    np.testing.assert_array_equal(engines[1].cycles, engines[0].cycles)
    np.testing.assert_array_equal(engines[2].cycles, engines[0].cycles)


def test_state_is_actually_sharded():
    cfg = small_test_config(n_cores=16, n_banks=8)
    trace = synth.stream(16)
    mesh = tile_mesh(8)
    e = Engine(cfg, trace, mesh=mesh)
    shardings = {
        "cycles": e.state.cycles.sharding,
        "dirm": e.state.dirm.sharding,
        "events": e.events.sharding,
    }
    for name, s in shardings.items():
        spec = s.spec
        assert spec and spec[0] == AXIS, (name, spec)
    # born sharded: after `Engine.__init__` alone no device holds a shard of
    # the directory or of the L1s with the array's full leading dimension
    for name in ("dirm", "l1"):
        whole = getattr(e.state, name)
        shards = whole.addressable_shards
        assert len(shards) == 8 and len({s.device for s in shards}) == 8
        assert all(s.data.shape[0] == whole.shape[0] // 8 for s in shards), name
    # and it still runs to completion sharded
    e.run()
    g = GoldenSim(cfg, trace)
    g.run()
    np.testing.assert_array_equal(e.cycles, g.cycles)


def test_state_builder_never_holds_an_unsharded_directory():
    """The program that builds a sharded machine's state
    (`parallel/sharding.py::build_state`) has, in its compiled text for one
    device of eight, no array with the directory's or the L1s' full leading
    dimension: each device fills its own shard and nothing else. (`Engine`
    used to build the whole state on device 0 and shard it afterwards:
    rung 4's 9.66 GB directory then fits no chip.)"""
    from primesim_tpu.parallel.sharding import _state_builder
    from primesim_tpu.sim.state import dirm_width

    cfg = MachineConfig(
        n_cores=256, n_banks=256,
        # an L1 of 1.3 MB over the cores: XLA folds a smaller one into a
        # literal that every device slices
        l1=CacheConfig(size=16384, ways=2, line=64, latency=2),
        llc=CacheConfig(size=4096, ways=4, line=64, latency=12),
        noc=NocConfig(mesh_x=16, mesh_y=16),
        quantum=600,
    )
    txt = _state_builder(tile_mesh(8)).lower(cfg).compile().as_text()
    rows, width = cfg.n_banks * cfg.llc.sets, dirm_width(cfg)
    assert f"s32[{rows // 8},{width}]" in txt  # the shard is there
    assert f"[{rows}," not in txt  # the whole directory is not
    l1_width = 5 * cfg.l1.ways * cfg.l1.sets
    assert f"s32[{cfg.n_cores // 8},{l1_width}]" in txt
    assert f"s32[{cfg.n_cores},{l1_width}]" not in txt


def test_global_tile_mesh_single_process():
    # parallel.distributed: in a single-process job the global mesh equals
    # the local-device mesh and the engine runs bit-exact on it (multi-host
    # behavior is XLA's SPMD contract over the same code path)
    from primesim_tpu.parallel.distributed import (
        global_tile_mesh,
        process_info,
    )

    info = process_info()
    assert info["process_count"] == 1 and info["global_devices"] == 8
    mesh = global_tile_mesh()
    cfg = small_test_config(8, n_banks=8)
    tr = synth.readers_writer(8, n_rounds=2, seed=92)
    e = Engine(cfg, tr, chunk_steps=16, mesh=mesh)
    e.run()
    g = GoldenSim(cfg, tr)
    g.run()
    np.testing.assert_array_equal(e.cycles, g.cycles)


def test_sharded_parity_256core():
    # VERDICT r4 #7: multi-chip correctness beyond toy shapes — 256 cores
    # / 256 banks sharded over all 8 devices, bit-exact vs the golden
    # scalar model (and transitively vs the unsharded engine, proven by
    # the other parity suites on the same generators)
    cfg = MachineConfig(
        n_cores=256, n_banks=256,
        l1=CacheConfig(size=1024, ways=2, line=64, latency=2),
        llc=CacheConfig(size=4096, ways=4, line=64, latency=12),
        noc=NocConfig(mesh_x=16, mesh_y=16),
        quantum=600,
    )
    tr = synth.readers_writer(256, n_rounds=2, block_lines=4, seed=93)
    e = Engine(cfg, tr, chunk_steps=64, mesh=tile_mesh(8))
    e.run()
    g = GoldenSim(cfg, tr)
    g.run()
    np.testing.assert_array_equal(e.cycles, g.cycles)
    ec = e.counters
    for k, v in g.counters.items():
        np.testing.assert_array_equal(ec[k], v, err_msg=k)


_COLLECTIVE = re.compile(
    r" = (.*?) (?:all-reduce|all-gather|reduce-scatter|all-to-all"
    r"|collective-permute)(?:-start)?\("
)


def _collectives(txt, scope=""):
    """(line, its operands' shapes) of every collective of a compiled text
    whose line names `scope`."""
    for line in txt.splitlines():
        found = _COLLECTIVE.search(line)
        if found and scope in line:
            yield line, [
                [int(d) for d in dims.split(",") if d]
                for dims in re.findall(r"\w+\[([\d,]*)\]", found.group(1))
            ]


@functools.lru_cache(maxsize=None)
def _sharded_chunk_text(**machine):
    """A 256-core machine and the compiled text of its `run_chunk` sharded
    over the eight devices: what the partitioner (and `read_rows`) made of
    the step. Compiled once a machine: two tests read each text."""
    import jax.numpy as jnp

    from primesim_tpu.parallel.sharding import shard_events, shard_state
    from primesim_tpu.sim.engine import run_chunk
    from primesim_tpu.sim.state import init_state

    cfg = MachineConfig(
        n_cores=256, n_banks=256,
        l1=CacheConfig(size=1024, ways=2, line=64, latency=2),
        llc=CacheConfig(size=4096, ways=4, line=64, latency=12),
        noc=NocConfig(mesh_x=16, mesh_y=16),
        quantum=600, **machine,
    )
    tr = synth.false_sharing(256, n_mem_ops=8, seed=94)
    mesh = tile_mesh(8)
    events = shard_events(mesh, jnp.asarray(tr.line_events(cfg.line_bits)))
    st = shard_state(mesh, init_state(cfg))
    return cfg, run_chunk.lower(
        cfg, 4, events, st, has_sync=False
    ).compile().as_text()


def test_sharded_step_never_allgathers_directory():
    # the round-2 regression's failure mode: a layout/sharding slip that
    # makes XLA materialize the FULL sharers/llc_meta array on every
    # device each step. Compile the sharded chunk and assert no
    # all-gather/all-reduce touches a directory-shaped operand.
    cfg, txt = _sharded_chunk_text()
    B_S2 = cfg.n_banks * cfg.llc.sets  # full (unsharded) leading dim
    bad = [
        l
        for l in txt.splitlines()
        if re.search(r"all-gather|all-reduce", l) and f"[{B_S2}," in l
    ]
    assert not bad, "directory arrays all-gathered:\n" + "\n".join(bad[:5])


def test_sharded_probe_sends_way_records_not_way_rows():
    """The probe reads 3 words of each of the C*W1 directory rows its way
    pointers name (`_validate_ways`, through `sharding.read_rows`), and the
    C home rows whole. Held on the compiled sharded chunk: no collective
    under `s.probe` carries more than C rows of `dirm` width (the home
    rows may cross; nothing of `[W1*C, DW]`), and the way read's two
    collectives are there, slots out and records back."""
    from primesim_tpu.sim.state import dirm_width

    cfg, txt = _sharded_chunk_text(local_run_len=4)
    C, W1, DW = cfg.n_cores, cfg.l1.ways, dirm_width(cfg)
    wide, shapes = [], []
    for line, operands in _collectives(txt, "s.probe"):
        for shape in operands:
            shapes.append(shape)
            if shape and shape[-1] == DW and np.prod(shape[:-1]) > C:
                wide.append(line.strip()[:200])
    assert W1 > 1 and not wide, "way rows cross chips whole:\n" + "\n".join(wide)
    # ways first: (slot, way) out, (tag, owner, bit) back
    assert [W1, C, 2] in shapes and [W1, C, 3] in shapes, shapes


def test_sharded_local_run_sends_records_not_rows():
    """The local run reads 17 words of each of its C*(rl+1) candidate rows.
    Left to the partitioner, `st.dirm[pslot]` all-reduces the whole rows
    (PERF.md section 6, PR 34: two fifths of rung 4's step); through
    `sharding.read_rows` each chip reduces its own rows and a few words a
    candidate cross chips. Held on the compiled sharded chunk: no
    collective under `s.local` has an operand as wide as a `dirm` row, and
    all of them together carry O(C*(rl+1)) words."""
    from primesim_tpu.sim.state import dirm_width

    cfg, txt = _sharded_chunk_text(local_run_len=4)
    words, wide = 0, []
    for line, operands in _collectives(txt, "s.local"):
        for shape in operands:
            words += int(np.prod(shape))
            if shape and shape[-1] == dirm_width(cfg):
                wide.append(line.strip()[:200])
    assert not wide, "whole directory rows cross chips:\n" + "\n".join(wide)
    # slots and lines out (2 words a candidate), records back (3); the
    # candidates' event records cross no chip: `DeviceTrace.window`'s
    # gather is batched over the cores, which the trace is sharded by
    candidates = cfg.n_cores * (cfg.local_run_len + 1)
    assert 0 < words <= 6 * candidates, (words, candidates)


def test_sharded_commit_sends_join_keys_not_the_table():
    """Phase 4.A's join table has one word a directory entry (B*S2*W2) and
    C lanes scatter into it. Left to the partitioner every chip fills a
    whole table and the table is all-reduced, every step (PERF.md section
    6, PR 49: 64 MB of rung 4's step); through `sharding.least_of_entry`
    each chip holds the entries of its own banks and the lanes' words
    cross. Held on the compiled sharded chunk: no collective anywhere has
    an operand of B*S2*W2 words, in any shape, and the seam's collectives
    under `s.commit` carry at most 4 x C words together (3 a lane out, 1
    back)."""
    cfg, txt = _sharded_chunk_text()
    table = cfg.n_banks * cfg.llc.sets * cfg.llc.ways
    assert table > 4 * cfg.n_cores
    whole, words = [], 0
    for line, operands in _collectives(txt):
        for shape in operands:
            if np.prod(shape) == table:
                whole.append(line.strip()[:200])
            if "s.commit" in line and "shard_map" in line:
                words += int(np.prod(shape))
    assert not whole, "the join table crosses chips whole:\n" + "\n".join(whole)
    assert 0 < words <= 4 * cfg.n_cores, (words, cfg.n_cores)
