"""Property tests for the sort-based segmented-rank primitive
(ops/ranking.py, ISSUE 6 tentpole): on every input shape the engine can
produce — duplicate arbitration keys, masked lanes/slots, real mesh XY
paths (including faulted-config geometries, whose ranking walk stays on
the NOMINAL path by design), and fleet-vmapped batches — the sort path
must return the EXACT int32 counts of the historical one-hot-matmul
path it replaced (DESIGN.md §13 equivalence argument)."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from primesim_tpu.config.machine import (
    FAULT_LINK_FAIL,
    NocConfig,
    small_test_config,
)
from primesim_tpu.noc.mesh import n_links, path_links
from primesim_tpu.ops import ranking
from primesim_tpu.ops.ranking import (
    lane_order,
    segmented_rank,
    segmented_rank_floor,
    segmented_table_max,
)

INT32_MAX = np.iinfo(np.int32).max
CLOCK_LO = -(1 << 30)  # where engine.py's rebase clamps a link's clock


def matmul_oracle(seg, key, n_seg, competitor=None):
    """The replaced path, reference-shaped: [C,C] strict-less comparison
    contracted against the [C,n_seg] one-hot membership (duplicates in a
    lane's row collapse via `set(1)`), gathered back per slot."""
    seg = np.asarray(seg)
    key = np.asarray(key)
    C, S = seg.shape
    comp = np.ones(C, bool) if competitor is None else np.asarray(competitor)
    kless = (key[None, :] < key[:, None]) & comp[None, :]
    U = np.zeros((C, n_seg + 1), np.int32)
    U[np.arange(C)[:, None], np.clip(seg, 0, n_seg)] = 1
    # float32 BLAS product: exact, the counts stay under 2^24
    ranks = (kless.astype(np.float32) @ U.astype(np.float32)).astype(np.int32)
    out = np.take_along_axis(ranks, np.clip(seg, 0, n_seg), axis=1)
    return out  # valid wherever seg < n_seg


def link_oracle(seg, val, table, dep):
    """The element form the sorted passes replaced, in numpy: a segment's
    floor is the larger of its table word and the least `val` of its
    entries (`minimum.at`, then a lookup per entry), and the table is
    raised to the largest `dep` of its entries (`maximum.at`)."""
    n_seg = table.shape[0]
    low = np.full(n_seg + 1, INT32_MAX, np.int32)
    np.minimum.at(low, seg.ravel(), val.ravel())
    floor = np.maximum(np.append(table, 0), low)[seg]
    raised = np.append(table, 0)
    np.maximum.at(raised, seg.ravel(), dep.ravel())
    return floor, raised[:n_seg]


def _clocks(rng, shape):
    """int32 values over the whole range a clock takes: down to the
    rebase clamp, up to near INT32_MAX, a few at the ends themselves."""
    v = rng.integers(CLOCK_LO, INT32_MAX, shape, dtype=np.int64)
    ends = rng.random(shape)
    v = np.where(ends < 0.05, CLOCK_LO, np.where(ends > 0.95, INT32_MAX - 1, v))
    return v.astype(np.int32)


@functools.partial(jax.jit, static_argnames="method")
def _link_passes(seg, key, val, dep, table, method="auto"):
    """Both sorted passes as the router walk chains them."""
    rank, floor, runs = segmented_rank_floor(
        seg, val, table, order=lane_order(key), method=method)
    return rank, floor, segmented_table_max(runs, dep, table)


def check_link_values(seg, key, n_seg, method, rng):
    """`segmented_rank_floor` and `segmented_table_max` on (seg, key):
    the rank is `segmented_rank`'s, floor and table the oracle's."""
    val, dep = _clocks(rng, seg.shape), _clocks(rng, seg.shape)
    table = _clocks(rng, n_seg)
    rank, floor, raised = _link_passes(
        *(jnp.asarray(a) for a in (seg, key, val, dep, table)), method=method)
    want_floor, want_raised = link_oracle(seg, val, table, dep)
    valid = seg < n_seg
    np.testing.assert_array_equal(
        np.asarray(rank)[valid], matmul_oracle(seg, key, n_seg)[valid])
    np.testing.assert_array_equal(np.asarray(floor)[valid], want_floor[valid])
    np.testing.assert_array_equal(np.asarray(raised), want_raised)
    unused = np.setdiff1d(np.arange(n_seg), seg[valid])
    np.testing.assert_array_equal(np.asarray(raised)[unused], table[unused])


def _unique_segs(rng, C, S, n_seg, mask_p=0.4):
    """Per-lane DISTINCT segment ids (the engine contract: one entry per
    (lane, segment)), with a random fraction masked to the sentinel."""
    seg = np.stack(
        [rng.choice(n_seg, size=S, replace=False) for _ in range(C)]
    ).astype(np.int32)
    return np.where(rng.random((C, S)) < mask_p, n_seg, seg).astype(np.int32)


@pytest.mark.parametrize("values", ["rank", "link-values"])
@pytest.mark.parametrize("method", ["packed", "lex"])
@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_random_matches_oracle(method, seed, values):
    rng = np.random.default_rng(seed)
    C, S, n_seg = 64, 9, 37
    seg = _unique_segs(rng, C, S, n_seg)
    key = rng.integers(0, 500, C).astype(np.int32)  # dense => duplicates
    if values == "link-values":
        return check_link_values(seg, key, n_seg, method, rng)
    got = np.asarray(
        segmented_rank(jnp.asarray(seg), jnp.asarray(key), n_seg,
                       method=method)
    )
    want = matmul_oracle(seg, key, n_seg)
    valid = seg < n_seg
    np.testing.assert_array_equal(got[valid], want[valid])


def test_duplicate_keys_never_count_each_other():
    # every lane shares ONE key: all ranks must be zero (strict <)
    C, S, n_seg = 16, 4, 8
    rng = np.random.default_rng(7)
    seg = _unique_segs(rng, C, S, n_seg, mask_p=0.0)
    key = np.full(C, 42, np.int32)
    got = np.asarray(segmented_rank(jnp.asarray(seg), jnp.asarray(key), n_seg))
    np.testing.assert_array_equal(got, np.zeros((C, S), np.int32))


def test_masked_lanes_via_sentinel():
    # lanes that don't compete are masked by writing the sentinel into
    # EVERY slot (the engine's tgt_all = where(ok, path, NL) idiom):
    # they must neither receive real ranks nor count as competitors
    rng = np.random.default_rng(11)
    C, S, n_seg = 32, 5, 19
    seg = _unique_segs(rng, C, S, n_seg, mask_p=0.2)
    key = rng.integers(0, 10_000, C).astype(np.int32)
    competing = rng.random(C) < 0.6
    seg_masked = np.where(competing[:, None], seg, n_seg).astype(np.int32)
    got = np.asarray(
        segmented_rank(jnp.asarray(seg_masked), jnp.asarray(key), n_seg)
    )
    want = matmul_oracle(seg_masked, key, n_seg, competitor=competing)
    valid = seg_masked < n_seg
    np.testing.assert_array_equal(got[valid], want[valid])


@pytest.mark.parametrize("mesh", [(2, 2), (4, 4), (3, 2)])
def test_engine_shaped_mesh_paths(mesh):
    # real router-block shapes: concatenated request/reply XY legs over
    # random (core tile, bank tile) pairs — reversed DIRECTED links, so
    # the per-(lane, segment) uniqueness contract holds by construction
    mx, my = mesh
    cfg = small_test_config(
        mx * my * 2, n_banks=8,
        noc=NocConfig(mesh_x=mx, mesh_y=my, link_lat=1, router_lat=1),
    )
    C = cfg.n_cores
    NL = n_links(cfg)
    rng = np.random.default_rng(mx * 10 + my)
    ctile = jnp.asarray(np.arange(C) % cfg.n_tiles, jnp.int32)
    btile = jnp.asarray(rng.integers(0, cfg.n_tiles, C), jnp.int32)
    req_p = path_links(cfg, ctile, btile)
    rep_p = path_links(cfg, btile, ctile)
    txn = rng.random(C) < 0.7
    pth = np.concatenate([np.asarray(req_p), np.asarray(rep_p)], axis=1)
    ok = txn[:, None] & (pth >= 0)
    seg = np.where(ok, pth, NL).astype(np.int32)
    key = ((rng.integers(0, 50, C) * C) + np.arange(C)).astype(np.int32)
    got = np.asarray(segmented_rank(jnp.asarray(seg), jnp.asarray(key), NL))
    want = matmul_oracle(seg, key, NL, competitor=txn)
    np.testing.assert_array_equal(got[ok], want[ok])


def test_faulted_detour_config_paths_stay_nominal_and_exact():
    # fault-injection reroutes add latency AFTER the contention walk;
    # the ranking itself always runs on the NOMINAL XY paths.  A config
    # with link faults armed must therefore produce identical path sets
    # — and identical sort-vs-matmul ranks — as the clean config.
    cfg = small_test_config(8, n_banks=8)
    cfg_f = small_test_config(
        8, n_banks=8, faults_enabled=True, max_fault_events=1,
        fault_events=((0, FAULT_LINK_FAIL, 1, 0),), fault_seed=123,
    )
    C, NL = cfg.n_cores, n_links(cfg)
    rng = np.random.default_rng(5)
    ctile = jnp.asarray(np.arange(C) % cfg.n_tiles, jnp.int32)
    btile = jnp.asarray(rng.integers(0, cfg.n_tiles, C), jnp.int32)
    p_clean = np.asarray(path_links(cfg, ctile, btile))
    p_fault = np.asarray(path_links(cfg_f, ctile, btile))
    np.testing.assert_array_equal(p_clean, p_fault)
    seg = np.where(p_clean >= 0, p_clean, NL).astype(np.int32)
    key = np.arange(C, 0, -1).astype(np.int32)
    got = np.asarray(segmented_rank(jnp.asarray(seg), jnp.asarray(key), NL))
    want = matmul_oracle(seg, key, NL)
    valid = seg < NL
    np.testing.assert_array_equal(got[valid], want[valid])


def test_fleet_vmapped_batches_match_solo():
    # the fleet engine vmaps the whole step: a batched segmented_rank
    # must equal per-element calls bit-for-bit
    rng = np.random.default_rng(21)
    B, C, S, n_seg = 4, 24, 6, 15
    segs = np.stack([_unique_segs(rng, C, S, n_seg) for _ in range(B)])
    keys = rng.integers(0, 200, (B, C)).astype(np.int32)
    batched = np.asarray(
        jax.vmap(lambda s, k: segmented_rank(s, k, n_seg))(
            jnp.asarray(segs), jnp.asarray(keys)
        )
    )
    for b in range(B):
        solo = np.asarray(
            segmented_rank(jnp.asarray(segs[b]), jnp.asarray(keys[b]), n_seg)
        )
        np.testing.assert_array_equal(batched[b], solo, err_msg=f"elem {b}")


def test_lane_order_properties():
    key = jnp.asarray([5, 1, 5, 0, 9, 1], jnp.int32)
    got = np.asarray(lane_order(key))
    np.testing.assert_array_equal(got, [3, 1, 3, 0, 5, 1])
    # strict-comparison agreement on random data incl. duplicates
    rng = np.random.default_rng(3)
    k = rng.integers(0, 30, 100).astype(np.int32)
    o = np.asarray(lane_order(jnp.asarray(k)))
    np.testing.assert_array_equal(
        k[None, :] < k[:, None], o[None, :] < o[:, None]
    )


def test_precomputed_order_shared_across_calls():
    rng = np.random.default_rng(9)
    C, n_seg = 32, 12
    key = rng.integers(0, 100, C).astype(np.int32)
    seg = _unique_segs(rng, C, 4, n_seg)
    ordr = lane_order(jnp.asarray(key))
    a = segmented_rank(jnp.asarray(seg), jnp.asarray(key), n_seg)
    b = segmented_rank(jnp.asarray(seg), n_seg=n_seg, order=ordr)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_packed_and_lex_agree_on_engine_scale():
    rng = np.random.default_rng(17)
    C, S, n_seg = 128, 12, 257
    seg = _unique_segs(rng, C, S, n_seg)
    key = rng.integers(0, 1 << 20, C).astype(np.int32)
    a = np.asarray(segmented_rank(jnp.asarray(seg), jnp.asarray(key), n_seg,
                                  method="packed"))
    b = np.asarray(segmented_rank(jnp.asarray(seg), jnp.asarray(key), n_seg,
                                  method="lex"))
    valid = seg < n_seg
    np.testing.assert_array_equal(a[valid], b[valid])


def _case_engine_sentinel_heavy(rng):
    # the rung-3 router shape: 1024 lanes x 124 (leg, hop) slots over the
    # 4096 directed links of a 32x32 mesh, 85 % of the entries masked
    C, S, n_seg = 1024, 124, 4096
    seg = rng.integers(0, n_seg - S, (C, 1)) + np.arange(S)[None, :]
    seg = np.where(rng.random((C, S)) < 0.85, n_seg, seg)
    assert (seg == n_seg).mean() >= 0.8
    return seg, rng.integers(0, 300, C), n_seg


def _case_all_keys_equal(rng):
    C, S, n_seg = 48, 5, 11
    return _unique_segs(rng, C, S, n_seg), np.full(C, 7), n_seg


def _case_one_segment_holds_every_entry(rng):
    # every lane enters segment 5 once; the other slots are masked
    C, S, n_seg = 40, 3, 9
    seg = np.full((C, S), n_seg)
    seg[:, 1] = 5
    return seg, rng.integers(0, 12, C), n_seg


def _case_dram_caller_one_slot(rng):
    # step.py's DRAM queue: S = 1, a bank or the sentinel per lane
    C, n_seg = 1024, 1024
    seg = rng.integers(0, 64, (C, 1))  # hot banks: long FIFOs
    seg = np.where(rng.random((C, 1)) < 0.5, n_seg, seg)
    return seg, rng.integers(0, 200, C), n_seg


def _case_entries_not_a_multiple_of_128(rng):
    C, S, n_seg = 50, 7, 23  # E = 350
    return _unique_segs(rng, C, S, n_seg), rng.integers(0, 20, C), n_seg


def _case_every_slot_masked(rng):
    # a step in which no lane has a home transaction: only table entries
    C, S, n_seg = 20, 6, 13
    return np.full((C, S), n_seg), rng.integers(0, 9, C), n_seg


def _case_one_link_used_by_all_lanes_others_by_none(rng):
    # the longest FIFO a step can hold, and n_seg - 1 untouched clocks
    C, S, n_seg = 300, 1, 140
    return np.full((C, S), 77), rng.integers(0, 50, C), n_seg


def _case_more_segments_than_entries(rng):
    # runs of one table entry alone, many in a row of the scan
    C, S, n_seg = 6, 2, 700
    return _unique_segs(rng, C, S, n_seg), rng.integers(0, 4, C), n_seg


@pytest.mark.parametrize("values", ["rank", "link-values"])
@pytest.mark.parametrize("method", ["packed", "lex"])
@pytest.mark.parametrize(
    "case",
    [
        _case_engine_sentinel_heavy,
        _case_all_keys_equal,
        _case_one_segment_holds_every_entry,
        _case_dram_caller_one_slot,
        _case_entries_not_a_multiple_of_128,
        _case_every_slot_masked,
        _case_one_link_used_by_all_lanes_others_by_none,
        _case_more_segments_than_entries,
    ],
    ids=lambda f: f.__name__[len("_case_"):],
)
def test_shapes_the_sorted_order_rank_has_to_survive(case, method, values):
    seg, key, n_seg = case(np.random.default_rng(26))
    seg, key = seg.astype(np.int32), key.astype(np.int32)
    if values == "link-values":
        return check_link_values(seg, key, n_seg, method,
                                 np.random.default_rng(31))
    got = np.asarray(
        segmented_rank(jnp.asarray(seg), jnp.asarray(key), n_seg,
                       method=method)
    )
    want = matmul_oracle(seg, key, n_seg)
    valid = seg < n_seg  # the rank of a masked slot is unspecified
    assert valid.any() or case is _case_every_slot_masked
    np.testing.assert_array_equal(got[valid], want[valid])


@pytest.mark.parametrize("table_value", [CLOCK_LO, -1, 0, INT32_MAX - 1])
def test_link_values_at_the_ends_of_the_clock_range(table_value):
    """A link's clock at the rebase clamp, just under zero, zero and near
    INT32_MAX, its entries' values over the whole range: `_running_max`
    is written for non-negative ints, the segmented scans for all."""
    rng = np.random.default_rng(abs(table_value) % 97)
    C, S, n_seg = 96, 5, 41
    seg = _unique_segs(rng, C, S, n_seg, mask_p=0.3)
    key = rng.integers(0, 40, C).astype(np.int32)
    val, dep = _clocks(rng, seg.shape), _clocks(rng, seg.shape)
    table = np.full(n_seg, table_value, np.int32)
    _, floor, raised = _link_passes(
        *(jnp.asarray(a) for a in (seg, key, val, dep, table)))
    want_floor, want_raised = link_oracle(seg, val, table, dep)
    valid = seg < n_seg
    np.testing.assert_array_equal(np.asarray(floor)[valid], want_floor[valid])
    np.testing.assert_array_equal(np.asarray(raised), want_raised)


def test_fleet_vmapped_link_values_match_solo():
    # as test_fleet_vmapped_batches_match_solo, for the carried values:
    # the batched sorts, scans and table reads equal per-element calls
    rng = np.random.default_rng(23)
    B, C, S, n_seg = 3, 40, 7, 150  # E + n_seg = 430: four rows of a scan
    segs = np.stack([_unique_segs(rng, C, S, n_seg) for _ in range(B)])
    keys = rng.integers(0, 200, (B, C)).astype(np.int32)
    vals, deps = _clocks(rng, segs.shape), _clocks(rng, segs.shape)
    tables = _clocks(rng, (B, n_seg))

    args = [jnp.asarray(a) for a in (segs, keys, vals, deps, tables)]
    batched = jax.vmap(_link_passes)(*args)
    for b in range(B):
        solo = _link_passes(*(a[b] for a in args))
        valid = segs[b] < n_seg
        for got, want, mask in zip(batched, solo, (valid, valid, slice(None))):
            np.testing.assert_array_equal(
                np.asarray(got[b])[mask], np.asarray(want)[mask],
                err_msg=f"elem {b}")
        want_floor, want_raised = link_oracle(
            segs[b], vals[b], tables[b], deps[b])
        np.testing.assert_array_equal(
            np.asarray(batched[1][b])[valid], want_floor[valid])
        np.testing.assert_array_equal(np.asarray(batched[2][b]), want_raised)


@pytest.mark.parametrize("method", ["packed", "lex"])
@pytest.mark.parametrize("mask_p", [0.0, 0.4, 1.0])
def test_sorted_runs_count_their_real_entries(method, mask_p):
    """`SortedRuns.n_real`, read off where the masked entries' run starts
    in the sorted order, is the number of entries that are not masked:
    also where none is masked (the last run is a real segment's) and where
    all are (the masked run starts right after the table entries)."""
    rng = np.random.default_rng(11)
    C, S, n_seg = 16, 6, 24
    seg = _unique_segs(rng, C, S, n_seg, mask_p=mask_p)
    key = rng.integers(0, 50, C).astype(np.int32)
    _, _, runs = segmented_rank_floor(
        jnp.asarray(seg), jnp.asarray(_clocks(rng, seg.shape)),
        jnp.asarray(_clocks(rng, n_seg)), order=lane_order(jnp.asarray(key)),
        method=method)
    assert int(runs.n_real) == int((seg < n_seg).sum())
    assert int(runs.n_real) == {0.0: C * S, 1.0: 0}.get(mask_p, int(runs.n_real))


# --- the entry sorts under a batch axis (`_entry_sort`, PR 48) -------------

INT32_MIN = np.iinfo(np.int32).min


def _sorts_of(fn, *args):
    """(operand shapes, `dimension`) of every `sort` equation of `fn`'s
    jaxpr, through every sub-jaxpr (a `custom_vmap_call`'s too)."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "sort":
                found.append(([v.aval.shape for v in eqn.invars],
                              eqn.params["dimension"], eqn.params["num_keys"]))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


def _entry_operands(rng, B, N, form, payloads, n_seg=11, width=7):
    """B machines' operands for one entry sort, `[B, N]` each, and the
    number of keys: tied keys (N entries over fewer values), a third
    of the entries in the sentinel segment, payloads at the ends of
    int32 and at the rebase clamp. The last payload numbers the entries,
    so no two of a machine's rows are equal."""
    seg = np.where(rng.random((B, N)) < 0.33, n_seg,
                   rng.integers(0, n_seg, (B, N))).astype(np.int32)
    ordr = rng.integers(0, width, (B, N)).astype(np.int32)
    if form == "packed":
        keys = [seg * width + ordr]
    else:
        keys = [seg, ordr]
    ends = np.array([INT32_MIN, INT32_MAX, CLOCK_LO, 0, -1], np.int32)
    pays = [np.where(rng.random((B, N)) < 0.5, rng.choice(ends, (B, N)),
                     _clocks(rng, (B, N))).astype(np.int32)
            for _ in range(payloads - 1)]
    pays.append(np.broadcast_to(np.arange(N, dtype=np.int32), (B, N)))
    return keys + pays, len(keys)


def _rows(operands):
    """A sort's result as a set of its rows: what is left to compare of
    an unstable sort once the keys are seen to be equal."""
    return sorted(zip(*(np.asarray(x).tolist() for x in operands)))


@pytest.mark.parametrize("payloads", [1, 3])
@pytest.mark.parametrize("form", ["packed", "lex"])
@pytest.mark.parametrize("B", [1, 3, 4])
def test_mapped_entry_sort_equals_a_loop_of_the_plain_one(B, form, payloads):
    rng = np.random.default_rng(100 * B + payloads)
    ops, num_keys = _entry_operands(rng, B, 300, form, payloads)
    # the entries' numbers arrive unbatched, as the callers' `arange`
    in_axes = (0,) * (len(ops) - 1) + (None,)
    mapped = jax.vmap(
        lambda *o: ranking._entry_sort(o, num_keys), in_axes=in_axes)(
        *(jnp.asarray(x) for x in ops[:-1]), jnp.asarray(ops[-1][0]))
    for b in range(B):
        solo = jax.lax.sort(
            tuple(jnp.asarray(x[b]) for x in ops), num_keys=num_keys,
            is_stable=False)
        for k in range(num_keys):  # the keys: equal place by place
            np.testing.assert_array_equal(mapped[k][b], solo[k], err_msg=f"elem {b}")
        # the payloads: equal but for the order among ties
        assert _rows(x[b] for x in mapped) == _rows(solo), f"elem {b}"
        assert _rows(solo) == _rows(x[b] for x in ops), f"elem {b}"


@pytest.mark.parametrize("B", [3, 8, 16])
@pytest.mark.parametrize("form", ["packed", "lex"])
def test_mapped_entry_sort_asks_nothing_of_its_keys(form, B):
    """The rule is the plain sort once a machine, so it holds for keys of
    any sign up to the ends of int32 and for any B (no room is needed
    for a machine's number in the key), and its program holds B sorts
    of 1-D operands and none of `[B, N]`."""
    rng = np.random.default_rng(5)
    N = 200
    ops, num_keys = _entry_operands(rng, B, N, form, 2)
    ends = np.array([INT32_MIN, INT32_MAX, -1, (1 << 30) + 7], np.int32)
    ops[0] = np.where(
        rng.random((B, N)) < 0.3, rng.choice(ends, (B, N)), ops[0]).astype(np.int32)
    fn = jax.vmap(lambda *o: ranking._entry_sort(o, num_keys))
    args = [jnp.asarray(x) for x in ops]
    assert [s for s, _, _ in _sorts_of(fn, *args)] == [[(N,)] * len(ops)] * B
    mapped = fn(*args)
    for b in range(B):
        solo = jax.lax.sort(
            tuple(a[b] for a in args), num_keys=num_keys, is_stable=False)
        for k in range(num_keys):
            np.testing.assert_array_equal(mapped[k][b], solo[k])
        assert _rows(x[b] for x in mapped) == _rows(solo)


def test_two_batch_axes_unroll_twice():
    """The rule sorts each machine with the helper again: a second `vmap`
    round the first still sorts 1-D, a machine at a time."""
    rng = np.random.default_rng(6)
    ops, num_keys = _entry_operands(rng, 6, 150, "packed", 2)
    args = [jnp.asarray(x.reshape(2, 3, 150)) for x in ops]
    fn = jax.vmap(jax.vmap(lambda *o: ranking._entry_sort(o, num_keys)))
    assert [s for s, _, _ in _sorts_of(fn, *args)] == [[(150,)] * len(ops)] * 6
    mapped = fn(*args)
    for a in range(2):
        for b in range(3):
            solo = ranking._entry_sort(tuple(x[a, b] for x in args), num_keys)
            np.testing.assert_array_equal(mapped[0][a, b], solo[0])
            assert _rows(x[a, b] for x in mapped) == _rows(solo)


@pytest.mark.parametrize("method", ["packed", "lex"])
def test_vmapped_link_passes_sort_a_machine_at_a_time(method):
    """`vmap` of `segmented_rank_floor` and `segmented_table_max` at B = 4
    holds the three sorts of the unmapped passes four times each, 1-D of
    `E + n_seg` entries with the same operands and `num_keys`, and none
    of `[4, N]`; unmapped, they are those of before the helper."""
    rng = np.random.default_rng(31)
    B, C, S, n_seg = 4, 24, 5, 40
    N = C * S + n_seg
    segs = np.stack([_unique_segs(rng, C, S, n_seg) for _ in range(B)])
    order = np.stack([rng.permutation(C) for _ in range(B)]).astype(np.int32)
    vals, deps = _clocks(rng, segs.shape), _clocks(rng, segs.shape)
    tables = _clocks(rng, (B, n_seg))

    def passes(seg, order, val, dep, table):
        rank, floor, runs = segmented_rank_floor(
            seg, val, table, order=order, method=method)
        return rank, floor, segmented_table_max(runs, dep, table)

    args = [jnp.asarray(a) for a in (segs, order, vals, deps, tables)]
    keys = 1 if method == "packed" else 2
    want = [(keys + 2, keys), (4, 1), (2, 1)]  # operands, num_keys
    for fn, fn_args, times in ((jax.vmap(passes), args, B),
                               (passes, [a[0] for a in args], 1)):
        sorts = _sorts_of(fn, *fn_args)
        assert [(len(s), k) for s, _, k in sorts] == [
            w for w in want for _ in range(times)]
        assert all(s == [(N,)] * len(s) and d == 0 for s, d, _ in sorts)
