"""The trace shape `ycsb_like` (YCSB core workload A against a shared
in-memory hash table; `trace/synth.py`, and the benchmark's own
`benchmark/generators/ycsb_like.py`): the zipfian draw against its law,
what a READ and an UPDATE reference and where, and the two generators
equal event for event."""

import numpy as np
import pytest

from benchmark_modules import ROOT  # puts benchmark/ on the path

import cells
import trafficgen
from primesim_tpu.trace import synth
from primesim_tpu.trace.format import EV_END, EV_LD, EV_ST, fold_ins

CELL = dict(ops_per_core=8, recordcount=1000000, theta=0.99, read_frac=0.5,
            fieldcount=10, fieldlength=100, ins_per_mem=3, op_ins=30)
SMALL = dict(CELL, recordcount=4096)
LINE = 64
YCSB_ZETAN = 26.46902820178302  # `ScrambledZipfianGenerator.ZETAN`


def _layout(recordcount, fieldcount=10, fieldlength=100, **_):
    """(first byte of the records, bytes a record, field lines a record)."""
    field_lines = -(-fieldcount * fieldlength // LINE)
    return -(-2 * recordcount * 8 // 4096) * 4096, (1 + field_lines) * LINE, field_lines


def _operations(ev, args):
    """Every core's events cut into operations: a reference into the index
    (below the records) opens one. [(core, [(type, addr, pre), ...])]."""
    records = _layout(**args)[0]
    ops = []
    for c in range(ev.shape[0]):
        row = [(int(t), int(a), int(p)) for t, _, a, p in ev[c] if t != EV_END]
        starts = [i for i, (_, a, _) in enumerate(row) if a < records] + [len(row)]
        assert starts[0] == 0
        ops += [(c, row[i:j]) for i, j in zip(starts, starts[1:])]
    return ops


@pytest.fixture(scope="module")
def small():
    return cells.load_generator("ycsb_like")(64, 11, **SMALL)


@pytest.mark.parametrize("rank", range(10))
@pytest.mark.parametrize("which", ["program", "benchmark"])
def test_rank_frequencies_follow_the_zipfian(which, rank):
    """10^5 draws over YCSB's 10^10 items at theta 0.99: rank i comes with
    probability (i+1)^-theta / ZETAN. Gray's method gives the two hottest
    ranks exactly and the others from a continuous tail that overshoots
    just behind them (rank 2 by a fifth), in YCSB as here."""
    ranks = {"program": synth._zipfian_ranks,
             "benchmark": cells._module("generators", "ycsb_like", ROOT).zipfian_ranks}[which]
    n, theta, draws = 10**10, 0.99, 10**5
    got = int((ranks(np.random.default_rng(5).random(draws), n, theta) == rank).sum())
    expect = draws * (rank + 1) ** -theta / YCSB_ZETAN
    if rank < 2:
        assert abs(got - expect) <= 4 * expect ** 0.5
    else:
        assert 0.9 * expect <= got <= 1.25 * expect
    if rank == 0:
        assert 0.037 < 1 / YCSB_ZETAN < 0.038  # the hottest record: 3.8 % of the operations


@pytest.mark.parametrize("which", ["program", "benchmark"])
def test_zetan_is_ycsbs_constant(which):
    """`ScrambledZipfianGenerator` carries zeta(10^10, 0.99) as a constant;
    here it is computed, the terms past 2^20 by Euler-Maclaurin, which a
    sum of 2^22 terms holds to account."""
    zeta = {"program": synth._zeta,
            "benchmark": cells._module("generators", "ycsb_like", ROOT).zeta}[which]
    assert zeta(10**10, 0.99) == pytest.approx(YCSB_ZETAN, rel=1e-10)
    for theta in (0.7, 0.99):
        whole = float(np.sum(np.arange(1, 2**22 + 1, dtype=np.float64) ** -theta))
        assert zeta(2**22, theta) == pytest.approx(whole, rel=1e-12)
    assert zeta(1000, 0.99) == float(np.sum(np.arange(1, 1001, dtype=np.float64) ** -0.99))


def test_the_ranks_are_drawn_over_ycsbs_items_not_the_table():
    """`ScrambledZipfianGenerator` draws over 10^10 items whatever the
    record count and folds them onto the records by the hash: two fifths
    of the draws lie past a table of 10^6, and the ten hottest records
    take a ninth of the operations (19 % were the ranks drawn over the
    table)."""
    u = np.random.default_rng(9).random(200000)
    r = synth._zipfian_ranks(u, synth._YCSB_ITEMS, 0.99)
    assert synth._YCSB_ITEMS == cells._module("generators", "ycsb_like", ROOT).ITEMS == 10**10
    assert 0.40 < (r >= 10**6).mean() < 0.43 and r.max() < 10**10
    record = np.array([synth._fnv1a_64(int(x)) % 10**6 for x in r])
    counts = np.sort(np.unique(record, return_counts=True)[1])[::-1]
    assert 0.036 < counts[0] / len(u) < 0.040 and 0.105 < counts[:10].sum() / len(u) < 0.125


def test_the_ranks_stay_inside_the_records():
    for ranks in (synth._zipfian_ranks,
                  cells._module("generators", "ycsb_like", ROOT).zipfian_ranks):
        u = np.array([0.0, 0.05, 0.15, 0.5, 1.0 - 2.0 ** -53])
        r = ranks(u, 1000, 0.99)
        assert r[0] == 0 and r[2] == 1 and r.min() >= 0 and r.max() <= 999
        assert (np.diff(r) >= 0).all()  # hotter ranks from smaller draws


def test_the_hash_is_fnv_1a_64():
    """The 64-bit FNV-1a of eight zero bytes and of the byte 1 then seven
    zeros, computed by hand from the offset basis and the prime."""
    mask, prime = 2**64 - 1, 0x100000001B3
    zero = 0xCBF29CE484222325
    for _ in range(8):
        zero = zero * prime & mask
    one = (0xCBF29CE484222325 ^ 1) * prime & mask
    for _ in range(7):
        one = one * prime & mask
    mine = cells._module("generators", "ycsb_like", ROOT).fnv1a_64(np.array([0, 1, 2**40 + 7]))
    assert [int(x) for x in mine[:2]] == [zero, one] == [synth._fnv1a_64(0), synth._fnv1a_64(1)]
    assert int(mine[2]) == synth._fnv1a_64(2**40 + 7)


def test_the_read_share(small):
    ops = _operations(small, SMALL)
    assert len(ops) == 64 * 8
    reads = sum(all(t == EV_LD for t, _, _ in refs) for _, refs in ops)
    assert abs(reads - 256) <= 4 * (512 * 0.25) ** 0.5
    big = cells.load_generator("ycsb_like")(256, 3, **dict(SMALL, read_frac=0.9))
    ops = _operations(big, SMALL)
    assert abs(sum(all(t == EV_LD for t, _, _ in r) for _, r in ops) / len(ops) - 0.9) < 0.03


@pytest.mark.parametrize("kind", ["read", "update"])
def test_an_operations_references(small, kind):
    """A READ: the bucket head, the header, the 16 field lines in rising
    order, 18 loads. An UPDATE: the same two loads, a store to the header,
    then a store to each line of one field: 5 or 6 references."""
    records, stride, field_lines = _layout(**SMALL)
    assert (stride, field_lines) == (17 * LINE, 16)
    seen = 0
    for _, refs in _operations(small, SMALL):
        (t0, bucket, _), (t1, header, _) = refs[:2]
        assert (t0, t1) == (EV_LD, EV_LD) and bucket % 8 == 0 and bucket < 2 * 4096 * 8
        assert header >= records and (header - records) % stride == 0
        record = (header - records) // stride
        assert record < 4096 and bucket == 8 * (synth._fnv1a_64(record) % (2 * 4096))
        if kind == "read" and refs[2][0] == EV_LD:
            assert len(refs) == 18
            assert [a for _, a, _ in refs[2:]] == [header + l * LINE for l in range(1, 17)]
            seen += 1
        elif kind == "update" and refs[2][0] == EV_ST:
            assert refs[2][1] == header and len(refs) in (5, 6)
            assert all(t == EV_ST for t, _, _ in refs[3:])
            seen += 1
    assert seen > 200


@pytest.mark.parametrize("field", range(10))
def test_an_update_stores_its_fields_lines(field):
    """Field f is bytes 100 f .. 100 f + 99 of the packed fields, which
    start a line after the header: the first store is at the field's first
    byte, the others at the lines it runs on into."""
    ev = cells.load_generator("ycsb_like")(256, 21, **dict(SMALL, read_frac=0.0))
    lo, hi = 100 * field, 100 * field + 99
    lines = list(range(lo // LINE, hi // LINE + 1))
    assert len(lines) == (3 if field in (1, 3, 5, 7, 8) else 2)
    expect = [LINE + lo] + [LINE + l * LINE for l in lines[1:]]
    found = 0
    for _, refs in _operations(ev, SMALL):
        header = refs[1][1]
        offsets = [a - header for _, a, _ in refs[3:]]
        if offsets[0] == LINE + lo:
            assert offsets == expect
            found += 1
    assert abs(found - 2048 / 10) <= 4 * (2048 * 0.09) ** 0.5  # the field is uniform


def test_addresses_instructions_and_padding_at_the_cells_size():
    ev = cells.load_generator("ycsb_like")(1024, 404, **CELL)
    records, stride, _ = _layout(**CELL)
    assert (records, stride) == (16003072, 1088)
    t, addr, pre = ev[:, :, 0], ev[:, :, 2].astype(np.int64), ev[:, :, 3]
    mem = t != EV_END
    assert set(np.unique(t)) == {EV_LD, EV_ST, EV_END} and (ev[:, :, 1][mem] == 4).all()
    assert 0 <= addr.min() and addr.max() < records + 10**6 * stride <= 2**31
    in_records = mem & (addr >= records)
    assert ((addr[in_records] - records) % stride < stride).all()
    heads = mem & (addr < records)
    assert (heads.sum(1) == CELL["ops_per_core"]).all()  # every core serves its operations
    # an operation's first batch holds the hash and the dispatch, the others 1 .. 6
    assert pre[heads].min() >= 31 and pre[heads].max() <= 36
    assert set(np.unique(pre[in_records])) == {1, 2, 3, 4, 5, 6}
    # rows are END-padded to the longest core's, and some core is that long
    lengths = mem.sum(1)
    assert ev.shape == (1024, lengths.max() + 1, 4) and not mem[:, -1].any()
    assert all(mem[c, :n].all() and not mem[c, n:].any() for c, n in enumerate(lengths))
    assert int(mem.sum()) == 96285 and trafficgen.total_instructions(ev) == 678480
    # the hottest record takes about 3.8 % of the operations
    n_ops = 1024 * CELL["ops_per_core"]
    _, counts = np.unique((addr[in_records] - records) // stride, return_counts=True)
    headers = np.unique(addr[in_records & ((addr - records) % stride == 0)], return_counts=True)[1]
    assert 0.028 * n_ops < headers.max() / 1.5 < 0.048 * n_ops  # a READ loads it once, an UPDATE twice
    assert counts.max() > 0.3 * n_ops  # some eleven references an operation


def test_the_same_seed_gives_the_same_trace():
    gen = cells.load_generator("ycsb_like")
    a, b, c = gen(64, 2**31 + 9, **SMALL), gen(64, 2**31 + 9, **SMALL), gen(64, 2**31 + 10, **SMALL)
    assert np.array_equal(a, b) and (a.shape != c.shape or not np.array_equal(a, c))


@pytest.mark.parametrize("n_cores,seed,args", [
    (16, 7, SMALL),
    (64, 2**31 + 11, dict(ops_per_core=5, recordcount=100, theta=0.7, read_frac=0.3,
                          fieldcount=3, fieldlength=250, ins_per_mem=1, op_ins=0)),
    (1024, 404, dict(CELL, ops_per_core=2)),
])
def test_generator_equals_the_programs(n_cores, seed, args):
    mine = cells.load_generator("ycsb_like")(n_cores, seed, **args)
    theirs = fold_ins(synth.ycsb_like(n_cores, seed=seed, **args))
    assert np.array_equal(mine, theirs.events)
    assert trafficgen.total_instructions(mine) == theirs.total_instructions()
    assert "ycsb_like" in synth.GENERATORS


def test_the_defaults_are_workload_as():
    import inspect

    d = {k: p.default for k, p in inspect.signature(synth.ycsb_like).parameters.items()}
    assert (d["theta"], d["read_frac"], d["fieldcount"], d["fieldlength"]) == (0.99, 0.5, 10, 100)
    assert list(d)[:10] == ["n_cores", "seed", "ops_per_core", "recordcount", "theta", "read_frac",
                            "fieldcount", "fieldlength", "ins_per_mem", "op_ins"]
    assert inspect.getfullargspec(cells.load_generator("ycsb_like")).args == list(d)[:10]


def test_what_the_shape_refuses():
    gen = cells.load_generator("ycsb_like")
    for bad in (dict(ops_per_core=0), dict(recordcount=2), dict(theta=1.0), dict(theta=0.0),
                dict(read_frac=1.5), dict(fieldcount=0), dict(fieldlength=0), dict(ins_per_mem=0),
                dict(op_ins=-1), dict(recordcount=2 * 10**6)):  # 2.2 GB: over 2^31
        args = dict(SMALL, **bad)
        with pytest.raises(ValueError):
            gen(16, 1, **args)
        with pytest.raises(ValueError):
            synth.ycsb_like(16, seed=1, **args)


def test_the_cli_names_it():
    from primesim_tpu.cli import _parse_synth

    tr = _parse_synth("ycsb_like:seed=3,ops_per_core=4,recordcount=4096", 16, True)
    mine = cells.load_generator("ycsb_like", ROOT)(16, 3, **dict(SMALL, ops_per_core=4))
    assert np.array_equal(tr.events, mine)
