"""The trace shape `moe_decode_like` (one decode step of one routed-expert
layer of DeepSeek-V3, an expert to 64 cores; `trace/synth.py`, and the
benchmark's own `benchmark/generators/moe_decode_like.py`): the routing
against its law, what a visit references and where, the widths'
arithmetic, the two generators equal event for event, and the golden
model, the engine and the coarse directory's plain reference bit-exact on
a 256-core machine of four experts with the controller queues on."""

import json

import numpy as np
import pytest

from benchmark_modules import ROOT, assert_reference_equals_golden  # puts benchmark/ on the path

import cells
import trafficgen
from primesim_tpu.trace import synth
from primesim_tpu.trace.format import EV_END, EV_LD, EV_ST, fold_ins

LINE = 64
# DeepSeek-V3's published widths, the cell's routing and its cut of rows
CELL = dict(tokens=8, hidden=7168, inter=2048, experts=256, top_k=8, n_group=8, topk_group=4,
            skew_milli=500, gate_rows=1, up_rows=1, down_rows=4, ins_per_mem=8)
# four experts on 256 cores, every width as published
FOUR = dict(CELL, tokens=3, experts=4, top_k=2, n_group=2, topk_group=1)
VISIT = 14 + 28 + 1 + 4 + 16 + 1  # references a visit at the cell's rows


def twin():
    return cells._module("generators", "moe_decode_like", ROOT)


def _layout(tokens, hidden, inter, experts, **_):
    """(first byte of the intermediates, of the outputs, of the weights)."""
    inter_base = tokens * hidden
    out_base = inter_base + experts * inter
    return inter_base, out_base, -(-(out_base + experts * 2 * hidden) // 2**18) * 2**18


def _rows(ev):
    """Every core's references: [[(type, addr, pre), ...]]."""
    return [[(int(t), int(a), int(p)) for t, _, a, p in row if t != EV_END] for row in ev]


@pytest.fixture(scope="module")
def four():
    return cells.load_generator("moe_decode_like")(256, 11, **FOUR)


# ---- the routing -----------------------------------------------------------

@pytest.fixture(scope="module", params=["program", "benchmark"])
def routed(request):
    """[4096, 256] bool: which experts each of 4096 tokens takes, seed 5."""
    if request.param == "benchmark":
        return twin().route(np.random.default_rng(5), 4096, 256, 8, 8, 4, 0.5)[0]
    # narrow rows, so that 4096 tokens' weights lie under 2^31; the routing reads no width
    plan = synth._moe_plan(16384, 5, **dict(CELL, tokens=4096, hidden=512, inter=512), line=LINE)
    out = np.zeros((4096, 256), bool)
    for t, experts in enumerate(plan["route"]):
        out[t, experts] = True
        assert plan["home"][t] in experts
    return out


def test_a_token_takes_eight_distinct_experts_in_at_most_four_groups(routed):
    assert (routed.sum(1) == 8).all()
    groups = [len(set(np.flatnonzero(r) // 32)) for r in routed]
    assert max(groups) == 4 and min(groups) >= 1
    assert sum(g == 4 for g in groups) > 2048  # most tokens use all four


def test_the_load_follows_rank_to_the_minus_half(routed):
    """4096 tokens: an expert's visits against 8 * 4096 * its share of
    rank ** -0.5, the ranks a seeded permutation. Drawing without
    replacement through the group limit flattens the hottest a little:
    tenths of the experts by rank each within a tenth of their share, the
    log-log slope within 0.05 of -0.5, the hottest about 8 times the mean."""
    weight = np.empty(256)
    weight[np.random.default_rng(5).permutation(256)] = np.arange(1, 257) ** -0.5
    assert 8.0 < weight.max() / weight.mean() < 8.8
    load = routed.sum(0)
    expect = 8 * 4096 * weight / weight.sum()
    by_rank = np.argsort(-weight)
    for i in range(0, 256, 32):
        part = by_rank[i:i + 32]
        assert 0.9 < load[part].sum() / expect[part].sum() < 1.1
    slope = np.polyfit(np.log(np.arange(1, 257)), np.log(load[by_rank]), 1)[0]
    assert -0.55 < slope < -0.45
    assert 6.0 < load.max() / load.mean() < 9.0


def test_a_small_batch_leaves_most_experts_idle():
    """What a decode batch does to wide expert parallelism: 64 visits over
    256 experts, 200 of them with no token, the fullest with 3."""
    d = synth.moe_decode_describe(16384, 404, **CELL)
    assert d["visits"] == {"all": 64, "fullest_expert": 3, "mean_expert": 0.25,
                           "experts_without": 200}
    assert d["references_a_visit"] == {"activation": 14, "weight": 44, "intermediate": 5, "output": 1}
    assert d["references"] == {"activation": 57344, "weight": 180224, "intermediate": 20480,
                               "output": 4096 + 64}
    assert d["events"] == {"fullest_core": 2 * 3 * VISIT, "mean_core": 2 * 262208 / 16384}
    assert d["bytes_laid_out"] < 2**31
    sixteen = synth.moe_decode_describe(16384, 404, **dict(CELL, tokens=16))
    assert sixteen["visits"] == {"all": 128, "fullest_expert": 4, "mean_expert": 0.5,
                                 "experts_without": 159}
    with pytest.raises(TypeError):
        synth.moe_decode_describe(16384, 404, rows=1)


# ---- a visit ---------------------------------------------------------------

def test_the_widths_arithmetic():
    """The lines of each tensor from the published widths: 112 a token's
    activations and a gate or up row, 32 a down row and an intermediate,
    14 and 4 a row segment of a core's 8 x 8 block, 224 an output; a core's
    slice of its expert 10752 lines, the expert's 44040192 bytes / 64."""
    hidden, inter = CELL["hidden"], CELL["inter"]
    assert (hidden // LINE, inter // LINE) == (112, 32)
    assert (hidden // 8 // LINE, inter // 8 // LINE) == (14, 4)
    assert 2 * hidden // LINE == 224 and (inter // 64, 2 * hidden // 64) == (32, 224)
    rows = (inter // 8, inter // 8, hidden // 8)
    assert rows == (256, 256, 896)
    assert rows[0] * 14 + rows[1] * 14 + rows[2] * 4 == 10752 == 3 * hidden * inter // 64 // LINE


def test_a_visits_references(four):
    """On each of an expert's 64 cores, in this order: the 14 lines of
    block j of the token's activations, a gate and an up row segment (28
    private lines), a store to its 32 bytes of the expert's intermediate,
    the 4 lines of block j of it, four down row segments (16 private
    lines), a store to the first word of its 224 bytes of the output."""
    inter_base, out_base, w_base = _layout(**FOUR)
    hidden, inter = FOUR["hidden"], FOUR["inter"]
    stride = (3 * 14 + 3 * 14 + 12 * 4) | 1  # what three tokens can reach, an odd count of lines
    rows = _rows(four)
    n_visits = 0
    for c, refs in enumerate(rows):
        e, q = divmod(c, 64)
        j = q % 8
        w = w_base + c * stride * LINE
        body = refs[:len(refs) // VISIT * VISIT]
        for v in range(len(body) // VISIT):
            visit = body[v * VISIT:(v + 1) * VISIT]
            kinds = [t for t, _, _ in visit]
            assert kinds == [EV_LD] * 42 + [EV_ST] + [EV_LD] * 20 + [EV_ST]
            addr = [a for _, a, _ in visit]
            token, offset = divmod(addr[0], hidden)
            assert token < 3 and offset == j * 14 * LINE
            assert addr[:14] == [token * hidden + (j * 14 + l) * LINE for l in range(14)]
            assert addr[14:28] == [w + (v * 14 + l) * LINE for l in range(14)]  # gate segment v
            assert addr[28:42] == [w + ((3 + v) * 14 + l) * LINE for l in range(14)]  # up
            assert addr[42] == inter_base + e * inter + 32 * q
            assert addr[43:47] == [inter_base + e * inter + (4 * j + l) * LINE for l in range(4)]
            assert addr[47:63] == [w + (6 * 14 + 4 * v * 4 + l) * LINE for l in range(16)]
            assert addr[63] == out_base + e * 2 * hidden + 224 * q
            n_visits += 1
    assert n_visits == 64 * 3 * 2  # three tokens at two experts each


def test_the_sharing_of_a_visits_lines(four):
    """A line of a token's activations is read by the 8 cores of one block
    column in each of its experts; a line of the intermediate is written by
    two cores and read by the 8 of a block column, all in one group; no
    weight line is met twice by anyone."""
    inter_base, out_base, w_base = _layout(**FOUR)
    readers, writers = {}, {}
    for c, refs in enumerate(_rows(four)):
        for t, a, _ in refs:
            (readers if t == EV_LD else writers).setdefault(a // LINE, []).append(c)
    acts = {l: cs for l, cs in readers.items() if l < inter_base // LINE}
    assert len(acts) == 3 * 112 and all(len(cs) == 8 * 2 for cs in acts.values())
    assert all(len({c % 8 for c in cs}) == 1 and len({c // 64 for c in cs}) == 2
               for cs in acts.values())
    scratch = {l: cs for l, cs in readers.items() if inter_base // LINE <= l < out_base // LINE}
    assert all(len(set(cs)) == 8 and len({c // 64 for c in cs}) == 1 for cs in scratch.values())
    assert all(len(set(writers[l])) == 2 for l in scratch)
    weights = {l: cs for l, cs in readers.items() if l >= w_base // LINE}
    assert all(len(cs) == 1 for cs in weights.values())
    assert len(weights) == 64 * 6 * 44


def test_a_tokens_home_combines_it(four):
    """After its visits, core t mod 64 of the first expert a token drew
    loads slice t mod 64 of the output of each of the token's experts."""
    _, out_base, _ = _layout(**FOUR)
    hidden = FOUR["hidden"]
    tails = {c: refs[len(refs) // VISIT * VISIT:] for c, refs in enumerate(_rows(four))
             if len(refs) % VISIT}
    assert sorted(c % 64 for c in tails) == [0, 1, 2] and len(tails) == 3
    for c, tail in tails.items():
        t = c % 64
        assert [k for k, _, _ in tail] == [EV_LD, EV_LD]
        experts = [(a - out_base) // (2 * hidden) for _, a, _ in tail]
        assert experts == sorted(experts) and c // 64 in experts
        assert all((a - out_base) % (2 * hidden) == 224 * t for _, a, _ in tail)


def test_at_full_rows_a_cores_private_lines_sum_to_10752():
    """The uncut layer: every visit streams a core's whole slice of its
    expert, 256 + 256 rows of 14 lines and 896 of 4."""
    full = dict(FOUR, tokens=1, gate_rows=256, up_rows=256, down_rows=896)
    ev = cells.load_generator("moe_decode_like")(256, 3, **full)
    _, _, w_base = _layout(**full)
    addr = ev[:, :, 2].astype(np.int64)
    private = (ev[:, :, 0] == EV_LD) & (addr >= w_base)
    visited = private.any(1)
    assert visited.sum() == 2 * 64
    for c in np.flatnonzero(visited)[::17]:
        lines = addr[c][private[c]] // LINE
        assert len(lines) == len(set(lines.tolist())) == 10752
        assert lines.max() - lines.min() == 10751 and lines.min() == w_base // LINE + c * 10753
    assert ev.shape == (256, 14 + 10752 + 1 + 4 + 1 + 2 + 1, 4)


def test_addresses_instructions_and_padding_at_the_cells_size():
    ev = cells.load_generator("moe_decode_like")(16384, 404, **CELL)
    inter_base, out_base, w_base = _layout(**CELL)
    assert (inter_base, out_base, w_base) == (57344, 581632, 4456448)
    t, addr, pre = ev[:, :, 0], ev[:, :, 2].astype(np.int64), ev[:, :, 3]
    mem = t != EV_END
    assert set(np.unique(t)) == {EV_LD, EV_ST, EV_END} and (ev[:, :, 1][mem] == 4).all()
    stride = (8 * 14 + 8 * 14 + 32 * 4) | 1
    assert 0 <= addr.min() and addr.max() < w_base + 16384 * stride * LINE < 2**31
    assert set(np.unique(pre[mem])) == set(range(1, 17))  # 1 .. 2 * ins_per_mem
    lengths = mem.sum(1)
    assert ev.shape == (16384, lengths.max() + 1, 4) == (16384, 3 * VISIT + 1, 4)
    assert all(mem[c, :lengths[c]].all() and not mem[c, lengths[c]:].any()
               for c in range(0, 16384, 97))
    assert int(mem.sum()) == 64 * 64 * VISIT + 8 * 8 == 262208
    assert trafficgen.total_instructions(ev) == 2486708
    assert int((lengths == 0).sum()) == 200 * 64  # the idle experts' cores hold END alone
    # the cores' weights start on every bank evenly: four cores a bank
    first = (w_base // LINE + np.arange(16384) * stride) % 4096
    assert (np.bincount(first, minlength=4096) == 4).all()


def test_the_same_seed_gives_the_same_trace():
    gen = cells.load_generator("moe_decode_like")
    a, b, c = gen(256, 2**31 + 9, **FOUR), gen(256, 2**31 + 9, **FOUR), gen(256, 2**31 + 10, **FOUR)
    assert np.array_equal(a, b) and (a.shape != c.shape or not np.array_equal(a, c))


@pytest.mark.parametrize("n_cores,seed,args", [
    (256, 7, FOUR),
    (512, 2**31 + 11, dict(tokens=70, hidden=512, inter=1024, experts=8, top_k=3, n_group=4,
                           topk_group=2, skew_milli=1200, gate_rows=3, up_rows=2, down_rows=64,
                           ins_per_mem=1)),
    (16384, 404, dict(CELL, tokens=2)),
])
def test_generator_equals_the_programs(n_cores, seed, args):
    mine = cells.load_generator("moe_decode_like")(n_cores, seed, **args)
    theirs = fold_ins(synth.moe_decode_like(n_cores, seed=seed, **args))
    assert np.array_equal(mine, theirs.events)
    assert trafficgen.total_instructions(mine) == theirs.total_instructions()
    assert "moe_decode_like" in synth.GENERATORS


def test_the_defaults_are_deepseek_v3s():
    import inspect

    d = {k: p.default for k, p in inspect.signature(synth.moe_decode_like).parameters.items()}
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        catalog = [json.loads(l) for l in f]
    row = next((r for r in catalog if r["name"] == "DeepSeek-V3"), None)
    if row is not None:  # the catalog beside the guide, where this machine has it
        c = row["config"]
        assert (d["hidden"], d["inter"], d["experts"], d["top_k"], d["n_group"], d["topk_group"]) == (
            c["hidden_size"], c["moe_intermediate_size"], c["n_routed_experts"],
            c["num_experts_per_tok"], c["n_group"], c["topk_group"])
    assert (d["hidden"], d["inter"], d["experts"], d["top_k"], d["n_group"], d["topk_group"]) == (
        7168, 2048, 256, 8, 8, 4)
    assert (d["skew_milli"], d["gate_rows"], d["up_rows"], d["down_rows"]) == (500, 1, 1, 4)
    assert inspect.getfullargspec(cells.load_generator("moe_decode_like")).args == list(d)[:14]
    # `moe_decode_describe` takes the same arguments at the same defaults, the cell's `tokens`
    assert d == {k: p.default for k, p in
                 inspect.signature(synth.moe_decode_describe).parameters.items()}
    assert d["tokens"] == CELL["tokens"]


def test_what_the_shape_refuses():
    gen = cells.load_generator("moe_decode_like")
    for bad in (dict(tokens=0), dict(experts=8), dict(n_group=3), dict(topk_group=3),
                dict(top_k=3), dict(top_k=0), dict(hidden=7000), dict(inter=2000),
                dict(gate_rows=0), dict(up_rows=257), dict(down_rows=897), dict(skew_milli=-1),
                dict(ins_per_mem=0)):
        args = dict(FOUR, **bad)
        with pytest.raises(ValueError):
            gen(256, 1, **args)
        with pytest.raises(ValueError):
            synth.moe_decode_like(256, seed=1, **args)
    # the whole layer's rows on the whole machine: 11.3 GB of weights, over 2^31
    with pytest.raises(ValueError, match="2\\^31"):
        gen(16384, 1, **dict(CELL, gate_rows=256, up_rows=256, down_rows=896))
    with pytest.raises(ValueError, match="2\\^31"):
        synth.moe_decode_describe(16384, 1, **dict(CELL, gate_rows=256, up_rows=256, down_rows=896))


def test_the_cli_names_it_and_spans_it(monkeypatch, capsys, tmp_path):
    import primesim_tpu.obs.span as span_module
    from primesim_tpu.cli import _parse_synth, main

    opened = []

    class Span(span_module.span):
        def __init__(self, name):
            opened.append(name)
            super().__init__(name)

    monkeypatch.setattr(span_module, "span", Span)
    spec = "moe_decode_like:seed=3,tokens=2,experts=4,top_k=2,n_group=2,topk_group=1,skew_milli=500"
    tr = _parse_synth(spec, 256, True)
    mine = cells.load_generator("moe_decode_like", ROOT)(256, 3, **dict(FOUR, tokens=2))
    assert np.array_equal(tr.events, mine) and opened == ["synth.moe_decode_like"]
    # `primetpu synth` prints what the shape holds, as one JSON line
    out = tmp_path / "m.ptpu"
    assert main(["synth", spec, "--cores", "256", "--out", str(out), "--fold"]) == 0
    said = json.loads(capsys.readouterr().out)
    assert said == synth.moe_decode_describe(256, 3, **dict(FOUR, tokens=2))
    assert said["visits"]["all"] == 4 and out.stat().st_size > 0


# ---- the machine: golden, the engine and the coarse reference --------------

def _machine():
    """Rung 5's machine with the controller queues on, at 256 cores: four
    experts, an expert a directory group, four cores a bank."""
    return {
        "n_cores": 256, "n_banks": 64,
        "core": {"cpi": 1, "o3_overlap_256": 128},
        "l1": {"size": 2048, "ways": 4, "line": 64, "latency": 2},
        "llc": {"size": 8192, "ways": 8, "line": 64, "latency": 16},
        "noc": {"mesh_x": 16, "mesh_y": 16, "link_lat": 1, "router_lat": 2},
        "dram_lat": 140, "dram_queue": True, "dram_service": 0,
        "quantum": 1000, "local_run_len": 8, "sharer_group": 64,
    }


def test_golden_the_engine_and_the_coarse_reference_agree(four):
    from primesim_tpu.config.machine import MachineConfig
    from primesim_tpu.sim.engine import Engine
    from primesim_tpu.trace.format import Trace

    coarse = cells.load_reference("coarse_dir", ROOT)
    machine = _machine()
    ref = assert_reference_equals_golden(coarse, machine, four)
    lengths = ((four[:, :, 0] != EV_END).sum(1) + 1).astype(np.int32)
    eng = Engine(MachineConfig.from_dict(machine), Trace(four, lengths), chunk_steps=8)
    eng.run()
    assert eng.steps_run == -(-ref.step_count // 8) * 8
    assert np.array_equal(np.asarray(eng.cycles), np.asarray(ref.cycles))
    for k in coarse.COUNTERS:
        assert np.array_equal(np.asarray(eng.counters[k]), np.asarray(ref.counters[k])), k
    total = {k: int(np.sum(v)) for k, v in ref.counters.items()}
    # every kind of traffic the shape exists for, on this machine too
    assert total["dram_queue_cycles"] > 100 * total["dram_accesses"] > 0
    assert total["retries"] > total["l1_write_misses"] > 0
    assert total["invalidations"] > 63 * total["upgrades"] and total["probes"] > 0
    assert total["llc_hits"] > total["llc_misses"] // 4
