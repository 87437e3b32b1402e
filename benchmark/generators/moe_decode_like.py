"""Traffic shape `moe_decode_like`: the access pattern of
`primesim_tpu/trace/synth.py::moe_decode_like` (one decode step of one
routed-expert layer of DeepSeek-V3 for a batch of tokens, an expert to 64
cores), written here on its own: a visit as a row of reference slots in
array calls over all cores, the same draws from the same random stream;
`tests/test_synth_moe_decode.py` holds the two equal, event for event."""

import numpy as np

from trafficgen import EV_END, EV_LD, EV_ST, LINE, finish

GROUP = 64  # cores an expert: an 8 x 8 blocking of each of its matrices
BANKS = 4096  # the weights start on a multiple of this many lines


def route(rng, tokens: int, experts: int, top_k: int, n_group: int, topk_group: int,
          skew: float) -> tuple:
    """(routed [tokens, experts] bool, home [tokens]): popularity rank **
    -skew over a seeded permutation of the experts; a token takes
    `topk_group` of the `n_group` groups of consecutive experts by their
    summed popularity, then `top_k` experts inside them by their own, both
    without replacement (the smallest of Exp(1) / weight); its home is the
    first expert drawn."""
    per = experts // n_group
    weight = np.empty(experts)
    weight[rng.permutation(experts)] = np.arange(1, experts + 1) ** -skew
    group_key = -np.log1p(-rng.random((tokens, n_group))) / weight.reshape(n_group, per).sum(1)
    expert_key = -np.log1p(-rng.random((tokens, experts))) / weight
    groups = np.argsort(group_key, axis=1, kind="stable")[:, :topk_group]
    open_ = (groups[:, :, None] == (np.arange(experts) // per)[None, None, :]).any(1)
    chosen = np.argsort(np.where(open_, expert_key, np.inf), axis=1, kind="stable")[:, :top_k]
    routed = np.zeros((tokens, experts), bool)
    np.put_along_axis(routed, chosen, True, axis=1)
    return routed, chosen[:, 0]


def generate(n_cores: int, seed: int, tokens: int, hidden: int, inter: int, experts: int,
             top_k: int, n_group: int, topk_group: int, skew_milli: int, gate_rows: int,
             up_rows: int, down_rows: int, ins_per_mem: int) -> np.ndarray:
    """Expert e is the cores 64 e .. 64 e + 63, core 8 i + j block (i, j) of
    each matrix. A visit, on every core of the expert: LD block j of the
    token's activations, LD `gate_rows` + `up_rows` row segments of
    `hidden` / 8 bytes, ST its 1/64 of the expert's intermediate, LD block
    j of it, LD `down_rows` row segments of `inter` / 8 bytes, ST its 1/64
    of the expert's output; visit v streams the segments from v * rows on.
    Core t mod 64 of token t's home then loads that slice of each of its
    experts' outputs. Activations lie from 0, then the intermediates, the
    outputs, and from the next 256 KB every core's weights: the segments
    `tokens` visits can reach, rounded up to an odd count of lines."""
    if tokens < 1 or experts < 1 or n_cores != GROUP * experts:
        raise ValueError("tokens >= 1, and an expert is 64 cores: n_cores = 64 * experts")
    if n_group < 1 or experts % n_group or not 1 <= topk_group <= n_group \
            or not 1 <= top_k <= topk_group * (experts // n_group):
        raise ValueError("n_group divides experts; topk_group of them hold top_k experts")
    if hidden < 1 or inter < 1 or hidden % (8 * LINE) or inter % (8 * LINE):
        raise ValueError("an eighth of a gate row and of a down row is whole lines")
    gseg, dseg = hidden // (8 * LINE), inter // (8 * LINE)
    rows = np.array([gate_rows, up_rows, down_rows])
    full = np.array([inter // 8, inter // 8, hidden // 8])
    if skew_milli < 0 or ins_per_mem < 1 or (rows < 1).any() or (rows > full).any():
        raise ValueError("skew_milli >= 0, ins_per_mem >= 1, 1 <= rows <= what a core holds")
    held = np.minimum(full, tokens * rows)
    seg_lines = np.array([gseg, gseg, dseg])
    at = np.concatenate([[0], np.cumsum(held * seg_lines)])  # gate, up, down, the end
    stride = int(at[3]) | 1
    inter_base = tokens * hidden
    out_base = inter_base + experts * inter
    w_base = -(-(out_base + experts * 2 * hidden) // (BANKS * LINE)) * BANKS * LINE
    if w_base + n_cores * stride * LINE > 2**31:
        raise ValueError("the weights a step touches do not fit under 2^31: fewer tokens or rows")

    rng = np.random.default_rng(seed)
    routed, home = route(rng, tokens, experts, top_k, n_group, topk_group, skew_milli / 1000.0)
    n_visits = routed.sum(0)  # [experts]
    V = int(n_visits.max())
    token_of = np.argsort(~routed.T, axis=1, kind="stable")[:, :V]  # [experts, V], rising

    # LD or ST of each of a visit's reference slots
    slot_kind = np.concatenate([
        np.full((1 + gate_rows + up_rows) * gseg, EV_LD), [EV_ST],
        np.full((1 + down_rows) * dseg, EV_LD), [EV_ST]])
    core = np.arange(n_cores)
    e, q = core // GROUP, core % GROUP
    j = q % 8
    w = (w_base + core * stride * LINE)[:, None, None]
    v = np.arange(V)[None, :, None]
    tok = token_of[e][:, :, None]  # [C, V, 1]
    scratch = (inter_base + e * inter)[:, None, None]
    out = (out_base + e * 2 * hidden)[:, None, None]

    def stream(kind: int, n: int) -> np.ndarray:
        """The lines of `n` row segments of matrix `kind` from visit v's first."""
        r, l = np.divmod(np.arange(n * seg_lines[kind]), seg_lines[kind])
        seg = (v * rows[kind] + r[None, None, :]) % held[kind]
        return w + (at[kind] + seg * seg_lines[kind] + l[None, None, :]) * LINE

    def every_visit(a: np.ndarray) -> np.ndarray:
        return np.broadcast_to(a, (n_cores, V, a.shape[2]))

    visit = np.concatenate([
        tok * hidden + (j[:, None, None] * gseg + np.arange(gseg)) * LINE,
        stream(0, gate_rows), stream(1, up_rows),
        every_visit(scratch + (q * (inter // 64))[:, None, None]),
        every_visit(scratch + (j[:, None, None] * dseg + np.arange(dseg)) * LINE),
        stream(2, down_rows),
        every_visit(out + (q * (2 * hidden // 64))[:, None, None]),
    ], axis=2)
    in_visit = np.broadcast_to((np.arange(V)[None, :] < n_visits[e][:, None])[:, :, None],
                               visit.shape)

    # the combines: token t on core t mod 64 of its home, in rising order of t
    comb_core = GROUP * home + np.arange(tokens) % GROUP
    M = int(np.bincount(comb_core, minlength=n_cores).max())
    comb = np.zeros((n_cores, M, top_k), np.int64)
    has_comb = np.zeros((n_cores, M, top_k), bool)
    filled = np.zeros(n_cores, np.int64)
    for t in range(tokens):
        c = comb_core[t]
        comb[c, filled[c]] = out_base + np.flatnonzero(routed[t]) * 2 * hidden \
            + (t % GROUP) * (2 * hidden // 64)
        has_comb[c, filled[c]] = True
        filled[c] += 1

    addrs = np.concatenate([visit.reshape(n_cores, -1), comb.reshape(n_cores, -1)], axis=1)
    used = np.concatenate([in_visit.reshape(n_cores, -1), has_comb.reshape(n_cores, -1)], axis=1)
    kinds = np.concatenate([np.tile(slot_kind, V), np.full(M * top_k, EV_LD)])
    # a core's references close up, the combines behind its last visit
    n_refs = int(used.sum(1).max())
    order = np.argsort(~used, axis=1, kind="stable")[:, :n_refs]
    kept = np.take_along_axis(used, order, axis=1)
    pre = rng.integers(1, 2 * ins_per_mem + 1, (n_cores, n_refs))
    return finish(np.where(kept, kinds[order], EV_END), np.where(kept, 4, 0),
                  np.where(kept, np.take_along_axis(addrs, order, axis=1), 0),
                  np.where(kept, pre, 0))
