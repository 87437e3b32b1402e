"""The two VMEM-resident fused step kernels (DESIGN.md §11).

`probe_classify` fuses the step's phase-1 front half: the L1 set probe
(five-plane gather + local-run patch), the pointer validation of every
way against its directory entry, hit classification, the LLC home-row
parse (tags/owner/LRU/epoch/sharers), the sharer-set predicates
(popcount, self bit), and victim selection — previously ~a dozen serial
XLA gather kernels, now one kernel over core blocks with the needed
directory rows STAGED into VMEM by two XLA row gathers (the one access
shape Pallas cannot beat XLA at; see the fusion-boundary contract in
DESIGN.md §11).

`commit_step` fuses the back half (the L1 and directory scatters and the
counter fold at the end of the scope `s.commit`): all 7 + 2*rl L1 plane
writes, the winner's full directory-row delta + join contributions, and
the stacked counter fold — emitting the new L1 block, the per-core [DW] row delta (the
engine applies the one remaining data-dependent row scatter-add), and
the folded counters.

Both kernels are written in the Mosaic-safe idioms of layouts.py (static
masked selects instead of gathers, first-occurrence emulations of
argmax/argmin, iota column arithmetic instead of reshapes) and are
BIT-EXACT vs the XLA step: same integer arithmetic, same tie-breaking,
same duplicate-write resolution (tests/test_step_pallas.py proves
golden/xla/pallas three-way parity on every workload generator,
including coarse-directory and fleet-vmapped paths). Core ids arrive as
a [BC, 1] input — never pl.program_id — so jax.vmap batching (the fleet
engine) stays correct, and traced step scalars ride as (1, 1) blocks so
timing sweeps never recompile.

FAULT-LANE CONTRACT (DESIGN.md §12). Fault injection is deliberately
IMPLEMENTATION-AGNOSTIC: every architectural fault effect lands outside
the kernel fusion boundary, so `step_impl=pallas` and `step_impl=xla`
see byte-identical operands and need no fault-specific code paths.
Concretely: the fail-stop directory scrub rewrites `dirm` BEFORE the
phase-1 row gathers stage it; dead cores are removed from the lane
predicates (`countable`/`active`/local-run `pref`) that gate what these
kernels classify and commit; NoC detour latencies and reroute/ECC
counter deltas are added to the composed per-lane latencies and counter
fold AFTER `commit_step` returns (the fold derives its width from
`counters.shape[0]`, so the four fault counters flow through the stacked
fold untouched). A faults-off config reaches these kernels with bit-
identical inputs to a build without the fault subsystem at all.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..config.machine import MachineConfig
from ..sim.state import I, M, S, dirm_width, llc_meta_width
from .layouts import (
    across,
    core_block,
    interpret_mode,
    popcount,
    select_col,
)

# probe_classify packed-lane indices (column k of the [C, PROBE_LANES]
# output): the scalar classification results phase 2/3 consume
(
    PL_HIT_ANY,
    PL_HIT_WAY,
    PL_HIT_STATE,
    PL_LLC_HAS,
    PL_LLC_HWAY,
    PL_OWNER,
    PL_SELF_BIT,
    PL_OTHER_SH,
    PL_VIC_TAG,
    PL_VIC_OWNER,
    PL_LLC_VWAY,
) = range(11)
PROBE_LANES = 11

# commit_step packed-lane indices (column k of the [C, COMMIT_LANES]
# input): every phase-2/3 scalar the fused tail needs
(
    CL_LINE,
    CL_HIT_WAY,
    CL_L1_VWAY,
    CL_HIT,
    CL_WRITE_HIT,
    CL_UPG_IN_PLACE,
    CL_WINNER,
    CL_JOIN,
    CL_LLC_HIT,
    CL_ST_VAL,
    CL_SLOT,
    CL_LLC_HWAY,
    CL_LLC_VWAY,
    CL_JREP,
    CL_TAKES_OWN,
    CL_GETS_PROBE,
    CL_GETS_SHARED,
    CL_OCLAMP,
) = range(18)
COMMIT_LANES = 18


def _sel_list(vals, idx):
    """vals[idx] over a python list of [BC, 1] columns (static unroll)."""
    acc = jnp.zeros_like(idx)
    for k, v in enumerate(vals):
        acc = acc + jnp.where(idx == k, v, 0)
    return acc


def _first_idx(masks, default: int):
    """Index of the first True across a python list of [BC, 1] bools
    (jnp.argmax tie-breaking), `default` when none."""
    idx = jnp.full_like(masks[0].astype(jnp.int32), default)
    for w in reversed(range(len(masks))):
        idx = jnp.where(masks[w], w, idx)
    return idx


def _probe_kernel(
    *refs, C: int, S1: int, W1: int, W2: int, NW: int, MW: int, DW: int,
    G: int, rl: int,
):
    FS = W1 * S1
    n_in = 6 + (3 if rl else 0)
    l1_ref, vrows_ref, mrows_ref, line_ref, cid_ref, step_ref = refs[:6]
    if rl:
        hm_ref, wm_ref, cm_ref = refs[6:9]
    tag_out, lru_out, weff_out, shw_out, vshw_out, lane_out = refs[n_in:]

    l1 = l1_ref[...]
    vrows = vrows_ref[...]
    mrows = mrows_ref[...]
    line = line_ref[...]  # [BC, 1]
    cid = cid_ref[...]
    step_no = step_ref[...]  # [1, 1], broadcasts

    # ---- L1 set probe: five planes x W1 ways via one-hot set select ----
    l1s = line & (S1 - 1)
    set_oh = jax.lax.broadcasted_iota(jnp.int32, (1, S1), 1) == l1s

    def pick(p, w):  # plane p, way w of the accessed set -> [BC, 1]
        c0 = p * FS + w * S1
        return jnp.sum(
            jnp.where(set_oh, l1[:, c0 : c0 + S1], 0), axis=1, keepdims=True
        )

    tag_w = [pick(0, w) for w in range(W1)]
    st_w = [pick(1, w) for w in range(W1)]
    lru_w = [pick(2, w) for w in range(W1)]
    ptr_w = [pick(3, w) for w in range(W1)]
    eph_w = [pick(4, w) for w in range(W1)] if G > 1 else None
    if rl:
        # the local run's deferred L1 writes patched in-register (silent
        # E->M at wm columns, LRU stamps at hm columns) — same values
        # regardless of which run slot matched, so sequential wheres
        # reproduce _l1_probe's any()-collapsed patch exactly
        hmm, wmm, cmm = hm_ref[...], wm_ref[...], cm_ref[...]
        for w in range(W1):
            wcol = w * S1 + l1s
            for k in range(rl):
                mk = cmm[:, k : k + 1] == wcol
                st_w[w] = jnp.where(
                    (wmm[:, k : k + 1] != 0) & mk, M, st_w[w]
                )
                lru_w[w] = jnp.where(
                    (hmm[:, k : k + 1] != 0) & mk, step_no, lru_w[w]
                )

    # ---- pointer validation (sim/step._validate_ways semantics) ------
    logG = G.bit_length() - 1
    g_c = cid >> logG
    u_w = g_c >> 5  # self -> sharer word / bit (group id under Dir-G)
    u_b = g_c & 31
    weff_w = []
    for w in range(W1):
        pway = ptr_w[w] % W2  # ptr = slot*W2 + way, nonneg
        base = w * DW
        vtag = select_col(vrows, pway, W2, lambda v: base + 2 * v)
        vown = select_col(vrows, pway, W2, lambda v: base + 2 * v + 1)
        # sharer word: way select over NW-wide segments, then word select
        row_w = jnp.zeros((line.shape[0], NW), jnp.int32)
        for v in range(W2):
            c0 = base + MW + v * NW
            row_w = row_w + jnp.where(pway == v, vrows[:, c0 : c0 + NW], 0)
        vsh = select_col(row_w, u_w, NW)
        vbit = ((vsh >> u_b) & 1) != 0
        if G > 1:
            veph = select_col(vrows, pway, W2, lambda v: base + 3 * W2 + v)
            vbit = vbit & (veph == eph_w[w])
        weff_w.append(
            jnp.where(
                (st_w[w] == I) | (vtag != tag_w[w]),
                I,
                jnp.where(vown == cid, st_w[w], jnp.where(vbit, S, I)),
            )
        )

    # ---- hit classification -------------------------------------------
    match_w = [(tag_w[w] == line) & (weff_w[w] != I) for w in range(W1)]
    hit_any = functools.reduce(jnp.logical_or, match_w)
    hit_way = jnp.where(hit_any, _first_idx(match_w, W1), 0)
    hit_state = _sel_list(weff_w, hit_way)

    # ---- LLC home-row parse -------------------------------------------
    ltag_w = [mrows[:, 2 * v : 2 * v + 1] for v in range(W2)]
    lown_w = [mrows[:, 2 * v + 1 : 2 * v + 2] for v in range(W2)]
    lmatch = [ltag_w[v] == line for v in range(W2)]
    llc_has = functools.reduce(jnp.logical_or, lmatch)
    llc_hway = jnp.where(llc_has, _first_idx(lmatch, W2), 0)
    owner = _sel_list(lown_w, llc_hway)
    shw = jnp.zeros((line.shape[0], NW), jnp.int32)
    for v in range(W2):
        c0 = MW + v * NW
        shw = shw + jnp.where(llc_hway == v, mrows[:, c0 : c0 + NW], 0)

    # sharer-set predicates from the packed words
    self_bit = (select_col(shw, u_w, NW) >> u_b) & 1
    total = jnp.sum(popcount(shw), axis=1, keepdims=True)
    if G > 1:
        # coarse: the requester's own group bit may cover OTHER cores
        other_sh = total > 0
    else:
        other_sh = (total - self_bit) > 0

    # ---- victim selection (first-minimum LRU over valid ways) ----------
    vkey_w = [
        jnp.where(ltag_w[v] != -1, mrows[:, 2 * W2 + v : 2 * W2 + v + 1], -1)
        for v in range(W2)
    ]
    vmin = functools.reduce(jnp.minimum, vkey_w)
    llc_vway = _first_idx([vkey_w[v] == vmin for v in range(W2)], 0)
    vic_tag = _sel_list(ltag_w, llc_vway)
    vic_owner = _sel_list(lown_w, llc_vway)
    vic_shw = jnp.zeros((line.shape[0], NW), jnp.int32)
    for v in range(W2):
        c0 = MW + v * NW
        vic_shw = vic_shw + jnp.where(llc_vway == v, mrows[:, c0 : c0 + NW], 0)

    tag_out[...] = across(tag_w, W1)
    lru_out[...] = across(lru_w, W1)
    weff_out[...] = across(weff_w, W1)
    shw_out[...] = shw
    vshw_out[...] = vic_shw
    lane_out[...] = across(
        [
            hit_any, hit_way, hit_state, llc_has, llc_hway, owner,
            self_bit, other_sh, vic_tag, vic_owner, llc_vway,
        ],
        PROBE_LANES,
    )


@functools.partial(jax.jit, static_argnums=(0,))
def probe_classify(
    cfg: MachineConfig, l1, vrows, mrows, line, arange_c, step_no,
    hm=None, wm=None, cm=None,
):
    """Fused phase 1: returns (tag_rows, lru_rows, weff) [C, W1],
    (shw, vic_shw) [C, NW], and the packed classification lanes
    [C, PROBE_LANES] (see PL_* indices). `vrows` is dirm[ptr//W2]
    flattened to [C, W1*DW] (XLA-staged), `mrows` is dirm[slot] [C, DW];
    `hm/wm/cm` carry the local run's deferred L1 patch when
    cfg.local_run_len > 0."""
    C = cfg.n_cores
    S1, W1 = cfg.l1.sets, cfg.l1.ways
    W2 = cfg.llc.ways
    NW = cfg.n_sharer_words
    MW = llc_meta_width(cfg)
    DW = dirm_width(cfg)
    FS = W1 * S1
    BC = core_block(C)
    rl = 0 if hm is None else hm.shape[1]
    kern = functools.partial(
        _probe_kernel, C=C, S1=S1, W1=W1, W2=W2, NW=NW, MW=MW, DW=DW,
        G=cfg.sharer_group, rl=rl,
    )
    col = lambda i: (i, 0)
    scal = lambda i: (0, 0)
    in_specs = [
        pl.BlockSpec((BC, 5 * FS), col),
        pl.BlockSpec((BC, W1 * DW), col),
        pl.BlockSpec((BC, DW), col),
        pl.BlockSpec((BC, 1), col),
        pl.BlockSpec((BC, 1), col),
        pl.BlockSpec((1, 1), scal),
    ]
    ins = [
        l1,
        vrows,
        mrows,
        line.astype(jnp.int32)[:, None],
        arange_c.astype(jnp.int32)[:, None],
        jnp.asarray(step_no, jnp.int32).reshape(1, 1),
    ]
    if rl:
        in_specs += [pl.BlockSpec((BC, rl), col)] * 3
        ins += [hm.astype(jnp.int32), wm.astype(jnp.int32), cm]
    return pl.pallas_call(
        kern,
        grid=(C // BC,),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((BC, W1), col)] * 3
        + [pl.BlockSpec((BC, NW), col)] * 2
        + [pl.BlockSpec((BC, PROBE_LANES), col)],
        out_shape=[jax.ShapeDtypeStruct((C, W1), jnp.int32)] * 3
        + [jax.ShapeDtypeStruct((C, NW), jnp.int32)] * 2
        + [jax.ShapeDtypeStruct((C, PROBE_LANES), jnp.int32)],
        interpret=interpret_mode(),
    )(*ins)


def _commit_kernel(
    *refs, NC: int, S1: int, W1: int, W2: int, NW: int, MW: int, DW: int,
    G: int, rl: int, moesi: bool,
):
    FS = W1 * S1
    n_in = 9 + (3 if rl else 0)
    (
        l1_ref, mrows_ref, tag_ref, shw_ref, lanes_ref, cid_ref, step_ref,
        cnt_ref, delta_ref,
    ) = refs[:9]
    if rl:
        hm_ref, wm_ref, cm_ref = refs[9:12]
    l1_out, drow_out, cnt_out = refs[n_in:]

    lanes = lanes_ref[...]

    def lane(k):
        return lanes[:, k : k + 1]

    def laneb(k):
        return lanes[:, k : k + 1] != 0

    mrows = mrows_ref[...]
    shw = shw_ref[...]
    cid = cid_ref[...]
    step_no = step_ref[...]  # [1, 1]
    line = lane(CL_LINE)
    hit_way = lane(CL_HIT_WAY)
    l1_vway = lane(CL_L1_VWAY)
    st_val = lane(CL_ST_VAL)
    slot = lane(CL_SLOT)
    llc_hway = lane(CL_LLC_HWAY)
    llc_vway = lane(CL_LLC_VWAY)
    oclamp = lane(CL_OCLAMP)
    hitb = laneb(CL_HIT)
    write_hit = laneb(CL_WRITE_HIT)
    upg_w = laneb(CL_UPG_IN_PLACE)
    winner = laneb(CL_WINNER)
    join = laneb(CL_JOIN)
    llc_hit = laneb(CL_LLC_HIT)
    jrep = laneb(CL_JREP)
    takes_own = laneb(CL_TAKES_OWN)
    gets_probe = laneb(CL_GETS_PROBE)
    gets_shared = laneb(CL_GETS_SHARED)

    # ---- L1 plane writes (phase 4.A's single fused scatter) ------------
    l1s = line & (S1 - 1)
    upd_way = jnp.where(upg_w, hit_way, l1_vway)
    hit_col = hit_way * S1 + l1s
    upd_col = upd_way * S1 + l1s
    fill = (winner & ~upg_w) | join
    tag_rows = tag_ref[...]
    tagm = [tag_rows[:, w : w + 1] == line for w in range(W1)]
    t_way = _first_idx(tagm, 0)
    any_tagm = functools.reduce(jnp.logical_or, tagm)
    dup = fill & any_tagm & (t_way != upd_way)
    dup_col = t_way * S1 + l1s
    wj = winner | join
    lru_m = hitb | wj
    lru_col = jnp.where(hitb, hit_col, upd_col)
    st_m = write_hit | wj
    st_col = jnp.where(write_hit, hit_col, upd_col)
    llc_uway = jnp.where(llc_hit, llc_hway, llc_vway)
    eph_way = jnp.where(join, llc_hway, llc_uway)
    eph_old = select_col(mrows, eph_way, W2, lambda v: 3 * W2 + v)
    new_eph = eph_old + takes_own.astype(jnp.int32)
    fill_ptr = slot * W2 + jnp.where(join | llc_hit, llc_hway, llc_vway)

    cols5 = jax.lax.broadcasted_iota(jnp.int32, (1, 5 * FS), 1)
    blk = l1_ref[...]

    def wr(b, m, col, val):
        return jnp.where(m & (cols5 == col), val, b)

    # write set identical to the XLA scatter (targets pairwise distinct
    # up to benign identical-value duplicates — see engine phase 4.A);
    # the run writes go last with the same E->M suppression, matching
    # the serialized order the XLA comment argues from
    blk = wr(blk, dup, dup_col, -1)  # stale duplicate tag clear
    blk = wr(blk, dup, dup_col + FS, I)  # stale duplicate state clear
    blk = wr(blk, lru_m, lru_col + 2 * FS, step_no)  # LRU stamp
    blk = wr(blk, st_m, st_col + FS, st_val)  # silent E->M + grant state
    blk = wr(blk, wj, upd_col, line)  # fill tag
    blk = wr(blk, wj, upd_col + 3 * FS, fill_ptr)  # fill way pointer
    blk = wr(blk, wj, upd_col + 4 * FS, new_eph)  # fill-time epoch
    if rl:
        hmm, wmm, cmm = hm_ref[...], wm_ref[...], cm_ref[...]
        for k in range(rl):
            cmk = cmm[:, k : k + 1]
            blk = wr(blk, hmm[:, k : k + 1] != 0, cmk + 2 * FS, step_no)
            sup = (wmm[:, k : k + 1] != 0) & ~(st_m & (st_col == cmk))
            blk = wr(blk, sup, cmk + FS, M)
    l1_out[...] = blk

    # ---- directory row delta (engine "Directory update:" semantics) ----
    logG = G.bit_length() - 1
    g = cid >> logG
    iota_nw = jax.lax.broadcasted_iota(jnp.int32, (1, NW), 1)
    self_word = jnp.where(iota_nw == (g >> 5), jnp.int32(1) << (g & 31), 0)
    og = oclamp >> logG
    owner_word = jnp.where(
        iota_nw == (og >> 5), jnp.int32(1) << (og & 31), 0
    )
    new_owner = jnp.where(takes_own, cid, -1)
    probe_word = self_word | owner_word
    if moesi:
        # dirty sharing (DESIGN.md §25): a GETS probe leaves the probed
        # owner recorded (derived Owned) and accumulates sharers; shw is
        # always 0 here under mesi, so mesi output is unchanged
        new_owner = jnp.where(gets_probe, oclamp, new_owner)
        probe_word = shw | probe_word
    new_shw = jnp.where(
        gets_probe,
        probe_word,
        jnp.where(gets_shared, shw | self_word, 0),
    )
    join_word = self_word & ~shw

    jD = jax.lax.broadcasted_iota(jnp.int32, (1, DW), 1)
    old = mrows
    pairv = jnp.where((jD & 1) == 0, line, new_owner)
    jsh = jnp.maximum(jD - MW, 0)
    w_sh = jsh // NW
    n_sh = jsh - w_sh * NW
    shv = jnp.zeros(old.shape, jnp.int32)
    jwv = jnp.zeros(old.shape, jnp.int32)
    for n in range(NW):
        n_oh = n_sh == n
        shv = shv + jnp.where(n_oh, new_shw[:, n : n + 1], 0)
        jwv = jwv + jnp.where(n_oh, join_word[:, n : n + 1], 0)
    new_full = jnp.where(
        jD < 2 * W2,
        jnp.where((jD >> 1) == llc_uway, pairv, old),
        jnp.where(
            jD < 3 * W2,
            jnp.where(jD - 2 * W2 == llc_uway, step_no, old),
            jnp.where(
                jD < 4 * W2,
                jnp.where(jD - 3 * W2 == llc_uway, new_eph, old),
                jnp.where(
                    jD < MW, old, jnp.where(w_sh == llc_uway, shv, old)
                ),
            ),
        ),
    )
    old_lru_h = select_col(mrows, llc_hway, W2, lambda v: 2 * W2 + v)
    jdelta = jnp.where(jrep, step_no - old_lru_h, 0)
    join_row = jnp.where(jD == 2 * W2 + llc_hway, jdelta, 0) + jnp.where(
        (jD >= MW) & (w_sh == llc_hway), jwv, 0
    )
    drow_out[...] = jnp.where(
        winner, new_full - old, jnp.where(join, join_row, 0)
    )

    # ---- counter fold --------------------------------------------------
    cnt_out[...] = cnt_ref[...] + delta_ref[...]


@functools.partial(jax.jit, static_argnums=(0,))
def commit_step(
    cfg: MachineConfig, l1, mrows, tag_rows, shw, lanes, arange_c, step_no,
    counters, delta, hm=None, wm=None, cm=None,
):
    """Fused phase 4.A + counter fold: returns (l1_new [C, 5*W1*S1],
    delta_row [C, DW], counters_new [NC, C]). `lanes` packs the CL_*
    columns; `mrows`/`tag_rows`/`shw` come straight from probe_classify's
    staging/outputs; `delta` is the step's stacked counter delta
    [NC, C]. The caller applies the one remaining data-dependent row
    scatter: dirm.at[upd_slot].add(delta_row)."""
    C = cfg.n_cores
    S1, W1 = cfg.l1.sets, cfg.l1.ways
    W2 = cfg.llc.ways
    NW = cfg.n_sharer_words
    MW = llc_meta_width(cfg)
    DW = dirm_width(cfg)
    FS = W1 * S1
    BC = core_block(C)
    NC = counters.shape[0]
    rl = 0 if hm is None else hm.shape[1]
    kern = functools.partial(
        _commit_kernel, NC=NC, S1=S1, W1=W1, W2=W2, NW=NW, MW=MW, DW=DW,
        G=cfg.sharer_group, rl=rl, moesi=cfg.coherence == "moesi",
    )
    col = lambda i: (i, 0)
    scal = lambda i: (0, 0)
    row = lambda i: (0, i)  # counters block the LANE axis
    in_specs = [
        pl.BlockSpec((BC, 5 * FS), col),
        pl.BlockSpec((BC, DW), col),
        pl.BlockSpec((BC, W1), col),
        pl.BlockSpec((BC, NW), col),
        pl.BlockSpec((BC, COMMIT_LANES), col),
        pl.BlockSpec((BC, 1), col),
        pl.BlockSpec((1, 1), scal),
        pl.BlockSpec((NC, BC), row),
        pl.BlockSpec((NC, BC), row),
    ]
    ins = [
        l1,
        mrows,
        tag_rows,
        shw,
        lanes,
        arange_c.astype(jnp.int32)[:, None],
        jnp.asarray(step_no, jnp.int32).reshape(1, 1),
        counters,
        delta,
    ]
    if rl:
        in_specs += [pl.BlockSpec((BC, rl), col)] * 3
        ins += [hm.astype(jnp.int32), wm.astype(jnp.int32), cm]
    return pl.pallas_call(
        kern,
        grid=(C // BC,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((BC, 5 * FS), col),
            pl.BlockSpec((BC, DW), col),
            pl.BlockSpec((NC, BC), row),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((C, 5 * FS), jnp.int32),
            jax.ShapeDtypeStruct((C, DW), jnp.int32),
            jax.ShapeDtypeStruct((NC, C), jnp.int32),
        ],
        interpret=interpret_mode(),
    )(*ins)
