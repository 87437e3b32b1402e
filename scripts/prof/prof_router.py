"""Where do the rung-3 ms go? Phase cuts for the FIFO-contention path.

Self-contained component timings at the SHIPPED rung-3 shapes
(`configs/rung3_1024core_o3.json`: 1024 cores, 32x32 mesh -> H=62 hop
columns, 4096 directed links, 1024 DRAM banks) isolating the three
costs of the router + DRAM-queue step tail (DESIGN.md §13):

- `rank`: the same-step FIFO rank primitive — the shipped sort-based
  `ops.ranking.segmented_rank` (O(E log E)) vs the retired one-hot
  matmul formulation ([C,C] int8 kless x [C,NL] one-hot, O(C^2 * NL)
  MACs) it replaced, at identical shapes. This is the cut that moved
  rung 3 from ~1296 to ~67 ms/step on a 1-core CPU container.
- `cascade`: the wait-floor + per-leg cummax cascade + departures, the
  closed form `sim/step.py::_router_walk` computes.
- `links`: the walk's per-link state. Shipped (PR 31): it rides the
  rank's sorted order, `ops.ranking.segmented_rank_floor` (rank, the
  link's earliest nominal arrival and its next-free clock out of one
  sort, two segmented scans and one sort back) and
  `segmented_table_max` (the departures into the clocks: one sort, one
  scan, NL reads); its pieces alone (`sort`, `scan`) beside it. Against
  the retired element form it replaced: the base scatter-min, the per-hop
  link_free/base gather pair and the departure scatter-max, each over
  all C * legs * H slots of a table of NL words.

Plus whole-step ms/step on the full rung-3 machine (the end-to-end
number the components should sum toward). No source surgery — everything here calls shipped
entry points, so this tool cannot rot silently.

`python scripts/prof/prof_router.py fleet [entries [B,B,... [form,...]]]`
(PR 48) times the walk's three sorts alone under a batch axis, in the
four forms `vmap` can be given (`fleet_cuts`; 131072 entries a machine, B
of 4 and 16 and every form unless told), and prints a table for
`scripts/prof/README.md`.

Usage: `python scripts/prof/prof_router.py` · env:
`PRIMETPU_PROF_MATMUL=0` skips the retired-matmul reference row (it is
deliberately the slow one), `PRIMETPU_PROF_WHOLE=0` the whole-step rows, `PRIMETPU_PROF_STEPS` (default 16) sizes
the whole-step chunks.
"""
import functools
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from primesim_tpu.config.machine import MachineConfig
from primesim_tpu.ops import ranking
from primesim_tpu.ops.ranking import (
    lane_order,
    segmented_rank,
    segmented_rank_floor,
    segmented_table_max,
)
from primesim_tpu.sim.engine import run_chunk
from primesim_tpu.sim.state import init_state
from primesim_tpu.trace import synth
from primesim_tpu.trace.format import fold_ins

SENT = -(1 << 30) - (1 << 21)  # `_router_walk`'s: under any real wait floor
R3 = os.path.join(os.path.dirname(__file__), "..", "..", "configs",
                  "rung3_1024core_o3.json")


def timed(fn, *args, runs=3, tag=""):
    """jit + compile warm-up + best-of-N; host-transfer sync (np.asarray
    of a leaf — the round-3 under-sync lesson, see prof_step.py)."""
    f = jax.jit(fn)
    out = f(*args)
    np.asarray(jax.tree_util.tree_leaves(out)[0])
    walls = []
    for _ in range(runs):
        t0 = time.perf_counter()
        out = f(*args)
        np.asarray(jax.tree_util.tree_leaves(out)[0])
        walls.append(time.perf_counter() - t0)
    ms = min(walls) * 1e3
    print(f"[{tag}] {ms:.3f} ms", flush=True)
    return ms


def router_shapes(cfg, seed=0):
    """Random operands at the engine's router-block shapes: per-lane
    FIFO keys, per-(lane,slot) link targets (within-lane distinct, the
    contract segmented_rank assumes), wait floors, masks."""
    rng = np.random.default_rng(seed)
    C = cfg.n_cores
    NL = cfg.n_tiles * 4
    H = max(1, (cfg.noc.mesh_x - 1) + (cfg.noc.mesh_y - 1))
    LT = 2 * H  # the first leg (request, or barrier arrival) + the reply
    key = jnp.asarray(
        rng.integers(0, 1 << 20, C).astype(np.int32) * C
        + np.arange(C, dtype=np.int32)
    )
    base_l = rng.integers(0, NL - LT, (C, 1)).astype(np.int32)
    tgt = jnp.asarray(base_l + np.arange(LT, dtype=np.int32)[None, :])
    ok = jnp.asarray(rng.random((C, LT)) < 0.7)
    tgt = jnp.where(ok, tgt, NL)
    lf = jnp.asarray(rng.integers(0, 1000, (C, LT)).astype(np.int32))
    bs = jnp.asarray(rng.integers(0, 1000, (C, LT)).astype(np.int32))
    t0 = jnp.asarray(rng.integers(0, 500, C).astype(np.int32))
    sv = jnp.asarray(rng.integers(1, 80, C).astype(np.int32))
    nh = jnp.asarray(rng.integers(0, H + 1, (2, C)).astype(np.int32))
    return dict(C=C, NL=NL, H=H, LT=LT, key=key, tgt=tgt, ok=ok,
                lf=lf, bs=bs, t0=t0, sv=sv, nh=nh)


def rank_cuts(s):
    def sort_rank(key, tgt):
        return segmented_rank(tgt, n_seg=s["NL"], order=lane_order(key))

    def matmul_rank(key, tgt):
        # the retired formulation: strict-less MXU product against the
        # per-slot one-hot competitor matrix, then per-slot gather
        kless = (key[None, :] < key[:, None]).astype(jnp.int8)
        seg = jnp.clip(tgt, 0, s["NL"] - 1)
        U = jnp.zeros((s["C"], s["NL"]), jnp.int8)
        U = U.at[jnp.arange(s["C"])[:, None], seg].set(1)
        full = jax.lax.dot_general(
            kless, U, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
        return jnp.take_along_axis(full, seg, axis=1)

    timed(sort_rank, s["key"], s["tgt"], tag="rank: sort segmented_rank")
    if os.environ.get("PRIMETPU_PROF_MATMUL", "1") != "0":
        timed(matmul_rank, s["key"], s["tgt"],
              tag="rank: retired one-hot matmul")


def cascade_cuts(s, cfg):
    H, LT = s["H"], s["LT"]
    L_lat = jnp.int32(cfg.noc.link_lat)
    R_lat = jnp.int32(cfg.noc.router_lat)
    r = segmented_rank(s["tgt"], n_seg=s["NL"], order=lane_order(s["key"]))

    def xla_cascade(lf, bs, r, ok, t0, sv, nh):
        c_hop = L_lat + R_lat
        hidx = jnp.arange(H, dtype=jnp.int32)[None, :]
        F = jnp.where(ok, jnp.maximum(lf, bs) + r * L_lat, SENT)

        def leg(t_start, Fl, n):
            G = Fl - hidx * c_hop
            cum = jax.lax.cummax(G, axis=1)
            t1 = t_start + R_lat
            t_end = jnp.maximum(t1, cum[:, -1]) + n * c_hop
            return t_end, jnp.maximum(t1[:, None], cum) + hidx * c_hop + L_lat

        te_req, d_req = leg(t0, F[:, :H], nh[0])
        te_rep, d_rep = leg(te_req + sv, F[:, H:], nh[1])
        return te_rep, te_req, jnp.concatenate([d_req, d_rep], axis=1)

    a = (s["lf"], s["bs"], r, s["ok"], s["t0"], s["sv"], s["nh"])
    timed(xla_cascade, *a, tag="cascade: xla closed form")


ITER = 50


def timed_loop(body, init, tag):
    """`body` (iteration, carry -> carry) ITER times inside one
    `fori_loop`, its outputs the next iteration's inputs: the time is the
    device's and holds no dispatch (prof_gather.py's way). ms an
    iteration."""
    f = jax.jit(lambda c: jax.lax.fori_loop(0, ITER, body, c))
    jax.block_until_ready(f(init))
    walls = []
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(f(init))
        walls.append(time.perf_counter() - t0)
    ms = min(walls) / ITER * 1e3
    print(f"[{tag}] {ms:.4f} ms", flush=True)
    return ms


def link_cuts(s):
    # the walk's two legs (a barrier arrival rides the first: PR 52):
    # E = 126976 at rung 3
    NL = s["NL"]
    E = s["C"] * s["LT"]
    rng = np.random.default_rng(1)
    lf0 = jnp.asarray(rng.integers(-(1 << 30), 1000, NL).astype(np.int32))
    tgt, a0, d0 = s["tgt"], s["bs"], s["lf"] + 7
    ordr = lane_order(s["key"])
    edge = jnp.asarray(rng.random(E) < 0.03)

    def sorted_floor(i, c):
        a, lf = c
        r, fl, _ = segmented_rank_floor(tgt, a, lf, order=ordr)
        return fl + r, lf + i

    def sorted_both(i, c):
        a, d, lf = c
        r, fl, runs = segmented_rank_floor(tgt, a, lf, order=ordr)
        return fl + r, d + i, segmented_table_max(runs, d, lf)

    def rank_alone(i, o):  # the key order turns, so no iteration is hoisted
        return (o + segmented_rank(tgt, n_seg=NL, order=o)[:, 0]) % s["C"]

    def sort_alone(i, c):
        k, a = c
        _, sidx, sa = jax.lax.sort(
            (k, jnp.arange(E, dtype=jnp.int32), a), num_keys=1,
            is_stable=False)
        return sa ^ i, sidx

    def scan_alone(i, x):
        return ranking._segmented_scan(x, edge, jnp.minimum) ^ i

    def retired_floor(i, c):
        a, lf = c
        base = jnp.full(NL, (1 << 31) - 1, jnp.int32)
        base = base.at[tgt].min(a, mode="drop")
        pc = jnp.clip(tgt, 0, NL - 1)
        return jnp.maximum(lf[pc], base[pc]) + i, lf + i

    def retired_max(i, c):
        d, lf = c
        return d + i, lf.at[tgt].max(d, mode="drop")

    timed_loop(rank_alone, ordr, "links: the rank alone (segmented_rank)")
    timed_loop(sorted_floor, (a0, lf0),
               "links: sorted rank + floor (segmented_rank_floor)")
    timed_loop(sorted_both, (a0, d0, lf0),
               "links: sorted, both passes (+ segmented_table_max)")
    timed_loop(sort_alone, (tgt.reshape(-1), a0.reshape(-1)),
               "links: one sort of the entries, two payloads")
    timed_loop(scan_alone, a0.reshape(-1),
               "links: one segmented scan of the entries")
    timed_loop(retired_floor, (a0, lf0),
               "links: retired base scatter-min + per-hop gather pair")
    timed_loop(retired_max, (d0, lf0),
               "links: retired departure scatter-max")


FLEET_SORTS = ((3, "rank+floor"), (4, "back"), (2, "table max"))


def fleet_cuts(n=131072, batches=(4, 16), only=None):
    """The router walk's three entry sorts under a batch axis (PERF.md
    section 6, PR 48): `n` entries a machine (rung 3's E + NL = 131072,
    with or without sync events since PR 52), 3, 4 and 2 operands, one
    key; B machines as `vmap` batches a sort (`[B, n]` along its last
    axis: the form until PR 48), as ONE flat sort of B·n with the
    machine's number in the key, as B solo sorts in a `lax.map`
    (`jax.custom_batching.sequential_vmap`; both tried in PR 48, not
    shipped) and as B solo sorts unrolled, each on its machine's rows
    (what `ranking._entry_sort` is); `only` names the forms to time.
    Beside them ONE solo sort of n, 2n, 4n and 16n entries, what a flat
    sort of B·n can cost at best. The keys are permutations of the
    entries, as the walk's second and third sorts have them, and each
    iteration sorts what the last one returned. ms an iteration, all B
    machines."""

    def plain(*ops):
        return tuple(jax.lax.sort(ops, num_keys=1, is_stable=False))

    def flat(*ops):
        shape = ops[0].shape
        off = jnp.arange(shape[0], dtype=jnp.int32)[:, None] * jnp.int32(n)
        out = plain(*(x.reshape(-1) for x in (ops[0] + off, *ops[1:])))
        out = [x.reshape(shape) for x in out]
        return (out[0] - off, *out[1:])

    def unrolled(*ops):
        rows = [plain(*(x[b] for x in ops)) for b in range(ops[0].shape[0])]
        return tuple(jnp.stack(x) for x in zip(*rows))

    forms = {
        "batched": jax.vmap(plain),
        "flat": flat,
        "sequential": jax.vmap(jax.custom_batching.sequential_vmap(plain)),
        "unrolled": unrolled,
    }
    forms = {k: v for k, v in forms.items() if only is None or k in only}

    def body(sort):
        def step(i, ops):  # two permutations go in, two come out
            out = sort(*ops)
            return (out[1], out[0], *(x ^ i for x in out[2:]))
        return step

    def operands(rng, shape, k):
        perm = rng.permuted(
            np.broadcast_to(np.arange(shape[-1], dtype=np.int32), shape), axis=-1)
        rest = [rng.integers(-(1 << 30), 1 << 30, shape).astype(np.int32)
                for _ in range(k - 2)]
        return tuple(jnp.asarray(x) for x in (
            perm, np.broadcast_to(np.arange(shape[-1], dtype=np.int32), shape),
            *rest))

    rng = np.random.default_rng(2)
    rows = []  # (what, operands, form, machines' worth of entries, ms)
    for k, what in FLEET_SORTS:
        for m in (1, 2, 4, 16):
            ms = timed_loop(body(plain), operands(rng, (m * n,), k),
                            f"fleet: solo sort of {m * n}, {k} operands")
            rows.append((what, k, "solo", m, ms))
        for B in batches:
            for form, sort in forms.items():
                ms = timed_loop(body(sort), operands(rng, (B, n), k),
                                f"fleet: {what}, {k} operands, B={B}, {form}")
                rows.append((what, k, form, B, ms))
    solo = {k: ms for _, k, form, m, ms in rows if form == "solo" and m == 1}
    print(f"\n| sort ({n} entries a machine) | operands | form | size | "
          f"ms, all machines | x B solo sorts of {n} |\n|---|---|---|---|---|---|")
    for what, k, form, m, ms in rows:
        size = f"{m * n}" if form == "solo" else f"B={m}"
        print(f"| {what} | {k} | {form} | {size} | {ms:.4f} | "
              f"{ms / (m * solo[k]):.2f} |")


def whole_step(cfg, n_steps):
    trace = fold_ins(synth.fft_like(
        cfg.n_cores, n_phases=2, points_per_core=16, ins_per_mem=8, seed=42))
    events = jnp.asarray(trace.line_events(cfg.line_bits))
    st = init_state(cfg)
    st = run_chunk(cfg, n_steps, events, st, has_sync=True)
    np.asarray(st.step)
    t0 = time.perf_counter()
    for _ in range(2):
        st = run_chunk(cfg, n_steps, events, st, has_sync=True)
    np.asarray(st.step)
    ms = (time.perf_counter() - t0) / 2 / n_steps * 1e3
    print(f"[whole rung-3 step] {ms:.3f} ms/step", flush=True)


if __name__ == "__main__":
    print("devices:", jax.devices(), flush=True)
    if sys.argv[1:2] == ["fleet"]:  # fleet [entries a machine [B,B,... [form,...]]]
        n, batches, only = (sys.argv[2:] + ["131072", "4,16", ""][len(sys.argv) - 2:])[:3]
        fleet_cuts(int(n), tuple(int(b) for b in batches.split(",")),
                   only.split(",") if only else None)
        raise SystemExit(0)
    with open(R3) as f:
        cfg = MachineConfig.from_json(f.read())
    s = router_shapes(cfg)
    print(f"shapes: C={s['C']} NL={s['NL']} H={s['H']} legs*H={s['LT']}")
    rank_cuts(s)
    cascade_cuts(s, cfg)
    link_cuts(s)
    if os.environ.get("PRIMETPU_PROF_WHOLE", "1") != "0":
        n = int(os.environ.get("PRIMETPU_PROF_STEPS", "16"))
        whole_step(cfg, n)
