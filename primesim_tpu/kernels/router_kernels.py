"""Pallas TPU kernel for the hop-by-hop router's wait-floor + cascade
block (ISSUE 6 second prong; DESIGN.md §13) — the fourth resident kernel
of the step subsystem, behind the same config-gated `step_impl="pallas"`
selector as probe/classify and commit.

The router walk (sim/step.py::_router_walk, NocConfig contention_model="router")
composes, per leg of every home transaction, the same-step FIFO wait
floors F_k = max(link_free, base) + rank·link_lat at each hop k, runs
the closed-form contention cascade

    t_k = max(t0 + router_lat, cummax_{k'<=k}(F_k' - k'·c)) + k·c,
    c = link_lat + router_lat,

and emits per-hop link departures plus each leg's end time.  That is a
dense [BC, H] VMEM shape with NO data-dependent indexing — exactly what
the block model handles — so this kernel fuses the wait-floor selects,
three per-leg cummax cascades (request, reply, barrier-arrival), and
the departure composition into one pallas_call.  The sorted passes on
either side stay XLA (ops/ranking.py): the one that hands the kernel
each hop's rank and floor term max(link_free, base), and the one that
raises link_free to the departures the kernel returns.  The floor term
arrives as both `lf_all` and `bs_all`: the kernel forms their maximum.

VMEM LAYOUT (layouts.py geometry): every per-leg operand is a [BC, H]
core-axis block (H = mesh diameter, the -1-padded XY path width); lane
vectors ride as [BC, 1] columns; link/router latencies arrive as TRACED
(1, 1) scalar blocks — the jit key stays geometry-only, so fleet knob
sweeps compile once.  The lane-dim cummax is `layouts.cummax_rows`, a
static unroll of masked reduces (Mosaic has no lane scan); masked hops
carry the engine's SENT sentinel and never surface: their departures
scatter to the dropped NL index upstream.

Legs chain exactly like the XLA path: the reply leg starts at
t_req_end + service, the barrier-arrival leg (compiled only when the
trace has sync events — `has_sync` is jit-static) at t0.  All int32;
bit-exact vs XLA and the golden scalar walk (tests/test_router_pallas.py
three-way parity).  On the CPU the kernel runs in Pallas interpreter
mode (tier-1-gated); on a TPU Mosaic compiles it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .layouts import core_block, cummax_rows, interpret_mode

#: masked-hop wait floor; must equal the engine's router-block SENT
#: (more negative than any real floor, offset-safe under - hidx*c_hop)
SENT = -(1 << 30) - (1 << 21)


def _cascade_kernel(
    lf_req, bs_req, r_req, ok_req,
    lf_rep, bs_rep, r_rep, ok_rep,
    *refs,
    H: int, has_sync: bool,
):
    if has_sync:
        (lf_arr, bs_arr, r_arr, ok_arr, t0, service, req_hops, rep_hops,
         arr_hops, link, router,
         d_req_o, d_rep_o, d_arr_o, t_rep_o, t_arr_o) = refs
    else:
        (t0, service, req_hops, rep_hops, link, router,
         d_req_o, d_rep_o, t_rep_o) = refs
    L = link[...]  # [1, 1] traced knobs
    R = router[...]
    c_hop = L + R
    hidx = jax.lax.broadcasted_iota(jnp.int32, (1, H), 1)

    def leg(lf, bs, r, ok, t_start, nh):
        F = jnp.where(
            ok[...] != 0,
            jnp.maximum(lf[...], bs[...]) + r[...] * L,
            SENT,
        )
        G = F - hidx * c_hop
        cum = cummax_rows(G)
        t1 = t_start + R  # [BC, 1]
        t_end = jnp.maximum(
            t1, jnp.max(G, axis=1, keepdims=True)
        ) + nh[...] * c_hop
        departs = jnp.maximum(t1, cum) + hidx * c_hop + L
        return t_end, departs

    t0v = t0[...]
    t_req_end, d_req = leg(lf_req, bs_req, r_req, ok_req, t0v, req_hops)
    t_rep_end, d_rep = leg(
        lf_rep, bs_rep, r_rep, ok_rep, t_req_end + service[...], rep_hops
    )
    d_req_o[...] = d_req
    d_rep_o[...] = d_rep
    t_rep_o[...] = t_rep_end
    if has_sync:
        t_arr_end, d_arr = leg(lf_arr, bs_arr, r_arr, ok_arr, t0v, arr_hops)
        d_arr_o[...] = d_arr
        t_arr_o[...] = t_arr_end


def router_cascade(
    lf_all, bs_all, r_all, ok_all, t0, service,
    req_hops, rep_hops, arr_hops, link_lat, router_lat, *, has_sync: bool,
):
    """Fused wait-floor + cascade + departures: takes the XLA-staged
    [C, legs·H] per-hop link_free and base (or their maximum as both:
    only `max(lf_all, bs_all)` is read), ranks, and hop masks,
    returns (t_rep_end [C], t_arr_end [C] | None, departs [C, legs·H])
    — bit-identical to the engine's XLA `_cascade` path.  `link_lat` /
    `router_lat` are the TRACED knob scalars."""
    C = lf_all.shape[0]
    legs = 3 if has_sync else 2
    H = lf_all.shape[1] // legs
    BC = core_block(C)
    kern = functools.partial(_cascade_kernel, H=H, has_sync=has_sync)
    col = lambda i: (i, 0)
    scal = lambda i: (0, 0)

    def leg_ins(k):
        s = slice(k * H, (k + 1) * H)
        return [
            lf_all[:, s], bs_all[:, s], r_all[:, s],
            ok_all[:, s].astype(jnp.int32),
        ]

    ins = leg_ins(0) + leg_ins(1)
    lane = [t0, service, req_hops, rep_hops]
    if has_sync:
        ins += leg_ins(2)
        lane.append(arr_hops)
    n_hout = legs  # one departure block per leg
    out = pl.pallas_call(
        kern,
        grid=(C // BC,),
        in_specs=[pl.BlockSpec((BC, H), col)] * (4 * legs)
        + [pl.BlockSpec((BC, 1), col)] * len(lane)
        + [pl.BlockSpec((1, 1), scal)] * 2,
        out_specs=[pl.BlockSpec((BC, H), col)] * n_hout
        + [pl.BlockSpec((BC, 1), col)] * (2 if has_sync else 1),
        out_shape=[jax.ShapeDtypeStruct((C, H), jnp.int32)] * n_hout
        + [jax.ShapeDtypeStruct((C, 1), jnp.int32)] * (2 if has_sync else 1),
        interpret=interpret_mode(),
    )(
        *ins,
        *[v.astype(jnp.int32)[:, None] for v in lane],
        jnp.asarray(link_lat, jnp.int32).reshape(1, 1),
        jnp.asarray(router_lat, jnp.int32).reshape(1, 1),
    )
    d_all = jnp.concatenate(out[:n_hout], axis=1)
    t_rep_end = out[n_hout][:, 0]
    t_arr_end = out[n_hout + 1][:, 0] if has_sync else None
    return t_rep_end, t_arr_end, d_all
