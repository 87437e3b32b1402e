"""Pallas TPU kernel for the dense sharer-expansion reductions
(SURVEY.md §2 #4/#6's "part of the Pallas uncore kernel" column) — the
third resident kernel of the step subsystem.

The step's invalidation / back-invalidation reductions expand each
winner's packed sharer words into per-target-core booleans and reduce
latencies/counts/hops over the target axis — a dense [C_block, C] tiled
computation with NO data-dependent indexing, which is the shape TPU
Pallas handles well: the word->bit expansion is a static masked select
(Mosaic rejects the reshape `jnp.repeat` would emit), and pair
latencies come from index arithmetic. `pallas_reduce=true` in
MachineConfig routes the engine's full-map dense path through this
kernel (and `step_impl="pallas"` routes it unconditionally); results are
BIT-IDENTICAL to the jnp path (tests/test_pallas.py runs the golden
parity suite through it).

Link/router latencies arrive as TRACED (1, 1) scalar inputs, not static
kwargs: the fleet engine's jit key is the timing-normalized geometry and
real timing lives in the traced knob pytree, so baking `cfg.noc` values
into the kernel would silently mistime every swept element.

On the CPU the kernel runs in Pallas interpreter mode, so the parity
suite exercises the identical kernel logic; on a TPU Mosaic compiles it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..config.machine import MachineConfig
from .layouts import core_block, interpret_mode


def _expand_bits(words, t, NW: int):
    """[BC, NW] packed words -> [BC, NW*32] per-target booleans, column
    c = bit (c % 32) of word (c // 32). Static masked select per word:
    Mosaic-friendly (no minor-dim reshape, no gather)."""
    wsel = t >> 5
    rep = jnp.zeros(t.shape, jnp.int32)
    for w in range(NW):
        rep = rep + jnp.where(wsel == w, words[:, w][:, None], 0)
    return ((rep >> (t & 31)) & 1) != 0


def _reduce_kernel(
    shw_ref, vic_ref, btile_ref, vic_owner_ref, inv_row_ref, vic_valid_ref,
    self_ref, link_ref, router_ref,
    inv_lat_ref, inv_cnt_ref, inv_hops_ref, back_cnt_ref, back_hops_ref,
    *, C: int, NW: int, n_tiles: int, mesh_x: int, mesh_y: int,
    topology: str,
):
    BC = shw_ref.shape[0]
    t = jax.lax.broadcasted_iota(jnp.int32, (BC, NW * 32), 1)  # target ids
    bits = _expand_bits(shw_ref[...], t, NW)  # recorded targets
    vbits = _expand_bits(vic_ref[...], t, NW)
    tvalid = t < C
    # pair geometry: home tile of this row vs target tile, from indices;
    # latencies are the traced knobs ((1, 1) blocks broadcast per row)
    bt = btile_ref[...]  # [BC, 1]
    link_lat = link_ref[...]  # [1, 1]
    router_lat = router_ref[...]
    tt = t % n_tiles
    bx, by = bt % mesh_x, bt // mesh_x
    tx, ty = tt % mesh_x, tt // mesh_x
    # topology is a STATIC kwarg (part of the jit/exec-cache key via
    # timing_normalized); coord_hops is all elementwise min/abs/where
    # arithmetic, so every topology stays Mosaic-safe
    from ..noc.topology import coord_hops

    hops = coord_hops(topology, bx, by, tx, ty, mesh_x, mesh_y, xp=jnp)
    lat2 = 2 * (hops * link_lat + (hops + 1) * router_lat)
    hops2 = 2 * hops
    selfid = self_ref[...]
    inv_row = inv_row_ref[...] != 0
    sh_b = bits & (t != selfid) & inv_row & tvalid
    inv_lat_ref[...] = jnp.max(
        jnp.where(sh_b, lat2, 0), axis=1, keepdims=True
    )
    inv_cnt_ref[...] = jnp.sum(
        sh_b.astype(jnp.int32), axis=1, keepdims=True
    )
    inv_hops_ref[...] = jnp.sum(
        jnp.where(sh_b, hops2, 0), axis=1, keepdims=True
    )
    vic_owner = vic_owner_ref[...]
    vic_valid = vic_valid_ref[...] != 0
    ob = (t == vic_owner) & (vic_owner >= 0)
    bk_b = (vbits | ob) & vic_valid & tvalid
    back_cnt_ref[...] = jnp.sum(
        bk_b.astype(jnp.int32), axis=1, keepdims=True
    )
    back_hops_ref[...] = jnp.sum(
        jnp.where(bk_b, hops2, 0), axis=1, keepdims=True
    )


@functools.partial(jax.jit, static_argnums=(0,))
def sharer_reductions(
    cfg: MachineConfig, shw, vic_shw, btile, vic_owner, inv_row, vic_valid,
    arange_c, link_lat=None, router_lat=None,
):
    """Dense invalidation/back-invalidation reductions as one Pallas
    kernel: returns (inv_lat, inv_count, inv_hops, back_count,
    back_hops), each [C] int32 — bit-identical to the engine's jnp dense
    path. Full-map vectors only (cfg validation enforces it).
    `link_lat`/`router_lat` are the TRACED knob scalars (the engine
    passes `kn.link_lat`/`kn.router_lat`); they default to the config
    values only for direct standalone calls."""
    C = cfg.n_cores
    NW = cfg.n_sharer_words
    BC = core_block(C)
    if link_lat is None:
        link_lat = cfg.noc.link_lat
    if router_lat is None:
        router_lat = cfg.noc.router_lat
    kern = functools.partial(
        _reduce_kernel,
        C=C,
        NW=NW,
        n_tiles=cfg.n_tiles,
        mesh_x=cfg.noc.mesh_x,
        mesh_y=cfg.noc.mesh_y,
        topology=cfg.noc.topology,
    )
    col = lambda i: (i, 0)
    scal = lambda i: (0, 0)
    out = pl.pallas_call(
        kern,
        grid=(C // BC,),
        in_specs=[
            pl.BlockSpec((BC, NW), col),
            pl.BlockSpec((BC, NW), col),
        ]
        + [pl.BlockSpec((BC, 1), col)] * 5
        + [pl.BlockSpec((1, 1), scal)] * 2,
        out_specs=[pl.BlockSpec((BC, 1), col)] * 5,
        out_shape=[jax.ShapeDtypeStruct((C, 1), jnp.int32)] * 5,
        interpret=interpret_mode(),
    )(
        shw.astype(jnp.int32),
        vic_shw.astype(jnp.int32),
        btile.astype(jnp.int32)[:, None],
        vic_owner.astype(jnp.int32)[:, None],
        inv_row.astype(jnp.int32)[:, None],
        vic_valid.astype(jnp.int32)[:, None],
        arange_c.astype(jnp.int32)[:, None],
        jnp.asarray(link_lat, jnp.int32).reshape(1, 1),
        jnp.asarray(router_lat, jnp.int32).reshape(1, 1),
    )
    return tuple(o[:, 0] for o in out)
