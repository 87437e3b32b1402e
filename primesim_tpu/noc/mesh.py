"""2-D mesh NoC geometry and analytic latency (DESIGN.md §1).

TPU-native replacement for the reference's hop-by-hop `Network` mesh router
(SURVEY.md §2 #6). v1 is the analytic uncontended model shared verbatim by
the golden simulator and the JAX engine (these helpers are written so they
work on NumPy arrays AND traced jnp arrays alike). The congestion-aware
models (per-link occupancy counts; the router's per-link next-free clocks,
`sim/step.py::_router_walk`) sit behind `NocConfig` gating.
"""

from __future__ import annotations

import functools

import jax.numpy as jnp

from ..config.machine import MachineConfig


def tile_xy(tile, mesh_x: int):
    return tile % mesh_x, tile // mesh_x


def hops(tile_a, tile_b, mesh_x: int):
    ax, ay = tile_xy(tile_a, mesh_x)
    bx, by = tile_xy(tile_b, mesh_x)
    return abs(ax - bx) + abs(ay - by)


def one_way_lat(tile_a, tile_b, cfg: MachineConfig):
    """One-way message latency: hops*link + (hops+1)*router."""
    h = hops(tile_a, tile_b, cfg.noc.mesh_x)
    return h * cfg.noc.link_lat + (h + 1) * cfg.noc.router_lat


def core_tile(core, cfg: MachineConfig):
    return core % cfg.n_tiles


def bank_tile(bank, cfg: MachineConfig):
    return bank % cfg.n_tiles


# Directed links for the per-link contention model: each tile sources four
# links, id = tile*4 + dir with dir 0=E (+x), 1=W (-x), 2=N (+y), 3=S (-y).
# XY routing uses x-phase links at the source row, then y-phase links at
# the destination column — `xy_links` is the scalar reference walk the
# vectorized engine path builder must match link-for-link.


def n_links(cfg: MachineConfig) -> int:
    return cfg.n_tiles * 4


@functools.lru_cache(maxsize=None)
def xy_links(a: int, b: int, mesh_x: int) -> tuple[int, ...]:
    """Directed link ids on the XY route tile a -> tile b (scalar,
    memoized — tile pairs repeat heavily across golden steps; immutable
    so the cached value cannot be corrupted)."""
    ax, ay = a % mesh_x, a // mesh_x
    bx, by = b % mesh_x, b // mesh_x
    links = []
    x, y = ax, ay
    while x != bx:
        d = 0 if bx > x else 1
        links.append((y * mesh_x + x) * 4 + d)
        x += 1 if bx > x else -1
    while y != by:
        d = 2 if by > y else 3
        links.append((y * mesh_x + x) * 4 + d)
        y += 1 if by > y else -1
    return tuple(links)


def path_links(cfg: MachineConfig, a, b):
    """Vectorized XY route a->b as directed link ids, -1-padded to the
    mesh diameter — link-for-link identical to `xy_links` (x phase at the
    source row, then y phase at the destination column). Shared by the
    engine's per-link contention models and the fault-injection detour
    model (faults/inject.py)."""
    mx, my = cfg.noc.mesh_x, cfg.noc.mesh_y
    H = max(1, (mx - 1) + (my - 1))
    ax, ay = a % mx, a // mx
    bx, by = b % mx, b // mx
    i = jnp.arange(H, dtype=jnp.int32)[None, :]
    sx = jnp.sign(bx - ax)
    nx = jnp.abs(bx - ax)
    px = ax[:, None] + sx[:, None] * i
    xlink = (ay[:, None] * mx + px) * 4 + jnp.where(sx[:, None] > 0, 0, 1)
    sy = jnp.sign(by - ay)
    ny = jnp.abs(by - ay)
    j = i - nx[:, None]
    py = ay[:, None] + sy[:, None] * j
    ylink = (py * mx + bx[:, None]) * 4 + jnp.where(sy[:, None] > 0, 2, 3)
    return jnp.where(
        i < nx[:, None], xlink, jnp.where(j < ny[:, None], ylink, -1)
    )


def concat_legs(legs):
    """Concatenate per-leg XY paths and their lane masks into the
    contention models' [C, legs·H] layout: ``legs`` is a sequence of
    (path_links result [C, H], lane mask [C]) pairs.  Both the "link"
    occupancy count and the hop-by-hop router block run every per-link
    operation ONCE over this concatenation (one scatter-add; or one
    sorted pass for rank and link state, one for the departures) — the
    overhead of each device op is the budget, and a loop over paths would
    pay it once a path (sim/step.py::_router_walk)."""
    pths = [p for p, _ in legs]
    masks = [jnp.broadcast_to(m[:, None], p.shape) for p, m in legs]
    return jnp.concatenate(pths, axis=1), jnp.concatenate(masks, axis=1)


# ---- fault-model detour (DESIGN.md §12) -----------------------------------
# A FAILED directed link on a message's XY path forces an adaptive
# fallback around it: one orthogonal sidestep and return, i.e. +2 hops and
# +2 * (link_lat + router_lat) cycles per failed hop (the minimal X-Y
# detour around a single dead edge of a >= 2x2 mesh; config validation
# rejects link faults on thinner meshes). A DEGRADED (alive) link adds its
# `extra` cycles each traversal; a dead link's extra is moot (the detour
# replaces the traversal). `detour_stats` is the scalar reference the
# vectorized `faults.inject.leg_fault_penalty` must match per leg.


def detour_stats(
    a: int, b: int, mesh_x: int, link_dead, link_extra,
    link_lat: int, router_lat: int,
) -> tuple[int, int, int]:
    """Scalar fault penalty of the one-way leg a -> b: (extra cycles,
    extra hops, rerouted flag)."""
    dead = 0
    extra = 0
    for l in xy_links(a, b, mesh_x):
        if link_dead[l]:
            dead += 1
        else:
            extra += int(link_extra[l])
    return (
        dead * 2 * (link_lat + router_lat) + extra,
        2 * dead,
        int(dead > 0),
    )
