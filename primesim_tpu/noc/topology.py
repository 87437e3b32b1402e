"""NoC topology dispatch (DESIGN.md §25).

The machine zoo's pluggable-topology seam: `cfg.noc.topology` is a STATIC
selector (part of `timing_normalized()`, so it joins the jit / exec-cache
key like `contention_model`), and every engine/golden/fault consumer
routes through this module instead of importing `mesh` directly. Each
plugin provides the same layered contract:

- ``coord_hops`` / ``hops``: hop count, generic over the array module
  (``xp=np`` for host-side tables and the golden model, ``xp=jnp`` for
  traced code, plain ints for scalars);
- ``route_links``: the memoized scalar reference walk;
- ``path_links``: the vectorized [C, H] route builder (-1-padded to the
  topology's ``path_width``) that must match ``route_links``
  link-for-link;
- ``detour_hops_table``: per-directed-link extra hops a route pays to
  detour around that link when FAILED (faults/inject.py);
- ``detour_stats``: the scalar fault-penalty reference for one leg.

All topologies share the mesh's link numbering (tile*4 + dir), so
``n_links`` and every contention/fault scatter shape is
topology-invariant.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ..config.machine import NOC_TOPOLOGIES as TOPOLOGIES
from ..config.machine import MachineConfig
from . import mesh as _mesh
from . import ring as _ring
from . import torus as _torus

__all__ = [
    "TOPOLOGIES", "coord_hops", "hops", "one_way_lat", "path_width",
    "route_links", "path_links", "detour_hops_table", "detour_stats",
]


def coord_hops(topology: str, ax, ay, bx, by, mesh_x: int, mesh_y: int, xp=jnp):
    """Hop count between tile COORDINATES under `topology`; `xp` picks the
    array module (np/jnp: all elementwise min/abs/where arithmetic)."""
    if topology == "torus":
        return _torus.ring_dist(xp, ax, bx, mesh_x) + _torus.ring_dist(
            xp, ay, by, mesh_y
        )
    if topology == "ring":
        direct = _torus.ring_dist(xp, ax, bx, mesh_x)
        via = (
            _torus.ring_dist(xp, ax, 0 * ax, mesh_x)
            + _torus.ring_dist(xp, ay, by, mesh_y)
            + _torus.ring_dist(xp, 0 * bx, bx, mesh_x)
        )
        return xp.where(ay == by, direct, via)
    return xp.abs(ax - bx) + xp.abs(ay - by)


def hops(cfg: MachineConfig, tile_a, tile_b, xp=jnp):
    """Hop count between TILE ids under cfg's topology."""
    mx, my = cfg.noc.mesh_x, cfg.noc.mesh_y
    return coord_hops(
        cfg.noc.topology, tile_a % mx, tile_a // mx, tile_b % mx,
        tile_b // mx, mx, my, xp,
    )


def one_way_lat(cfg: MachineConfig, tile_a, tile_b):
    """One-way message latency: hops*link + (hops+1)*router (the golden
    model's scalar form; `mesh.one_way_lat` stays as the mesh-only
    legacy entry point)."""
    h = hops(cfg, tile_a, tile_b, xp=np)
    return h * cfg.noc.link_lat + (h + 1) * cfg.noc.router_lat


def path_width(cfg: MachineConfig) -> int:
    """The -1-padded route length H of `path_links` for this topology."""
    mx, my = cfg.noc.mesh_x, cfg.noc.mesh_y
    if cfg.noc.topology == "torus":
        return _torus.path_width(mx, my)
    if cfg.noc.topology == "ring":
        return _ring.path_width(mx, my)
    return max(1, (mx - 1) + (my - 1))


def route_links(cfg: MachineConfig, a: int, b: int) -> tuple[int, ...]:
    """Directed link ids on the scalar reference route a -> b."""
    mx, my = cfg.noc.mesh_x, cfg.noc.mesh_y
    if cfg.noc.topology == "torus":
        return _torus.route_links(int(a), int(b), mx, my)
    if cfg.noc.topology == "ring":
        return _ring.route_links(int(a), int(b), mx, my)
    return _mesh.xy_links(int(a), int(b), mx)


def path_links(cfg: MachineConfig, a, b):
    """Vectorized route a->b as directed link ids [C, H], -1-padded."""
    if cfg.noc.topology == "torus":
        return _torus.path_links(cfg, a, b)
    if cfg.noc.topology == "ring":
        return _ring.path_links(cfg, a, b)
    return _mesh.path_links(cfg, a, b)


def detour_hops_table(cfg: MachineConfig) -> np.ndarray:
    """[n_links] extra hops a route pays to detour around each directed
    link when FAILED. Mesh and torus pay the orthogonal sidestep (+2
    everywhere); the ring pays the long way around the affected ring."""
    if cfg.noc.topology == "ring":
        return _ring.detour_hops_table(cfg)
    if cfg.noc.topology == "torus":
        return _torus.detour_hops_table(cfg)
    return np.full(cfg.n_tiles * 4, 2, np.int32)


def detour_stats(
    cfg: MachineConfig, a: int, b: int, link_dead, link_extra,
    link_lat: int, router_lat: int,
) -> tuple[int, int, int]:
    """Scalar fault penalty of the one-way leg a -> b under cfg's
    topology: (extra cycles, extra hops, rerouted flag) — the reference
    the vectorized `faults.inject.leg_fault_penalty` must match per leg
    (generalizes `mesh.detour_stats`, which remains the mesh-only form)."""
    tbl = detour_hops_table(cfg)
    dead_hops = 0
    extra = 0
    for l in route_links(cfg, a, b):
        if link_dead[l]:
            dead_hops += int(tbl[l])
        else:
            extra += int(link_extra[l])
    return (
        dead_hops * (link_lat + router_lat) + extra,
        dead_hops,
        int(dead_hops > 0),
    )
