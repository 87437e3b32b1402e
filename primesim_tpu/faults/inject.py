"""Step-time fault injection: scheduled events, ECC draws, dead-core
scrubbing, and link-detour penalties (DESIGN.md §12).

Everything here is called from inside `sim.step.step` under the STATIC
`cfg.faults_enabled` gate, on TRACED values only — no host randomness, no
data-dependent shapes — so a fault-enabled program still compiles once
per geometry and vmaps over the fleet's batch axis unchanged.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..config.machine import (
    FAULT_CORE_FAILSTOP,
    FAULT_LINK_DEGRADE,
    FAULT_LINK_FAIL,
    MachineConfig,
)
from ..noc.topology import detour_hops_table, path_links
from ..sim.state import llc_meta_width
from .prng import DUE_SALT, site_hash


def fire_events(cfg: MachineConfig, fs, step_no):
    """Apply this step's scheduled events: (kill_sched [C] int32 0/1,
    link_dead [NL], link_extra [NL]). Duplicate events are idempotent
    (set/max scatters); padding rows (ev_step == -1) never match."""
    C = cfg.n_cores
    NL = cfg.n_tiles * 4
    fire = fs.ev_step == step_no  # [K]; K == 0 is fine (drop scatters)
    kill_t = fire & (fs.ev_kind == FAULT_CORE_FAILSTOP)
    kill_sched = (
        jnp.zeros(C, jnp.int32)
        .at[jnp.where(kill_t, fs.ev_a, C)]
        .max(1, mode="drop")
    )
    lf = fire & (fs.ev_kind == FAULT_LINK_FAIL)
    link_dead = fs.link_dead.at[jnp.where(lf, fs.ev_a, NL)].max(
        1, mode="drop"
    )
    ld = fire & (fs.ev_kind == FAULT_LINK_DEGRADE)
    link_extra = fs.link_extra.at[jnp.where(ld, fs.ev_a, NL)].max(
        fs.ev_b, mode="drop"
    )
    return kill_sched, link_dead, link_extra


def ecc_step(cfg: MachineConfig, fs, step_no, arange_c):
    """This step's transient-flip draws under the SECDED model.

    One flip draw per L1 (site = core id) and per LLC bank (site =
    C + bank), plus a salted second draw classifying each flip as
    single-bit (corrected in-line by SECDED — counted, no architectural
    effect) or double-bit (detected-uncorrectable). Returns
    (corrected [C], due [C], l1_due [C] bool): LLC-bank draws are
    attributed to core (bank % C) for counting; only an L1 DUE can
    escalate to a fail-stop of its core (an LLC DUE has no single owning
    core — the line's data is lost but which core pays is workload
    policy, out of model scope)."""
    C = cfg.n_cores
    B = cfg.n_banks
    h1 = site_hash(fs.seed, step_no, arange_c)
    l1_flip = h1 < fs.flip_l1
    l1_due = l1_flip & (
        site_hash(fs.seed, step_no, arange_c, DUE_SALT) < fs.due_rate
    )
    arange_b = jnp.arange(B, dtype=jnp.int32)
    site_b = C + arange_b
    hb = site_hash(fs.seed, step_no, site_b)
    llc_flip = hb < fs.flip_llc
    llc_due = llc_flip & (
        site_hash(fs.seed, step_no, site_b, DUE_SALT) < fs.due_rate
    )
    corr = (l1_flip & ~l1_due).astype(jnp.int32)
    due = l1_due.astype(jnp.int32)
    corr = corr.at[arange_b % C].add(
        (llc_flip & ~llc_due).astype(jnp.int32), mode="drop"
    )
    due = due.at[arange_b % C].add(llc_due.astype(jnp.int32), mode="drop")
    return corr, due, l1_due


def scrub_dead(cfg: MachineConfig, dirm, lock_holder, kill_b):
    """Remove this step's freshly killed cores from the coherence fabric.

    - Sharer bits: every sharer word drops the killed cores' bits (fail-
      stop requires sharer_group == 1 — config-validated — so bit == core
      id; with G == 1 the epoch guard is unused and no epoch bump is
      needed: clearing a core's own bit only affects that core's future
      validation, and a dead core never accesses again).
    - Owners: entries owned by a killed core lose their owner. Under
      "writeback" policy the line's data survives in the LLC (the home
      cannot see silent E->M, so every owned line conservatively counts
      one writeback, attributed to the dead owner — golden does the same
      for back-invalidated owners); under "drop" the tag is invalidated
      and the way's sharer words cleared — the dirty data is lost and the
      next access refetches from DRAM.
    - Locks: slots held by a killed core release (a fail-stop detection +
      recovery idealization; without it every waiter spins forever, which
      is a workload property, not a machine one).

    The dead core's own L1 needs no scrub: pull-based coherence means no
    other core ever reads it. Returns (dirm, lock_holder, wb [C])."""
    C = cfg.n_cores
    W2 = cfg.llc.ways
    NW = cfg.n_sharer_words
    MW = llc_meta_width(cfg)
    R = dirm.shape[0]
    arange_c = jnp.arange(C, dtype=jnp.int32)
    kill_i = kill_b.astype(jnp.int32)
    # killed-core bits packed as words (distinct bits: add == OR)
    killw = jnp.zeros(NW, jnp.int32).at[arange_c >> 5].add(
        jnp.where(kill_b, jnp.int32(1) << (arange_c & 31), 0)
    )
    rowmask = jnp.concatenate(
        [jnp.zeros(MW, jnp.int32), jnp.tile(killw, W2)]
    )
    dirm = dirm & ~rowmask[None, :]
    meta = dirm[:, : 2 * W2].reshape(R, W2, 2)
    own = meta[..., 1]
    tag = meta[..., 0]
    downer = (own >= 0) & (jnp.take(kill_i, jnp.clip(own, 0, C - 1)) != 0)
    new_own = jnp.where(downer, -1, own)
    if cfg.fault_dead_policy == "drop":
        new_tag = jnp.where(downer, -1, tag)
        way_dead = jnp.repeat(downer, NW, axis=1)  # [R, W2*NW]
        sh = jnp.where(way_dead, 0, dirm[:, MW:])
        wb = jnp.zeros(C, jnp.int32)
    else:
        new_tag = tag
        sh = dirm[:, MW:]
        wb = jnp.zeros(C, jnp.int32).at[
            jnp.where(downer, jnp.clip(own, 0, C - 1), C)
        ].add(1, mode="drop")
    dirm = jnp.concatenate(
        [
            jnp.stack([new_tag, new_own], axis=-1).reshape(R, 2 * W2),
            dirm[:, 2 * W2 : MW],
            sh,
        ],
        axis=1,
    )
    held_dead = (lock_holder >= 0) & (
        jnp.take(kill_i, jnp.clip(lock_holder, 0, C - 1)) != 0
    )
    lock_holder = jnp.where(held_dead, -1, lock_holder)
    return dirm, lock_holder, wb


def scrub_dead_cond(cfg: MachineConfig, dirm, lock_holder, kill_now):
    """`scrub_dead` behind a lax.cond on `any(kill_now)`: fail-stops fire
    on a handful of steps per run, so the full-directory scrub pass must
    not execute on the steps where nothing died (the faults-on steady-
    state overhead is the two ECC hashes and the leg gathers)."""
    C = cfg.n_cores
    return jax.lax.cond(
        jnp.any(kill_now != 0),
        lambda args: scrub_dead(cfg, args[0], args[1], args[2] != 0),
        lambda args: (args[0], args[1], jnp.zeros(C, jnp.int32)),
        (dirm, lock_holder, kill_now),
    )


def leg_fault_penalty(cfg: MachineConfig, fs, kn, atile, btile):
    """Vectorized fault penalty of the one-way legs atile -> btile:
    (extra cycles, extra hops, rerouted 0/1) per lane — the traced twin
    of `noc.topology.detour_stats`. Each dead link on the route detours
    at the TOPOLOGY's per-link extra-hop cost (mesh/torus: the orthogonal
    sidestep, +2 everywhere; ring: the long way around the affected
    ring), paying (link+router) per extra hop; each live degraded link
    adds its extra cycles. The table is a host-side constant baked per
    geometry, so fault sweeps still compile once."""
    p = path_links(cfg, atile, btile)  # [C, H]
    ok = p >= 0
    pc = jnp.where(ok, p, 0)
    tbl = jnp.asarray(detour_hops_table(cfg), jnp.int32)
    dead = jnp.where(ok, fs.link_dead[pc], 0)
    dh = jnp.where(ok, tbl[pc] * dead, 0)  # extra hops per dead link
    extra = jnp.where(ok & (dead == 0), fs.link_extra[pc], 0)
    d = jnp.sum(dh, axis=1)
    lat = d * (kn.link_lat + kn.router_lat) + jnp.sum(extra, axis=1)
    return lat, d, (jnp.sum(dead, axis=1) > 0).astype(jnp.int32)


__all__ = [
    "fire_events",
    "ecc_step",
    "scrub_dead",
    "scrub_dead_cond",
    "leg_fault_penalty",
]
