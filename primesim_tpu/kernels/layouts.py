"""Shared block geometry + Mosaic-safe idioms for the step kernels.

VMEM BLOCK LAYOUT (DESIGN.md §11). All step kernels block the CORE axis:
grid = (C // core_block(C),), every per-core operand arrives as a
[BC, width] VMEM block with index map `lambda i: (i, 0)` (the counter
array, [N_BLOCK_ROWS, C] (counters, then stat rows), blocks its LANE axis instead: `lambda i: (0, i)`).
Widths are the engine's own fused-array layouts, staged verbatim:

- L1 block: [BC, 5 * W1 * S1] — five planes (tag/state/lru/ptr/epoch) at
  an FS = W1*S1 column stride, way w of set s of plane p at column
  p*FS + w*S1 + s (sim/state.py).
- Directory rows: [BC, DW] — tag/owner pairs at columns 2w / 2w+1, LRU at
  2*W2 + w, epoch at 3*W2 + w, zero padding to MW = llc_meta_width, then
  sharer word n of way w at MW + w*NW + n (sim/state.py).
- Lane vectors ([C] classification flags and ids) ride as [BC, 1]
  columns; traced step scalars as (1, 1) blocks broadcast to every grid
  step.

MOSAIC IDIOMS. TPU Pallas rejects minor-dim reshapes and data-dependent
gathers, so every "index with a computed id" becomes a static unroll of
masked selects (`select_col`, `across`) and every argmax/argmin becomes
the first-occurrence emulation (`first_true` / `first_min`) — all
bit-exact against the XLA step's jnp.argmax/argmin/take_along_axis
semantics, which the parity suite proves.

Kernels must NOT derive core ids from `pl.program_id`: the fleet engine
vmaps the whole step, and the Pallas batching rule prepends a grid axis,
which would silently renumber the blocks. Global core ids arrive as a
[BC, 1] input instead (`sharer_reductions` set the pattern).

These layouts are also the reason fault injection (DESIGN.md §12) never
touches kernel code: fault effects are expressed entirely on the staged
operands (a pre-gather `dirm` scrub, lane-predicate masking, post-fold
latency/counter addends), and the counter fold is width-generic over
`counters.shape[0]` — adding the fault counters changed no block spec,
nor did the stat rows below them (PR 37).
See the FAULT-LANE CONTRACT note in step_kernels.py.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def core_block(C: int) -> int:
    """Core-axis block size: full 128-lane blocks when the core count
    allows, else one block of all C cores (small test geometries)."""
    return 128 if C % 128 == 0 else C


def interpret_mode(platform: str | None = None) -> bool:
    """Whether the kernels run in Pallas interpreter mode, decided from
    the platform of the default device: `cpu` interprets (the identical
    kernel logic, tier-1-gated), `tpu` compiles through Mosaic, anything
    else is an error — no kernel silently falls back on a device nobody
    tested. `platform` overrides the lookup (tests)."""
    if platform is None:
        platform = jax.devices()[0].platform
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(
        f"primesim_tpu kernels support platforms 'cpu' (interpreted) and "
        f"'tpu' (Mosaic-compiled); found {platform!r}"
    )


def block_spec(width: int):
    """BlockSpec tuple args for a [BC, width] core-axis block."""
    return width, (lambda i: (i, 0))


def select_col(mat, idx, ncols: int, colf=None):
    """mat[:, colf(v)] at v = idx per row — a data-dependent column pick
    as a static unroll of masked adds. `mat` [BC, W], `idx` [BC, 1],
    colf maps v -> static column (default identity). Returns [BC, 1]."""
    colf = colf or (lambda v: v)
    acc = jnp.zeros_like(idx)
    for v in range(ncols):
        c = colf(v)
        acc = acc + jnp.where(idx == v, mat[:, c : c + 1], 0)
    return acc


def across(vals, width: int):
    """Pack a list of `width` [BC, 1] columns into one [BC, width] value
    via one-hot masked adds (no concatenate on the lane dim)."""
    iota = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
    acc = jnp.zeros((vals[0].shape[0], width), jnp.int32)
    for k, v in enumerate(vals):
        acc = acc + jnp.where(iota == k, v.astype(jnp.int32), 0)
    return acc


def first_true(mask):
    """jnp.argmax semantics over axis 1 of a [BC, W] bool: index of the
    FIRST True, 0 when none. Returns ([BC, 1] any, [BC, 1] index)."""
    W = mask.shape[1]
    iota = jax.lax.broadcasted_iota(jnp.int32, (1, W), 1)
    any_ = jnp.max(mask.astype(jnp.int32), axis=1, keepdims=True) != 0
    idx = jnp.min(jnp.where(mask, iota, W), axis=1, keepdims=True)
    return any_, jnp.where(any_, idx, 0)


def first_min(vals):
    """jnp.argmin semantics over axis 1 of a [BC, W] int32: index of the
    FIRST minimum. Returns [BC, 1]."""
    W = vals.shape[1]
    iota = jax.lax.broadcasted_iota(jnp.int32, (1, W), 1)
    m = jnp.min(vals, axis=1, keepdims=True)
    return jnp.min(jnp.where(vals == m, iota, W), axis=1, keepdims=True)


def cummax_rows(vals):
    """jax.lax.cummax(axis=1) semantics over a [BC, W] int32: inclusive
    running max along the lane dim as a static unroll of masked reduces
    (one masked max + one-hot select per output column — Mosaic has no
    lane-dim scan or shift). Bit-exact vs lax.cummax: integer max is
    associative, so the per-column reduce IS the prefix."""
    W = vals.shape[1]
    iota = jax.lax.broadcasted_iota(jnp.int32, (1, W), 1)
    NEG = jnp.iinfo(jnp.int32).min
    out = jnp.zeros_like(vals)
    for k in range(W):
        m = jnp.max(jnp.where(iota <= k, vals, NEG), axis=1, keepdims=True)
        out = jnp.where(iota == k, m, out)
    return out


def popcount(x):
    """Per-element bit count of nonneg int32 words, shift/mask form (no
    multiply that could wrap; matches lax.population_count exactly)."""
    x = x - ((x >> 1) & 0x55555555)
    x = (x & 0x33333333) + ((x >> 2) & 0x33333333)
    x = (x + (x >> 4)) & 0x0F0F0F0F
    x = x + (x >> 8)
    x = x + (x >> 16)
    return x & 0x3F
