"""Canonical stat counters (DESIGN.md §3).

Replaces the reference's scattered per-model counters + report fields
(SURVEY.md §2 #12). Every counter is tracked PER CORE (attributed to the
requesting core for uncore events) so the report can show both per-core and
aggregate numbers like the reference's text report.

Both engines carry these as arrays `[n_cores]`; the JAX engine uses int32 on
device and drains into an int64 host-side accumulator at chunk boundaries.
"""

from __future__ import annotations

import numpy as np

COUNTER_NAMES = (
    "instructions",    # INS batch counts + 1 per retired memory op
    "l1_read_hits",
    "l1_read_misses",  # GETS issued
    "l1_write_hits",   # write hit in E/M (incl. silent E->M)
    "l1_write_misses", # GETM issued
    "upgrades",        # ST hit in S -> UPG issued
    "llc_hits",
    "llc_misses",
    "dram_accesses",
    "l1_writebacks",   # M victim evicted from L1
    "llc_writebacks",  # owned victim evicted from LLC
    "probes",          # owner probes sent
    "invalidations",   # invalidation messages sent (sharer + back-inv)
    "noc_msgs",
    "noc_hops",
    "retries",         # conflict-serialization retries (lost (bank,set) race)
    "lock_acquires",   # LOCK events retired
    "lock_spins",      # failed LOCK attempts (charged spin round trips)
    "barrier_waits",   # BARRIER arrivals
    "noc_contention_cycles",  # router-occupancy queueing cycles charged
    "dram_queue_cycles",  # memory-controller queueing waits (dram_queue)
    # ---- fault injection (DESIGN.md §12; zero with faults disabled) ----
    "noc_reroutes",    # one-way messages detoured around a dead link
    "ecc_corrected",   # single-bit flips corrected in-line by SECDED
    "ecc_due",         # detected-uncorrectable (double-bit) errors
    "core_failstops",  # cores fail-stopped (scheduled or DUE-escalated)
    # ---- machine zoo (DESIGN.md §25; zero with prefetcher "none") ------
    "prefetch_hits",   # LLC misses served by the stride prefetcher
)

# Stat rows (DESIGN.md §15): the step's account of its own lane-slots,
# carried in the same device block below the modelled counters, folded by
# the same stacked add, drained and read back with them. They say nothing
# of the simulated machine (a reference counts none of them, and nothing
# in the step reads one back), so on the host they part from the
# counters: `Engine.counters` keeps COUNTER_NAMES alone, the stat totals
# are `Engine.step_stats`. Every row is per core except the last. With
# them the block is 32 rows, four whole (8, 128) tiles of int32: a 33rd
# row is a fifth tile in every op over the block (PR 37 measured one:
# +0.9 % on the plain machine's step), so a new row wants a reason.
STAT_NAMES = (
    "slot_active",    # core-steps that presented an event to phases 1-4
    "slot_quantum",   # core-steps ahead of the quantum window, waiting
    "slot_frozen",    # core-steps frozen at a barrier, not released
    "run_events",     # local-run slots that retired an event (of rl a step)
    "noc_entries",    # real entries the core's legs put into the router's sort
    # NOT per core: lane b counts the steps whose real router entries,
    # over all cores, numbered in (2^(b-1), 2^b] (lane 0: none or one, no
    # sort; lanes past the last core fold into the last). The lanes sum
    # to the steps run on a router machine, and are zero elsewhere
    "noc_sort_log2",
)
# rows of the device block `MachineState.counters`, in order
BLOCK_NAMES = COUNTER_NAMES + STAT_NAMES
N_BLOCK_ROWS = len(BLOCK_NAMES)


def zero_counters(n_cores: int, dtype=np.int64) -> dict[str, np.ndarray]:
    return {k: np.zeros(n_cores, dtype=dtype) for k in COUNTER_NAMES}


def zero_stats(n_cores: int, dtype=np.int64) -> dict[str, np.ndarray]:
    return {k: np.zeros(n_cores, dtype=dtype) for k in STAT_NAMES}


def stat_totals(stats: dict) -> dict:
    """The stat rows as a job's summary holds them: each per-core row's
    total over the cores, the histogram as its lanes (32: log2 of a count
    below 2^31; lanes past them are zero on any machine that has them)."""
    out = {k: int(np.asarray(v).sum()) for k, v in stats.items()}
    out["noc_sort_log2"] = np.asarray(stats["noc_sort_log2"])[:32].tolist()
    return out


def fold_block(counters: dict, stats: dict, block: np.ndarray) -> None:
    """Add a drained block's rows [N_BLOCK_ROWS, ...] to the host's 64-bit
    totals: the modelled counters' to `counters`, the stat rows' to
    `stats`."""
    n = len(COUNTER_NAMES)
    for i, k in enumerate(COUNTER_NAMES):
        counters[k] += block[i]
    # a block of the counters' height alone carries no stat row (a mesh)
    for i, k in enumerate(STAT_NAMES[:len(block) - n], n):
        stats[k] += block[i]


def stack_block(counters: dict, stats: dict) -> np.ndarray:
    """The host totals as the block's rows (what a checkpoint stores)."""
    return np.stack([counters[k] for k in COUNTER_NAMES]
                    + [stats[k] for k in STAT_NAMES])


def unstack_block(block: np.ndarray) -> tuple[dict, dict]:
    """(`counters`, `stats`) of a stored block of N_BLOCK_ROWS rows."""
    rows = {k: np.asarray(block[i], np.int64) for i, k in enumerate(BLOCK_NAMES)}
    return ({k: rows[k] for k in COUNTER_NAMES},
            {k: rows[k] for k in STAT_NAMES})
