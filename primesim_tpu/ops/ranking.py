"""Sort-based segmented FIFO ranking — the shared rank primitive of the
router and DRAM-queue contention models (DESIGN.md §13).

Both models need, per same-step transaction i and per FIFO segment s it
enters (a directed NoC link, or a DRAM bank controller),

    rank[i, s] = #{ j : key[j] < key[i],  lane j enters segment s }

— the number of packets ahead of lane i in s's same-step FIFO, ordered
by the phase-2 arbitration key.  The engine historically produced this
as an int8 one-hot matmul: a [C, C] `kless` comparison matrix contracted
against a [C, n_seg] membership one-hot — O(C² · n_seg) int-MACs
(~4×10⁹ per step at C=1024, n_seg≈4096).  `segmented_rank` computes the
identical int32 counts in O(E log E) over the E = C·S flattened
(segment, key) entries: one sort that carries each entry's flat index,
two running maxima over the sorted order, one sort back by that index.

EXACT-EQUIVALENCE ARGUMENT (why the counts are integer-equal to the
matmul's, including duplicate keys):

1. `lane_order` maps each lane's key to its dense first-occurrence rank
   ``ord[i] = #{j : key[j] < key[i]}``.  ord is monotone in key and
   collapses ties, so ``key[j] < key[i]  ⟺  ord[j] < ord[i]``.
2. Each entry packs to ``seg·C + ord`` (strictly ordered by (seg, ord);
   or keeps the pair as two sort keys).  After one flat sort an entry's
   sorted position is the count of entries before it, and every member
   of a (seg, ord) group — a run of equal sorted keys, found by a compare
   against the left neighbour — shares the group's FIRST position
   ``grp0`` (a running max of the group-start positions): the count of
   entries with a strictly smaller (seg, ord), i.e. all entries of
   earlier segments plus same-segment entries with strictly smaller
   ord.  Equal keys share one group, so tied lanes never count each
   other, exactly like the matmul's strict `<`.
3. Subtracting the first position of the entry's segment ``seg0`` (the
   same running max over segment starts = the count of entries in
   earlier segments) leaves the same-segment strictly-smaller count:
   the matmul rank.  The flat index the sort carried as payload is a
   permutation; sorting ``grp0 - seg0`` by it returns the ranks to
   entry order.

CONTRACT: one entry per (lane, segment) — a lane may not enter the same
segment's FIFO twice in one step, or the sort counts it twice while the
matmul's one-hot `.set(1)` collapses it.  The engine guarantees this by
construction: request and reply legs traverse *reversed directed* links
(distinct ids), and the barrier-arrival leg is masked to barrier lanes,
disjoint from home-transaction lanes.  Masked entries use ``seg ==
n_seg`` (one past the last real segment); their ranks are garbage the
caller must mask, same as the matmul path's out-of-range gathers.

Everything here is plain int32 sort/scan/scatter — vmap-safe, so the
fleet engine batches it unchanged, and the jit key stays geometry-only
(keys/segments are traced data).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

INT32_MAX = jnp.iinfo(jnp.int32).max


_LANES = 128  # a TPU vector register's lane count: the row length of a scan


def _running_max(x):
    """Running max of a 1-D array of non-negative ints.

    Written as XLA's TPU pipeline would rewrite a long 1-D ``cummax``
    anyway — rows of 128, then the scan of the row maxima — because that
    rewrite drops the ops' ``op_name``, and with it the phase scope a
    profile bills them to (PERF.md §6, PR 26)."""
    n = x.shape[0]
    if n <= _LANES:
        return jax.lax.cummax(x)
    rows = jax.lax.cummax(
        jnp.pad(x, (0, -n % _LANES)).reshape(-1, _LANES), axis=1
    )
    before = _running_max(rows[:, -1])[:-1]  # max of all earlier rows
    before = jnp.concatenate([jnp.zeros((1,), x.dtype), before])
    return jnp.maximum(rows, before[:, None]).reshape(-1)[:n]


def _run_starts(sorted_vals):
    """Position of the first element of each element's run of equal
    values in a sorted 1-D array: a compare against the left neighbour
    marks the starts, and since start positions only grow, a running max
    carries each one forward over its run."""
    pos = jnp.arange(sorted_vals.shape[0], dtype=jnp.int32)
    start = jnp.concatenate(
        [jnp.ones((1,), jnp.bool_), sorted_vals[1:] != sorted_vals[:-1]]
    )
    return _running_max(jnp.where(start, pos, 0))


def lane_order(key):
    """Dense first-occurrence rank of each lane's arbitration key:
    ``ord[i] = #{j : key[j] < key[i]}`` — [C] int32 in [0, C).

    Monotone in key with ties collapsed, so strict key comparisons and
    strict ord comparisons agree; computed with one C-element sort plus
    a running max of the group starts (duplicates inherit their group's
    start)."""
    C = key.shape[0]
    pos = jnp.arange(C, dtype=jnp.int32)
    sk, sl = jax.lax.sort((key.astype(jnp.int32), pos), num_keys=1)
    return jnp.zeros((C,), jnp.int32).at[sl].set(_run_starts(sk))


def segmented_rank(seg, key=None, n_seg=None, *, order=None, method="auto"):
    """Same-step FIFO ranks, integer-equal to the one-hot-matmul path.

    seg    [C, S] int32 — segment id per (lane, slot), in [0, n_seg];
           ``n_seg`` is the masked sentinel (ranks at masked slots are
           unspecified, mask them downstream).
    key    [C] int32 — per-lane arbitration key (any dtype ordering);
           ignored when a precomputed ``order=lane_order(key)`` is given
           (share one lane_order across the router and DRAM blocks).
    n_seg  static int — number of real segments.

    Returns [C, S] int32: rank[i, s] = # of (lane j ≠ i, slot) entries
    with seg == seg[i, s] and key[j] strictly < key[i], counting each
    such lane once (contract: entries unique per (lane, segment)).

    method="packed" sorts ``seg·C + ord`` as ONE int32 key (requires
    (n_seg + 1)·C ≤ int32 max — true for every shipped geometry);
    "lex" sorts the two keys (seg, ord) lexicographically; "auto" picks
    by that guard.  The key tuple is all the two forms differ in.
    """
    if n_seg is None:
        raise TypeError("segmented_rank: n_seg is required")
    C, S = seg.shape
    E = C * S
    if order is None:
        order = lane_order(key)
    seg_flat = seg.astype(jnp.int32).reshape(E)
    ord_flat = jnp.broadcast_to(order[:, None], (C, S)).reshape(E)
    if method == "auto":
        method = "packed" if (n_seg + 1) * C <= int(INT32_MAX) else "lex"
    if method == "packed":
        keys = (seg_flat * jnp.int32(C) + ord_flat,)
    elif method == "lex":
        keys = (seg_flat, ord_flat)
    else:
        raise ValueError(f"segmented_rank: unknown method {method!r}")
    pos = jnp.arange(E, dtype=jnp.int32)
    # no order is needed among ties: tied entries share their rank
    *skeys, sidx = jax.lax.sort(
        (*keys, pos), num_keys=len(keys), is_stable=False
    )
    # sorted position of the first entry of each entry's segment, and of
    # its (segment, ord) group: the later of the segment's start and the
    # start of the run of equal last keys
    seg0 = _run_starts(skeys[0] // C if method == "packed" else skeys[0])
    grp0 = jnp.maximum(seg0, _run_starts(skeys[-1]))
    # back to entry order: sidx is a permutation, sorting by it inverts it
    _, rank = jax.lax.sort((sidx, grp0 - seg0), num_keys=1, is_stable=False)
    return rank.reshape(C, S)
