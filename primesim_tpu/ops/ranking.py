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
construction: a lane's first leg is its request OR, on a barrier lane,
its arrival (one XY path, which crosses a directed link once); first leg
and reply traverse *reversed directed* links (distinct ids); and a
barrier lane has no reply (the reply is masked to home-transaction
lanes, disjoint from barrier lanes).  Masked entries use ``seg ==
n_seg`` (one past the last real segment); their ranks are garbage the
caller must mask, same as the matmul path's out-of-range gathers.

VALUES CARRIED THROUGH THE SAME SORT (`segmented_rank_floor`,
`segmented_table_max`; the router's per-link state, DESIGN.md §13). A
per-segment table indexed entry by entry (a scatter-min, a gather, a
scatter-max over all E entries) is, in the sorted order, a scan: there a
segment's entries are one contiguous run.  Why the results equal the
table form's, for every int32:

4. Each real segment g gets one more entry, its TABLE ENTRY, with ord 0
   where a lane's ord is moved up by one (keys ``g·(C+1) + ord + 1``):
   strictly first in g's run, its own (seg, ord) group.  Step 2's
   ``grp0`` of a lane's entry then counts it too, so the rank is
   ``grp0 - seg0 - 1``; steps 1-3 are otherwise untouched, and the
   contract (one entry a (lane, segment), ties share a rank, masked
   slots in segment ``n_seg``, which has no table entry) is the same.
5. The sort's second payload is ``val`` at a lane's entry and
   ``table[g]`` at a table entry.  A segmented scan (`_segmented_scan`)
   is min or max over exactly the elements between the run's edge and
   the element: runs are marked by flags from the sorted segment ids,
   never by arithmetic on the values, and the only constant mixed in is
   the operation's identity (INT32_MAX for min, INT32_MIN for max) at
   elements that must not count.  So scanning back from the run's end
   leaves at its first element, the table entry, the exact
   ``min{val : entry in g}`` (the table entry itself holds the
   identity); ``floor[g] = max(table[g], that)`` is formed there; and a
   forward max-scan of (floor[g] at the table entry, the identity
   elsewhere) hands every element of the run floor[g].  min and max are
   associative, commutative and idempotent: neither the order among
   tied keys (the sort is unstable) nor the blocking into rows of 128
   can change a result.  Nothing is added to or subtracted from a
   value, so no int32 can overflow: clocks at -(1 << 30) after a rebase
   and near INT32_MAX are as good as small ones (`_running_max`, by
   contrast, is only ever given positions).
6. `segmented_table_max` sorts by the sorted POSITIONS step 3 returned
   beside the rank: a permutation, so the order, its runs and their end
   flags are pass 1's element for element.  Scanning max back from each
   run's end leaves ``max(table[g], max{val : entry in g})`` at g's
   table entry, whose position pass 1 handed over; a segment with no
   entry is a run of its table entry alone and keeps ``table[g]``.
   Masked slots sit in the sentinel run, which no table entry reads.

Everything here is plain int32 sort/scan/scatter, and one read of
``n_seg`` words, and the jit key stays geometry-only (keys/segments are
traced data).

UNDER A BATCH AXIS (the fleet engine's `vmap`, DESIGN.md §22) the scans,
maxima and reads batch as `vmap` batches them. The entry sorts do not:
`vmap` of a sort of ``[N]`` is a sort of ``[B, N]`` along its last axis,
which the TPU lays out with the B machines in the sublanes of a tile and
runs at the price of WHOLE tiles of eight machines: one machine at 8.1
times its solo sort, four at 2.0 times four solo sorts, eight and
sixteen at 0.99 (PERF.md §6, PR 48). So every entry sort of this module
goes through `_entry_sort`, a `custom_vmap` whose rule sorts the B
machines' entries one machine after another, each in the solo layout,
B plain sorts unrolled at trace time (B is static): no loop enters the
step, and a value that goes from one entry sort to the next goes
machine by machine without being stacked in between. The integers are
`vmap`'s own: each machine's sort is the solo sort of its own operands.
Unmapped, the helper lowers to the plain sort it wraps: a solo program
is the same text with or without it. `lane_order`'s sort (C arbitration
keys, 1024 on rung 3) is not an entry sort and batches as `vmap`
batches it.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

INT32_MAX = jnp.iinfo(jnp.int32).max
INT32_MIN = jnp.iinfo(jnp.int32).min


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


def _is_start(sorted_vals):
    """True at the first element of each run of equal values in a sorted
    1-D array: a compare against the left neighbour."""
    return jnp.concatenate(
        [jnp.ones((1,), jnp.bool_), sorted_vals[1:] != sorted_vals[:-1]]
    )


def _run_starts(sorted_vals):
    """Position of the first element of each element's run of equal
    values in a sorted 1-D array: since start positions only grow, a
    running max carries each one forward over its run."""
    pos = jnp.arange(sorted_vals.shape[0], dtype=jnp.int32)
    return _running_max(jnp.where(_is_start(sorted_vals), pos, 0))


def _shift(x, k, fill, reverse):
    """`x` moved `k` places along its last axis, away from where a scan
    starts (toward higher indices, or lower under `reverse`), `fill`
    moving in."""
    pad = jnp.full(x.shape[:-1] + (k,), fill, x.dtype)
    if reverse:
        return jnp.concatenate([x[..., k:], pad], axis=-1)
    return jnp.concatenate([pad, x[..., :-k]], axis=-1)


def _scan_lanes(x, edge, op, ident, reverse):
    """Segmented inclusive scan of `op` along the last axis by doubling
    (Hillis-Steele): after the pass of distance k an element holds its
    run's last 2k values, or all of them, and `edge` whether its run
    began inside that reach. Returns both."""
    k = 1
    while k < x.shape[-1]:
        x = jnp.where(edge, x, op(x, _shift(x, k, ident, reverse)))
        edge = edge | _shift(edge, k, False, reverse)
        k *= 2
    return x, edge


def _segmented_scan(x, edge, op, *, reverse=False):
    """Inclusive scan of `op` (``jnp.minimum`` or ``jnp.maximum``) over
    the runs of a 1-D int32 array: `edge` marks the element a run's scan
    starts at (its first, or its last under `reverse`), and each element
    gets `op` over its run from there to itself.

    Right for every int32 value: runs are told apart by `edge` alone,
    never by an offset added to the values, and the one constant is
    `op`'s identity. Rows of 128 as `_running_max`, for the same reason;
    what a row inherits is the same scan over the rows' totals."""
    ident = INT32_MAX if op is jnp.minimum else INT32_MIN
    n = x.shape[0]
    if n <= _LANES:
        return _scan_lanes(x, edge, op, ident, reverse)[0]
    # a padded tail joins the last run: it is scanned after that run's
    # elements, or hands them the identity under `reverse`
    rows, inner = _scan_lanes(
        jnp.pad(x, (0, -n % _LANES), constant_values=ident).reshape(-1, _LANES),
        jnp.pad(edge, (0, -n % _LANES)).reshape(-1, _LANES),
        op, ident, reverse,
    )
    last = 0 if reverse else -1
    carry = _segmented_scan(rows[:, last], inner[:, last], op, reverse=reverse)
    carry = _shift(carry, 1, ident, reverse)  # of the rows scanned before
    return jnp.where(inner, rows, op(rows, carry[:, None])).reshape(-1)[:n]


def _entry_sort(operands, num_keys):
    """The module's one sort of entries: 1-D `operands` by the first
    `num_keys` of them, in no order among ties. Unmapped it is
    ``jax.lax.sort(operands, num_keys=num_keys, is_stable=False)``; under
    `vmap` it is that sort once a machine (the module's docstring, UNDER
    A BATCH AXIS)."""

    @jax.custom_batching.custom_vmap
    def sort(*operands):
        return tuple(jax.lax.sort(operands, num_keys=num_keys, is_stable=False))

    @sort.def_vmap
    def one_machine_at_a_time(batch, mapped, *operands):
        rows = [sort(*(x[b] if m else x for x, m in zip(operands, mapped)))
                for b in range(batch)]
        return tuple(jnp.stack(x) for x in zip(*rows)), (True,) * len(operands)

    return sort(*operands)


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


def _sort_keys(seg_flat, ord_flat, width, n_seg, method):
    """The sort keys of flat (segment, ord) entries, ord in [0, width):
    one packed int32 ``seg·width + ord``, or the pair."""
    if method == "auto":
        method = "packed" if (n_seg + 1) * width <= int(INT32_MAX) else "lex"
    if method == "packed":
        return (seg_flat * jnp.int32(width) + ord_flat,)
    if method == "lex":
        return (seg_flat, ord_flat)
    raise ValueError(f"segmented_rank: unknown method {method!r}")


def _sorted_seg(skeys, width):
    """The segment ids out of `_sort_keys`' keys, in whatever order."""
    return skeys[0] // width if len(skeys) == 1 else skeys[0]


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
    keys = _sort_keys(seg_flat, ord_flat, C, n_seg, method)
    pos = jnp.arange(E, dtype=jnp.int32)
    # no order is needed among ties: tied entries share their rank
    *skeys, sidx = _entry_sort((*keys, pos), len(keys))
    # sorted position of the first entry of each entry's segment, and of
    # its (segment, ord) group: the later of the segment's start and the
    # start of the run of equal last keys
    seg0 = _run_starts(_sorted_seg(skeys, C))
    grp0 = jnp.maximum(seg0, _run_starts(skeys[-1]))
    # back to entry order: sidx is a permutation, sorting by it inverts it
    _, rank = _entry_sort((sidx, grp0 - seg0), 1)
    return rank.reshape(C, S)


class SortedRuns(NamedTuple):
    """The sorted order `segmented_rank_floor` built, for the passes that
    ride it again: `spos` [E + n_seg] the sorted position of every entry
    and then of every segment's table entry; `ends` [E + n_seg] in sorted
    order, true at the last element of a segment's run; `n_real` [] the
    number of entries that were not masked, read off where the masked
    ones' run starts (no pass over the entries, and no collective where
    they came from several chips: the sorted order is whole on each)."""

    spos: jax.Array
    ends: jax.Array
    n_real: jax.Array


def segmented_rank_floor(seg, val, table, *, order, method="auto"):
    """`segmented_rank`, and per-segment state through the same sort.

    seg    [C, S] int32 as `segmented_rank`'s, ``n_seg = table.shape[0]``
           the masked sentinel.
    val    [C, S] int32 — a value per entry (any int32).
    table  [n_seg] int32 — a value per segment (any int32).
    order  [C] = ``lane_order(key)``.

    Returns ``(rank, floor, runs)``: `rank` [C, S] as `segmented_rank`'s;
    ``floor[i, s] = max(table[g], min{val[j, t] : seg[j, t] == g})`` for
    ``g = seg[i, s]``, the minimum over every entry of the segment, the
    entry's own included; `runs` for `segmented_table_max`. Rank and floor
    at masked slots are unspecified."""
    C, S = seg.shape
    E = C * S
    n_seg = table.shape[0]
    # a segment's table entry sorts first in the segment's run: ord 0,
    # the lanes' ord moved up by one. The sentinel segment has none.
    keys = _sort_keys(
        jnp.concatenate([seg.astype(jnp.int32).reshape(E),
                         jnp.arange(n_seg, dtype=jnp.int32)]),
        jnp.concatenate([
            jnp.broadcast_to(order[:, None] + 1, (C, S)).reshape(E),
            jnp.zeros((n_seg,), jnp.int32)]),
        C + 1, n_seg, method,
    )
    pos = jnp.arange(E + n_seg, dtype=jnp.int32)
    *skeys, sidx, sval = _entry_sort(
        (*keys, pos, jnp.concatenate([val.reshape(E), table])), len(keys))
    sseg = _sorted_seg(skeys, C + 1)
    starts = _is_start(sseg)
    ends = jnp.concatenate([starts[1:], jnp.ones((1,), jnp.bool_)])
    seg0 = _running_max(jnp.where(starts, pos, 0))
    # the masked entries sort last, after n_seg table entries and every
    # real entry; where none is masked the last run is a real segment's
    n_real = jnp.where(sseg[-1] == n_seg, seg0[-1] - n_seg, E)
    grp0 = jnp.maximum(seg0, _run_starts(skeys[-1]))
    # the segment's minimum arrives at the run's first element, the table
    # entry, scanning from the run's end; the floor is formed there, once
    # a segment, and spread forward over the run
    is_table = sidx >= E
    low = _segmented_scan(
        jnp.where(is_table, INT32_MAX, sval), ends, jnp.minimum, reverse=True)
    sfloor = _segmented_scan(
        jnp.where(is_table, jnp.maximum(sval, low), INT32_MIN),
        starts, jnp.maximum)
    # the table entry is one more element ahead of every lane of its run
    _, rank, floor, spos = _entry_sort(
        (sidx, grp0 - seg0 - 1, sfloor, pos), 1)
    return (rank[:E].reshape(C, S), floor[:E].reshape(C, S),
            SortedRuns(spos, ends, n_real))


def segmented_table_max(runs, val, table):
    """``table[g]`` raised to the maximum of `val` over segment g's
    entries -> [n_seg] int32; a segment no entry is in keeps its value.
    `val` [C, S] lies as the `seg` that `runs` was built from."""
    E = val.size
    _, sval = _entry_sort(
        (runs.spos, jnp.concatenate([val.reshape(E), table])), 1)
    top = _segmented_scan(sval, runs.ends, jnp.maximum, reverse=True)
    return top[runs.spos[E:]]  # n_seg reads, at the runs' first elements
