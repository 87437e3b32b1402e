"""Traffic shape `ycsb_like`: the access pattern of
`primesim_tpu/trace/synth.py::ycsb_like` (YCSB core workload A against a
shared in-memory hash table with in-place updates), written here on its
own: every operation as a row of reference slots in array calls over all
cores, the slots an operation does not use closed up, the same draws
from the same random stream; `tests/test_synth_ycsb.py` holds the two
equal, event for event."""

import numpy as np

from trafficgen import EV_END, EV_LD, EV_ST, LINE, finish

FNV_OFFSET, FNV_PRIME = np.uint64(0xCBF29CE484222325), np.uint64(0x100000001B3)
PAGE, HEAD = 4096, 8  # the records start on a page; a bucket head is a pointer
ITEMS = 10**10  # the ranks are drawn over `ScrambledZipfianGenerator.ITEM_COUNT`, not the table


def fnv1a_64(x: np.ndarray) -> np.ndarray:
    """FNV-1a over the eight bytes of each value, low byte first, in
    wrapping 64-bit arithmetic (YCSB's `Utils.fnvhash64`, unsigned)."""
    x = x.astype(np.uint64)
    h = np.full(x.shape, FNV_OFFSET)
    for _ in range(8):
        h = (h ^ (x & np.uint64(0xFF))) * FNV_PRIME
        x = x >> np.uint64(8)
    return h


def zeta(n: int, theta: float) -> float:
    """sum of i^-theta for i in 1 .. n, the terms past 2^20 by
    Euler-Maclaurin; zeta(10^10, 0.99) = 26.46902820178302, the `ZETAN`
    that YCSB's `ScrambledZipfianGenerator` carries as a constant."""
    m = min(n, 1 << 20)
    head = float(np.sum(np.arange(1, m + 1, dtype=np.float64) ** -theta))
    if n == m:
        return head
    n, m = float(n), float(m)
    return (head + (n ** (1.0 - theta) - m ** (1.0 - theta)) / (1.0 - theta)
            + (n ** -theta - m ** -theta) / 2.0
            - theta * (n ** (-theta - 1.0) - m ** (-theta - 1.0)) / 12.0)


def zipfian_ranks(u: np.ndarray, n: int, theta: float) -> np.ndarray:
    """Gray et al.'s zipfian draw as YCSB's `ZipfianGenerator.nextLong`
    makes it: rank i of [0, n) with probability near (i+1)^-theta / zetan."""
    zetan = zeta(n, theta)
    zeta2 = 1.0 + 0.5 ** theta
    alpha = 1.0 / (1.0 - theta)
    eta = (1.0 - (2.0 / n) ** (1.0 - theta)) / (1.0 - zeta2 / zetan)
    tail = np.floor(n * (eta * u - eta + 1.0) ** alpha).astype(np.int64)
    uz = u * zetan
    return np.where(uz < 1.0, 0, np.where(uz < zeta2, 1, np.minimum(tail, n - 1)))


def generate(n_cores: int, seed: int, ops_per_core: int, recordcount: int, theta: float,
             read_frac: float, fieldcount: int, fieldlength: int, ins_per_mem: int,
             op_ins: int) -> np.ndarray:
    """Every core serves `ops_per_core` operations on zipfian keys, the
    ranks drawn over 10^10 items and scattered over the records by FNV: a READ loads the bucket head, the record's header
    and every field line; an UPDATE loads the first two, then stores the
    header and the lines of one field. The store is an index of
    2 * `recordcount` bucket heads from address 0 and, from the next page,
    records of a header line and the packed fields."""
    n, fl = recordcount, fieldlength
    if ops_per_core < 1 or n < 3 or not 0.0 < theta < 1.0 or not 0.0 <= read_frac <= 1.0:
        raise ValueError("ops_per_core >= 1, recordcount >= 3, 0 < theta < 1, 0 <= read_frac <= 1")
    if fieldcount < 1 or fl < 1 or ins_per_mem < 1 or op_ins < 0:
        raise ValueError("fieldcount, fieldlength, ins_per_mem >= 1; op_ins >= 0")
    field_lines = -(-fieldcount * fl // LINE)
    stride = (1 + field_lines) * LINE
    records = -(-2 * n * HEAD // PAGE) * PAGE
    if records + n * stride > 2**31:
        raise ValueError(f"{n} records of {stride} bytes do not fit under 2^31")
    first = np.arange(fieldcount) * fl // LINE  # the line a field starts on
    last = (np.arange(fieldcount) * fl + fl - 1) // LINE
    slots = 2 + max(field_lines, 1 + int((last - first + 1).max()))  # the longest operation's

    rng = np.random.default_rng(seed)
    shape = (n_cores, ops_per_core)
    ranks = zipfian_ranks(rng.random(shape), ITEMS, theta)
    is_read = (rng.random(shape) < read_frac)[:, :, None]
    field = rng.integers(0, fieldcount, shape)
    pre = rng.integers(1, 2 * ins_per_mem + 1, shape + (slots,))
    pre[:, :, 0] += op_ins

    record = (fnv1a_64(ranks) % np.uint64(n)).astype(np.int64)
    bucket = (fnv1a_64(record) % np.uint64(2 * n)).astype(np.int64)[:, :, None]
    base = (records + record * stride)[:, :, None]
    j = np.arange(slots)[None, None, :]
    # slot 0 the bucket head, 1 the header; a READ then field line j - 1,
    # an UPDATE the header again and from slot 3 the field's lines
    lo = (field * fl)[:, :, None]
    line = first[field][:, :, None] + j - 3
    field_addr = base + LINE + np.maximum(lo, line * LINE) // 4 * 4
    addrs = np.where(j == 0, HEAD * bucket,
                     np.where(is_read, base + np.maximum(j - 1, 0) * LINE,
                              np.where(j <= 2, base, field_addr)))
    used = np.where(is_read, j < 2 + field_lines, line <= last[field][:, :, None])
    types = np.where(is_read | (j < 2), EV_LD, EV_ST)

    # the slots an operation leaves empty close up, core by core
    used = used.reshape(n_cores, -1)
    order = np.argsort(~used, axis=1, kind="stable")[:, :int(used.sum(1).max())]
    kept = np.take_along_axis(used, order, axis=1)

    def column(a):
        return np.where(kept, np.take_along_axis(a.reshape(n_cores, -1), order, axis=1), 0)

    return finish(np.where(kept, column(types), EV_END), np.where(kept, 4, 0),
                  column(addrs), column(pre))
