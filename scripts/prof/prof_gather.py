"""Micro-benchmarks of TPU gathers — the data behind the engine's
array-layout choices and behind `sim/step.py::_l1_set_read` having one form.

    python scripts/prof/prof_gather.py          # the L1 set read's two forms
    python scripts/prof/prof_gather.py rows     # the probe's way read: rows / elements
    python scripts/prof/prof_gather.py local    # the local run's row read: candidates / cores first
    python scripts/prof/prof_gather.py writes   # phase 4.A's L1 write: select / scatter
    python scripts/prof/prof_gather.py events   # the local run's candidates: blocks / elements
    python scripts/prof/prof_gather.py raw      # row / element gather, row scatter

Default: the L1 set read's two forms alone (select: `_l1_set_read`, the core's
row read whole and the set picked by a compare and a masked sum; gather: the
element `take_along_axis` it replaced in PR 29, kept here as `set_gather`), at
S1 in {128, 512, 2048} x C in {1024, 16384}, for the local run's read (K = 9
candidates, tag and state planes) and the probe's (K = 1, four planes). Each form runs ITER times inside one `fori_loop`
on sets that change every iteration, so the time is the device's and holds no
dispatch; us an iteration, and ns a gathered word for the gather.

`rows`: the probe's read of the directory entries its W1 way pointers name
(`sim/step.py::_validate_ways`), in the form the step has (ONE gather of whole
`dirm` rows at `[W1, C]` slots through `sharding.read_rows`, the words selected
out of each row: `_way_record`), ways first and cores first (the order
`read_rows` lost with `core_axis` in PR 56, kept here as `way_record_cores1st`),
against the three (coarse vector: four) ELEMENT gathers at the same rows that it
replaced in PR 36, kept here as `ways_elements`; at rows of 768 / 1536 / 4608 / 8704 /
16896 bytes x C in {1024, 4096, 16384}, a table of 524288 rows (262144 of the
widest), inside one `fori_loop` on pointers that change every iteration. The
evidence that `_validate_ways` needs no second form, and where the two would
cross (PERF.md section 6, PR 36).

`local`: the local run's read of its K = `local_run_len` + 1 = 9 candidate
home rows a core alone (`sim/step.py::_local`: ONE gather of `C * 9` whole
`dirm` rows through `sharding.read_rows`, then `_run_record`'s selects and
sums), candidates first (`[K, C]` slots: the gather's `[K*C, DW]` result is
`[K, C, DW]` as it lies; the form the step has since PR 56) against cores first
(`[C, K]` slots: `[C, K, DW]` is a copy that pads K = 9 to the tile's 16 rows;
the step's form on one chip until PR 56, kept here as `run_record_cores1st`),
at the three machines whose cells show it: 1024 cores with a full map (rows of
384 words), rung 4's 4096 (1152) and rung 5's 16384 under the coarse vector
(192, the epoch too), each on a table of its machine's rows but rung 4's, which
is cut to a quarter; inside one `fori_loop` on lines that change every
iteration; us a read and ns a row (PERF.md section 6, PR 56).

`writes`: phase 4.A's write of the fused L1 array (`sim/step.py::
_commit_writes`), 7 + 2 * 8 = 23 words a core, in the form the step has (each
core edits its own row: `_l1_row_write`, a compare-select over each plane of
the row, written back in place) against the ONE element scatter of all C x 23
(row, column) pairs that it replaced in PR 38, kept here as `scatter_write`; at
FS = W1 * S1 in {512, 2048, 8192} x C in {1024, 1472, 4096, 16384}, an array of
four planes and of five, inside one `fori_loop` on columns that change every
iteration; us a write, ns a scattered word, and whether the scatter's compiled
text relays the array flat (`relay`) or sorts its indices (`sort`). The
evidence for which form `_commit_writes` takes (PERF.md section 6, PR 38).

`events`: the local run's read of its `rl + 1` candidate event records
(`sim/step.py::_local`, phase 0), in the form the step has (`trace/device.py::
DeviceTrace.window`: the trace as `[C, Tb, 128]` blocks of 32 records, ONE
gather, batched over the cores, of the two whole blocks that hold the window,
which a lane shifter then moves down to lane 0 of the rows in hand; `pairs`)
against the element read of `[C, T, 4]` at `min(ptr + i, T - 1)` that it
replaced in PR 40, kept here as `events_elements`, and against the forms it did
not take: `picked` (the same gather, the records picked by a compare and a
masked sum a word), `b1st` (the pairs as two index arrays, blocks first, the
same shifter), `flat` (two row reads of a flat `[C * Tb, 128]`, picked) and
`slice2` (one slice of two blocks a core, picked); at C in {1024, 4096, 16384}
x T in {150, 546, 8192} x `rl` in {0, 8}, inside one `fori_loop` on pointers
that change every iteration; us a read, ns an index of the element read, ns a
row of the block read, and whether a form's compiled text holds an op that
writes the whole array anew (`*`). The evidence that the read needs no second
form (scripts/prof/README.md; PERF.md section 6, PR 40).

`raw`: cost against index count, row width and operand size. Hypothesis from
single-op ablations of the step: cost ~= per-INDEX overhead, mostly independent
of row width and operand bytes; windowed (dynamic column) forms are
pathological.
"""
import functools
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ITER = 50
W1 = 4


def timeit(fn, *args, n=20, jit=True):
    f = jax.jit(fn) if jit else fn
    out = f(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = f(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n


def set_gather(cfg, l1, sets, planes):
    """`_l1_set_read` as ONE element gather of K * planes * W1 words a core."""
    S1, W1 = cfg.l1.sets, cfg.l1.ways
    base = jnp.asarray(
        [(p * W1 + w) * S1 for p in planes for w in range(W1)], jnp.int32)
    cols = (sets[:, :, None] + base).reshape(sets.shape[0], -1)
    return jnp.take_along_axis(l1, cols, axis=1).reshape(
        *sets.shape, len(planes), W1)


def set_read_forms():
    from primesim_tpu.config.machine import CacheConfig, MachineConfig
    from primesim_tpu.sim.step import _l1_set_read

    rng = np.random.default_rng(0)
    print(f"device {jax.devices()[0].device_kind}; us an iteration, {ITER} in a loop")
    print("   S1      C  K planes  select_us  gather_us  gather_ns_word")
    for S1 in (128, 512, 2048):
        # only `l1.sets` and `l1.ways` are read
        cfg = MachineConfig(l1=CacheConfig(S1 * W1 * 64, W1, 64, 2))
        for C in (1024, 16384):
            l1 = jax.lax.bitcast_convert_type(
                jax.random.bits(jax.random.key(S1 + C), (C, 5 * W1 * S1),
                                jnp.uint32), jnp.int32)
            for K, planes in ((9, (0, 1)), (1, (0, 1, 2, 3))):
                sets0 = jnp.asarray(rng.integers(0, S1, (C, K), dtype=np.int32))
                us = {}
                for name, form in (("select", _l1_set_read),
                                   ("gather", set_gather)):
                    def loop(l1, sets0, form=form):
                        def body(i, acc):
                            sets = (sets0 + i * 7) & (S1 - 1)
                            return acc ^ form(cfg, l1, sets, planes)
                        acc = jnp.zeros((C, K, len(planes), W1), jnp.int32)
                        return jax.lax.fori_loop(0, ITER, body, acc)
                    us[name] = timeit(loop, l1, sets0, n=3) / ITER * 1e6
                words = C * K * len(planes) * W1
                print(f"{S1:5d} {C:6d} {K:2d} {len(planes):6d} "
                      f"{us['select']:10.1f} {us['gather']:10.1f} "
                      f"{us['gather'] * 1e3 / words:15.2f}", flush=True)
            del l1


def ways_elements(cfg, dirm, ptr_rows, core):
    """`_way_record`'s record as `_validate_ways` read it until PR 36: an
    element gather of `dirm` a field, all at `[C, W1]`."""
    from primesim_tpu.sim.state import llc_meta_width

    W2, NW, MW = cfg.llc.ways, cfg.n_sharer_words, llc_meta_width(cfg)
    g_c = (core >> (cfg.sharer_group.bit_length() - 1))[:, None]
    pway, pslot = ptr_rows % W2, ptr_rows // W2
    vsh = dirm[pslot, MW + pway * NW + (g_c >> 5)]
    record = [dirm[pslot, 2 * pway], dirm[pslot, 2 * pway + 1],
              ((vsh >> (g_c & 31)) & 1) != 0]
    if cfg.sharer_group > 1:
        record.append(dirm[pslot, 3 * W2 + pway])
    return tuple(record)


def way_record_cores1st(cfg, rows, pway, core):
    """`_way_record` on `[C, W1, DW]` rows and `[C, W1]` ways: the order
    `read_rows` took with `core_axis=0` until PR 56."""
    from primesim_tpu.sim.state import llc_meta_width
    from primesim_tpu.sim.step import _pick

    W2, NW, MW = cfg.llc.ways, cfg.n_sharer_words, llc_meta_width(cfg)
    pairs = rows[..., : 2 * W2]
    g_c = (core >> (cfg.sharer_group.bit_length() - 1))[:, None]
    vsh = _pick(rows[..., MW:], pway * NW + (g_c >> 5))
    record = [_pick(pairs, 2 * pway), _pick(pairs, 2 * pway + 1),
              ((vsh >> (g_c & 31)) & 1) != 0]
    if cfg.sharer_group > 1:
        record.append(_pick(rows[..., 3 * W2 : 4 * W2], pway))
    return tuple(record)


def way_read_forms(widths=(768, 1536, 4608, 8704, 16896),
                   cores=(1024, 4096, 16384), table_bytes=4.6e9):
    from types import SimpleNamespace

    from primesim_tpu.parallel.sharding import read_rows
    from primesim_tpu.sim.step import _way_record

    W2 = 8

    def ways1st(cfg, dirm, ptr_rows, core):
        ptr = ptr_rows.T
        return read_rows(
            None, dirm, ptr // W2, functools.partial(_way_record, cfg),
            per_slot=(ptr % W2,), whole=(core,))

    def cores1st(cfg, dirm, ptr_rows, core):
        return way_record_cores1st(
            cfg, dirm[ptr_rows // W2], ptr_rows % W2, core)

    # a full map's record (3 words), then the coarse vector's (the epoch too)
    forms = (("ways1st", ways1st, 1),
             ("cores1st", cores1st, 1),
             ("elements", ways_elements, 1),
             ("ways1st_c", ways1st, 64),
             ("elements_c", ways_elements, 64))
    rng = np.random.default_rng(0)
    print(f"device {jax.devices()[0].device_kind}; us an iteration, {ITER} in "
          f"a loop; W1 {W1}, W2 {W2}")
    print(" row_B    rows      C  " + "  ".join(f"{n:>10s}" for n, _, _ in forms)
          + "  ns_row  ns_elem")
    for width in widths:
        DW = width // 4
        NW = (DW - 128) // W2
        R = min(524288, 1 << int(np.log2(table_bytes / width)))
        # one fused pass: built eagerly the two iotas and their sum are
        # three tables, and the widest is 4.6 GB
        dirm = jax.jit(lambda R=R, DW=DW: (
            jax.lax.broadcasted_iota(jnp.int32, (R, DW), 0) * 40503
            + jax.lax.broadcasted_iota(jnp.int32, (R, DW), 1)))()
        for C in cores:
            ptr0 = jnp.asarray(rng.integers(0, R * W2, (C, W1), dtype=np.int32))
            # the core's sharer word has to lie inside the row
            core = jnp.arange(C, dtype=jnp.int32) % (32 * NW)
            us = {}
            for name, form, group in forms:
                # only these three fields are read
                cfg = SimpleNamespace(llc=SimpleNamespace(ways=W2),
                                      n_sharer_words=NW, sharer_group=group)

                def loop(dirm, ptr0, core, form=form, cfg=cfg):
                    def body(i, acc):
                        ptr = (ptr0 + i * 7919) % (R * W2)
                        rec = [r.astype(jnp.int32)
                               for r in form(cfg, dirm, ptr, core)]
                        if rec[0].shape[0] != C:
                            rec = [r.T for r in rec]
                        return acc ^ functools.reduce(jnp.bitwise_xor, rec)
                    return jax.lax.fori_loop(
                        0, ITER, body, jnp.zeros((C, W1), jnp.int32))
                us[name] = timeit(loop, dirm, ptr0, core, n=3) / ITER * 1e6
            n = C * W1
            print(f"{width:6d} {R:7d} {C:6d}  " + "  ".join(
                f"{us[name]:10.1f}" for name, _, _ in forms)
                  + f"  {us['ways1st'] * 1e3 / n:6.1f}"
                  f"  {us['elements'] * 1e3 / (3 * n):7.1f}", flush=True)
        del dirm


def run_record_cores1st(cfg, rows, line, core):
    """`_run_record` on `[C, K, DW]` rows and `[C, K]` lines: what `_local`
    read on one chip until PR 56 (`read_rows` at `core_axis=0`)."""
    from primesim_tpu.sim.state import llc_meta_width
    from primesim_tpu.sim.step import _pick

    W2, NW, MW = cfg.llc.ways, cfg.n_sharer_words, llc_meta_width(cfg)
    pmeta = rows[:, :, : 2 * W2].reshape(*line.shape, W2, 2)
    pmmatch = pmeta[..., 0] == line[:, :, None]
    pmway = jnp.argmax(pmmatch, axis=2).astype(jnp.int32)
    g_c = (core >> (cfg.sharer_group.bit_length() - 1))[:, None]
    pshw = _pick(rows[:, :, MW:], pmway * NW + (g_c >> 5))
    record = [jnp.any(pmmatch, axis=2), _pick(pmeta[..., 1], pmway),
              ((pshw >> (g_c & 31)) & 1) != 0]
    if cfg.sharer_group > 1:
        record.append(_pick(rows[:, :, 3 * W2 : 4 * W2], pmway))
    return tuple(record)


def local_read_forms(machines=(("1024 full", 1024, 1024, 1, 1),
                               ("rung4", 4096, 4096, 1, 4),
                               ("rung5", 16384, 4096, 64, 1))):
    """(name, cores, banks, sharer_group, the share of the machine's rows
    the table holds)."""
    from primesim_tpu.config.machine import CacheConfig, MachineConfig
    from primesim_tpu.parallel.sharding import read_rows
    from primesim_tpu.sim.state import dirm_width
    from primesim_tpu.sim.step import _run_record

    K = 9

    def cands1st(cfg, dirm, slot, line, core):
        return read_rows(
            None, dirm, slot.T, functools.partial(_run_record, cfg),
            per_slot=(line.T,), whole=(core,))

    def cores1st(cfg, dirm, slot, line, core):
        return run_record_cores1st(cfg, dirm[slot], line, core)

    forms = (("cands1st", cands1st), ("cores1st", cores1st))
    rng = np.random.default_rng(0)
    print(f"device {jax.devices()[0].device_kind}; us an iteration, {ITER} in "
          f"a loop; K {K}")
    print("   machine      C     DW     rows  "
          + "  ".join(f"{n:>10s}" for n, _ in forms)
          + "  ns_row_cands  ns_row_cores  cores/cands")
    for name, C, B, group, cut in machines:
        # only the directory's geometry is read: LLC slices of 512 sets x 8
        cfg = MachineConfig(n_cores=C, n_banks=B, sharer_group=group,
                            llc=CacheConfig(512 * 8 * 64, 8, 64, 10))
        DW, R = dirm_width(cfg), B * cfg.llc.sets // cut
        dirm = jax.jit(lambda R=R, DW=DW: (
            jax.lax.broadcasted_iota(jnp.int32, (R, DW), 0) * 40503
            + jax.lax.broadcasted_iota(jnp.int32, (R, DW), 1)))()
        line0 = jnp.asarray(rng.integers(0, 2**30, (C, K), dtype=np.int32))
        core = jnp.arange(C, dtype=jnp.int32)
        us = {}
        for form_name, form in forms:
            def loop(dirm, line0, core, form=form):
                def body(i, acc):
                    line = line0 + i * 7919
                    # a line a way of its row holds, for every other core
                    slot = line % R
                    held = slot * 40503 + 2 * (line % 8)
                    line = jnp.where(core[:, None] % 2 == 0, held, line)
                    rec = [r.astype(jnp.int32)
                           for r in form(cfg, dirm, slot, line, core)]
                    if rec[0].shape[0] != C:
                        rec = [r.T for r in rec]
                    return acc ^ functools.reduce(jnp.bitwise_xor, rec)
                return jax.lax.fori_loop(
                    0, ITER, body, jnp.zeros((C, K), jnp.int32))
            us[form_name] = timeit(loop, dirm, line0, core, n=3) / ITER * 1e6
        n = C * K
        print(f"{name:>10s} {C:6d} {DW:6d} {R:8d}  " + "  ".join(
            f"{us[n_]:10.1f}" for n_, _ in forms)
              + f"  {us['cands1st'] * 1e3 / n:12.1f}"
              f"  {us['cores1st'] * 1e3 / n:12.1f}"
              f"  {us['cores1st'] / us['cands1st']:11.2f}", flush=True)
        del dirm


def scatter_write(cfg, l1, writes):
    """`_l1_row_write` as `_commit_writes` wrote until PR 38: ONE element
    scatter of every (row, column, word), a masked lane's to the dropped
    row C."""
    C, FS = l1.shape[0], cfg.l1.ways * cfg.l1.sets
    own = jnp.arange(C, dtype=jnp.int32)[:, None]
    rows, cols, vals = [], [], []
    for plane, mask, col, val in writes:
        rows.append(jnp.where(mask.reshape(C, -1), own, C))
        cols.append(jnp.broadcast_to(col, mask.shape).reshape(C, -1) + plane * FS)
        vals.append(jnp.broadcast_to(val, mask.shape).reshape(C, -1))
    return l1.at[jnp.concatenate(rows, 1), jnp.concatenate(cols, 1)].set(
        jnp.concatenate(vals, 1), mode="drop")


def write_forms(strides=(512, 2048, 8192), cores=(1024, 1472, 4096, 16384),
                rl=8):
    import re

    from primesim_tpu.config.machine import CacheConfig, MachineConfig
    from primesim_tpu.sim.step import _l1_row_write

    rng = np.random.default_rng(0)
    K = 7 + 2 * rl
    print(f"device {jax.devices()[0].device_kind}; us an iteration, {ITER} in "
          f"a loop; W1 {W1}, {K} words a core")
    print("    FS      C planes  select_us  scatter_us  scatter_ns_word  "
          "scatter's text")
    for FS in strides:
        S1 = FS // W1
        # only `l1.sets` and `l1.ways` are read
        cfg = MachineConfig(l1=CacheConfig(FS * 64, W1, 64, 2))
        for C in cores:
            for NP in (4, 5):
                l1 = jax.lax.bitcast_convert_type(
                    jax.random.bits(jax.random.key(FS + C), (C, NP * FS),
                                    jnp.uint32), jnp.int32)
                seeds = jnp.asarray(
                    rng.integers(0, 1 << 20, (3, C, K), dtype=np.int32))

                def writes_of(i, seeds):
                    """The step's writes: seven of phase 4 in one set (two
                    ways), a run's `rl` stamps and `rl` E->M; half masked."""
                    col, val, live = seeds[0] + i * 7, seeds[1] ^ i, seeds[2] + i
                    sets = col[:, 0] & (S1 - 1)
                    way = lambda k: ((col[:, k] >> 12) % W1) * S1 + sets  # noqa: E731
                    on = lambda k: (live[:, k] & 1) == 1  # noqa: E731
                    run = slice(7, 7 + rl), slice(7 + rl, K)
                    phase4 = [(0, 1), (1, 1), (2, 0), (1, 0), (0, 0), (3, 0),
                              (4, 0)]  # (plane, which of the two ways)
                    return [(p, on(k), way(w), val[:, k])
                            for k, (p, w) in enumerate(phase4) if p < NP] + [
                        (2, (live[:, run[0]] & 1) == 1, col[:, run[0]] % FS, i),
                        (1, (live[:, run[1]] & 1) == 1, col[:, run[0]] % FS, 3)]

                us, text = {}, ""
                for name, form in (("select", _l1_row_write),
                                   ("scatter", scatter_write)):
                    def loop(l1, seeds, form=form):
                        return jax.lax.fori_loop(
                            0, ITER,
                            lambda i, l1: form(cfg, l1, writes_of(i, seeds)), l1)
                    compiled = jax.jit(loop).lower(l1, seeds).compile()
                    us[name] = timeit(compiled, l1, seeds, n=3, jit=False) / ITER * 1e6
                    if name == "scatter":
                        text = compiled.as_text()
                words = C * (K - (NP < 5))
                found = [w for w, pat in (
                    ("relay", rf"s32\[{C * NP * FS}\]\S* (?:reshape|bitcast|copy|fusion)\("),
                    ("sort", r" sort\(")) if re.search(pat, text)]
                print(f"{FS:6d} {C:6d} {NP:6d} {us['select']:10.1f} "
                      f"{us['scatter']:11.1f} {us['scatter'] * 1e3 / words:16.2f}  "
                      f"{' '.join(found) or '-'}", flush=True)
                del l1


def events_elements(events, ptr, n):
    """`DeviceTrace.window` as `_local` read it until PR 40: `n` slices of
    one record a core out of `[C, T, 4]`."""
    C, T, _ = events.shape
    idx = jnp.minimum(ptr[:, None] + jnp.arange(n, dtype=jnp.int32), T - 1)
    return events[jnp.arange(C, dtype=jnp.int32)[:, None], idx]


def _window_rows(tr, ptr, n):
    """The blocks of a window and where it starts in them: `(first, off,
    k)`, the first block, the record of it the window starts at, and the
    blocks it may span."""
    from primesim_tpu.trace.device import RECORDS, _span

    p = jnp.minimum(ptr, tr.length - 1)
    first = p // RECORDS
    return first, p - first * RECORDS, _span(n)


def _picked(rows, off, n):
    """Records `off + i`, `i < n`, of `rows` `[C, k, 128]` laid end to end."""
    lane = off[:, None, None] * 4 + jnp.arange(n * 4, dtype=jnp.int32).reshape(n, 4)
    hit = jnp.arange(128, dtype=jnp.int32) == (lane % 128)[..., None]
    src = rows[:, 0][:, None, None]
    for j in range(1, rows.shape[1]):
        src = jnp.where((lane // 128 == j)[..., None], rows[:, j][:, None, None], src)
    return jnp.sum(jnp.where(hit, src, 0), axis=-1)


def window_b1st(tr, ptr, n):
    """(core, block) pairs as two index arrays, blocks first (`[k, C, 128]`:
    whole tiles), where the type's gather is batched over the cores (`[C, k,
    128]`); the same shifter."""
    from primesim_tpu.trace.device import _shifted

    first, off, k = _window_rows(tr, ptr, n)
    C = tr.blocks.shape[0]
    rows = tr.blocks[jnp.arange(C, dtype=jnp.int32)[None, :],
                     first[None, :] + jnp.arange(k, dtype=jnp.int32)[:, None]]
    return _shifted(jnp.concatenate(list(rows), axis=1), off, n)


def window_picked(tr, ptr, n):
    """`DeviceTrace.window`'s gather, the records PICKED out of the rows (a
    compare against an iota and a masked sum a word, `step.py::_pick`'s
    idiom) where the type moves the window down by a lane shifter."""
    first, off, k = _window_rows(tr, ptr, n)
    rows = jnp.take_along_axis(
        tr.blocks, (first[:, None] + jnp.arange(k, dtype=jnp.int32))[:, :, None],
        axis=1, mode="promise_in_bounds")
    return _picked(rows, off, n)


def window_flat(tr, ptr, n):
    """Row reads of the blocks as one flat `[C * Tb, 128]` table."""
    first, off, k = _window_rows(tr, ptr, n)
    C, Tb, _ = tr.blocks.shape
    slot = (jnp.arange(C, dtype=jnp.int32) * Tb + first)[:, None] + jnp.arange(
        k, dtype=jnp.int32)
    return _picked(tr.blocks.reshape(C * Tb, 128)[slot], off, n)


def window_slice2(tr, ptr, n):
    """ONE slice of `k` blocks a core."""
    first, off, k = _window_rows(tr, ptr, n)
    rows = jax.vmap(lambda blk, b: jax.lax.dynamic_slice_in_dim(blk, b, k, 0))(
        tr.blocks, first)
    return _picked(rows, off, n)


def event_read_forms(cores=(1024, 4096, 16384), lengths=(150, 546, 8192),
                     run_lens=(0, 8)):
    import re

    from primesim_tpu.trace.device import DeviceTrace, _span

    forms = (("elements", events_elements), ("pairs", DeviceTrace.window),
             ("picked", window_picked), ("b1st", window_b1st),
             ("flat", window_flat), ("slice2", window_slice2))
    rng = np.random.default_rng(0)
    print(f"device {jax.devices()[0].device_kind}; us an iteration, {ITER} in a "
          "loop; `*`: the compiled loop writes the whole array anew")
    print("     C     T rl  " + "  ".join(f"{n:>11s}" for n, _ in forms)
          + "  elements_ns_index  pairs_ns_row")
    for C in cores:
        for T in lengths:
            events = jax.jit(lambda C=C, T=T: (
                jax.lax.broadcasted_iota(jnp.int32, (C, T, 4), 0) * 40503
                + jax.lax.broadcasted_iota(jnp.int32, (C, T, 4), 1) * 4
                + jax.lax.broadcasted_iota(jnp.int32, (C, T, 4), 2)))()
            ptr0 = jnp.asarray(rng.integers(0, T + 4, C, dtype=np.int32))
            for rl in run_lens:
                n = rl + 1
                tr = jax.jit(lambda ev, rl=rl: DeviceTrace.of(ev, rl))(events)
                us, copies, want = {}, {}, None
                for name, form in forms:
                    arg = events if name == "elements" else tr

                    def loop(arg, ptr0, form=form):
                        def body(i, acc):
                            return acc ^ form(arg, (ptr0 + i * 7) % (T + 4), n)
                        return jax.lax.fori_loop(
                            0, ITER, body, jnp.zeros((C, n, 4), jnp.int32))
                    compiled = jax.jit(loop).lower(arg, ptr0).compile()
                    us[name] = timeit(compiled, arg, ptr0, n=3, jit=False) / ITER * 1e6
                    # an op inside the loop whose result is the whole array
                    # (as it is, or flat): a copy or a relayout a call
                    shape = jax.tree.leaves(arg)[0].shape
                    dims = "|".join(",".join(map(str, d)) for d in (
                        shape, (shape[0] * shape[1], shape[2])))
                    copies[name] = bool(re.search(
                        rf"= s32\[(?:{dims})\]\S* (?:copy|fusion|transpose)\(",
                        compiled.as_text()))
                    got = np.asarray(compiled(arg, ptr0))
                    want = got if want is None else want  # `elements` runs first
                    np.testing.assert_array_equal(got, want, err_msg=name)
                print(f"{C:6d} {T:5d} {rl:2d}  " + "  ".join(
                    f"{us[name]:10.1f}{'*' if copies[name] else ' '}"
                    for name, _ in forms)
                      + f"  {us['elements'] * 1e3 / (C * n):17.2f}"
                      f"  {us['pairs'] * 1e3 / (C * _span(n)):12.2f}",
                      flush=True)
                del tr
            del events


def raw():
    rng = np.random.default_rng(0)
    R = 524288
    for width in (8, 24, 128, 280, 384):
        A = jnp.asarray(rng.integers(0, 100, (R, width), dtype=np.int32))
        for n_idx in (1024, 4096, 9216, 18432):
            idx = jnp.asarray(rng.integers(0, R, n_idx, dtype=np.int32))
            t_row = timeit(lambda a, i: a[i], A, idx)
            col = jnp.asarray(
                rng.integers(0, width, n_idx, dtype=np.int32)
            )
            t_el = timeit(lambda a, i, c: a[i, c], A, idx, col)
            upd = jnp.zeros((n_idx, width), jnp.int32)
            t_sc = timeit(
                lambda a, i, u: a.at[i].set(u, mode="drop"), A, idx, upd
            )
            print(
                f"w={width:4d} n={n_idx:6d}  row-gather {t_row*1e3:7.3f} ms"
                f"  elem-gather {t_el*1e3:7.3f} ms"
                f"  row-scatter {t_sc*1e3:7.3f} ms",
                flush=True,
            )


if __name__ == "__main__":
    {("rows",): way_read_forms, ("local",): local_read_forms,
     ("writes",): write_forms,
     ("events",): event_read_forms, ("raw",): raw}.get(
        tuple(sys.argv[1:]), set_read_forms)()
