"""Micro-benchmarks of TPU gathers — the data behind the engine's
array-layout choices and behind `sim/step.py::_l1_set_read` having one form.

    python scripts/prof/prof_gather.py          # the L1 set read's two forms
    python scripts/prof/prof_gather.py rows     # row / element gather, row scatter

Default: the L1 set read's two forms alone (select: `_l1_set_read`, the core's
row read whole and the set picked by a compare and a masked sum; gather: the
element `take_along_axis` it replaced in PR 29, kept here as `set_gather`), at
S1 in {128, 512, 2048} x C in {1024, 16384}, for the local run's read (K = 9
candidates, tag and state planes) and the probe's (K = 1, four planes). Each form runs ITER times inside one `fori_loop`
on sets that change every iteration, so the time is the device's and holds no
dispatch; us an iteration, and ns a gathered word for the gather.

`rows`: cost against index count, row width and operand size. Hypothesis from
single-op ablations of the step: cost ~= per-INDEX overhead, mostly independent
of row width and operand bytes; windowed (dynamic column) forms are
pathological.
"""
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ITER = 50
W1 = 4


def timeit(fn, *args, n=20):
    f = jax.jit(fn)
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


def rows():
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
    rows() if sys.argv[1:] == ["rows"] else set_read_forms()
