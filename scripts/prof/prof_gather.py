"""Micro-benchmark: TPU gather/scatter cost vs index count, row width,
and operand size — the data behind the engine's array-layout choices.

Hypothesis from single-op ablations of the step: cost ~= per-INDEX overhead (~80 ns),
mostly independent of row width and operand bytes; windowed (dynamic
column) forms are pathological. If true, fusing metadata columns into the
sharers rows (one gather per probe instead of three) is the right call.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np


def timeit(fn, *args, n=20):
    f = jax.jit(fn)
    out = f(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = f(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n


def main():
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
    main()
