"""Traffic shape `uniform_random`: the access pattern of
`primesim_tpu/trace/synth.py::uniform_random`, drawn from the same
random stream in array calls instead of one call per event;
`tests/test_benchmark.py::test_generator_equals_the_programs` holds the
two equal, event for event."""

import numpy as np

from trafficgen import EV_LD, EV_ST, LINE, finish


def generate(n_cores: int, seed: int, n_mem_ops: int, working_set: int,
                   write_frac: float, shared_frac: float,
                   ins_per_mem: int) -> np.ndarray:
    """Uncorrelated loads and stores: each core over a range of its own, a
    `shared_frac` of them in one range common to all cores."""
    if ins_per_mem < 1:
        raise ValueError("ins_per_mem must be >= 1")
    rng = np.random.default_rng(seed)
    n = n_mem_ops
    shared_size = max(LINE * 16, working_set // 8)
    types = np.empty((n_cores, n), np.int64)
    addrs = np.empty((n_cores, n), np.int64)
    pre = np.empty((n_cores, n), np.int64)
    for c in range(n_cores):
        is_shared = rng.random(n) < shared_frac
        is_write = rng.random(n) < write_frac
        offs = rng.integers(0, working_set, n)
        sh_offs = rng.integers(0, shared_size, n)
        a = np.where(is_shared, sh_offs, (1 + c) * working_set + offs)
        addrs[c] = (a // 4) * 4
        types[c] = np.where(is_write, EV_ST, EV_LD)
        pre[c] = rng.integers(1, 2 * ins_per_mem + 1, size=n)
    return finish(types, 4, addrs, pre)
