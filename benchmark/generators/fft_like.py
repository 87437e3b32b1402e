"""Traffic shape `fft_like`: the access pattern of
`primesim_tpu/trace/synth.py::fft_like`, drawn from the same random
stream in array calls instead of one call per event;
`tests/test_benchmark.py::test_generator_equals_the_programs` holds the
two equal, event for event."""

import numpy as np

from trafficgen import EV_LD, EV_ST, LINE, finish


def generate(n_cores: int, seed: int, n_phases: int, points_per_core: int,
             ins_per_mem: int) -> np.ndarray:
    """SPLASH-2 FFT's shape: in each phase a core loads and stores its own
    points at a stride that doubles, then reads one word per line of the
    block of its butterfly partner (core XOR 2^phase)."""
    if ins_per_mem < 1:
        raise ValueError("ins_per_mem must be >= 1")
    rng = np.random.default_rng(seed)
    C, ppc = n_cores, points_per_core
    block = ppc * 8
    cores = np.arange(C, dtype=np.int64)
    base = (1 + cores) * (block * 8)
    i = np.arange(ppc, dtype=np.int64)
    j = np.arange(0, ppc, max(1, LINE // 8), dtype=np.int64)
    n_ev = 2 * ppc + len(j)
    pre = rng.integers(1, 2 * ins_per_mem + 1, size=(n_phases, C, n_ev))
    types = np.empty((C, n_phases, n_ev), np.int64)
    addrs = np.empty((C, n_phases, n_ev), np.int64)
    types[:, :, : 2 * ppc : 2] = EV_LD
    types[:, :, 1 : 2 * ppc : 2] = EV_ST
    types[:, :, 2 * ppc :] = EV_LD
    for p in range(n_phases):
        own = base[:, None] + ((i * (8 << p)) % block)[None, :]
        addrs[:, p, : 2 * ppc : 2] = own
        addrs[:, p, 1 : 2 * ppc : 2] = own
        partner = (cores ^ (1 << (p % max(1, (C - 1).bit_length())))) % C
        addrs[:, p, 2 * ppc :] = ((1 + partner) * (block * 8))[:, None] + (j * 8)[None, :]
    n = n_phases * n_ev
    return finish(types.reshape(C, n), 8, addrs.reshape(C, n),
                   pre.transpose(1, 0, 2).reshape(C, n))
