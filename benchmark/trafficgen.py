"""The one traffic generator: a traffic file's parameters and a seed in,
target traces out. numpy only, so it runs before JAX is touched.

A traffic file (`benchmark/traffic/<name>.json`) names a `generator` (a
trace shape: `benchmark/generators/<name>.py`, one function
`generate(n_cores, seed, **args)`), its `args`, and `panel_seeds`, the
generator seeds of the traces every run of the mix simulates. The work
is the same for every `--seed`: the seed draws the order in which the
panel's traces run, and with it the job that is held to the reference,
and it is the generator seed of the short trace of the parity job, which
`parity_args` cut from `args`. All traces of one file have one shape, so
one compiled program serves them.

Traces come out folded, as the repo's `fold_ins` leaves them: every
memory event carries the batch of plain instructions before it in its
`pre` field.
"""

from __future__ import annotations

import numpy as np

import cells

EV_LD, EV_ST, EV_END = 1, 2, 3
LINE = 64


def finish(types, args, addrs, pre) -> np.ndarray:
    """[C, n] columns -> events [C, n+1, 4] int32 with the END row."""
    if addrs.max(initial=0) >= 2**31 or addrs.min(initial=0) < 0:
        raise ValueError("addresses must lie in [0, 2^31)")
    C, n = types.shape
    ev = np.zeros((C, n + 1, 4), np.int32)
    ev[:, :, 0] = EV_END
    ev[:, :n, 0] = types
    ev[:, :n, 1] = args
    ev[:, :n, 2] = addrs
    ev[:, :n, 3] = pre
    return ev


def make_trace(traffic: dict, n_cores: int, gen_seed: int, parity: bool = False,
               root: str = cells.ROOT) -> np.ndarray:
    if not traffic.get("fold", True):
        raise ValueError("only folded traces (fold: true) are generated")
    args = dict(traffic["args"])
    if parity:
        args.update(traffic["parity_args"])
    return cells.load_generator(traffic["generator"], root)(n_cores, gen_seed, **args)


def panel_order(traffic: dict, seed: int) -> list[int]:
    """The order in which a run simulates the panel's traces: every seed
    the same traces, in another order."""
    return [int(i) for i in np.random.default_rng(seed).permutation(len(traffic["panel_seeds"]))]


def make_panel(traffic: dict, n_cores: int, seed: int, root: str = cells.ROOT) -> list[tuple]:
    """[(index into `panel_seeds`, events)] in the run's order."""
    seeds = traffic["panel_seeds"]
    return [(i, make_trace(traffic, n_cores, int(seeds[i]), root=root))
            for i in panel_order(traffic, seed)]


def pad_to(events: np.ndarray, length: int) -> np.ndarray:
    """Pad every core's row with END up to `length` events."""
    C, T, F = events.shape
    if T > length:
        raise ValueError(f"trace of {T} events does not fit {length}")
    out = np.zeros((C, length, F), np.int32)
    out[:, :, 0] = EV_END
    out[:, :T] = events
    return out


def total_instructions(events: np.ndarray) -> int:
    """Instructions a folded trace holds: 1 + pre for every memory event."""
    mem = events[:, :, 0] != EV_END
    return int(mem.sum()) + int(events[:, :, 3][mem].astype(np.int64).sum())
