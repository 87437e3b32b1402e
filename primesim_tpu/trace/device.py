"""The device layout of a trace, and every read of it.

`Trace.line_events` is `[C, T, 4]` int32: a record of four words an event,
T of them a core, END-padded. Left to the compiler that array lies with the
cores on the lanes and the time index outermost (`{0,2,1:T(4,128)}`), so a
core's consecutive records are T tiles apart and reading `rl + 1` of them
costs `rl + 1` indices a core: nine slices of 16 bytes, 12.6-16.6 ns each,
the heaviest op of the plain step until PR 40 (PERF.md section 6).

`DeviceTrace` holds the same words as `blocks` `[C, Tb, 128]`: block `b` of
core `c` is records `32 b .. 32 b + 31` of that core as they lie in
`line_events`, four words each, one row of 128 lanes. Past record `T - 1`
the padding REPEATS that record (a core's last, its END), which is what a
read at `min(ptr + i, T - 1)` finds: a window that overruns the trace reads
what the clamp read, to the word. `Tb` is the blocks that hold the trace
plus those a window of `run_len + 1` records may overrun (`_span`), so
every block a window names is there.

A window is then ONE gather of the whole blocks that hold it (two at a run
length of 1 to 32, one at 0), batched over the cores: 2 C rows of 512 bytes
where there were 9 C slices of 16. The window starts at record `ptr % 32` of
the rows in hand and is moved down to their lane 0 by a lane shifter
(`_shifted`): static slices and selects, dense vector work. The core is a
batch dimension of the gather, so on a mesh the array shards by core
(`sharding.events_pspec`) and every chip reads its own cores' blocks: no
collective, whichever backend partitions it. On the v5e the read is x4.3-5.4
under the element read at every C 1024-16384 x T 150-8192 and nothing in it
grows with T, so there is one form and no size branch. Not taken, with their
prices (`scripts/prof/prof_gather.py events`, scripts/prof/README.md): picking
the words one by one (`step.py::_pick`'s idiom: x2.2), the pairs as two
index arrays (10-13 % slower, and the CPU's partitioner all-reduces the
rows), one slice of two blocks a core (x1.9), a flat `[C * Tb, 128]` (the
loop copies the array, and the partitioner cannot see whose rows are whose).
A machine without local runs reads the one record at `ptr` the same way
(`at`: one block and the shifter, 2 us over the C-index read at 1024 cores):
the element read would want the other layout, and a trace has one.

It presents `.shape == (C, T, 4)` and `.dtype` int32, the trace's own: the
benchmark's roofline takes the bytes of one event record from them.
Registered as a pytree of one leaf (`blocks`; `T` is static), so `jit`,
`vmap` (the fleet's batch: `blocks` `[B, C, Tb, 128]`), `device_put` with
a `NamedSharding` and `block_until_ready` take it as they took the array.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

RECORDS = 32  # records a block
WORDS = 4  # words a record: `trace.format.N_FIELDS`
LANES = RECORDS * WORDS  # 128: one lane row


def _span(n: int) -> int:
    """Blocks that hold any `n` consecutive records: the first may start
    at the last record of its block."""
    return (RECORDS - 2 + n) // RECORDS + 1


def _shifted(x, off, n: int):
    """Records `off + i`, `i < n`, of each row of `x` `[C, k * 128]` (`off
    < 32`) -> `[C, n, 4]`: the window starts at lane `off * 4` and is moved
    down to lane 0 by a shifter, a stage a bit of `off`, each a static lane
    slice and a select, and narrower than the last (after the stage of `s`
    lanes at most `s - 4` are still to go). No lane reduction: picking the
    4 n words one by one (a compare against an iota and a masked sum each,
    `step.py::_pick`) costs twice the gather itself (`prof_gather.py
    events`: `picked`)."""
    for bit in reversed(range(RECORDS.bit_length() - 1)):
        s = WORDS << bit
        x = x[:, : n * WORDS + 2 * s - WORDS]
        x = jnp.where((((off >> bit) & 1) != 0)[:, None],
                      x[:, s:], x[:, : x.shape[1] - s])
    return x[:, : n * WORDS].reshape(x.shape[0], n, WORDS)


@jax.tree_util.register_pytree_node_class
class DeviceTrace:
    """A trace (or a window of a stream, or a fleet's batch of traces) as
    the device holds it; see the module's docstring."""

    def __init__(self, blocks, length: int):
        self.blocks = blocks  # [..., C, Tb, 128] int32
        self.length = length  # T: records a core, the END padding included

    @classmethod
    def of(cls, events, run_len: int) -> "DeviceTrace":
        """`events` `[..., C, T, 4]` laid out for windows of up to
        `run_len + 1` records (`cfg.local_run_len`); a `DeviceTrace`
        passes through. A host array is laid out on the host, so the
        device never holds both forms; a device array or a tracer (a
        caller that hands a loop the raw array) inside the program."""
        if isinstance(events, cls):
            return events
        xp = np if isinstance(events, np.ndarray) else jnp
        *lead, T, words = events.shape
        assert words == WORDS and events.dtype == np.int32, (events.shape, events.dtype)
        n_blocks = (T - 1) // RECORDS + _span(run_len + 1)
        last = xp.broadcast_to(
            events[..., -1:, :], (*lead, n_blocks * RECORDS - T, WORDS))
        blocks = xp.concatenate([events, last], axis=-2)
        return cls(blocks.reshape(*lead, n_blocks, LANES), T)

    def tree_flatten(self):
        return (self.blocks,), self.length

    @classmethod
    def tree_unflatten(cls, length, children):
        return cls(children[0], length)

    @property
    def shape(self) -> tuple:
        return (*self.blocks.shape[:-2], self.length, WORDS)

    @property
    def dtype(self):
        return self.blocks.dtype

    @property
    def sharding(self):
        return self.blocks.sharding

    def window(self, ptr, n: int):
        """Records `min(ptr + i, T - 1)`, `i < n`, of every core -> `[C, n,
        4]`: `events[arange_c[:, None], minimum(ptr[:, None] + arange(n),
        T - 1)]` of the `[C, T, 4]` array, word for word."""
        C, n_blocks, _ = self.blocks.shape
        k = _span(n)
        assert (self.length - 1) // RECORDS + k <= n_blocks, (
            f"a window of {n} records overruns {n_blocks} blocks of a "
            f"trace of {self.length}: lay it out with run_len >= {n - 1}")
        p = jnp.minimum(ptr, self.length - 1)
        first = p // RECORDS
        rows = jnp.take_along_axis(
            self.blocks,
            (first[:, None] + jnp.arange(k, dtype=jnp.int32))[:, :, None],
            axis=1, mode="promise_in_bounds")  # [C, k, 128]; `of` laid them all out
        return _shifted(rows.reshape(C, k * LANES), p - first * RECORDS, n)

    def at(self, ptr):
        """The record at `min(ptr, T - 1)` of every core -> `[C, 4]`."""
        return self.window(ptr, 1)[:, 0]
