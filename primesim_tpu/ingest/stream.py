"""Windowed (streaming) trace ingest — SURVEY.md §2 #8 / §7.

The reference's UncoreManager drains a bounded queue of frontend events;
the TPU-native equivalent streams a trace through BOUNDED device memory:
the host holds per-core cursors into the (possibly memory-mapped) event
source, uploads one `window_events`-deep window at a time, and the device
`stream_loop` simulates until some core's window runs dry — its per-STEP
exit condition fires before that core could have joined an arbitration it
would have entered with the full trace, so windowed results are BIT-EXACT
with a preloaded `Engine.run()`, LRU stamps included.

This is what makes BASELINE rung-4/5 traces (billions of events, far
beyond the [C, T, 4] device array a preloaded run needs) simulatable:
device memory is O(C * window_events), host memory is O(1) beyond the
mmapped file.

    from primesim_tpu.ingest.stream import StreamEngine
    eng = StreamEngine(cfg, Trace.load("huge.ptpu", mmap=True),
                       window_events=4096)
    eng.run()
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from ..config.machine import MachineConfig
from ..stats.counters import fold_block, zero_counters, zero_stats
from ..sim import exec_cache
from ..sim.engine import _ACC_BITS, stream_loop
from ..sim.state import init_state
from ..trace.device import DeviceTrace
from ..trace.format import (
    EV_BARRIER,
    EV_END,
    Trace,
    TraceError,
    scan_trace_meta,
)


def absorb_stream_outputs(eng, out, buf):
    """Fold one `stream_loop` dispatch's outputs into a streaming
    engine's host accumulators (64-bit counter fold with the _ACC_BITS
    carry, cycle-base advance, cursor advance) — the ONE implementation
    of the drain protocol, shared by StreamEngine and the online
    ring-fed engine so the two can never diverge. Returns
    (steps_executed, consumed, at_end_mask)."""
    import jax.numpy as jnp

    st, acc_lo, acc_hi, base_lo, base_hi, k = out
    acc = (
        (np.asarray(acc_hi).astype(np.int64) << _ACC_BITS)
        + np.asarray(acc_lo).astype(np.int64)
        + np.asarray(st.counters).astype(np.int64)
    )
    fold_block(eng.host_counters, eng.host_stats, acc)
    eng.cycle_base += (
        np.int64(np.asarray(base_hi)) << _ACC_BITS
    ) + np.int64(np.asarray(base_lo))
    st = st._replace(counters=jnp.zeros_like(st.counters))
    consumed = np.asarray(st.ptr).astype(np.int64)
    k_int = int(np.asarray(k))
    eng.steps_run += k_int
    eng.state = st
    at_end = (
        buf[np.arange(eng.cfg.n_cores), np.minimum(consumed, eng.W), 0]
        == EV_END
    )
    eng.cursor += consumed
    return k_int, consumed, at_end


class StreamEngine:
    """Bounded-memory streaming runner; results bit-exact vs Engine.run."""

    def __init__(
        self,
        cfg: MachineConfig,
        trace: Trace,
        window_events: int = 1024,
        mesh=None,
    ):
        assert trace.n_cores == cfg.n_cores
        if window_events < max(1, cfg.local_run_len + 1):
            raise ValueError(
                "window_events must cover at least one local run + 1 event"
            )
        self.cfg = cfg
        self.trace = trace
        # raw (possibly mmapped) source; byte-addressed traces are
        # line-normalized PER WINDOW below so no full-array copy ever
        # materializes (v4 line-addressed traces need no conversion, but
        # their recorded line size must match — reuse the shared check)
        if trace.line_addressed:
            trace.line_events(cfg.line_bits)  # line-size validation only
        self.src = trace.events
        # one bounded-memory pass (chunked by core rows, mmap-friendly)
        # for sync presence, the max instruction batch, and barrier ids
        self.has_sync, per_ev, bad_bid = scan_trace_meta(
            trace, cfg.barrier_slots
        )
        if bad_bid:
            raise TraceError(
                f"trace uses barrier ids >= barrier_slots={cfg.barrier_slots}",
                core=bad_bid[0],
                offset=bad_bid[1],
            )
        # real (pre-END) event count per core
        self.real_len = np.asarray(trace.lengths, dtype=np.int64) - 1
        self.cursor = np.zeros(cfg.n_cores, dtype=np.int64)
        self.W = int(window_events)
        # 64-step on-device drain cadence bounds per-drain counter growth
        if 64 * (cfg.local_run_len + 1) * per_ev >= 1 << _ACC_BITS:
            raise ValueError(
                "trace's max per-event instruction batch overflows the "
                "streaming 64-step counter drain; split INS batches"
            )
        self.state = init_state(cfg)
        # multi-chip layout (DESIGN.md §22): shard the machine over the
        # mesh's "tiles" axis at init; stream_loop outputs keep it by
        # propagation, so only the per-window fresh uploads (window
        # buffer, exhausted/filled masks, the reset ptr) need explicit
        # placement — see _place_core_axis/_zero_ptr.
        self.mesh = mesh
        if mesh is not None:
            from ..parallel.sharding import shard_state

            self.state = shard_state(mesh, self.state)
        self.cycle_base = np.int64(0)
        self.host_counters = zero_counters(cfg.n_cores)
        self.host_stats = zero_stats(cfg.n_cores)  # STAT_NAMES, as Engine
        self.steps_run = 0
        # telemetry sink (obs.Recorder) — None skips every telemetry
        # branch in _advance_window
        self.obs = None
        self.obs_label = "stream"
        # attestation chain (attest.SoloAttest) — window-scoped: the
        # stream engine's natural chunk is the WINDOW, so its chain is
        # comparable only to another streamed run of the same trace
        # (DESIGN.md §24); None = never fingerprint
        self.attest = None

    def _fill_window(self):
        from ..trace.format import EV_LD, EV_LOCK, EV_ST, EV_UNLOCK

        C = self.cfg.n_cores
        buf = np.zeros((C, self.W + 1, 4), dtype=np.int32)
        buf[:, :, 0] = EV_END
        # vectorized fill: one gather over per-core cursors instead of an
        # O(C) Python loop (the loop was the wall at 4096-16384 cores —
        # thousands of host iterations per window refill). Peak temporaries
        # stay O(C * W), the same bound as the window itself.
        take = np.minimum(self.W, self.real_len - self.cursor)
        take = np.maximum(take, 0)
        idx = self.cursor[:, None] + np.arange(self.W, dtype=np.int64)[None, :]
        valid = idx < (self.cursor + take)[:, None]
        idx = np.minimum(idx, self.src.shape[1] - 1)
        vals = np.take_along_axis(
            self.src, idx[:, :, None], axis=1
        )  # [C, W, 4]; memmap sources fault in only the touched pages
        buf[:, : self.W] = np.where(valid[:, :, None], vals, buf[:, : self.W])
        filled = take.astype(np.int32)
        exhausted = self.cursor + take >= self.real_len
        if not self.trace.line_addressed:
            t = buf[:, :, 0]
            addr_ev = (
                (t == EV_LD) | (t == EV_ST) | (t == EV_LOCK) | (t == EV_UNLOCK)
            )
            buf[:, :, 2] = np.where(
                addr_ev, buf[:, :, 2] >> self.cfg.line_bits, buf[:, :, 2]
            )
        return buf, exhausted, filled

    def _place_core_axis(self, x):
        """Upload a host array (or the window, a `DeviceTrace`) whose
        leading axis is the core axis, sharded over the mesh when one is
        set (fresh uploads carry no sharding of their own to propagate
        from)."""
        if self.mesh is None:
            return jax.device_put(x)
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..parallel.sharding import AXIS

        return jax.device_put(x, NamedSharding(self.mesh, P(AXIS)))

    def _zero_ptr(self):
        """The per-window ptr reset, placed like state.ptr so the reset
        cannot silently drop the mesh layout mid-run."""
        return self._place_core_axis(
            np.zeros(self.cfg.n_cores, np.int32)
        )

    def warmup(self) -> None:
        """Compile `stream_loop` at this run's window shapes with a
        ZERO-step budget (the budget is a traced arg, so the real run
        reuses the compilation) and block until ready. Call before a
        wall-clock measurement, mirroring Engine.block_until_ready —
        keeping this next to run() so the warm-up and the real dispatch
        cannot desynchronize."""
        cfg = self.cfg
        buf, exhausted, filled = self._fill_window()
        out = exec_cache.call(
            stream_loop, "stream.loop",
            (cfg,),
            (
                self._place_core_axis(
                    DeviceTrace.of(buf, cfg.local_run_len)),
                self.state._replace(ptr=self._zero_ptr()),
                self._place_core_axis(exhausted),
                self._place_core_axis(filled),
                jnp.asarray(0, jnp.int32),
            ),
            {"has_sync": self.has_sync},
        )
        np.asarray(out[0].cycles)  # block until compiled

    def _advance_window(self, budget: int) -> tuple[int, bool]:
        """Dispatch ONE windowed device loop: fill, simulate until some
        core's window runs low, drain counters, advance cursors. Returns
        (steps executed, finished). After it returns, the engine is at a
        CONSISTENT CUT — cursors and state fully describe the run — which
        is what makes streaming checkpoints possible."""
        cfg = self.cfg
        t0 = time.perf_counter() if self.obs is not None else 0.0
        buf, exhausted, filled = self._fill_window()
        t1 = time.perf_counter() if self.obs is not None else 0.0
        st = self.state._replace(ptr=self._zero_ptr())
        # NOTE: no overlapped dispatch here — the next window's input is
        # produced by the host-side fill/absorb cycle itself (the very
        # work overlap would hide), so there is nothing device-side to
        # speculate. The exec cache still applies.
        out = exec_cache.call(
            stream_loop, "stream.loop",
            (cfg,),
            (
                self._place_core_axis(
                    DeviceTrace.of(buf, cfg.local_run_len)),
                st,
                self._place_core_axis(exhausted),
                self._place_core_axis(filled),
                jnp.asarray(min(budget, 2**31 - 1), jnp.int32),
            ),
            {"has_sync": self.has_sync},
        )
        t2 = time.perf_counter() if self.obs is not None else 0.0
        k_int, consumed, at_end = absorb_stream_outputs(self, out, buf)
        if self.obs is not None:
            # one sample per WINDOW (the stream engine's natural chunk);
            # absorb's host transfer synchronizes, so it includes the
            # device executing the window
            t3 = time.perf_counter()
            self.obs.chunk_committed(
                self.obs_label, k_int, t3 - t0, self.host_counters,
                phases={"fill": t1 - t0, "dispatch": t2 - t1,
                        "absorb": t3 - t2},
            )
        if self.attest is not None:
            self.attest.observe(self)
        finished = bool((at_end & exhausted).all())
        if not finished and k_int == 0 and not consumed.any():
            raise RuntimeError(
                "stream engine: no progress in a window (window_events "
                "too small for this trace shape?)"
            )
        return k_int, finished

    def _default_budget(self) -> int:
        return max(10_000_000, 64 * int(self.real_len.sum()))

    def done(self) -> bool:
        """All cores consumed their real (pre-END) events."""
        return bool((self.cursor >= self.real_len).all())

    def done_mask(self) -> np.ndarray:
        """Per-core finished mask (host-side, from the stream cursors)."""
        return self.cursor >= self.real_len

    def live_mask(self) -> np.ndarray:
        """Cores that bound the quantum window at this cut: not finished
        and not frozen at a barrier (frozen clocks legally lag
        quantum_end until release). Supervisor guard input — same
        contract as Engine.live_mask, but read from host cursors into
        the (possibly mmapped) source instead of a device ptr gather."""
        C = self.cfg.n_cores
        at = np.minimum(self.cursor, np.maximum(self.real_len - 1, 0))
        et = np.asarray(self.src[np.arange(C), at, 0])
        frozen = (et == EV_BARRIER) & (
            np.asarray(self.state.sync_flag) != 0
        )
        return (self.cursor < self.real_len) & ~frozen

    def run(self, max_steps: int | None = None) -> None:
        """Stream to completion. `max_steps` defaults to a budget derived
        from the trace's total event count (retries/spins included via a
        generous per-event multiplier) — a 10M constant would abort the
        billion-event runs this engine exists for."""
        budget = max_steps if max_steps is not None else self._default_budget()
        while True:
            k, finished = self._advance_window(budget)
            budget -= k
            if finished:
                return
            if budget <= 0:
                raise RuntimeError(
                    f"stream engine: step budget ({max_steps}) exhausted at "
                    f"{int(self.cursor.sum())}/{int(self.real_len.sum())} "
                    "events consumed — deadlocked barrier/lock, or pass a "
                    "larger max_steps"
                )

    def run_events(self, target_events: int) -> bool:
        """Advance window-by-window until at least `target_events` trace
        events are consumed in total (or the stream finishes); the natural
        pause point for a streaming checkpoint. Returns finished."""
        budget = self._default_budget()
        while int(self.cursor.sum()) < target_events:
            k, finished = self._advance_window(budget)
            budget -= k
            if finished:
                return True
            if budget <= 0:
                raise RuntimeError("stream engine: step budget exhausted")
        return False

    # ---- checkpoint / resume (SURVEY.md §5.4, streaming) -----------------

    def save_checkpoint(self, path: str) -> None:
        from ..sim.checkpoint import save_stream_checkpoint

        save_stream_checkpoint(path, self)

    def load_checkpoint(self, path: str) -> None:
        from ..sim.checkpoint import load_stream_checkpoint

        load_stream_checkpoint(path, self)

    # ---- results (Engine-compatible surface) -----------------------------

    @property
    def cycles(self) -> np.ndarray:
        return np.asarray(self.state.cycles).astype(np.int64) + self.cycle_base

    @property
    def counters(self):
        return self.host_counters

    @property
    def step_stats(self):
        return self.host_stats
