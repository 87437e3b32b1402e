"""FleetEngine — batch B independent simulations through ONE program.

Round-5 profiling (the pre-round r05 record, ROADMAP Queue S) pinned the
~2.8 ms/step floor on the step's SERIAL kernel-chain depth, not bytes: isolated gathers/scatters of
any tested shape cost ~0.02 ms, so each kernel launch is mostly idle
capacity. PriME's headline use case is throughput across many concurrent
runs (the ISPASS'14 multi-host aggregate), and
a parameter sweep is the common shape of that traffic. So: `jax.vmap` the
existing `run_chunk`, and `run_loop`'s chunk, over a leading batch axis of
B independent simulations sharing one GEOMETRY (core count, cache shapes, mesh), and one
scan step retires one event per core *per simulation*.

What that costs was measured on a TPU v5e in PR 42 and 43 (PERF.md
section 6; rung 2's 256-core machine, one FFT trace an element), and it
is NOT the B=1 kernel-chain cost the round-5 reckoning hoped for: a
fleet's step is B steps and more, 0.291 ms a step an element at B = 1 and
4, 0.405 at 16 and 0.439 at 32, where a solo `Engine` on the same machine
and trace takes 0.150, so B solo jobs back to back were the faster way to
run B simulations on one chip. Of the 4.05 ms a step that lay outside
every phase at B = 16, the compiled text (`scripts/prof/compile_v5e.py
--fleet 16`, PR 44) says 1.85 was not the loop's carry at all: phase
4.A's join table, relaid whole under the batch axis one row a loop trip,
every step, which `step.py::_join_representative` cured by reading the
table as rows of a tile (0.280 ms a step an element since, 17.7 Minstr/s
for 12.3). The other half was the freeze, once a CHUNK: until PR 45
`fleet_run_loop` was `jax.vmap(run_loop)`, and what `vmap` makes of a
`lax.while_loop` with a batched predicate selects every leaf of the
carry between old and new, `dirm` included (`broadcast_select_fusion`
and `copy` of all B directories, 15.8 ms a chunk at B = 16: 1.98 ms a
step at the benchmark cell's `chunk_steps` 8, 0.06 at the CLI's default
256). Since PR 45 the loop over chunks is written out, outside the
`vmap`, and freezes every leaf but the two a finished machine's step
cannot change (`FREEZE_EXEMPT`, below): the fleet's step no longer
depends on `chunk_steps` (PERF.md section 6, PR 45). Open: donation of
the input state (ROADMAP S11: memory, at any chunk size) and the 13 %
the phases cost more under the batch axis; the benchmark cell
`rung2.sweep-b16` (PR 43) is where either is judged, and one compile for
the whole sweep and the served buckets' elastic slots are what the fleet
gives besides.

Two design points make a whole sweep ONE compilation:

- The per-simulation TIMING knobs (quantum, cpi, cache/NoC/DRAM latencies
  — `sim.state.TimingKnobs`) are TRACED, carried in `MachineState.knobs`
  and stacked over the batch axis. The static jit key is
  `cfg.timing_normalized()`: every timing variant of one geometry hits the
  same cache entry.
- Termination: `fleet_run_loop` runs the chunk for EVERY element while
  ANY element's predicate holds (`engine.loop_live`, `run_loop`'s own)
  and then puts back, leaf by leaf, what an element held before the
  chunk if it was not live, so finished elements FREEZE at their own
  chunk boundary — exactly where a solo `run_loop` with the same
  `chunk_steps` stops. Fleet element i is therefore bit-exact with a solo
  `Engine` run of the same (config, trace), including the step counter
  (tests/test_fleet.py::test_fleet_freeze_elements_chunks_apart). The
  two large leaves, `dirm` and `l1`, are handed on unselected: a step
  over a machine whose cores all stand at END writes neither
  (`test_finished_machine_keeps_exempt_leaves`), and selecting them
  copied the directories once a chunk.

Observability: `FleetEngine.run` opens the host spans `fleet.init`,
`fleet.dispatch`, `fleet.wait` and `fleet.readback` (`obs/span.py`) and
commits ONE sample a run, as `Engine.run` does (`engine.commit_job`,
DESIGN.md §15): the totals of all its machines, the longest element's
steps, and `caps` that say B.

Scope: preloaded traces only. Streamed (windowed) ingest stays solo — the
host-side window refill rate is per-element state, and batching it buys
nothing while any element's refill stalls the fleet (see DESIGN.md §6).
"""

from __future__ import annotations

import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..chaos import sites as chaos
from ..config.machine import MachineConfig
from ..obs.span import span
from ..parallel.sharding import (
    AXIS,
    build_fleet_state,
    check_fleet_mesh,
    fleet_is_cut,
    mesh_jit,
    shard_fleet_events,
    shard_fleet_state,
)
from ..stats.counters import COUNTER_NAMES, STAT_NAMES, fold_block
from ..trace.device import DeviceTrace
from ..trace.format import EV_BARRIER, EV_END, EV_LOCK, EV_UNLOCK, Trace
from . import exec_cache
from .engine import (
    _ACC_BITS,
    _np,
    alloc_now,
    commit_job,
    job_place,
    loop_chunk,
    loop_live,
    place_run,
    run_chunk,
)
from .state import MachineState, init_state


def idle_trace(n_cores: int) -> Trace:
    """The empty workload: every core's trace is a single END event, so
    the element is done before its first step. Free slots in a serving
    fleet (serve/scheduler.py) hold this trace — the vmapped step is a
    no-op for them while live slots advance."""
    events = np.zeros((n_cores, 1, 4), np.int32)
    events[:, :, 0] = EV_END
    return Trace(events, np.ones(n_cores, np.int32))


def _trace_per_step_bound(cfg: MachineConfig, trace: Trace) -> int:
    """Worst-case per-step instruction-counter increment for one trace
    (the Engine/FleetEngine accumulator-overflow bound)."""
    per_ev = max(
        1,
        int(trace.events[:, :, 1].max(initial=0)),
        int(trace.events[:, :, 3].max(initial=0)) + 1,
    )
    return (cfg.local_run_len + 1) * per_ev

#: Override keys `apply_overrides` accepts — the TimingKnobs fields, named
#: as a user would write them in a sweep spec, plus `fault_seed` (not a
#: TimingKnob — it seeds the traced FaultState — but traced all the same,
#: so `sweep --vary fault_seed` shares one compilation per geometry).
KNOB_KEYS = (
    "quantum",
    "cpi",
    "l1_lat",
    "llc_lat",
    "link_lat",
    "router_lat",
    "dram_lat",
    "dram_service",
    "contention_lat",
    "prefetch_degree",
    "prefetch_lat",
    "fault_seed",
)


def apply_overrides(cfg: MachineConfig, ov: dict | None) -> MachineConfig:
    """A copy of `cfg` with the timing overrides `ov` applied — the
    element's EFFECTIVE config (a solo Engine on it reproduces the fleet
    element exactly). Keys are KNOB_KEYS; `cpi` takes an int (homogeneous)
    or a length-n_cores sequence. Validation runs via the dataclass
    constructors, plus the conflict-key packing bound on quantum."""
    ov = dict(ov or {})
    unknown = sorted(set(ov) - set(KNOB_KEYS))
    if unknown:
        raise ValueError(
            f"unknown timing override(s) {unknown}; valid keys: {KNOB_KEYS}"
        )
    out = cfg
    if "quantum" in ov:
        out = dataclasses.replace(out, quantum=int(ov["quantum"]))
    if "cpi" in ov:
        v = ov["cpi"]
        if isinstance(v, (int, np.integer)):
            core = dataclasses.replace(
                out.core, cpi=int(v), cpi_per_core=None, cpi_pattern=None
            )
        else:
            core = dataclasses.replace(
                out.core,
                cpi_per_core=tuple(int(x) for x in v),
                cpi_pattern=None,
            )
        out = dataclasses.replace(out, core=core)
    if "l1_lat" in ov:
        out = dataclasses.replace(
            out, l1=dataclasses.replace(out.l1, latency=int(ov["l1_lat"]))
        )
    if "llc_lat" in ov:
        out = dataclasses.replace(
            out, llc=dataclasses.replace(out.llc, latency=int(ov["llc_lat"]))
        )
    noc_kw = {
        k: int(ov[k])
        for k in ("link_lat", "router_lat", "contention_lat")
        if k in ov
    }
    if noc_kw:
        out = dataclasses.replace(
            out, noc=dataclasses.replace(out.noc, **noc_kw)
        )
    if "dram_lat" in ov:
        out = dataclasses.replace(out, dram_lat=int(ov["dram_lat"]))
    if "dram_service" in ov:
        out = dataclasses.replace(out, dram_service=int(ov["dram_service"]))
    if "prefetch_degree" in ov:
        out = dataclasses.replace(
            out, prefetch_degree=int(ov["prefetch_degree"])
        )
    if "prefetch_lat" in ov:
        out = dataclasses.replace(out, prefetch_lat=int(ov["prefetch_lat"]))
    if "fault_seed" in ov:
        out = dataclasses.replace(out, fault_seed=int(ov["fault_seed"]))
    if out.quantum * out.n_cores >= 2**31:
        raise ValueError(
            "quantum * n_cores must be < 2^31 (conflict-key packing); "
            f"got {out.quantum} * {out.n_cores}"
        )
    return out


def _each_chip(mesh, st: MachineState, fn, n_whole: int = 0):
    """`fn(events, st, *whole, mesh)` for a fleet that lies on `mesh` (None:
    on no mesh, and `fn` is called as it stands). A fleet on a mesh lies
    with its machines whole, B / D a chip (`sharding.fleet_state_pspecs`),
    and `fn` then runs under `jax.shard_map` over the chips: every chip
    the one-chip program (`mesh` None inside, so the step's two seams for
    a machine that is cut, `read_rows` and `least_of_entry`, are not in
    its text) on its own machines, the `n_whole` last arguments whole on
    every chip, every output with the batch as its leading axis again.
    Nothing crosses chips: no collective, no barrier. The fleet of ONE
    machine on several chips (`fleet_is_cut`: the pool's unit) is cut as
    `Engine`'s machine is and takes `fn` with the mesh, as until PR 51."""
    if mesh is None or fleet_is_cut(st.step.shape[0], mesh.shape[AXIS]):
        return functools.partial(fn, mesh=mesh)
    # `check_vma` off: every input and output is a chip's own and no
    # collective is inside, so there is nothing for it to hold, and the
    # step's `lax.cond`s (fault injection) need not type their branches
    return jax.shard_map(
        functools.partial(fn, mesh=None), mesh=mesh,
        in_specs=(P(AXIS), P(AXIS)) + (P(),) * n_whole, out_specs=P(AXIS),
        check_vma=False,
    )


@functools.partial(
    mesh_jit, static_argnums=(0, 1), static_argnames=("has_sync",)
)
def fleet_run_chunk(
    cfg: MachineConfig, n_steps: int, events, st: MachineState,
    has_sync: bool = True, mesh=None,
):
    """`run_chunk` vmapped over the leading batch axis. `cfg` must be the
    TIMING-NORMALIZED geometry config — timing comes from st.knobs. The
    fleet's mesh is read off the batched arguments by `mesh_jit`; on it
    every chip maps the chunk over its own machines (`_each_chip`). `st`
    is not donated, as `run_chunk`'s is not: the prefetch, the
    supervisor's snapshots and element surgery hold the source."""

    def chunk(events, st, mesh):
        return jax.vmap(
            lambda ev, s: run_chunk(
                cfg, n_steps, ev, s, has_sync=has_sync, mesh=mesh
            )
        )(events, st)

    return _each_chip(mesh, st, chunk)(events, st)


#: The state leaves `fleet_run_loop`'s freeze leaves out: a step writes
#: them only for cores that present an event (`dirm` through a winner's
#: or a joiner's row, `l1` through a hit, a grant, a fill or a local
#: run), so a machine whose cores all stand at END hands them on as they
#: were (tests/test_fleet.py::test_finished_machine_keeps_exempt_leaves).
#: Every other leaf a finished machine's step can change (`step`, the
#: counter block's stat rows) or is too small to be worth the proof.
FREEZE_EXEMPT = ("dirm", "l1")


@functools.partial(
    mesh_jit, static_argnums=(0, 1), static_argnames=("has_sync",)
)
def fleet_run_loop(
    cfg: MachineConfig, chunk_steps: int, events, st: MachineState,
    max_chunks, has_sync: bool = True, mesh=None,
):
    """`run_loop` for a whole FLEET, one dispatched device program: ONE
    `lax.while_loop` over chunks, outside the `vmap`, whose predicate is
    any(live) and whose body maps `run_loop`'s own body (`loop_chunk`)
    over every machine, live or not, and then FREEZES the finished ones:
    `where(live, new, old)` on every leaf of the carry but the state's
    `FREEZE_EXEMPT`, which a finished machine's step cannot change. So
    each element's (state, counter accumulators, cycle base, chunk count)
    stops where a solo `run_loop` stops, `state.step` included, and the
    directory is never copied. With `cfg.faults_enabled` phase -1
    (`step.py::_fault`) rewrites `dirm` by the schedule's absolute step
    index on finished machines too; what gates it is no part of that
    proof, and every leaf is frozen.

    `k` starts at 0 for every machine and counts with it while it lives,
    so the live machines all hold the same `k`: when `max_chunks` stops
    one that is not done it stops them all, and the body never runs over
    a machine that still had work (whose exempt leaves would move).

    On a mesh (`_each_chip`) every chip runs this loop over its own B / D
    machines to THEIR end: `any(live)` is a chip's own, so a chip whose
    machines finish early stops early and waits for nobody, and the six
    outputs come back with all B machines on their leading axis.

    Unlike `run_loop`, `st` is NOT donated (PR 54 tried it: DESIGN.md §6).
    With the fleet's state aliased the TPU's compiler keeps fewer values in
    fast memory: `rung2.sweep-b16` lost 2.5 % to a slice of `s.local` on the
    chip, and four rung-3 machines' program gained a copy of the 64 MB join
    table out of fast memory every step; and a one-chip fleet's peak is its
    build's, which the donation does not touch (ROADMAP S11 (2))."""

    def loop(events, st, max_chunks, mesh):
        events = DeviceTrace.of(events, cfg.local_run_len)
        exempt = () if cfg.faults_enabled else FREEZE_EXEMPT
        live_of = jax.vmap(lambda ev, c: loop_live(cfg, ev, c, max_chunks))
        chunk_of = jax.vmap(
            lambda ev, c: loop_chunk(cfg, chunk_steps, ev, c, has_sync, mesh))

        def body(carry):
            live = live_of(events, carry)
            new = chunk_of(events, carry)
            frozen = jax.tree.map(
                lambda n, o: jnp.where(jnp.expand_dims(
                    live, tuple(range(1, n.ndim))), n, o),
                new, carry)
            st = frozen[0]._replace(**{f: getattr(new[0], f) for f in exempt})
            return (st, *frozen[1:])

        acc = jnp.zeros_like(st.counters)
        zero = jnp.zeros_like(st.step)  # [B]: the bases and the chunk counts
        return jax.lax.while_loop(
            lambda carry: jnp.any(live_of(events, carry)), body,
            (st, acc, acc, zero, zero, zero),
        )

    return _each_chip(mesh, st, loop, n_whole=1)(events, st, max_chunks)


class FleetEngine:
    """Host runner for a batch of independent simulations on one geometry.

    Elements may differ in TRACE and in the traced TIMING knobs
    (per-element `overrides` dicts, see KNOB_KEYS); everything else —
    geometry and model selectors — comes from the shared `cfg`. The
    public surface mirrors `Engine`, batched: `cycles` is [B, C],
    `counters` maps name -> [B, C], and `element_*` accessors slice out
    solo-shaped views.
    """

    def __init__(
        self,
        cfg: MachineConfig,
        traces: list[Trace],
        overrides: list[dict] | None = None,
        chunk_steps: int = 256,
        min_events_capacity: int = 0,
        force_sync: bool = False,
        mesh=None,
    ):
        traces = list(traces)
        if not traces:
            raise ValueError("FleetEngine needs at least one trace")
        if overrides is None:
            overrides = [{}] * len(traces)
        overrides = list(overrides)
        if len(overrides) != len(traces):
            raise ValueError(
                f"got {len(traces)} traces but {len(overrides)} override "
                "dicts (must match 1:1)"
            )
        B = len(traces)
        C = cfg.n_cores
        self.cfg = cfg
        # effective per-element configs (a solo Engine on elem_cfgs[i] +
        # traces[i] reproduces element i bit-exactly); building them also
        # validates every override combination
        self.elem_cfgs = [apply_overrides(cfg, ov) for ov in overrides]
        # the static jit key: one compilation per GEOMETRY
        self.geom_cfg = cfg.timing_normalized()
        self.traces = traces
        from ..trace.format import validate_sync

        has_sync = False
        for t in traces:
            if t.n_cores != C:
                raise ValueError(
                    f"trace has {t.n_cores} cores, config {C}"
                )
            validate_sync(t, cfg.barrier_slots)
            ty = t.events[:, :, 0]
            has_sync = has_sync or bool(
                ((ty == EV_LOCK) | (ty == EV_UNLOCK) | (ty == EV_BARRIER)).any()
            )
        # static specialization is shared: ANY element with sync events
        # turns phase 2.7 on for the whole fleet (a no-op for the others).
        # `force_sync` pins it True so a serving fleet's compiled program
        # never depends on which jobs happen to occupy its slots.
        self.has_sync = has_sync or force_sync
        # events: per-element line-event arrays END-padded to a common T
        # and stacked [B, C, T, 4] (END padding is the format's own
        # convention — `DeviceTrace` clamps ptr to T-1), on the device as
        # `DeviceTrace` lays them out, the batch its leading axis.
        # `min_events_capacity` reserves slack so traces up to that length
        # can be SPLICED in later (replace_element) without changing the
        # compiled shape.
        T = max(max(t.max_len for t in traces), int(min_events_capacity))
        # a fleet on a mesh lies with its machines whole, B / D a chip
        # (DESIGN.md §22): a B the devices do not divide is refused here,
        # before anything is built. `upload_events` and `build_fleet_state`
        # make each chip's machines on that chip.
        if mesh is not None:
            check_fleet_mesh(B, mesh.shape[AXIS])
        self.mesh = mesh
        with span("fleet.init") as init:
            held = alloc_now()  # before this fleet's arrays are laid
            evs = []
            for t in traces:
                e = np.asarray(t.line_events(cfg.line_bits))
                if e.shape[1] < T:
                    pad = np.zeros((C, T - e.shape[1], 4), e.dtype)
                    pad[:, :, 0] = EV_END
                    e = np.concatenate([e, pad], axis=1)
                evs.append(e)
            self._events_np = np.stack(evs)
            self.upload_events()
            # state: the elements' solo init states stacked — init_state(elem
            # cfg) already seeds knobs and quantum_end from the element's
            # effective timing
            self.state = build_fleet_state(self.elem_cfgs, mesh)
            built = alloc_now()  # what the build passed through: its peak
        self._init_s = init.seconds  # reported with the first job's sample
        self.place = job_place(held, built, self.state)  # in every job's sample
        self.chunk_steps = chunk_steps
        # same per-chunk counter-accumulator bound as Engine, over the
        # worst event of ANY element
        per_step = max(_trace_per_step_bound(cfg, t) for t in traces)
        if chunk_steps * per_step >= 1 << _ACC_BITS:
            raise ValueError(
                f"chunk_steps={chunk_steps} x max per-step instruction "
                f"increment {per_step} overflows the 2^{_ACC_BITS} "
                "per-chunk counter accumulator; lower chunk_steps or split "
                "large INS batches"
            )
        self.cycle_base = np.zeros(B, np.int64)
        self.host_counters = {
            k: np.zeros((B, C), np.int64) for k in COUNTER_NAMES
        }
        # the stat rows' totals, [B, C] each, as Engine.host_stats
        self.host_stats = {k: np.zeros((B, C), np.int64) for k in STAT_NAMES}
        self.steps_run = np.zeros(B, np.int64)
        # original (caller-side) index of each batch position; the fault
        # isolation builder (sim.supervisor.build_fleet_isolated) rewrites
        # this after quarantining elements so reports keep caller indices
        self.element_ids = list(range(B))
        self.element_overrides = [dict(ov) for ov in overrides]
        # telemetry sink (obs.Recorder) — None skips every telemetry
        # branch in the chunked loops; fleet_run_loop never consults it
        self.obs = None
        self.obs_label = "fleet"
        self.last_job = None  # the sample of the last fused run (`commit_job`)
        # attestation chains (attest.FleetAttest) — None means chunks are
        # never fingerprinted (DESIGN.md §24); per-element chains advance
        # only for elements live at chunk start, matching the solo loop
        self.attest = None
        # prefix-fork provenance (checkpoint format v6): steps of shared
        # prefix each element was forked from, and the warm-cache key the
        # prefix was saved/loaded under (None = element ran from step 0)
        self.prefix_steps = np.zeros(B, np.int64)
        self.prefix_cache_keys: list = [None] * B
        # overlapped chunk dispatch (§23), mirroring Engine: speculate
        # chunk k+1 from the committed state before the caller's host-side
        # durability work; identity of the source state object validates
        # the speculation (element surgery / restore / reshard all
        # reassign self.state, invalidating it automatically)
        self.overlap = False
        self._pending = None

    def _reshard(self) -> None:
        """Re-place events and state on the fleet mesh layout. Called
        after any host-side state surgery (splice/restore/fork) whose
        `.at[i].set` output sharding is not guaranteed to match, and by
        the supervisor once it has given the fleet another mesh."""
        self.events = shard_fleet_events(self.mesh, self.events)
        self.state = shard_fleet_state(self.mesh, self.state)

    # ---- batched bookkeeping (Engine's host helpers, vectorized) ---------

    @property
    def n_elements(self) -> int:
        return len(self.traces)

    def _drain(self) -> None:
        cnt = _np(self.state.counters)  # [B, N_BLOCK_ROWS, C]
        fold_block(self.host_counters, self.host_stats,
                   np.swapaxes(cnt, 0, 1).astype(np.int64))
        self.state = self.state._replace(
            counters=jnp.zeros_like(self.state.counters)
        )

    def _event_types_at_ptr(self) -> np.ndarray:
        """[B, C] event type codes under each element's trace pointer
        (reads the padded host copy — END padding included)."""
        p = np.minimum(_np(self.state.ptr), self._events_np.shape[2] - 1)
        B, C = p.shape
        return self._events_np[
            np.arange(B)[:, None], np.arange(C)[None, :], p, 0
        ]

    def _dead_mask(self) -> np.ndarray:
        """[B, C] bool — fail-stopped cores (all-False with faults off);
        same contract as Engine._dead_mask, batched."""
        if self.cfg.faults_enabled:
            return _np(self.state.faults.core_dead) != 0
        return np.zeros((self.n_elements, self.cfg.n_cores), bool)

    def done_mask(self) -> np.ndarray:
        return self.core_done_mask().all(axis=1)

    def done(self) -> bool:
        return bool(self.done_mask().all())

    def core_done_mask(self) -> np.ndarray:
        """[B, C] bool — per-element per-core END-or-dead mask (guard
        input; a fail-stopped core never reaches END)."""
        return (self._event_types_at_ptr() == EV_END) | self._dead_mask()

    def live_mask(self) -> np.ndarray:
        """[B, C] bool — cores bounding each element's quantum window:
        not at END, not frozen at a barrier, not fail-stopped (same
        contract as Engine.live_mask, batched)."""
        et = self._event_types_at_ptr()
        frozen = (et == EV_BARRIER) & (_np(self.state.sync_flag) != 0)
        return (et != EV_END) & ~frozen & ~self._dead_mask()

    def _rebase(self) -> None:
        """Per-element host rebase (run_steps path; `run` rebases on
        device): shift each live element's epoch-relative clocks down by
        a multiple of ITS quantum."""
        cyc = _np(self.state.cycles)  # [B, C]
        nd = (self._event_types_at_ptr() != EV_END) & ~self._dead_mask()
        quanta = np.asarray([c.quantum for c in self.elem_cfgs], np.int64)
        m = np.where(nd, cyc, np.iinfo(np.int32).max).min(axis=1)
        delta = np.where(nd.any(axis=1), (m // quanta) * quanta, 0)
        delta = np.maximum(delta, 0)
        if not (delta > 0).any():
            return
        self.cycle_base += delta
        d = jnp.asarray(delta.astype(np.int32))  # [B]
        st = self.state
        self.state = st._replace(
            cycles=st.cycles - d[:, None],
            quantum_end=st.quantum_end - d,
            barrier_time=jnp.where(
                st.barrier_count > 0,
                st.barrier_time - d[:, None],
                st.barrier_time,
            ),
            link_free=(
                jnp.maximum(st.link_free - d[:, None], -(1 << 30))
                if self.cfg.noc.contention
                and self.cfg.noc.contention_model == "router"
                else st.link_free
            ),
            dram_free=(
                jnp.maximum(st.dram_free - d[:, None], -(1 << 30))
                if self.cfg.dram_queue
                else st.dram_free
            ),
        )

    # ---- run -------------------------------------------------------------

    def run(self, max_steps: int = 10_000_000) -> None:
        """Run every element to completion in ONE device dispatch."""
        max_chunks = -(-max_steps // self.chunk_steps)
        # the host spans of a fused run and its one sample, as
        # `Engine.run` has them (DESIGN.md §15)
        with span("fleet.dispatch") as dispatch:
            st, acc_lo, acc_hi, base_lo, base_hi, k = exec_cache.call(
                fleet_run_loop, "fleet.run_loop",
                (self.geom_cfg, self.chunk_steps),
                (self.events, self.state,
                 jnp.asarray(max_chunks, jnp.int32)),
                {"has_sync": self.has_sync},
            )
        with span("fleet.wait") as wait:
            jax.block_until_ready(k)
        self.place = place_run(self.place)  # while input and result are both held
        with span("fleet.readback") as readback:
            acc_lo = _np(acc_lo).astype(np.int64)  # [B, N_BLOCK_ROWS, C]
            acc_hi = _np(acc_hi).astype(np.int64)
            total = (acc_hi << _ACC_BITS) + acc_lo
            fold_block(self.host_counters, self.host_stats,
                       np.swapaxes(total, 0, 1))
            self.cycle_base += (
                _np(base_hi).astype(np.int64) << _ACC_BITS
            ) + _np(base_lo).astype(np.int64)
            self.state = st
            steps = _np(k).astype(np.int64) * self.chunk_steps  # [B]
            self.steps_run += steps
        # the longest element's steps are what the device ran
        commit_job(self, total.sum(axis=0), int(steps.max()), {
            "init": self._init_s, "dispatch": dispatch.seconds,
            "wait": wait.seconds, "readback": readback.seconds},
            element_steps=steps.tolist(), chip_steps=self._chip_steps(steps))
        self._init_s = 0.0  # the fleet's build belongs to its first job
        if not self.done():
            bad = np.flatnonzero(~self.done_mask()).tolist()
            raise RuntimeError(
                f"fleet: max_steps exceeded on element(s) {bad} (deadlock?)"
            )

    def _chip_steps(self, steps: np.ndarray) -> list:
        """The steps each chip's loop ran in a fused run whose elements
        ran `steps` [B]: the most of that chip's own machines (all of
        them where the fleet is one chip's, or one machine cut over its
        chips)."""
        chips = 1 if self.mesh is None else self.mesh.shape[AXIS]
        if fleet_is_cut(len(steps), chips):
            return [int(steps.max())] * chips
        return [int(block.max()) for block in np.split(steps, chips)]

    def run_steps(self, n_steps: int) -> None:
        """Advance every LIVE element by `n_steps` (whole chunks) without
        the completion check — the checkpointed-run building block.

        Unlike `run` (whose `fleet_run_loop` puts a finished element's
        small leaves back after every chunk), the plain vmapped scan
        steps EVERY element and freezes nothing; a finished element's
        steps are no-ops except the `step` counter and, on a router
        machine, the stat rows (phase 0 proves quantum_end cannot bump
        once every core sits at END), so its machine state stays
        bit-exact while `state.step` may run ahead of a solo engine's.
        `fleet_run_loop` leans on the same invariant for `dirm` and `l1`
        (`FREEZE_EXEMPT`)."""
        target = int(self.steps_run.max()) + n_steps
        while int(self.steps_run.max()) < target and not self.done():
            self._chunk_once()

    def _chunk_once(self) -> None:
        """One committed chunk: dispatch, drain counters, rebase clocks
        (shared by run_steps and the serving tick's step_chunk)."""
        live = ~self.done_mask()
        if self.obs is None:
            self._dispatch_chunk()
            self.steps_run += np.where(live, self.chunk_steps, 0)
            self._drain()
            self._corrupt_hook()
            self._rebase()
            if self.attest is not None:
                self.attest.observe(self, live)
            if self.overlap and not self.done():
                self._prefetch_chunk()
            return
        # phase cuts mirror Engine.run_steps: dispatch = async enqueue,
        # drain = synchronizing transfer (includes device execution),
        # rebase = host clock bookkeeping
        t0 = time.perf_counter()
        self._dispatch_chunk()
        t1 = time.perf_counter()
        self.steps_run += np.where(live, self.chunk_steps, 0)
        self._drain()
        self._corrupt_hook()
        t2 = time.perf_counter()
        self._rebase()
        t3 = time.perf_counter()
        phases = {"dispatch": t1 - t0, "drain": t2 - t1, "rebase": t3 - t2}
        if self.attest is not None:
            self.attest.observe(self, live)
        if self.overlap and not self.done():
            self._prefetch_chunk()
            phases["prefetch"] = time.perf_counter() - t3
        self.obs.chunk_committed(
            self.obs_label, self.chunk_steps, t3 - t0, self.host_counters,
            phases=phases,
        )

    def _corrupt_hook(self) -> None:
        """silent_corruption site `fleet.counters` (DESIGN.md §24): a
        flip lands AFTER drain and BEFORE the chunk is fingerprinted,
        so the chain honestly covers the corrupted data — exactly what
        a flaky DIMM does. Detection is attestation's cross-execution
        compare, never this process."""
        chaos.corrupt("fleet.counters", self.host_counters)

    def _dispatch_chunk(self) -> None:
        """Advance self.state by one chunk, consuming the prefetched
        result when it was speculated from exactly this state object at
        this chunk size (Engine._dispatch_chunk, batched)."""
        pend, self._pending = self._pending, None
        if (
            pend is not None
            and pend[0] is self.state
            and pend[2] == self.chunk_steps
        ):
            self.state = pend[1]
            return
        self.state = exec_cache.call(
            fleet_run_chunk, "fleet.run_chunk",
            (self.geom_cfg, self.chunk_steps), (self.events, self.state),
            {"has_sync": self.has_sync},
        )

    def _prefetch_chunk(self) -> None:
        src = self.state
        nxt = exec_cache.call(
            fleet_run_chunk, "fleet.run_chunk",
            (self.geom_cfg, self.chunk_steps), (self.events, src),
            {"has_sync": self.has_sync},
        )
        self._pending = (src, nxt, self.chunk_steps)

    def discard_prefetch(self) -> None:
        self._pending = None

    def warm_exec(self) -> bool:
        """Load-or-compile this fleet's chunk executable through the
        active exec cache WITHOUT running it — the pool worker calls this
        at lease grant so a cache hit pays deserialization (not XLA
        compile) before the first chunk, and compile never eats lease
        TTL. No-op (False) when no cache is active."""
        cache = exec_cache.active()
        if cache is None:
            return False
        return cache.ensure(
            fleet_run_chunk, "fleet.run_chunk",
            (self.geom_cfg, self.chunk_steps), (self.events, self.state),
            {"has_sync": self.has_sync},
        )

    def block_until_ready(self) -> None:
        jax.block_until_ready(self.events)
        jax.block_until_ready(self.state)

    # ---- results ---------------------------------------------------------

    @property
    def cycles(self) -> np.ndarray:
        """[B, C] absolute core clocks."""
        return (
            _np(self.state.cycles).astype(np.int64)
            + self.cycle_base[:, None]
        )

    @property
    def counters(self) -> dict[str, np.ndarray]:
        """name -> [B, C] int64."""
        self._drain()
        return self.host_counters

    def element_state(self, i: int) -> MachineState:
        """Element i's machine state, solo-shaped (batch axis sliced)."""
        return jax.tree.map(lambda x: x[i], self.state)

    def element_counters(self, i: int) -> dict[str, np.ndarray]:
        self._drain()
        return {k: v[i] for k, v in self.host_counters.items()}

    @property
    def step_stats(self) -> dict[str, np.ndarray]:
        """name -> [B, C] int64: the stat rows (STAT_NAMES), as
        `Engine.step_stats`."""
        self._drain()
        return self.host_stats

    # ---- checkpoint / resume --------------------------------------------

    def save_checkpoint(self, path: str) -> None:
        from .checkpoint import save_fleet_checkpoint

        save_fleet_checkpoint(path, self)

    def load_checkpoint(self, path: str) -> None:
        from .checkpoint import load_fleet_checkpoint

        load_fleet_checkpoint(path, self)

    # ---- slot splice / retire (continuous batching; serve/) --------------

    @classmethod
    def make_slots(
        cls,
        cfg: MachineConfig,
        n_slots: int,
        capacity_events: int,
        chunk_steps: int = 256,
        mesh=None,
    ) -> "FleetEngine":
        """An all-idle serving fleet: `n_slots` elements holding the empty
        workload (`idle_trace`), with event storage reserved for traces up
        to `capacity_events` per core. Jobs are spliced into free slots
        with `replace_element` and retired with `clear_element`; the
        compiled program (geometry, [B, C, T] shapes, has_sync=True) never
        changes across the fleet's whole service lifetime."""
        return cls(
            cfg,
            [idle_trace(cfg.n_cores)] * n_slots,
            chunk_steps=chunk_steps,
            min_events_capacity=capacity_events,
            force_sync=True,
            mesh=mesh,
        )

    @property
    def events_capacity(self) -> int:
        """Per-core event-slot capacity (the padded T of the compiled
        shape) — the longest trace `replace_element` accepts."""
        return int(self._events_np.shape[2])

    def replace_element(
        self,
        i: int,
        trace: Trace,
        override: dict | None = None,
        base_cfg: MachineConfig | None = None,
        upload: bool = True,
    ) -> None:
        """Splice a new (trace, override) workload into batch position `i`
        without touching any other element: rewrite the element's event
        row (END-padded to the fleet capacity), reset its machine state to
        `init_state` of its effective config, and zero its host
        accumulators. The compiled program is untouched — geometry, shapes
        and `has_sync` are all static — so admission never recompiles.

        `base_cfg` (default: the fleet's own config) lets a server admit
        under a RELOADED traced-knob config (e.g. a SIGHUP-refreshed fault
        schedule); it must normalize to the fleet's geometry key.

        `upload=False` defers the host->device events copy so a batch of
        splices in one scheduling tick pays for ONE `upload_events()`."""
        from ..trace.format import validate_sync

        ov = dict(override or {})
        ecfg = apply_overrides(base_cfg or self.cfg, ov)
        if ecfg.timing_normalized() != self.geom_cfg:
            raise ValueError(
                "replace_element: effective config does not share this "
                "fleet's compiled geometry"
            )
        if trace.n_cores != self.cfg.n_cores:
            raise ValueError(
                f"trace has {trace.n_cores} cores, config {self.cfg.n_cores}"
            )
        validate_sync(trace, self.cfg.barrier_slots)
        e = np.asarray(trace.line_events(self.cfg.line_bits))
        T = self.events_capacity
        if e.shape[1] > T:
            raise ValueError(
                f"trace needs {e.shape[1]} event slots/core but this "
                f"fleet's capacity is {T}"
            )
        per_step = _trace_per_step_bound(self.cfg, trace)
        if self.chunk_steps * per_step >= 1 << _ACC_BITS:
            raise ValueError(
                f"chunk_steps={self.chunk_steps} x max per-step "
                f"instruction increment {per_step} overflows the "
                f"2^{_ACC_BITS} per-chunk counter accumulator"
            )
        row = np.zeros((self.cfg.n_cores, T, 4), np.int32)
        row[:, :, 0] = EV_END
        row[:, : e.shape[1]] = e
        self._events_np[i] = row
        self.traces[i] = trace
        self.elem_cfgs[i] = ecfg
        self.element_overrides[i] = ov
        # flush the previous occupant's device counters before its state
        # row is overwritten (harvest reads host_counters afterwards)
        self._drain()
        solo = init_state(ecfg)
        self.state = jax.tree.map(
            lambda b, s: b.at[i].set(s), self.state, solo
        )
        self.cycle_base[i] = 0
        self.steps_run[i] = 0
        self.prefix_steps[i] = 0
        self.prefix_cache_keys[i] = None
        for totals in (self.host_counters, self.host_stats):
            for k in totals:
                totals[k][i] = 0
        # a new occupant never inherits the previous job's chain; the
        # owner re-tracks the slot if the new workload is attested
        if self.attest is not None:
            self.attest.drop(i)
        if self.mesh is not None:
            self._reshard()
        if upload:
            self.upload_events()

    def clear_element(self, i: int, upload: bool = True) -> None:
        """Retire batch position `i` back to the idle workload (done at
        step 0): the slot stops contributing work to the vmapped step and
        is ready for the next `replace_element`."""
        self.replace_element(i, idle_trace(self.cfg.n_cores), upload=upload)

    def restore_element(self, i: int, snap: dict) -> None:
        """Load an element checkpoint (checkpoint.load_element_checkpoint)
        into batch position `i`. Call `replace_element(i, trace, override)`
        with the SAME workload first — this only overlays the mid-run
        machine state and 64-bit host accumulators, making the resumed
        element bit-exact with one that was never interrupted."""
        self.state = jax.tree.map(
            lambda b, s: b.at[i].set(jnp.asarray(s)),
            self.state,
            snap["state"],
        )
        self.cycle_base[i] = snap["cycle_base"]
        self.steps_run[i] = snap["steps_run"]
        for k in COUNTER_NAMES:
            self.host_counters[k][i] = snap["host_counters"][k]
        for k in STAT_NAMES:
            self.host_stats[k][i] = snap["host_stats"][k]
        if self.mesh is not None:
            self._reshard()

    def fork_element(self, i: int, snap: dict, cache_key: str | None = None) -> None:
        """Fork batch position `i` from a shared-prefix snapshot: overlay
        the snapshot's mid-run machine state (restore_element), then RESEED
        the per-element traced inputs from the element's OWN effective
        config — timing knobs and the FaultState schedule/seed/ECC
        thresholds — while keeping the snapshot's TRAJECTORY state
        (dead-core / dead-link / degrade masks, which record events that
        already fired during the prefix).

        The caller (sim.prefix) guarantees the snapshot's step count is at
        or below the element's divergence point, so the inputs being
        swapped in could not have influenced any state the snapshot
        carries: the forked element is bit-exact with an unforked run.
        Events with step < steps_run never re-fire (firing matches the
        absolute step index), so resetting the schedule arrays wholesale
        is safe. Call `replace_element(i, trace, override)` with the
        element's workload first, exactly as for `restore_element`."""
        from ..faults.schedule import fault_state_from_config
        from .state import knobs_from_config

        self.restore_element(i, snap)
        ecfg = self.elem_cfgs[i]
        fresh = fault_state_from_config(ecfg)
        faults = jax.tree.map(lambda x: x[i], self.state.faults)._replace(
            seed=fresh.seed,
            ev_step=fresh.ev_step,
            ev_kind=fresh.ev_kind,
            ev_a=fresh.ev_a,
            ev_b=fresh.ev_b,
            flip_l1=fresh.flip_l1,
            flip_llc=fresh.flip_llc,
            due_rate=fresh.due_rate,
        )
        self.state = self.state._replace(
            knobs=jax.tree.map(
                lambda b, s: b.at[i].set(jnp.asarray(s)),
                self.state.knobs,
                knobs_from_config(ecfg),
            ),
            faults=jax.tree.map(
                lambda b, s: b.at[i].set(jnp.asarray(s)),
                self.state.faults,
                faults,
            ),
        )
        self.prefix_steps[i] = int(snap["steps_run"])
        self.prefix_cache_keys[i] = cache_key
        if self.mesh is not None:
            self._reshard()

    def upload_events(self) -> None:
        """Push the host event array (mutated by splices) to the device.
        One call covers any number of `upload=False` splices."""
        events = DeviceTrace.of(self._events_np, self.cfg.local_run_len)
        if self.mesh is None:
            self.events = jax.device_put(events)
        else:
            self.events = shard_fleet_events(self.mesh, events)

    def step_chunk(self) -> None:
        """Advance the whole batch by exactly ONE committed chunk (the
        serving tick): dispatch, drain counters, rebase clocks. Finished
        and idle elements freeze (their steps_run stays put)."""
        self._chunk_once()
