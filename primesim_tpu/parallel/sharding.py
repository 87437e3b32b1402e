"""Multi-chip sharding of the simulated machine over a jax device mesh.

TPU-native replacement for the reference's MPI process topology (SURVEY.md
§2 "Parallelism-strategy inventory"): where PriME splits the uncore across
MPI ranks each owning LLC banks/directory slices, we lay the simulated
machine out over a 1-D `jax.sharding.Mesh` axis ``"tiles"``:

- core-axis arrays (clocks, trace pointers, private L1s, per-core counters,
  the event stream) are sharded by core — each device simulates a sub-grid
  of tiles' cores;
- bank-axis arrays (LLC tags/owners/LRU, directory sharer words) are
  sharded by bank over the same axis — each device owns a slice of the
  LLC/directory, exactly like a PriME uncore rank.

Cross-device traffic (a core's request to a remote home bank, probes and
invalidations back to remote cores) is NOT hand-written message passing:
the step function stays pure and global, and XLA's SPMD partitioner inserts
the all-gathers/reduce-scatters that realize it over ICI (multi-host: DCN).
The per-step `lax.scan` boundary doubles as the quantum barrier collective
(SURVEY.md §2 #10 [DRIVER]). Two seams are written by hand. `read_rows`: a
read of whole directory rows by slot, of which the reader wants a few
words. The partitioner sends the rows; `read_rows` reduces each row on the
chip that holds it and sends the words. `least_of_entry`: a scratch table
of one word a directory entry into which C lanes scatter. The partitioner
all-reduces the table; `least_of_entry` cuts it by bank and sends the
lanes' words to the chip that holds their entry. The device loops learn
their mesh from their arguments (`mesh_jit`), as `build_state` learns it
from `Engine`: no configuration field says it.

Works identically on real TPU meshes and on virtual CPU meshes
(``--xla_force_host_platform_device_count``), which is how tests and the
driver's `dryrun_multichip` validate multi-chip behavior without hardware.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..faults.schedule import FaultState, fault_state_from_config
from ..sim.state import MachineState, TimingKnobs, init_state, knobs_from_config

AXIS = "tiles"

# Revoked-device registry (DESIGN.md §26). Real accelerators vanish from
# the runtime on ICI/PCIe failure; virtual CPU meshes cannot, so device
# loss is modeled the same way everywhere: a process-local set of device
# ids that `healthy_devices()` filters out. Chaos `capacity_loss` trials
# and the kill+shrink acceptance test populate it; on real hardware the
# runtime's own device list shrinking has the identical effect because
# `healthy_devices()` starts from `jax.devices()`.
_REVOKED: set = set()


def revoke_devices(ids) -> None:
    """Mark device ids as lost (chaos injection / test hook)."""
    _REVOKED.update(int(i) for i in ids)


def restore_devices(ids=None) -> None:
    """Heal revoked devices (all of them when `ids` is None)."""
    if ids is None:
        _REVOKED.clear()
    else:
        _REVOKED.difference_update(int(i) for i in ids)


def healthy_devices() -> list:
    """Currently-visible devices minus the revoked set."""
    return [d for d in jax.devices() if d.id not in _REVOKED]


class DeviceMeshError(ValueError):
    """Typed `--devices N` validation failure (CLI exit 2, structured
    ``{"error": …}`` on stderr) raised BEFORE any compile, instead of the
    mid-compile shape error XLA would produce for a non-dividing mesh."""

    def __init__(self, detail: str, *, devices: int, visible: int | None = None):
        super().__init__(detail)
        self.devices = devices
        self.visible = visible

    def location(self):
        loc = {"devices": self.devices}
        if self.visible is not None:
            loc["visible"] = self.visible
        return loc


def validate_mesh_shape(cfg, n_devices: int, visible: int | None = None) -> None:
    """The part of `validate_devices` that needs no backend: N >= 1 and
    N divides the core and bank axes. A parent that spawns the workers
    which own the chips calls only this (a chip belongs to one process;
    each worker validates visibility for itself)."""
    if n_devices < 1:
        raise DeviceMeshError(
            f"--devices must be >= 1, got {n_devices}", devices=n_devices
        )
    for name, extent in (("n_cores", cfg.n_cores), ("n_banks", cfg.n_banks)):
        if extent % n_devices != 0:
            raise DeviceMeshError(
                f"--devices {n_devices} does not divide {name}={extent}; "
                f"the {AXIS!r} mesh axis shards cores and banks evenly",
                devices=n_devices,
                visible=visible,
            )


def validate_devices(cfg, n_devices: int) -> None:
    """Validate a `--devices N` request against the machine geometry and
    the visible device set. Raises DeviceMeshError (exit 2 at the CLI)
    on any mismatch; returns None when a tile_mesh(n_devices) run of this
    config is shape-sound."""
    visible = len(jax.devices())
    if n_devices > visible:
        raise DeviceMeshError(
            f"--devices {n_devices} exceeds the {visible} visible "
            f"device(s); set XLA_FLAGS=--xla_force_host_platform_device_"
            f"count={n_devices} for a virtual CPU mesh",
            devices=n_devices,
            visible=visible,
        )
    validate_mesh_shape(cfg, n_devices, visible)


def largest_valid_submesh(cfg, n_available: int) -> int:
    """Largest mesh size <= `n_available` that shards this geometry
    evenly (divides both n_cores and n_banks). n=1 always qualifies, so
    any run with at least one healthy device has a valid landing mesh;
    zero healthy devices is a hard DeviceMeshError."""
    if n_available < 1:
        raise DeviceMeshError(
            "no healthy devices remain to host the mesh",
            devices=0,
            visible=n_available,
        )
    for n in range(int(n_available), 0, -1):
        if cfg.n_cores % n == 0 and cfg.n_banks % n == 0:
            return n
    return 1


def tile_mesh(n_devices: int | None = None, devices=None) -> Mesh:
    """1-D device mesh over the tile axis (the only axis the sim needs:
    cores and banks shard over the same tile sub-grids)."""
    if devices is None:
        devices = jax.devices()
        if n_devices is not None:
            if len(devices) < n_devices:
                raise ValueError(
                    f"tile_mesh: {n_devices} devices requested but only "
                    f"{len(devices)} visible"
                )
            devices = devices[:n_devices]
    return Mesh(np.asarray(devices), (AXIS,))


def state_pspecs() -> MachineState:
    """PartitionSpec per MachineState field (leading core/bank axis)."""
    return MachineState(
        cycles=P(AXIS),
        ptr=P(AXIS),
        l1=P(AXIS),
        dirm=P(AXIS),
        # link/lock/barrier tables are small and written from arbitrary
        # cores' lanes — replicate them (XLA reduces the scatters across
        # devices)
        link_free=P(),
        dram_free=P(AXIS),  # bank-axis, like the LLC it sits beside
        lock_holder=P(),
        barrier_count=P(),
        barrier_time=P(),
        sync_flag=P(AXIS),
        quantum_end=P(),
        step=P(),
        # per-core stride-prefetcher tracking state shards with its cores
        pf_line=P(AXIS),
        pf_stride=P(AXIS),
        pf_streak=P(AXIS),
        counters=P(None, AXIS),
        # traced timing knobs: the per-core cpi vector shards with the
        # cores it feeds; the scalars replicate
        knobs=TimingKnobs(
            quantum=P(),
            cpi=P(AXIS),
            l1_lat=P(),
            llc_lat=P(),
            link_lat=P(),
            router_lat=P(),
            dram_lat=P(),
            dram_service=P(),
            contention_lat=P(),
            prefetch_degree=P(),
            prefetch_lat=P(),
        ),
        # fault state: the per-core dead mask shards with the cores it
        # gates; link masks and the (tiny) schedule arrays replicate like
        # the link/lock tables above
        faults=FaultState(
            seed=P(),
            core_dead=P(AXIS),
            link_dead=P(),
            link_extra=P(),
            ev_step=P(),
            ev_kind=P(),
            ev_a=P(),
            ev_b=P(),
            flip_l1=P(),
            flip_llc=P(),
            due_rate=P(),
        ),
    )


def events_pspec() -> P:
    return P(AXIS)  # a `trace/device.py::DeviceTrace`, sharded by core


def _on(mesh: Mesh, specs: MachineState) -> MachineState:
    return jax.tree.map(
        lambda spec: NamedSharding(mesh, spec), specs,
        is_leaf=lambda x: isinstance(x, P),
    )


def state_shardings(mesh: Mesh) -> MachineState:
    """`state_pspecs()` on `mesh`: a NamedSharding per MachineState field."""
    return _on(mesh, state_pspecs())


def shard_state(mesh: Mesh, st: MachineState) -> MachineState:
    return jax.tree.map(jax.device_put, st, state_shardings(mesh))


@functools.lru_cache(maxsize=None)
def _state_builder(mesh: Mesh):
    # no stat rows on a mesh: the block keeps the counters' height there
    return jax.jit(
        functools.partial(init_state, stat_rows=False), static_argnums=0,
        out_shardings=state_shardings(mesh),
    )


def build_state(cfg, mesh: Mesh | None = None) -> MachineState:
    """A machine's initial state, born in the layout it runs in. On a
    mesh: `init_state` as one compiled program a geometry whose outputs
    are laid out by `state_pspecs()`, so each device fills only its own
    shard and none ever holds a whole `dirm` or `l1` (rung 4's directory
    is 9.66 GB: `init_state` then `shard_state` fails on a 16 GB chip).
    Without a mesh: `init_state` itself, array by array on the default
    device, each large leaf by one op that holds nothing but its result
    (`sim/state.py::_rows`, PR 54: the build passes through the machine
    once). The compiled builder gives the same bytes there, but lays
    them elsewhere in HBM, and the step's speed follows that placement:
    one one-chip cell lost 5 % to it (PERF.md section 6, PR 33). What the
    allocator held and had free as an engine's arrays were laid, and its
    high-water mark once they were, is on record: a fused job's sample
    holds `place` (`sim/engine.py::job_place`, DESIGN.md §15), and 512 bytes more or
    less there are a fifth of the way rows' gather (PERF.md section 7
    (n)). On a mesh
    the counter block carries no stat rows (`init_state(stat_rows=False)`):
    rung 4's row gathers on four chips lost 4 % to the taller block alone
    and 10 % with the rows counted (PERF.md section 6, PR 37), so a sharded
    run executes the program it executed before the rows were there."""
    if mesh is None:
        return init_state(cfg)
    return _state_builder(mesh)(cfg)


def shard_events(mesh: Mesh, events) -> jax.Array:
    return jax.device_put(events, NamedSharding(mesh, events_pspec()))


def mesh_of(*trees) -> Mesh | None:
    """The tile mesh the arrays (or `ShapeDtypeStruct`s) of `trees` are
    laid out over, None where none is: the first leaf whose sharding names
    `AXIS` decides. Tracers carry no layout, so inside a `jit` or a `vmap`
    the answer is None and the caller hands its mesh on by name."""
    for leaf in jax.tree.leaves(trees):
        sh = getattr(leaf, "sharding", None)
        if isinstance(sh, NamedSharding) and AXIS in sh.mesh.axis_names:
            return sh.mesh
    return None


class mesh_jit:
    """`jax.jit` of a device loop that takes its mesh as the static keyword
    `mesh`, filled in from the arguments' layout (`mesh_of`) where the
    caller does not name it: `run_loop(cfg, n, events, st, k)` on a
    sharded state compiles the sharded step, on a plain one today's
    program, and the jit key tells the two apart, and one mesh from
    another. Keeps what callers use of a jitted function: the call,
    `lower`, `_cache_size`."""

    def __init__(self, fn, **jit_kwargs):
        names = tuple(jit_kwargs.pop("static_argnames", ()))
        self._jit = jax.jit(
            fn, static_argnames=(*names, "mesh"), **jit_kwargs
        )
        functools.update_wrapper(self, fn)

    def _bind(self, args, kwargs):
        if "mesh" not in kwargs:
            kwargs = {**kwargs, "mesh": mesh_of(args)}
        return kwargs

    def __call__(self, *args, **kwargs):
        return self._jit(*args, **self._bind(args, kwargs))

    def lower(self, *args, **kwargs):
        return self._jit.lower(*args, **self._bind(args, kwargs))

    def _cache_size(self) -> int:
        return self._jit._cache_size()


def read_rows(mesh: Mesh | None, table, slot, reduce_rows, per_slot=(),
              whole=()):
    """`reduce_rows(table[slot], *per_slot, *whole)`: a tuple of arrays of
    `slot`'s shape, the few words a reader wants of the rows `slot` of
    `table` [R, W]. `slot` is `[K, C]`, K rows a core and the cores along
    the last axis, on one device and on a mesh: the gather yields
    `[K*C, W]`, which splits into `[K, C, W]` as it lies, where
    `[C, K, W]` is a copy that pads K to the tile's 8 rows (rung 4 on one
    chip: 0.73 ms of a 4.8 ms step; PERF.md section 6, PR 56). `per_slot`
    are int32 values of `slot`'s shape the reduction needs beside the
    rows, `whole` values every chip has in full (an iota, a replicated
    table). `reduce_rows` always sees every core's rows, in core order.

    Without a mesh it is exactly that expression. On a mesh `table` is
    sharded by row and `slot` names any row, and left to the partitioner
    the expression has every chip gather all C*K rows, masked to its own
    shard, and all-reduce the WHOLE rows (rung 4's local run: 170 MB a
    step at 31 GB/s, two fifths of the step; PERF.md section 6, PR 34).
    Here each chip takes every core's `slot` and `per_slot` (one
    all-gather of words), gathers from its own shard the rows it holds
    (the index clamped for the others), reduces each row there, zeroes
    the RECORD (not the row: a zero row could match a line 0) where the
    slot is another chip's, and the records are summed. One chip holds
    each slot, so the sum is that chip's record to the bit, and
    C*K*len(record) words cross chips."""
    if mesh is None:
        return reduce_rows(table[slot], *per_slot, *whole)
    rows = table.shape[0] // mesh.shape[AXIS]

    def on_chip(tab, packed, *whole):
        packed = jax.lax.all_gather(packed, AXIS, axis=1, tiled=True)
        slot, *per_slot = (packed[..., i] for i in range(packed.shape[-1]))
        local = slot - jax.lax.axis_index(AXIS) * rows
        mine = (local >= 0) & (local < rows)
        record = reduce_rows(
            tab[jnp.clip(local, 0, rows - 1)], *per_slot, *whole
        )
        dtypes[:] = [r.dtype for r in record]
        record = jnp.stack(
            [jnp.where(mine, r.astype(jnp.int32), 0) for r in record], axis=-1
        )
        return jax.lax.psum(record, AXIS)

    dtypes: list = []  # of the record's fields, as `reduce_rows` gives them
    record = jax.shard_map(
        on_chip, mesh=mesh, out_specs=P(),
        in_specs=(P(AXIS), P(None, AXIS), *(P() for _ in whole)),
    )(table, jnp.stack((slot, *per_slot), axis=-1), *whole)
    return tuple(record[..., i].astype(dt) for i, dt in enumerate(dtypes))


def least_of_entry(mesh: Mesh | None, table_least, join, entry, key, n):
    """`table_least(join, entry, key, n)`: `[C]` bool, of the lanes with
    `join` set the one an `entry` (of `n`, bank-major) whose `key` is
    least, by a scatter-min into a table of one word an entry, read back
    at each lane's own entry (`step.py::_join_representative`).

    Without a mesh it is exactly that expression. On a mesh the lanes are
    sharded by core and the table by nothing, and left to the partitioner
    every chip fills and scatters into a whole table and the TABLE is
    all-reduced, every step (rung 4: 64 MB at 55 GB/s, a fifth of the
    step, to settle 4096 lanes; PERF.md section 6, PR 49). Here the table
    is cut by bank like `dirm`, a contiguous `n // devices` entries a
    chip: each chip takes every lane's `(join, entry, key)` (one
    all-gather of 3 x C words), runs the SAME `table_least` on the lanes
    whose entry it holds, the index made local (clamped for the others,
    whose `join` it clears), and the 0/1 answers are summed (one
    all-reduce of C words; a `psum_scatter` compiles to the same
    all-reduce and a slice, and loses its phase scope on the way). One
    chip holds each entry, so the sum is that chip's answer to the bit.
    The table keeps its one form; only its indices and its length are
    the chip's."""
    if mesh is None:
        return table_least(join, entry, key, n)
    held = n // mesh.shape[AXIS]

    def on_chip(packed):
        packed = jax.lax.all_gather(packed, AXIS, axis=1, tiled=True)
        join, entry, key = packed[0] != 0, packed[1], packed[2]
        local = entry - jax.lax.axis_index(AXIS) * held
        mine = join & (local >= 0) & (local < held)
        least = mine & table_least(
            mine, jnp.clip(local, 0, held - 1), key, held)
        return jax.lax.psum(least.astype(jnp.int32), AXIS)

    packed = jnp.stack((join.astype(jnp.int32), entry, key))
    return jax.shard_map(
        on_chip, mesh=mesh, in_specs=P(None, AXIS), out_specs=P(),
    )(packed) != 0


def fleet_is_cut(n_elements: int, n_devices: int) -> bool:
    """Whether a fleet of `n_elements` machines on `n_devices` chips lies
    as `Engine`'s one machine does, cut by core and bank over the chips,
    and not with its machines whole: a fleet of ONE machine on several
    chips, which is what a pool worker, the auditor and a served bucket
    build for a unit that asks for devices (`pool/worker.py`,
    `attest/audit.py`: a unit is one element, and no code of theirs can
    give more). A rule on the two counts alone; ROADMAP D13 books it."""
    return n_elements == 1 and n_devices > 1


def check_fleet_mesh(n_elements: int, n_devices: int) -> None:
    """A fleet on a mesh lies B / D whole machines a chip (or is one
    machine, `fleet_is_cut`): any other B is refused, typed, before
    anything is built."""
    if n_elements % n_devices and not fleet_is_cut(n_elements, n_devices):
        raise DeviceMeshError(
            f"a fleet of {n_elements} machines does not lie on {n_devices} "
            f"devices: every machine is whole on one device, "
            f"{n_elements} / {n_devices} a device, so the devices must "
            f"divide the machines",
            devices=n_devices,
        )


def fleet_devices(n_elements: int, n_devices: int) -> int:
    """The most of `n_devices` a fleet of `n_elements` machines can lie
    on (`check_fleet_mesh`): for a caller whose fleet is what is left of
    the one that was asked for (a sweep's quarantined or deduplicated
    elements, a mesh that lost a chip)."""
    if fleet_is_cut(n_elements, n_devices):
        return n_devices
    return max(d for d in range(1, n_devices + 1) if n_elements % d == 0)


def fleet_submesh(mesh: Mesh, n_elements: int) -> Mesh:
    """`mesh`, or its first `fleet_devices` devices where a fleet of
    `n_elements` does not lie on all of it."""
    n = fleet_devices(n_elements, mesh.shape[AXIS])
    if n == mesh.shape[AXIS]:
        return mesh
    return tile_mesh(devices=list(mesh.devices.flat)[:n])


def fleet_state_pspecs(cut: bool = False) -> MachineState:
    """PartitionSpec per leaf of a fleet's state: the leading (batch) axis
    over the chips, every machine whole on one chip, element `e` of B on
    chip `e // (B // D)`. Nothing of a machine crosses chips then, and a
    chip's program is the one-chip fleet's (`sim/fleet.py::fleet_run_loop`).
    Until PR 51 the batch axis was replicated and every machine cut, and
    the chip gave four chips for the speed of one (5.894 ms a step on four
    chips, 5.828 on one: ROADMAP S15 (iii)); `cut` is that layout, `state_pspecs()`
    under an unsharded leading axis, for the fleet of one (`fleet_is_cut`)."""
    return jax.tree.map(
        (lambda spec: P(None, *spec)) if cut else (lambda spec: P(AXIS)),
        state_pspecs(),
        is_leaf=lambda x: isinstance(x, P),
    )


def fleet_events_pspec(cut: bool = False) -> P:
    """Of a fleet's `DeviceTrace`: with its machines, as the state."""
    return P(None, AXIS) if cut else P(AXIS)


def _cut(mesh: Mesh, batched) -> bool:
    return fleet_is_cut(batched.shape[0], mesh.shape[AXIS])


def fleet_state_shardings(mesh: Mesh, cut: bool = False) -> MachineState:
    return _on(mesh, fleet_state_pspecs(cut))


def shard_fleet_state(mesh: Mesh, st: MachineState) -> MachineState:
    return jax.tree.map(
        jax.device_put, st, fleet_state_shardings(mesh, _cut(mesh, st.step))
    )


def shard_fleet_events(mesh: Mesh, events) -> jax.Array:
    """A host array goes to each chip as that chip's shard: no chip holds
    another's machines' events on the way."""
    return jax.device_put(
        events, NamedSharding(mesh, fleet_events_pspec(_cut(mesh, events)))
    )


@functools.lru_cache(maxsize=None)
def _fleet_state_builder(mesh: Mesh, geom_cfg, n_elements: int):
    def build(knobs, faults, quantum_end):
        one = init_state(geom_cfg)
        st = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (n_elements, *x.shape)), one
        )
        return st._replace(knobs=knobs, faults=faults, quantum_end=quantum_end)

    return jax.jit(build, out_shardings=fleet_state_shardings(mesh))


def build_fleet_state(elem_cfgs, mesh: Mesh | None = None) -> MachineState:
    """A fleet's initial state, `init_state` of every element's effective
    config stacked. Without a mesh exactly that, on the default device
    (the one-chip fleets' build, left as it was: ROADMAP N11). On a mesh,
    machines whole: one compiled program a geometry and B whose outputs
    are laid out by `fleet_state_pspecs()`, so every chip fills its own
    machines and none ever holds another's (sixteen rung-3 machines are
    13.2 GB: stacked on one chip they fail on 16 GB before they are laid
    out). The leaves that an element's timing reaches (`knobs`, `faults`,
    `quantum_end`: a few words a machine) are made element by element as
    `init_state` makes them and handed in; every other leaf is the
    geometry's, the same for every element
    (tests/test_fleet_on_chips.py holds the two builds equal)."""
    if mesh is None or fleet_is_cut(len(elem_cfgs), mesh.shape[AXIS]):
        st = jax.tree.map(
            lambda *xs: jnp.stack(xs), *[init_state(c) for c in elem_cfgs]
        )
        return st if mesh is None else shard_fleet_state(mesh, st)

    def stacked(make):
        return jax.tree.map(lambda *xs: jnp.stack(xs), *map(make, elem_cfgs))

    return _fleet_state_builder(
        mesh, elem_cfgs[0].timing_normalized(), len(elem_cfgs)
    )(
        stacked(knobs_from_config),
        stacked(fault_state_from_config),
        stacked(lambda c: jnp.asarray(c.quantum, jnp.int32)),
    )
