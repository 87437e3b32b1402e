"""`trace/device.py::DeviceTrace`: the block layout of a trace on the device
holds every word the `[C, T, 4]` array held, and its reads (`window`, `at`)
are the element read `events[c, min(ptr + i, T - 1)]` word for word: under
`jit`, under `vmap` (the fleet's batch) and sharded by core over four virtual
devices. And what the benchmark takes from `Engine.events` (`.shape`,
`.dtype`) is the trace's own, so `step_roofline`'s least bytes stay what
they were before PR 40."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from primesim_tpu.parallel.sharding import shard_events, shard_fleet_events, tile_mesh
from primesim_tpu.trace.device import RECORDS, DeviceTrace, _span

C = 16  # four cores a device on the mesh of four


def _events(T: int, batch: int = 0, seed: int = 0) -> np.ndarray:
    """Every word distinct, so a read from the wrong record, core or batch
    element cannot pass."""
    shape = (batch, C, T, 4) if batch else (C, T, 4)
    return (np.arange(np.prod(shape), dtype=np.int32) * 7 + seed).reshape(shape)


def _pointers(T: int) -> np.ndarray:
    """[P, C]: every core meets 0, the inside of a block, the block's
    edges 31 / 32 / 33, the trace's last record, and pointers past it
    (the clamp's END row); each row gives the cores different ones."""
    special = [0, 5, 31, 32, 33, 63, 64, 65, T - 2, T - 1, T, T + 5, T + 40]
    special = sorted({max(0, p) for p in special})
    rows = [[special[(r + c) % len(special)] for c in range(C)]
            for r in range(len(special))]
    return np.asarray(rows, np.int32)


def _element_read(events: np.ndarray, ptr: np.ndarray, n: int) -> np.ndarray:
    """The read `_local` made until PR 40, on the host."""
    T = events.shape[-2]
    idx = np.minimum(ptr[..., None] + np.arange(n), T - 1)  # [..., C, n]
    return np.take_along_axis(events, idx[..., None], axis=-2)


@pytest.mark.parametrize("mode", ["jit", "vmap", "mesh4"])
@pytest.mark.parametrize("rl", [0, 1, 8, 32])
@pytest.mark.parametrize("T", [20, 64, 150, 546])
def test_window_and_at_are_the_element_read(T, rl, mode):
    """`T` under a block, a whole number of blocks, and neither; `rl` with
    one block read (0), two (1, 8, 32: a window of 33 records that starts
    at record 31 of its block ends in the next one's last)."""
    n = rl + 1
    batch = 3 if mode == "vmap" else 0
    host = _events(T, batch, seed=T + rl)
    tr = DeviceTrace.of(host, rl)
    assert tr.blocks.shape[-2:] == ((T - 1) // RECORDS + _span(n), 128)
    read = lambda tr, ptr: (tr.window(ptr, n), tr.at(ptr))  # noqa: E731
    if mode == "vmap":
        read = jax.vmap(read)
        tr = jax.device_put(tr)
    elif mode == "mesh4":
        tr = shard_events(tile_mesh(4), tr)
        assert tr.sharding.spec[0] is not None
        assert {s.data.shape[0] for s in tr.blocks.addressable_shards} == {C // 4}
    else:
        tr = jax.device_put(tr)
    read = jax.jit(read)
    for ptr in _pointers(T):
        if batch:  # each element of the batch its own pointers
            ptr = np.stack([np.roll(ptr, b) for b in range(batch)])
        window, at = read(tr, jnp.asarray(ptr))
        want = _element_read(host, ptr, n)
        np.testing.assert_array_equal(np.asarray(window), want)
        np.testing.assert_array_equal(np.asarray(at), want[..., 0, :])


@pytest.mark.parametrize("device_side", [False, True])
@pytest.mark.parametrize("T,rl", [(1, 0), (1, 8), (32, 8), (33, 64), (150, 8)])
def test_layout_holds_the_trace_and_repeats_its_last_record(T, rl, device_side):
    """Block `b` is records `32 b .. 32 b + 31` as `line_events` has them;
    past `T - 1` every record is record `T - 1`. The host's layout (numpy:
    `Engine`, `Fleet`, the streamed windows) and the one inside a program
    (a caller's raw array) are the same words."""
    host = _events(T)
    tr = DeviceTrace.of(jnp.asarray(host) if device_side else host, rl)
    assert isinstance(tr.blocks, jax.Array if device_side else np.ndarray)
    records = np.asarray(tr.blocks).reshape(C, -1, 4)
    np.testing.assert_array_equal(records[:, :T], host)
    assert (records[:, T:] == host[:, T - 1:T]).all()
    assert records.shape[1] >= T + rl  # the longest window's last record
    assert DeviceTrace.of(tr, rl) is tr  # already laid out: handed through


def test_a_window_longer_than_the_layout_allows_is_refused():
    tr = DeviceTrace.of(_events(40), 0)
    with pytest.raises(AssertionError, match="overruns"):
        tr.window(jnp.zeros(C, jnp.int32), 9)


def test_fleet_batch_shards_by_machine_and_a_fleet_of_one_by_core():
    tr = shard_fleet_events(tile_mesh(2), DeviceTrace.of(_events(70, batch=2), 8))
    assert tr.shape == (2, C, 70, 4)  # whole machines, B / D a chip
    assert {s.data.shape[:2] for s in tr.blocks.addressable_shards} == {(1, C)}
    # one machine on several chips is cut by core, as `Engine`'s trace is
    tr = shard_fleet_events(tile_mesh(4), DeviceTrace.of(_events(70, batch=1), 8))
    assert {s.data.shape[:2] for s in tr.blocks.addressable_shards} == {(1, C // 4)}


def test_it_is_a_pytree_of_one_leaf_and_presents_the_trace_shape():
    host = _events(150)
    tr = DeviceTrace.of(host, 8)
    leaves, treedef = jax.tree.flatten(tr)
    assert len(leaves) == 1 and leaves[0] is tr.blocks
    again = jax.tree.unflatten(treedef, leaves)
    assert again.length == 150 and again.shape == tr.shape == (C, 150, 4)
    assert tr.dtype == np.int32 and tr.dtype.itemsize == 4
    # the trace's length is static: another length, another program
    assert treedef != jax.tree.structure(DeviceTrace.of(_events(149), 8))
    out = jax.jit(lambda t: t)(tr)  # through a jit boundary whole
    assert isinstance(out, DeviceTrace) and out.shape == (C, 150, 4)
    np.testing.assert_array_equal(np.asarray(out.blocks), tr.blocks)


def test_step_roofline_least_bytes_are_the_parents():
    """`benchmark/measure.py` records `eng.events.shape` and `.dtype.itemsize`
    and `metrics/step_roofline.py::step_bytes` takes `shape[2] x itemsize`
    as the bytes of one event record: 16, whatever the device layout. With
    the plain benchmark machine's state that is the 3.34 MB a step PERF.md
    has had since PR 24."""
    import benchmark_modules
    from metrics.step_roofline import step_bytes

    from primesim_tpu.config.machine import MachineConfig
    from primesim_tpu.sim.engine import Engine
    from primesim_tpu.sim.state import init_state
    from primesim_tpu.trace import synth

    conf = json.load(open(os.path.join(
        benchmark_modules.BENCH, "configs", "mesh1024.json")))
    machine = conf["machine"]
    small = MachineConfig.from_dict({**machine, "n_cores": 16, "n_banks": 16,
                                     "noc": {**machine["noc"], "mesh_x": 4, "mesh_y": 4}})
    trace = synth.fft_like(16, n_phases=2, points_per_core=8, seed=3)
    eng = Engine(small, trace, chunk_steps=8)
    raw = trace.line_events(small.line_bits)
    assert tuple(eng.events.shape) == raw.shape  # [C, T, 4]: measure.py:136
    assert int(eng.events.dtype.itemsize) == raw.dtype.itemsize == 4
    assert eng.events.blocks.shape[2] == 128  # what the device really holds

    def run_record(cfg, events_shape, itemsize):
        st = jax.eval_shape(lambda: init_state(cfg))
        return {"n_cores": cfg.n_cores, "machine": machine, "jobs": [{
            "state_shapes": {k: [list(v.shape), int(v.dtype.itemsize)]
                             for k, v in st._asdict().items() if hasattr(v, "shape")},
            "events_shape": [list(events_shape), int(itemsize)]}]}

    assert step_bytes(run_record(small, eng.events.shape, eng.events.dtype.itemsize)) == \
        step_bytes(run_record(small, raw.shape, raw.dtype.itemsize))
    big = MachineConfig.from_dict(machine)
    events = DeviceTrace(jax.ShapeDtypeStruct((1024, 19, 128), jnp.int32), 546)
    assert step_bytes(run_record(big, events.shape, events.dtype.itemsize)) == 3342336
