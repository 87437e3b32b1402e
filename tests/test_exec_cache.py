"""Content-addressed AOT executable cache + overlapped dispatch
(ISSUE 17, DESIGN.md §23).

The contract: with `--exec-cache on` every jitted entry point
(solo/fleet/stream) is compiled once, serialized to
`$PRIMETPU_CACHE_DIR/exec/<key>.bin`, and every later process with the
same geometry deserializes instead of compiling — and the simulation is
BIT-EXACT with the freshly-jitted path, leaf for leaf, across timing
knobs, fault schedules, prefix forks, sharded meshes and kill→resume.
A corrupt/stale/unusable entry degrades to miss-and-recompile with a
structured warning; the cache can make a run faster, never wrong, and
never dead. `--overlap on` speculatively dispatches chunk k+1 while the
host works on chunk k and must be bit-exact with `--overlap off`.
"""

import dataclasses
import json
import os
import signal

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from primesim_tpu.config.machine import (
    FAULT_LINK_DEGRADE,
    small_test_config,
)
from primesim_tpu.sim import exec_cache
from primesim_tpu.sim.engine import Engine
from primesim_tpu.sim.exec_cache import (
    ExecCache,
    exec_key,
    exec_key_payload,
)
from primesim_tpu.sim.fleet import FleetEngine
from primesim_tpu.sim.prefix import execute_prefix_plan, plan_prefix
from primesim_tpu.sim.supervisor import Preempted, RunSupervisor
from primesim_tpu.trace import synth

CHUNK = 16


@pytest.fixture(autouse=True)
def _deactivate_after():
    """Tests flip the process-global cache on; never leak it."""
    yield
    exec_cache.configure(False)


def _cfg(**kw):
    kw.setdefault("quantum", 200)
    return small_test_config(8, n_banks=4, **kw)


def _trace(seed=41):
    return synth.fft_like(8, n_phases=2, points_per_core=12, seed=seed)


def _full_state_equal(a, b):
    for k in a._fields:
        va, vb = getattr(a, k), getattr(b, k)
        if hasattr(va, "_fields"):
            _full_state_equal(va, vb)
            continue
        np.testing.assert_array_equal(
            np.asarray(va), np.asarray(vb), err_msg=k
        )


def _same_results(eng, ref):
    np.testing.assert_array_equal(eng.cycles, ref.cycles)
    for k, v in ref.counters.items():
        np.testing.assert_array_equal(eng.counters[k], v, err_msg=k)
    _full_state_equal(eng.state, ref.state)


def _payload(cfg, chunk=CHUNK, entry="engine.run_chunk", has_sync=True,
             trace=None):
    eng = Engine(cfg, trace if trace is not None else _trace(),
                 chunk_steps=chunk)
    payload, _ = exec_key_payload(
        entry, (cfg, chunk), (eng.events, eng.state),
        {"has_sync": has_sync},
    )
    return payload


# ---- key derivation --------------------------------------------------------


def test_key_sensitive_to_geometry_statics_and_entry():
    base = _payload(_cfg())
    # geometry: different machine -> different key
    big = small_test_config(16, n_banks=4, quantum=200)
    assert exec_key(base) != exec_key(
        _payload(big, trace=synth.fft_like(16, n_phases=2,
                                           points_per_core=12, seed=41)))
    # statics: chunk cadence is baked into the loop bound
    assert exec_key(base) != exec_key(_payload(_cfg(), chunk=32))
    # static kwargs: has_sync selects a different graph
    assert exec_key(base) != exec_key(_payload(_cfg(), has_sync=False))
    # entry name partitions the pool
    assert exec_key(base) != exec_key(
        _payload(_cfg(), entry="engine.run_loop"))


def test_key_invariant_to_traced_timing_knobs():
    """Timing knobs ride in state.knobs (traced), so every timing
    variant of one geometry shares one executable — the same contract
    FleetEngine's geom_cfg static already relies on."""
    base = _payload(_cfg())
    for kw in ({"quantum": 900}, {"dram_lat": 7}):
        variant = _payload(_cfg(**kw))
        assert exec_key(base) == exec_key(variant), kw


def test_key_payload_carries_toolchain_and_formats():
    p = _payload(_cfg())
    for field in ("jax", "jaxlib", "backend", "devices",
                  "exec_format", "ckpt_format", "geom", "tree", "avals"):
        assert field in p, field


# ---- solo engine: bit-exact, disk round trip, fallbacks --------------------


def test_solo_bit_exact_and_fresh_process_disk_hit(tmp_path):
    cfg, tr = _cfg(), _trace()
    ref = Engine(cfg, tr, chunk_steps=CHUNK)
    ref.run()

    root = str(tmp_path / "exec")
    cache = exec_cache.configure(True, root=root)
    eng = Engine(cfg, tr, chunk_steps=CHUNK)
    eng.run()
    _same_results(eng, ref)
    assert cache.stats["misses"] >= 1 and cache.stats["errors"] == 0
    bins = [f for f in os.listdir(root) if f.endswith(".bin")]
    assert bins, "miss must persist an entry"
    # every .bin has its key-payload sidecar
    for b in bins:
        assert os.path.exists(os.path.join(root, b[:-4] + ".json"))

    # a fresh ExecCache == a fresh process: no memo, loads from disk
    cache2 = exec_cache.configure(True, root=root)
    eng2 = Engine(cfg, tr, chunk_steps=CHUNK)
    eng2.run()
    _same_results(eng2, ref)
    assert cache2.stats["hits"] >= 1
    assert cache2.stats["misses"] == 0
    assert cache2.stats["compile_wall_s"] == 0.0


def test_corrupt_entry_degrades_to_recompile(tmp_path):
    cfg, tr = _cfg(), _trace()
    root = str(tmp_path / "exec")
    exec_cache.configure(True, root=root)
    ref = Engine(cfg, tr, chunk_steps=CHUNK)
    ref.run()

    for name in os.listdir(root):
        if name.endswith(".bin"):
            path = os.path.join(root, name)
            blob = bytearray(open(path, "rb").read())
            blob[20] ^= 0xFF
            open(path, "wb").write(bytes(blob))

    cache = exec_cache.configure(True, root=root)
    eng = Engine(cfg, tr, chunk_steps=CHUNK)
    eng.run()
    _same_results(eng, ref)
    assert cache.stats["errors"] >= 1
    assert cache.stats["misses"] >= 1  # recompiled
    assert any(w["stage"] == "load" and "CRC" in w["error"]
               for w in cache.warnings)


def test_truncated_and_bad_magic_entries(tmp_path):
    cfg, tr = _cfg(), _trace()
    root = str(tmp_path / "exec")
    exec_cache.configure(True, root=root)
    Engine(cfg, tr, chunk_steps=CHUNK).run()

    paths = [os.path.join(root, f) for f in os.listdir(root)
             if f.endswith(".bin")]
    open(paths[0], "wb").write(b"NOTEXEC!")
    cache = exec_cache.configure(True, root=root)
    eng = Engine(cfg, tr, chunk_steps=CHUNK)
    eng.run()
    assert cache.stats["errors"] >= 1
    assert any(w["stage"] == "load" for w in cache.warnings)
    ref = Engine(cfg, tr, chunk_steps=CHUNK)
    ref.run()
    _same_results(eng, ref)


def test_persist_failure_still_runs(tmp_path, monkeypatch):
    """serialize() blowing up must not take the run down — the compiled
    executable still serves this process; only persistence degrades."""
    import jax.experimental.serialize_executable as se

    def boom(exe):
        raise RuntimeError("no serialization on this backend")

    monkeypatch.setattr(se, "serialize", boom)
    cfg, tr = _cfg(), _trace()
    cache = exec_cache.configure(True, root=str(tmp_path / "exec"))
    eng = Engine(cfg, tr, chunk_steps=CHUNK)
    eng.run()
    ref = Engine(cfg, tr, chunk_steps=CHUNK)
    ref.run()
    _same_results(eng, ref)
    assert any(w["stage"] == "save" for w in cache.warnings)
    root = str(tmp_path / "exec")
    assert not os.path.isdir(root) or not [
        f for f in os.listdir(root) if f.endswith(".bin")
    ]


def test_inactive_cache_is_a_tail_call():
    """With no cache configured, exec_cache.call is byte-identical to
    calling the jitted fn directly."""
    exec_cache.configure(False)
    seen = {}

    def fake(cfg, chunk, ev, st, has_sync=False):
        seen["args"] = (cfg, chunk, ev, st, has_sync)
        return "out"

    out = exec_cache.call(fake, "engine.run_chunk", ("CFG", 16),
                          ("EV", "ST"), {"has_sync": True})
    assert out == "out"
    assert seen["args"] == ("CFG", 16, "EV", "ST", True)


# ---- the fused loops donate their state (PR 54) ----------------------------


def _fused_args(eng):
    """(entry, statics, dynamics, static kwargs) as `Engine.run` hands them."""
    return ("engine.run_loop", (eng.cfg, eng.chunk_steps),
            (eng.events, eng.state, jnp.asarray(1 << 20, jnp.int32)),
            {"has_sync": eng.has_sync})


def _deleted(state):
    return [x.is_deleted() for x in jax.tree.leaves(state)]


def test_a_blob_of_an_undonated_program_is_not_loaded_for_the_donating_one(
        tmp_path, monkeypatch):
    """A serialized executable of before PR 54 aliases nothing: loaded for
    today's `run_loop` it would lay a second machine beside the one it is
    given, every job, in silence. The payload's `exec_format` tells the
    two apart: the parent's blob (format 1) lies under another key, and
    nothing else of the payload differs."""
    from primesim_tpu.parallel.sharding import mesh_jit
    from primesim_tpu.sim.engine import run_loop

    cfg, tr = _cfg(), _trace()
    root = str(tmp_path / "exec")
    undonated = mesh_jit(run_loop.__wrapped__, static_argnums=(0, 1),
                         static_argnames=("has_sync",))
    today = exec_cache._FORMAT
    monkeypatch.setattr(exec_cache, "_FORMAT", 1)  # the parent writes its entry
    parent = ExecCache(root)
    held = Engine(cfg, tr, chunk_steps=CHUNK)
    entry, statics, dynamics, kw = _fused_args(held)
    parent.call(undonated, entry, statics, dynamics, kw)
    assert parent.stats["misses"] == 1 and not any(_deleted(held.state))
    (old_key,) = parent._memo
    old_payload = exec_key_payload(entry, statics, dynamics, kw)[0]

    monkeypatch.setattr(exec_cache, "_FORMAT", today)
    assert today > 1
    new_payload = exec_key_payload(entry, statics, dynamics, kw)[0]
    assert {k for k in new_payload if new_payload[k] != old_payload[k]} == {"exec_format"}
    cache = exec_cache.configure(True, root=root)
    ref = Engine(cfg, tr, chunk_steps=CHUNK)
    eng = Engine(cfg, tr, chunk_steps=CHUNK)
    handed = eng.state
    eng.run()
    assert cache.stats["errors"] == 0
    assert (cache.stats["hits"], cache.stats["misses"]) == (0, 1)
    assert old_key not in cache._memo and exec_key(new_payload) != old_key
    assert os.path.exists(os.path.join(root, old_key + ".bin"))  # there, and not found
    assert all(_deleted(handed))  # the program that ran took its state in place
    exec_cache.configure(False)
    ref.run()
    _same_results(eng, ref)


def test_a_failed_execute_does_not_run_again_on_consumed_arguments(tmp_path):
    """`cache.call` falls back to the jitted call when the loaded
    executable fails, but not once the failed call has consumed its donated
    state: there is nothing left to run on, and what happened is raised."""
    from primesim_tpu.sim.engine import run_loop

    cfg, tr = _cfg(), _trace()
    cache = ExecCache(str(tmp_path / "exec"))
    eng = Engine(cfg, tr, chunk_steps=CHUNK)
    entry, statics, dynamics, kw = _fused_args(eng)
    assert cache.ensure(run_loop, entry, statics, dynamics, kw)
    (key,) = cache._memo
    exe, calls = cache._memo[key], []

    def fn(*args, **kwargs):
        calls.append(args)
        return run_loop(*args, **kwargs)

    def refuses(*args):  # fails before it touches an argument
        raise RuntimeError("stale artifact")

    cache._memo[key] = refuses
    out = cache.call(fn, entry, statics, dynamics, kw)
    assert len(calls) == 1 and cache.warnings[-1]["stage"] == "execute"
    assert all(_deleted(eng.state)) and not any(_deleted(out[0]))

    def dies_after(*args):  # the device ran; the call dies afterwards
        exe(*args)
        raise RuntimeError("UNAVAILABLE: died after the work")

    eng = Engine(cfg, tr, chunk_steps=CHUNK)
    entry, statics, dynamics, kw = _fused_args(eng)
    cache._memo[key] = dies_after
    with pytest.raises(RuntimeError, match="died after the work"):
        cache.call(fn, entry, statics, dynamics, kw)
    assert len(calls) == 1  # not run again
    assert cache.warnings[-1]["stage"] == "execute" and cache.stats["errors"] == 2


# ---- composes with faults, timing variants, fleets -------------------------


@pytest.mark.slow
def test_faulted_run_bit_exact(tmp_path):
    cfg = dataclasses.replace(
        _cfg(),
        faults_enabled=True,
        max_fault_events=1,
        fault_events=((40, FAULT_LINK_DEGRADE, 0, 3),),
    )
    tr = _trace()
    ref = Engine(cfg, tr, chunk_steps=CHUNK)
    ref.run()
    exec_cache.configure(True, root=str(tmp_path / "exec"))
    eng = Engine(cfg, tr, chunk_steps=CHUNK)
    eng.run()
    _same_results(eng, ref)


def test_timing_variants_share_one_entry(tmp_path):
    """Two timing variants of one geometry: one compile, both bit-exact
    vs their own jitted references."""
    tr = _trace()
    cfgs = [_cfg(), _cfg(quantum=900, dram_lat=60)]
    refs = []
    for cfg in cfgs:
        r = Engine(cfg, tr, chunk_steps=CHUNK)
        r.run()
        refs.append(r)

    cache = exec_cache.configure(True, root=str(tmp_path / "exec"))
    for cfg, ref in zip(cfgs, refs):
        eng = Engine(cfg, tr, chunk_steps=CHUNK)
        eng.run()
        _same_results(eng, ref)
    assert cache.stats["misses"] == 1  # second variant reused the entry
    assert cache.stats["memo_hits"] >= 1


@pytest.mark.slow
def test_fleet_warm_exec_and_bit_exact(tmp_path):
    cfg = _cfg()
    traces = [_trace(45), synth.false_sharing(8, n_mem_ops=40, seed=47)]
    ovs = [{}, {"llc_lat": 25}]
    ref = FleetEngine(cfg, traces, ovs, chunk_steps=CHUNK)
    ref.run()

    fleet0 = FleetEngine(cfg, traces, ovs, chunk_steps=CHUNK)
    assert fleet0.warm_exec() is False  # no cache configured -> no-op

    cache = exec_cache.configure(True, root=str(tmp_path / "exec"))
    fleet = FleetEngine(cfg, traces, ovs, chunk_steps=CHUNK)
    assert fleet.warm_exec() is True  # lease-grant warm: compiles now
    assert cache.stats["misses"] == 1
    fleet.run()
    np.testing.assert_array_equal(fleet.cycles, ref.cycles)
    for k, v in ref.counters.items():
        np.testing.assert_array_equal(fleet.counters[k], v, err_msg=k)
    _full_state_equal(fleet.state, ref.state)


# ---- overlapped dispatch ---------------------------------------------------


def test_overlap_bit_exact_solo_and_fleet():
    cfg, tr = _cfg(), _trace()
    ref = Engine(cfg, tr, chunk_steps=CHUNK)
    ref.run_steps(6 * CHUNK)

    eng = Engine(cfg, tr, chunk_steps=CHUNK)
    eng.overlap = True
    eng.run_steps(6 * CHUNK)
    _same_results(eng, ref)

    traces = [_trace(45), _trace(46)]
    fref = FleetEngine(cfg, traces, [{}, {}], chunk_steps=CHUNK)
    fref.run_steps(6 * CHUNK)
    fleet = FleetEngine(cfg, traces, [{}, {}], chunk_steps=CHUNK)
    fleet.overlap = True
    fleet.run_steps(6 * CHUNK)
    np.testing.assert_array_equal(fleet.cycles, fref.cycles)
    _full_state_equal(fleet.state, fref.state)


def test_overlap_discard_on_state_surgery():
    """Anything that reassigns eng.state (checkpoint restore, retry)
    invalidates the speculated chunk — identity check + explicit
    discard_prefetch both cover it."""
    cfg, tr = _cfg(), _trace()
    eng = Engine(cfg, tr, chunk_steps=CHUNK)
    eng.overlap = True
    eng.run_steps(2 * CHUNK)
    assert eng._pending is not None
    saved = eng.state
    eng.discard_prefetch()
    assert eng._pending is None
    # and the identity guard alone: a stale pending for a different
    # state object must not be consumed
    eng._pending = (object(), "bogus", CHUNK)
    eng.run_steps(CHUNK)
    assert eng.state is not saved  # simulation advanced past the bogus


def test_overlap_preempt_resume_bit_exact(tmp_path):
    """kill -TERM at a chunk boundary with overlap+cache on; the resumed
    run (also overlap+cache) is bit-exact with a plain uninterrupted
    run."""
    cfg, tr = _cfg(), _trace()
    ref = Engine(cfg, tr, chunk_steps=CHUNK)
    ref.run()

    exec_cache.configure(True, root=str(tmp_path / "exec"))
    kills = {"n": 0}

    def _kill(sup):
        kills["n"] += 1
        if kills["n"] == 2:
            os.kill(os.getpid(), signal.SIGTERM)

    eng = Engine(cfg, tr, chunk_steps=CHUNK)
    eng.overlap = True
    sup = RunSupervisor(
        eng, snapshot_dir=str(tmp_path / "snap"),
        checkpoint_every_chunks=1, guard="fail", on_chunk=_kill,
    )
    with pytest.raises(Preempted):
        sup.run()
    assert not eng.done()

    eng2 = Engine(cfg, tr, chunk_steps=CHUNK)
    eng2.overlap = True
    sup2 = RunSupervisor(eng2, snapshot_dir=str(tmp_path / "snap"),
                         guard="fail")
    assert sup2.resume() is not None
    sup2.run()
    _same_results(eng2, ref)


# ---- heavier compositions: sharded mesh, prefix fork (CI job runs these) --


@pytest.mark.slow
def test_sharded_fleet_cache_bit_exact(tmp_path):
    from primesim_tpu.parallel.sharding import tile_mesh

    cfg = _cfg()
    traces = [_trace(50 + i) for i in range(4)]
    ovs = [{"fault_seed": 7 + i} for i in range(4)]
    ref = FleetEngine(cfg, traces, ovs, chunk_steps=CHUNK,
                      mesh=tile_mesh(4))
    ref.run()

    cache = exec_cache.configure(True, root=str(tmp_path / "exec"))
    fleet = FleetEngine(cfg, traces, ovs, chunk_steps=CHUNK,
                        mesh=tile_mesh(4))
    fleet.run()
    np.testing.assert_array_equal(fleet.cycles, ref.cycles)
    _full_state_equal(fleet.state, ref.state)
    assert cache.stats["errors"] == 0
    # the sharded entry is addressable: a fresh cache hits from disk
    cache2 = exec_cache.configure(True, root=str(tmp_path / "exec"))
    again = FleetEngine(cfg, traces, ovs, chunk_steps=CHUNK,
                        mesh=tile_mesh(4))
    again.run()
    np.testing.assert_array_equal(again.cycles, ref.cycles)
    assert cache2.stats["hits"] >= 1 and cache2.stats["misses"] == 0


@pytest.mark.slow
def test_prefix_fork_composes_with_cache(tmp_path):
    cfg = dataclasses.replace(
        _cfg(),
        faults_enabled=True,
        max_fault_events=1,
        fault_events=((40, FAULT_LINK_DEGRADE, 0, 3),),
    )
    tr = _trace()
    ovs = [{"fault_seed": 100 + i} for i in range(4)]
    plain = FleetEngine(cfg, [tr] * 4, ovs, chunk_steps=CHUNK)
    plain.run()

    exec_cache.configure(True, root=str(tmp_path / "exec"))
    forked = FleetEngine(cfg, [tr] * 4, ovs, chunk_steps=CHUNK)
    groups = plan_prefix(forked.elem_cfgs, forked.traces, chunk_steps=CHUNK)
    assert groups and groups[0].prefix_steps > 0
    st = execute_prefix_plan(forked, groups)
    assert st["forked_elements"] == 4
    forked.run()
    np.testing.assert_array_equal(forked.cycles, plain.cycles)
    for k, v in plain.counters.items():
        np.testing.assert_array_equal(forked.counters[k], v, err_msg=k)
    _full_state_equal(forked.state, plain.state)


# ---- stream engine ---------------------------------------------------------


def test_stream_engine_bit_exact(tmp_path):
    from primesim_tpu.ingest.stream import StreamEngine

    cfg = _cfg()
    tr = synth.false_sharing(8, n_mem_ops=40, seed=44)
    ref = Engine(cfg, tr, chunk_steps=CHUNK)
    ref.run()

    exec_cache.configure(True, root=str(tmp_path / "exec"))
    eng = StreamEngine(cfg, tr, window_events=8)
    eng.run()
    np.testing.assert_array_equal(eng.cycles, ref.cycles)
    for k, v in ref.counters.items():
        np.testing.assert_array_equal(eng.counters[k], v, err_msg=k)


# ---- shared LRU budget -----------------------------------------------------


def test_shared_lru_budget_spans_warm_and_exec(tmp_path):
    from primesim_tpu.sim.checkpoint import prune_warm_cache

    root = str(tmp_path)
    exec_root = os.path.join(root, "exec")
    os.makedirs(exec_root)

    def put(path, size, mtime):
        with open(path, "wb") as f:
            f.write(b"x" * size)
        json_twin = path[: path.rfind(".")] + ".json"
        with open(json_twin, "w") as f:
            f.write("{}")
        os.utime(path, (mtime, mtime))

    put(os.path.join(root, "warm-old.npz"), 400, 1000)
    put(os.path.join(exec_root, "exec-old.bin"), 400, 2000)
    put(os.path.join(root, "warm-new.npz"), 400, 3000)
    put(os.path.join(exec_root, "exec-new.bin"), 400, 4000)

    removed = prune_warm_cache(root, max_bytes=900)
    assert removed == 2
    # LRU across BOTH pools: the two oldest went, one from each
    assert not os.path.exists(os.path.join(root, "warm-old.npz"))
    assert not os.path.exists(os.path.join(exec_root, "exec-old.bin"))
    assert os.path.exists(os.path.join(root, "warm-new.npz"))
    assert os.path.exists(os.path.join(exec_root, "exec-new.bin"))
    # sidecars go with their entries
    assert not os.path.exists(os.path.join(exec_root, "exec-old.json"))
    assert os.path.exists(os.path.join(exec_root, "exec-new.json"))


def test_write_entry_prunes(tmp_path, monkeypatch):
    """A compile that lands a new .bin immediately re-applies the shared
    budget (so the cache tree cannot grow unbounded between runs)."""
    monkeypatch.setenv("PRIMETPU_CACHE_MAX_BYTES", "1")
    root = str(tmp_path / "warm" / "exec")
    os.makedirs(os.path.dirname(root), exist_ok=True)
    cache = exec_cache.configure(True, root=root)
    cfg, tr = _cfg(), _trace()
    eng = Engine(cfg, tr, chunk_steps=CHUNK)
    eng.run_steps(CHUNK)
    assert cache.stats["misses"] >= 1
    # with a 1-byte budget the entry was pruned right after the write —
    # and the run still completed (the executable is memo-resident)
    assert not [f for f in os.listdir(root) if f.endswith(".bin")]


# ---- fsck integration ------------------------------------------------------


def test_fsck_checks_exec_entries(tmp_path):
    from primesim_tpu.analysis.fsck import run_fsck

    root = str(tmp_path / "exec")
    exec_cache.configure(True, root=root)
    cfg, tr = _cfg(), _trace()
    Engine(cfg, tr, chunk_steps=CHUNK).run_steps(CHUNK)
    bins = [f for f in os.listdir(root) if f.endswith(".bin")]
    assert bins

    res = run_fsck(str(tmp_path))
    assert res.checked["exec_entries"] == len(bins)
    assert res.clean and not res.findings

    # corrupt one: fsck flags it, --repair quarantines it (move aside)
    victim = os.path.join(root, bins[0])
    blob = bytearray(open(victim, "rb").read())
    blob[20] ^= 0xFF
    open(victim, "wb").write(bytes(blob))
    res = run_fsck(str(tmp_path))
    assert any(f.kind == "exec-cache" and f.corrupt for f in res.findings)
    res = run_fsck(str(tmp_path), repair="quarantine")
    assert not os.path.exists(victim)
    assert os.path.exists(os.path.join(
        str(tmp_path), ".fsck-quarantine", "exec", bins[0]))


def test_fsck_exec_sidecar_key_content_agreement(tmp_path):
    from primesim_tpu.analysis.fsck import run_fsck

    root = str(tmp_path / "exec")
    exec_cache.configure(True, root=root)
    Engine(_cfg(), _trace(), chunk_steps=CHUNK).run_steps(CHUNK)
    bins = [f for f in os.listdir(root) if f.endswith(".bin")]
    sidecar = os.path.join(root, bins[0][:-4] + ".json")

    with open(sidecar) as f:
        meta = json.load(f)
    good_payload = dict(meta["payload"])

    # edit the payload: it no longer hashes to the entry's address
    meta["payload"]["entry"] = "tampered"
    with open(sidecar, "w") as f:
        json.dump(meta, f)
    res = run_fsck(str(tmp_path))
    assert any(
        f.kind == "exec-cache" and f.corrupt and "hash" in f.detail
        for f in res.findings
    )
    with open(sidecar, "w") as f:  # restore
        json.dump({"key": meta["key"], "payload": good_payload}, f)

    # a toolchain drift is a NOTE (dead address, plain miss), never
    # corrupt: fabricate an entry correctly addressed under another jax
    drifted = dict(good_payload, jax="0.0.1", jaxlib="0.0.1")
    key2 = exec_cache.exec_key(drifted)
    with open(os.path.join(root, bins[0]), "rb") as f:
        body = f.read()
    with open(os.path.join(root, key2 + ".bin"), "wb") as f:
        f.write(body)
    with open(os.path.join(root, key2 + ".json"), "w") as f:
        json.dump({"key": key2, "payload": drifted}, f)
    res = run_fsck(str(tmp_path))
    drift = [f for f in res.findings
             if f.kind == "exec-cache" and "toolchain" in f.detail]
    assert drift and not any(f.corrupt for f in drift)
    assert res.clean
