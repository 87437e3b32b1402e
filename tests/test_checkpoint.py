"""Checkpoint/resume bit-exactness (SURVEY.md §5.4) + xml_compat loader."""

import json
import os

import numpy as np
import pytest

from primesim_tpu.config.machine import (
    ConfigError,
    MachineConfig,
    small_test_config,
)
from primesim_tpu.sim.checkpoint import atomic_save_npz, load_verified_npz
from primesim_tpu.sim.engine import Engine
from primesim_tpu.trace import synth

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _full_state_equal(a, b):
    for k in a._fields:
        va, vb = getattr(a, k), getattr(b, k)
        if hasattr(va, "_fields"):  # nested pytree (TimingKnobs)
            _full_state_equal(va, vb)
            continue
        np.testing.assert_array_equal(
            np.asarray(va), np.asarray(vb), err_msg=k
        )


@pytest.mark.parametrize("gen", ["fft_like", "lock_contention"])
def test_checkpoint_resume_bit_exact(tmp_path, gen):
    cfg = small_test_config(8, n_banks=4, quantum=200)
    tr = (
        synth.fft_like(8, n_phases=2, points_per_core=12, seed=41)
        if gen == "fft_like"
        else synth.lock_contention(8, n_critical=8, seed=42)
    )
    ckpt = str(tmp_path / "mid.npz")

    # uninterrupted reference run
    ref = Engine(cfg, tr, chunk_steps=16)
    ref.run()
    ref_counters = {k: v.copy() for k, v in ref.counters.items()}

    # run A steps -> save -> fresh engine -> load -> finish
    a = Engine(cfg, tr, chunk_steps=16)
    a.run_steps(48)
    assert not a.done()  # checkpoint taken mid-run, not at the end
    a.save_checkpoint(ckpt)

    b = Engine(cfg, tr, chunk_steps=16)
    b.load_checkpoint(ckpt)
    b.run()

    np.testing.assert_array_equal(b.cycles, ref.cycles)
    _full_state_equal(b.state, ref.state)
    bc = b.counters
    for k, v in ref_counters.items():
        np.testing.assert_array_equal(bc[k], v, err_msg=k)


def test_stream_checkpoint_resume_bit_exact(tmp_path):
    # VERDICT r4 #8: the billion-event runs streaming exists for need
    # resume. run_events pauses at a window boundary (the consistent
    # cut); save -> fresh StreamEngine -> load -> finish must be
    # bit-exact with an uninterrupted streamed run AND the preloaded
    # engine.
    from primesim_tpu.ingest.stream import StreamEngine

    cfg = small_test_config(8, n_banks=4, quantum=200)
    tr = synth.false_sharing(8, n_mem_ops=40, seed=44)
    ckpt = str(tmp_path / "stream.npz")

    ref = Engine(cfg, tr, chunk_steps=16)
    ref.run()

    a = StreamEngine(cfg, tr, window_events=8)
    finished = a.run_events(80)
    assert not finished  # mid-stream cut
    a.save_checkpoint(ckpt)

    b = StreamEngine(cfg, tr, window_events=8)
    b.load_checkpoint(ckpt)
    b.run()
    np.testing.assert_array_equal(b.cycles, ref.cycles)
    bc, rc = b.counters, ref.counters
    for k, v in rc.items():
        np.testing.assert_array_equal(bc[k], v, err_msg=k)

    # a plain Engine must refuse a streaming checkpoint
    c = Engine(cfg, tr, chunk_steps=16)
    with pytest.raises(ValueError, match="[Ss]tream"):
        c.load_checkpoint(ckpt)
    # and window geometry is part of the resume contract
    d = StreamEngine(cfg, tr, window_events=16)
    with pytest.raises(ValueError, match="window"):
        d.load_checkpoint(ckpt)


def test_checkpoint_resume_multichip_mesh(tmp_path):
    # load_checkpoint must restore the multi-chip sharding layout, not
    # materialize the state unsharded on one device
    from primesim_tpu.parallel.sharding import tile_mesh

    cfg = small_test_config(8, n_banks=8)
    tr = synth.false_sharing(8, n_mem_ops=24, seed=44)
    mesh = tile_mesh(8)

    ref = Engine(cfg, tr, chunk_steps=8, mesh=mesh)
    ref.run()

    a = Engine(cfg, tr, chunk_steps=8, mesh=mesh)
    a.run_steps(16)
    ckpt = str(tmp_path / "mesh.npz")
    a.save_checkpoint(ckpt)
    b = Engine(cfg, tr, chunk_steps=8, mesh=mesh)
    b.load_checkpoint(ckpt)
    assert len(b.state.cycles.sharding.device_set) == 8  # re-sharded
    b.run()
    np.testing.assert_array_equal(b.cycles, ref.cycles)
    _full_state_equal(b.state, ref.state)


def test_checkpoint_rejects_mismatches(tmp_path):
    cfg = small_test_config(4)
    tr = synth.stream(4, n_mem_ops=10, seed=43)
    e = Engine(cfg, tr, chunk_steps=8)
    e.run_steps(8)
    ckpt = str(tmp_path / "c.npz")
    e.save_checkpoint(ckpt)

    other_cfg = small_test_config(4, quantum=777)
    with pytest.raises(ValueError, match="config does not match"):
        Engine(other_cfg, tr, chunk_steps=8).load_checkpoint(ckpt)
    other_tr = synth.stream(4, n_mem_ops=10, seed=99)
    with pytest.raises(ValueError, match="trace does not match"):
        Engine(cfg, other_tr, chunk_steps=8).load_checkpoint(ckpt)


def _as_the_parent_wrote(cfg) -> str:
    """`cfg.to_json()` as the parent of PR 46 wrote it: the two options of
    the removed Pallas step stated at their defaults."""
    d = json.loads(cfg.to_json())
    d.update(pallas_reduce=False, step_impl="xla")
    return json.dumps(d, indent=2)


@pytest.mark.parametrize("case", [
    "step_impl-xla", "pallas_reduce-false", "step_impl-pallas",
    "pallas_reduce-true", "parents-checkpoint"])
def test_removed_step_options_at_the_edge(tmp_path, case):
    # `MachineConfig.from_dict` drops the defaults of the two options PR 46
    # removed (benchmark/ and the parent's checkpoints still state them)
    # and refuses anything else (ROADMAP D15: the shim's removal)
    cfg = small_test_config(4)
    plain = json.loads(cfg.to_json())
    assert "step_impl" not in plain and "pallas_reduce" not in plain
    if case == "step_impl-xla":
        assert MachineConfig.from_dict({**plain, "step_impl": "xla"}) == cfg
    elif case == "pallas_reduce-false":
        assert MachineConfig.from_dict({**plain, "pallas_reduce": False}) == cfg
    elif case in ("step_impl-pallas", "pallas_reduce-true"):
        key, said = (("step_impl", "pallas") if case == "step_impl-pallas"
                     else ("pallas_reduce", True))
        with pytest.raises(ConfigError, match="removed in PR 46") as e:
            MachineConfig.from_dict({**plain, key: said})
        assert (e.value.selector, e.value.value) == (key, said)
    else:
        stored = _as_the_parent_wrote(cfg)
        assert MachineConfig.from_json(stored) == cfg
        tr = synth.stream(4, n_mem_ops=10, seed=43)
        ref = Engine(cfg, tr, chunk_steps=8)
        ref.run()
        e = Engine(cfg, tr, chunk_steps=8)
        e.run_steps(8)
        ckpt = str(tmp_path / "c.npz")
        e.save_checkpoint(ckpt)
        members = load_verified_npz(ckpt)
        members["config_json"] = np.frombuffer(stored.encode(), dtype=np.uint8)
        atomic_save_npz(ckpt, **members)
        b = Engine(cfg, tr, chunk_steps=8)
        b.load_checkpoint(ckpt)
        b.run()
        np.testing.assert_array_equal(b.cycles, ref.cycles)


def test_fleet_checkpoint_resume_bit_exact(tmp_path):
    # fleet snapshots carry the BATCHED state plus per-element 64-bit
    # cycle bases / counter accumulators; resume must be bit-exact per
    # element against an uninterrupted fleet run
    from primesim_tpu.sim.fleet import FleetEngine

    cfg = small_test_config(8, n_banks=4, quantum=200)
    traces = [
        synth.fft_like(8, n_phases=2, points_per_core=12, seed=45),
        synth.lock_contention(8, n_critical=8, seed=46),
        synth.false_sharing(8, n_mem_ops=40, seed=47),
    ]
    overrides = [{}, {"llc_lat": 25, "quantum": 150}, {"dram_lat": 140}]
    ckpt = str(tmp_path / "fleet.npz")

    ref = FleetEngine(cfg, traces, overrides, chunk_steps=16)
    ref.run()
    ref_counters = {k: v.copy() for k, v in ref.counters.items()}

    a = FleetEngine(cfg, traces, overrides, chunk_steps=16)
    a.run_steps(48)
    assert not a.done()  # mid-run cut
    a.save_checkpoint(ckpt)

    b = FleetEngine(cfg, traces, overrides, chunk_steps=16)
    b.load_checkpoint(ckpt)
    b.run()

    np.testing.assert_array_equal(b.cycles, ref.cycles)
    _full_state_equal(b.state, ref.state)
    bc = b.counters
    for k, v in ref_counters.items():
        np.testing.assert_array_equal(bc[k], v, err_msg=k)


def test_fleet_checkpoint_rejects_mismatches(tmp_path):
    from primesim_tpu.sim.fleet import FleetEngine

    cfg = small_test_config(4, n_banks=4)
    traces = [
        synth.stream(4, n_mem_ops=20, seed=48),
        synth.uniform_random(4, n_mem_ops=20, seed=49),
    ]
    fl = FleetEngine(cfg, traces, [{}, {"llc_lat": 20}], chunk_steps=8)
    fl.run_steps(8)
    ckpt = str(tmp_path / "fleet.npz")
    fl.save_checkpoint(ckpt)

    # a plain Engine must refuse a fleet checkpoint, and vice versa
    with pytest.raises(ValueError, match="[Ff]leet"):
        Engine(cfg, traces[0], chunk_steps=8).load_checkpoint(ckpt)
    solo_ckpt = str(tmp_path / "solo.npz")
    e = Engine(cfg, traces[0], chunk_steps=8)
    e.run_steps(8)
    e.save_checkpoint(solo_ckpt)
    with pytest.raises(ValueError, match="fleet checkpoint"):
        FleetEngine(cfg, traces, chunk_steps=8).load_checkpoint(solo_ckpt)

    # element configs (overrides included) and traces are part of the
    # resume contract — the batch axis is positional
    with pytest.raises(ValueError, match="configs do not match"):
        FleetEngine(cfg, traces, [{}, {"llc_lat": 99}],
                    chunk_steps=8).load_checkpoint(ckpt)
    with pytest.raises(ValueError, match="traces do not match"):
        FleetEngine(
            cfg, list(reversed(traces)), [{}, {"llc_lat": 20}],
            chunk_steps=8,
        ).load_checkpoint(ckpt)


def test_crash_mid_write_never_replaces_good_snapshot(tmp_path, monkeypatch):
    # DESIGN.md §10 durability contract: saves go tmp + fsync +
    # os.replace, so a crash mid-write leaves the previous snapshot
    # byte-identical (and no .tmp litter)
    from primesim_tpu.sim import checkpoint as ckpt_mod

    cfg = small_test_config(8, n_banks=4, quantum=200)
    tr = synth.fft_like(8, n_phases=2, points_per_core=12, seed=41)
    eng = Engine(cfg, tr, chunk_steps=16)
    eng.run_steps(16)
    path = tmp_path / "c.npz"
    eng.save_checkpoint(str(path))
    good = path.read_bytes()

    eng.run_steps(16)

    def dies_mid_write(f, **arrays):
        f.write(b"torn partial npz bytes")
        raise OSError("simulated crash mid-write")

    monkeypatch.setattr(ckpt_mod.np, "savez_compressed", dies_mid_write)
    with pytest.raises(OSError, match="simulated crash"):
        eng.save_checkpoint(str(path))
    monkeypatch.undo()

    assert path.read_bytes() == good  # untouched by the torn write
    assert not (tmp_path / "c.npz.tmp").exists()  # tmp cleaned up
    fresh = Engine(cfg, tr, chunk_steps=16)
    fresh.load_checkpoint(str(path))  # and it still loads + verifies
    assert fresh.steps_run == 16


def test_accumulator_guard_rejects_oversized_chunks():
    from primesim_tpu.trace.format import EV_INS, from_event_lists

    cfg = small_test_config(2, n_banks=2)
    tr = from_event_lists([[(EV_INS, 1 << 22, 0)], []])
    with pytest.raises(ValueError, match="accumulator"):
        Engine(cfg, tr, chunk_steps=512)
    Engine(cfg, tr, chunk_steps=64)  # small chunks stay under the guard


# ------------------------------------------------------------- xml_compat


def test_xml_compat_matches_json_rung1():
    from primesim_tpu.config.xml_compat import load_xml

    cfg = load_xml(os.path.join(REPO, "configs", "example_prime.xml"))
    with open(os.path.join(REPO, "configs", "rung1_64core_fft.json")) as f:
        want = MachineConfig.from_json(f.read())
    # the XML example mirrors rung 1 except the local_run_len tuning knob
    import dataclasses

    assert cfg == dataclasses.replace(want, local_run_len=0)


def test_xml_compat_aliases_and_errors(tmp_path):
    from primesim_tpu.config.xml_compat import load_xml

    p = tmp_path / "alias.xml"
    p.write_text(
        """<sim><sys>
        <n_cores>8</n_cores>
        <quantum>500</quantum>
        <dram_latency>90</dram_latency>
        <network><x_dimension>2</x_dimension><y_dimension>2</y_dimension>
        </network>
        <cache level="1"><size>1024</size><associativity>2</associativity>
          <line_size>64</line_size><latency>2</latency></cache>
        <cache level="2" shared="yes" num_banks="4"><size>8192</size>
          <num_ways>4</num_ways><line_size>64</line_size>
          <access_time>11</access_time></cache>
        </sys></sim>"""
    )
    cfg = load_xml(str(p))
    assert cfg.n_cores == 8 and cfg.quantum == 500 and cfg.dram_lat == 90
    assert cfg.l1.ways == 2 and cfg.llc.latency == 11 and cfg.n_banks == 4

    bad = tmp_path / "bad.xml"
    bad.write_text("<sim><sys><num_cores>8</num_cores></sys></sim>")
    with pytest.raises(ValueError, match="cache"):
        load_xml(str(bad))


def test_cli_accepts_xml_config(capsys):
    import json

    from primesim_tpu.cli import main

    xml = os.path.join(REPO, "configs", "example_prime.xml")
    assert main(["info", xml]) == 0
    d = json.loads(capsys.readouterr().out)
    assert d["n_cores"] == 64 and d["llc"]["size"] == 262144
