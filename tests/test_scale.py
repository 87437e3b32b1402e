"""Scale-ladder enablers (BASELINE rungs 4-5, VERDICT r3 item #7).

- PTPU v4 line-addressed traces: addr = cache-line index (2^31 lines =
  128 GiB at 64B lines, 64x the byte-addressed range; larger captured
  spaces still alias under the 31-bit mask). Both engines normalize
  ingest to line granularity, so a byte trace and its line-converted twin
  simulate identically; round-trips through the binary format preserve
  the flag and the capture line size.
- Chunked sharer reductions (cfg.sharer_chunk_words): the [C, C]
  invalidation/back-invalidation expansions become a lax.scan over K-word
  blocks with [C, 32K] temporaries — bit-exact vs both the dense engine
  path and the golden model.
- 4096-core step: compiles and runs with chunking enabled.
"""

import numpy as np
import pytest

from primesim_tpu.config.machine import CacheConfig, MachineConfig, NocConfig
from primesim_tpu.golden.sim import GoldenSim
from primesim_tpu.trace import synth
from primesim_tpu.trace.format import EV_LD, EV_ST, Trace, from_event_lists

from test_parity import assert_parity
from test_parity_scale import scale_machine


# ------------------------------------------------------- v4 line addressing


def test_v4_roundtrip_preserves_line_flag(tmp_path):
    tr = from_event_lists(
        [[(EV_LD, 4, 123), (EV_ST, 4, 2**31 - 1)], [(EV_LD, 4, 0)]],
        line_addressed=True,
    )
    p = str(tmp_path / "t.ptpu")
    tr.save(p)
    tr2 = Trace.load(p)
    assert tr2.line_addressed
    np.testing.assert_array_equal(tr2.events, tr.events)


def test_line_addressed_equals_byte_addressed():
    # the same workload expressed byte- and line-addressed must produce
    # IDENTICAL simulations through both engines
    cfg = MachineConfig(n_cores=4, n_banks=4, quantum=500)
    byte_tr = synth.false_sharing(4, n_mem_ops=40, seed=61)
    ev = byte_tr.line_events(cfg.line_bits)
    line_tr = Trace(ev, byte_tr.lengths, line_addressed=True)

    gb = GoldenSim(cfg, byte_tr)
    gb.run()
    gl = GoldenSim(cfg, line_tr)
    gl.run()
    np.testing.assert_array_equal(gb.cycles, gl.cycles)
    for k in gb.counters:
        np.testing.assert_array_equal(gb.counters[k], gl.counters[k])
    # and the engine agrees with golden on the line-addressed form
    assert_parity(cfg, line_tr)


def test_line_addressed_wide_addresses_simulate():
    # line indices beyond 2^25 (byte addresses beyond 2^31) — impossible
    # in byte addressing — must simulate fine
    wide = 1 << 30  # line index ~ byte address 2^36
    cfg = MachineConfig(n_cores=2, n_banks=2)
    tr = from_event_lists(
        [
            [(EV_LD, 4, wide), (EV_ST, 4, wide)],
            [(EV_LD, 4, wide + 1)],
        ],
        line_addressed=True,
    )
    assert_parity(cfg, tr)


def test_captured_traces_are_line_addressed(tmp_path):
    # the C++ frontend emits v4 line-granular traces
    import shutil
    import subprocess

    if shutil.which("gcc") is None:
        pytest.skip("no toolchain")
    import os

    from primesim_tpu.ingest.capture import capture_run

    frontend = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "primesim_tpu", "frontend",
    )
    binary = str(tmp_path / "ocean_like")
    subprocess.run(
        ["gcc", "-O2", "-fno-builtin", "-o", binary,
         os.path.join(frontend, "examples", "ocean_like.c"), "-lpthread"],
        check=True, capture_output=True,
    )
    # heap line indices exceed 2^25 — BYTE addressing would have had to
    # alias these into its 2 GiB window; line addressing holds them. Where
    # the heap lies is ASLR's draw, and one run in about sixty puts every
    # line of the capture under 2^25 (seen in tier-1, PR 43): draw again
    for _ in range(4):
        tr = capture_run([binary, "2", "1", "2"], line=64)
        assert tr.line_addressed
        assert tr.line_bits == 6  # capture line size travels in the v4 flags
        mem = (tr.events[:, :, 0] == EV_LD) | (tr.events[:, :, 0] == EV_ST)
        if tr.events[:, :, 2][mem].max() > (1 << 25):
            break
    else:
        pytest.fail("four captures in a row held no line index above 2^25")
    # line-size mismatch is rejected, not silently misinterpreted
    from primesim_tpu.config.machine import CacheConfig, MachineConfig

    bad_cfg = MachineConfig(
        n_cores=tr.n_cores, n_banks=2,
        l1=CacheConfig(size=1024, ways=2, line=32, latency=2),
        llc=CacheConfig(size=8192, ways=4, line=32, latency=10),
    )
    with pytest.raises(ValueError, match="line"):
        tr.line_events(bad_cfg.line_bits)


# ------------------------------------------------- chunked sharer reductions


@pytest.mark.parametrize("chunk", [1, 2])
def test_parity_chunked_sharers_64core(chunk):
    # NW=2 at 64 cores; K=1 and K=2 cover multi-block and single-block
    cfg = scale_machine(64, 8, 8, sharer_chunk_words=chunk)
    assert_parity(
        cfg, synth.readers_writer(64, n_rounds=2, block_lines=4, seed=62),
        chunk_steps=64,
    )


def test_parity_chunked_sharers_sync_and_contention():
    cfg = scale_machine(
        64, 8, 8, sharer_chunk_words=2,
        noc=NocConfig(mesh_x=8, mesh_y=8, contention=True, contention_lat=2),
    )
    assert_parity(
        cfg, synth.barrier_phases(64, n_phases=2, work_per_phase=6, seed=63),
        chunk_steps=64,
    )


def test_4096core_step_runs_chunked():
    # BASELINE rung 4 scale: one chunk of steps compiles and runs with
    # bounded memory ([C, 64] temporaries instead of [C, C] = 16M)
    import jax.numpy as jnp

    from primesim_tpu.sim.engine import run_chunk
    from primesim_tpu.sim.state import init_state

    C = 4096
    cfg = MachineConfig(
        n_cores=C,
        n_banks=64,
        core=__import__("primesim_tpu.config.machine", fromlist=["CoreConfig"])
        .CoreConfig(cpi_pattern=(1, 1, 3, 3)),
        l1=CacheConfig(size=1024, ways=2, line=64, latency=2),
        llc=CacheConfig(size=8192, ways=4, line=64, latency=12),
        noc=NocConfig(mesh_x=8, mesh_y=8),
        quantum=1000,
        sharer_chunk_words=8,  # NW=128 -> 16 blocks
    )
    tr = synth.false_sharing(C, n_mem_ops=6, n_hot_lines=2, seed=64)
    events = jnp.asarray(tr.line_events(cfg.line_bits))
    st = run_chunk(cfg, 8, events, init_state(cfg), has_sync=False)
    assert int(st.step) == 8
    assert int(jnp.sum(st.counters)) > 0  # work actually happened


def test_16384core_step_runs_coarse():
    # BASELINE rung 5 scale (VERDICT r4 #5): with the full-map vector this
    # machine's sharer array alone is 256 GiB — the coarse vector (G=64,
    # 256 group bits) plus group-table reductions make the 16384-core step
    # executable on ONE chip. Small caches keep the CI footprint modest;
    # the shipped configs/rung5_16384core_wafer.json carries the full
    # geometry with the same sharer_group.
    import jax.numpy as jnp

    from primesim_tpu.config.machine import CoreConfig
    from primesim_tpu.sim.engine import run_chunk
    from primesim_tpu.sim.state import init_state

    C = 16384
    cfg = MachineConfig(
        n_cores=C,
        n_banks=256,
        core=CoreConfig(o3_overlap_256=128),
        l1=CacheConfig(size=1024, ways=2, line=64, latency=2),
        llc=CacheConfig(size=8192, ways=4, line=64, latency=16),
        noc=NocConfig(mesh_x=16, mesh_y=16),
        quantum=1000,
        sharer_group=64,
    )
    assert cfg.n_sharer_words == 8  # 256 groups, not 16384 bits
    tr = synth.false_sharing(C, n_mem_ops=4, n_hot_lines=2, seed=65)
    events = jnp.asarray(tr.line_events(cfg.line_bits))
    st = run_chunk(cfg, 4, events, init_state(cfg), has_sync=False)
    assert int(st.step) == 4
    assert int(jnp.sum(st.counters)) > 0
