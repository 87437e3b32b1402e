"""Ad-hoc step profiler: where do the 2.7 ms go at 1024 cores?"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from primesim_tpu.config.machine import CacheConfig, MachineConfig, NocConfig
from primesim_tpu.sim.engine import run_chunk
from primesim_tpu.sim.state import init_state
from primesim_tpu.trace import synth
from primesim_tpu.trace.format import fold_ins


def bench_cfg(C=1024, llc_kb=256, **kw):
    return MachineConfig(
        n_cores=C,
        n_banks=C,
        l1=CacheConfig(size=32 * 1024, ways=4, line=64, latency=2),
        llc=CacheConfig(size=llc_kb * 1024, ways=8, line=64, latency=10),
        noc=NocConfig(mesh_x=32, mesh_y=32, link_lat=1, router_lat=1),
        dram_lat=100,
        quantum=1000,
        **kw,
    )


def time_chunk(cfg, n_steps=256, tag="", has_sync=False):
    trace = fold_ins(synth.fft_like(cfg.n_cores, n_phases=4, points_per_core=256,
                                    ins_per_mem=8, seed=42))
    events = jnp.asarray(trace.line_events(cfg.line_bits))
    st = init_state(cfg)
    # NOTE: sync via an explicit host transfer (np.asarray of a leaf):
    # in round 3 jax.block_until_ready on AOT-compiled outputs
    # under-synced and reported ~1000x-too-fast times. Not re-checked on
    # the current machine; the host transfer is correct everywhere.
    st2 = run_chunk(cfg, n_steps, events, st, has_sync=has_sync)
    np.asarray(st2.step)
    t0 = time.perf_counter()
    for _ in range(3):
        st2 = run_chunk(cfg, n_steps, events, st2, has_sync=has_sync)
    np.asarray(st2.step)
    dt = (time.perf_counter() - t0) / 3 / n_steps
    print(f"[{tag}] {dt*1e3:.3f} ms/step", flush=True)
    return dt


if __name__ == "__main__":
    print("devices:", jax.devices())
    time_chunk(bench_cfg(1024), tag="1024c full")
    time_chunk(bench_cfg(1024, llc_kb=64), tag="1024c llc64KB (1/4 sets)")
    time_chunk(bench_cfg(256), tag="256c full")
