"""Pallas reduction kernel (kernels/reductions.py — SURVEY.md §2 #4's Pallas
uncore piece): the engine's dense sharer-expansion reductions routed
through one Pallas kernel must stay BIT-EXACT against the golden model
on the same workloads that prove the jnp path (interpreter mode on CPU,
compiled on TPU)."""

import numpy as np
import pytest

from primesim_tpu.config.machine import MachineConfig, small_test_config
from primesim_tpu.trace import synth

from test_parity import assert_parity


@pytest.mark.parametrize(
    "gen", ["false_sharing", "uniform_random", "readers_writer"]
)
def test_parity_pallas_reduce(gen):
    cfg = small_test_config(8, n_banks=4, quantum=400, pallas_reduce=True)
    tr = {
        "false_sharing": lambda: synth.false_sharing(8, n_mem_ops=40, seed=41),
        "uniform_random": lambda: synth.uniform_random(8, n_mem_ops=50, seed=42),
        "readers_writer": lambda: synth.readers_writer(8, n_rounds=3, seed=43),
    }[gen]()
    assert_parity(cfg, tr, chunk_steps=32)


def test_parity_pallas_reduce_64core():
    # multi-block grid (BC=... rows per kernel instance), word-boundary
    # sharer sets, back-invalidations under a tiny LLC
    from primesim_tpu.config.machine import CacheConfig, NocConfig

    cfg = MachineConfig(
        n_cores=64, n_banks=16,
        l1=CacheConfig(size=1024, ways=2, line=64, latency=2),
        llc=CacheConfig(size=4096, ways=4, line=64, latency=10),
        noc=NocConfig(mesh_x=4, mesh_y=4),
        quantum=500, pallas_reduce=True,
    )
    assert_parity(
        cfg, synth.readers_writer(64, n_rounds=2, block_lines=4, seed=44),
        chunk_steps=32,
    )


def test_pallas_reduce_rejects_non_dense_modes():
    with pytest.raises(ValueError, match="pallas_reduce"):
        small_test_config(8, pallas_reduce=True, sharer_group=4)
    with pytest.raises(ValueError, match="pallas_reduce"):
        MachineConfig(
            n_cores=64, n_banks=16, pallas_reduce=True, sharer_chunk_words=1
        )
