"""The picks of `step` that are selects, not gathers (sim/step.py:
`_l1_set_read`, `_pick`): each equals the `take_along_axis` it replaced
to the bit, on every word an int32 can hold, under the fleet's `vmap`.
"""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from primesim_tpu.config.machine import CacheConfig, MachineConfig
from primesim_tpu.sim.step import _l1_set_read, _pick

C, BATCH = 8, 2
EDGES = np.array([-(2**31), -1, 0, 1, 2**31 - 1], np.int32)


def _words(rng, shape):
    """Random int32 over the whole range, a fifth of them edge values."""
    w = rng.integers(-(2**31), 2**31, shape, dtype=np.int64).astype(np.int32)
    return np.where(rng.random(shape) < 0.2, rng.choice(EDGES, shape), w)


@pytest.mark.parametrize(
    "S1,W1,n_planes,K",
    list(itertools.product((64, 128, 2048), (2, 4), (4, 5), (1, 9))),
)
def test_l1_set_read_equals_take_along_axis(S1, W1, n_planes, K):
    rng = np.random.default_rng(S1 + 10 * W1 + 100 * n_planes + K)
    cfg = MachineConfig(l1=CacheConfig(S1 * W1 * 64, W1, 64, 2))
    assert (cfg.l1.sets, cfg.l1.ways) == (S1, W1)
    FS = W1 * S1
    l1 = jnp.asarray(_words(rng, (BATCH, C, 5 * FS)))  # the fused array
    sets = jnp.asarray(rng.integers(0, S1, (BATCH, C, K), dtype=np.int32))
    # the local run reads tag, state and the epoch; the probe every plane
    planes = (0, 1, 4) if (K, n_planes) == (9, 5) else tuple(range(n_planes))
    got = jax.vmap(lambda a, s: _l1_set_read(cfg, a, s, planes))(l1, sets)
    cols = sets[..., None, None] + jnp.asarray(
        [[p * FS + w * S1 for w in range(W1)] for p in planes], jnp.int32)
    want = jnp.take_along_axis(
        l1, cols.reshape(BATCH, C, -1), axis=2).reshape(cols.shape)
    assert got.shape == (BATCH, C, K, len(planes), W1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _match(rng, shape, kind):
    if kind == "none":
        return np.zeros(shape, bool)
    if kind == "one":
        return np.arange(shape[-1]) == rng.integers(0, shape[-1], shape[:-1] + (1,))
    if kind == "several":  # two or more ways match: the lowest has to win
        m = rng.random(shape) < 0.6
        m[..., -1] = m[..., -2] = True
        return m
    return rng.random(shape) < 0.3  # mixed: rows of every kind


@pytest.mark.parametrize("kind", ["none", "one", "several", "mixed"])
@pytest.mark.parametrize("ways", [2, 4, 8])
def test_way_pick_equals_take_along_axis_of_argmax(ways, kind):
    rng = np.random.default_rng(ways)
    shape = (BATCH, C, 9, ways)
    x = jnp.asarray(_words(rng, shape))
    match = jnp.asarray(_match(rng, shape, kind))
    way = jnp.argmax(match, axis=-1).astype(jnp.int32)  # 0 where none matches
    got = jax.vmap(_pick)(x, way)
    want = jnp.take_along_axis(x, way[..., None], axis=-1)[..., 0]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("NW", [1, 8, 32])
def test_word_pick_out_of_a_gathered_row(NW):
    """`pshw`: way * NW + word out of the row's W2 * NW sharer words."""
    rng = np.random.default_rng(NW)
    W2 = 8
    row = jnp.asarray(_words(rng, (C, 9, W2 * NW)))
    idx = jnp.asarray(rng.integers(0, W2 * NW, (C, 9), dtype=np.int32))
    want = jnp.take_along_axis(row, idx[..., None], axis=2)[..., 0]
    np.testing.assert_array_equal(np.asarray(_pick(row, idx)), np.asarray(want))


def test_pick_broadcasts_one_index_over_leading_axes():
    """`ev`: candidate `consumed` of the prefetch, for all four fields."""
    rng = np.random.default_rng(4)
    pev = jnp.asarray(_words(rng, (C, 9, 4)))
    consumed = jnp.asarray(rng.integers(0, 9, (C, 1), dtype=np.int32))
    got = _pick(jnp.swapaxes(pev, 1, 2), consumed)
    want = jnp.take_along_axis(pev, consumed[:, :, None], axis=1)[:, 0]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
