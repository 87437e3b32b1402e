"""Test environment: force an 8-device virtual CPU mesh.

This is the single-host stand-in for multi-chip TPU (SURVEY.md §4d): all
sharding/shard_map logic is exercised on 8 virtual CPU devices; the driver
separately dry-run-compiles the multi-chip path via __graft_entry__.

`JAX_PLATFORMS=cpu` plus the config update below pins the suite (and,
through the environment, every child it launches) to the CPU. The
persistent compile cache `cli.main` would otherwise place at
`<checkout>/.jax_cache` is switched off for the suite and its children:
tests must not leave state in the checkout for the next run to hit.
"""

import os

import pytest

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
if len(jax.devices()) != 8:
    raise RuntimeError(
        f"tests need an 8-device virtual CPU mesh, got {jax.devices()}; "
        f"XLA_FLAGS={os.environ.get('XLA_FLAGS')!r} already carried a "
        "conflicting xla_force_host_platform_device_count?"
    )


@pytest.fixture(autouse=True, scope="module")
def _free_compiled_programs():
    """One process runs the whole suite, and every compiled XLA:CPU
    program stays memory-mapped for as long as its jit-cache entry lives.
    Left alone the process climbs to vm.max_map_count (65530 maps after
    ~375 tests, measured in PR 21) and the next compile segfaults, so the
    last third of the suite never ran. Dropping the caches between test
    modules keeps the map count bounded; modules share few programs."""
    yield
    jax.clear_caches()
