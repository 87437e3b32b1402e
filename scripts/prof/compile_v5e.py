"""Compile a configuration's `run_loop` with the TPU's compiler for a
DESCRIBED v5e, no chip attached: what a four-chip call would compile,
before the call.

    JAX_PLATFORMS=cpu PYTHONPATH=<checkout> python scripts/prof/compile_v5e.py \\
        <benchmark/configs/*.json or configs/*.json> <out.txt> [devices] [--sync]

`devices` defaults to the configuration's `run.devices` (1 where it has
none): above 1 the state and the events (a `DeviceTrace` of `trace_len`
records a core) get their `NamedSharding` over a
1-D tile mesh of the first `devices` chips of a v5e 2x2, as `Engine`
lays them out; `--sync` compiles the step for a trace with locks and
barriers (`has_sync` true), as `hlo_same.py dump --sync` does. Writes the compiled module's text (for
`hlo_same.py compare`), and prints the compiler's bytes a chip
(arguments, outputs, temporaries) and every collective with its shape
and the tail of its `op_name`, which holds the phase scope. Nothing
runs: a time never comes from here (PERF.md section 6: PR 21, 31, 33,
34 each read their collectives off this before a call).
"""

from __future__ import annotations

import re
import sys
import time

from hlo_same import load_config  # beside this file: the script's directory

_COLLECTIVE = re.compile(
    r"^\s*(?:ROOT )?%?(\S+) = (.*?) (all-reduce|all-gather|reduce-scatter"
    r"|all-to-all|collective-permute)(-start)?\(")


def main(conf_path: str, out_path: str, devices: int | None = None,
         trace_len: int = 546, has_sync: bool = False) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
    from jax.sharding import PartitionSpec as P

    from primesim_tpu.parallel import sharding
    from primesim_tpu.sim.engine import run_loop
    from primesim_tpu.sim.state import init_state
    from primesim_tpu.trace.device import DeviceTrace

    # a compile for a described device is written to the persistent cache
    # and cannot be read back without the chip
    jax.config.update("jax_enable_compilation_cache", False)
    cfg, chunk_steps, conf_devices = load_config(conf_path)
    devices = devices or conf_devices
    topo = topologies.get_topology_desc(topology_name="v5e:2x2", platform="tpu")
    # as `build_state` lays the block out: no stat rows on a mesh
    st = jax.eval_shape(lambda: init_state(cfg, stat_rows=devices == 1))
    if devices > 1:
        mesh = Mesh(np.asarray(topo.devices[:devices]), (sharding.AXIS,))
        place = lambda spec: NamedSharding(mesh, spec)  # noqa: E731
        st = jax.tree.map(
            lambda s, spec: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=place(spec)),
            st, sharding.state_pspecs())
        events, scalar = place(sharding.events_pspec()), place(P())
    else:
        events = scalar = SingleDeviceSharding(topo.devices[0])
        st = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=events), st)
    # the trace as `Engine` hands it over: `DeviceTrace`'s blocks
    ev = jax.eval_shape(lambda: DeviceTrace.of(
        jnp.zeros((cfg.n_cores, trace_len, 4), jnp.int32), cfg.local_run_len))
    ev = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=events), ev)
    t0 = time.perf_counter()
    compiled = run_loop.lower(
        cfg, chunk_steps, ev, st,
        jax.ShapeDtypeStruct((), jnp.int32, sharding=scalar), has_sync=has_sync).compile()
    text = compiled.as_text()
    with open(out_path, "w") as f:
        f.write(text)
    print(f"{out_path}: {len(text)} bytes, {devices} described device(s), "
          f"compiled in {time.perf_counter() - t0:.1f} s")
    mem = compiled.memory_analysis()
    print(f"a chip: arguments {mem.argument_size_in_bytes / 1e9:.3f} GB, outputs "
          f"{mem.output_size_in_bytes / 1e9:.3f} GB, temporaries "
          f"{mem.temp_size_in_bytes / 1e9:.3f} GB")
    for line in text.splitlines():
        found = _COLLECTIVE.match(line)
        if found:
            op_name = re.search(r'op_name="([^"]*)"', line)
            print(f"  {found.group(1)} {found.group(2)[:80]} | "
                  f"{(op_name.group(1) if op_name else '')[-60:]}")


if __name__ == "__main__":
    args = [a for a in sys.argv[1:] if a != "--sync"]
    if len(args) not in (2, 3):
        print(__doc__, file=sys.stderr)
        raise SystemExit(2)
    main(args[0], args[1], int(args[2]) if len(args) == 3 else None,
         has_sync="--sync" in sys.argv[1:])
