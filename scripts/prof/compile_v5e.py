"""Compile a configuration's `run_loop` with the TPU's compiler for a
DESCRIBED v5e, no chip attached: what a four-chip call would compile,
before the call.

    JAX_PLATFORMS=cpu PYTHONPATH=<checkout> python scripts/prof/compile_v5e.py \\
        <benchmark/configs/*.json or configs/*.json> <out.txt> [devices] [--sync] [--fleet B]

`devices` defaults to the configuration's `run.devices` (1 where it has
none): above 1 the state and the events (a `DeviceTrace` of `trace_len`
records a core) get their `NamedSharding` over a
1-D tile mesh of the first `devices` chips of a v5e 2x2, as `Engine`
lays them out; `--sync` compiles the step for a trace with locks and
barriers (`has_sync` true), as `hlo_same.py dump --sync` does;
`--fleet B` compiles `fleet_run_loop` for B machines of the configuration
(a leading axis of B on the state and the `DeviceTrace`, the static key
`cfg.timing_normalized()`, as `FleetEngine` hands them over; with
`devices` above 1 as `FleetEngine(mesh=...)` places them: the batch axis
over the chips, B / devices whole machines a chip, and the loop the
one-chip program a chip under `shard_map`: no collective is to be
printed). Writes the compiled module's text (for
`hlo_same.py compare`), and prints the compiler's bytes a chip
(arguments, outputs, what of the outputs is laid in an argument's
buffer (`alias`: the donated state, PR 54: a job then holds its machine
once), temporaries, and the generated code's bytes, which lie in HBM
before the buffers: ROADMAP S13), every collective with its shape
and the tail of its `op_name`, which holds the phase scope, and every
`sort` with its operands' shape and layout, the dimension it sorts, its
scoped memory and what made each operand (PR 48), and every
`while` nested inside the step (the chunk loop's scan body) with the
arrays it carries: a loop there that no phase of `step` wrote is a
relayout the compiler made (PERF.md section 6, PR 44: the fleet's join
table, one element a trip); with `--fleet` also what the loop over
chunks itself makes of the B directories' shape, once a chunk (PR 45:
the freeze's select and copy, 2 ops, now none). Nothing
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
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$")
_WHILE = re.compile(r"^\s*(?:ROOT )?%?(\S+) = (.*) while\(.*body=%?([\w.\-]+)")
_CALLED = re.compile(
    r"(?:calls|to_apply|true_computation|false_computation)"
    r"=%?([\w.\-]+)|branch_computations=\{([^}]*)\}")
_SHAPE = re.compile(r"\w+\[[\d,]*\](?:\{[^}]*\})?")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def op_name_of(line: str) -> str:
    found = _OP_NAME.search(line)
    return found.group(1) if found else ""


def computations(text: str) -> tuple:
    """({computation: its lines}, the entry computation's name) of a
    compiled module's text."""
    bodies: dict = {}
    entry = at = None
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            at = head.group(1)
            bodies[at] = []
            if line.startswith("ENTRY"):
                entry = at
        elif at is not None:
            bodies[at].append(line)
    return bodies, entry


def chunk_loop_ops(text: str, shape: str) -> list:
    """The instructions of `shape` (`s32[16,131072,192]`) that the body of
    the loop over chunks runs itself, once a chunk: not what its scan over
    the steps holds, and no reading of the carry (PERF.md section 6, PR
    45: the fleet's freeze was a select and a copy of the B directories
    there)."""
    bodies, entry = computations(text)
    outer = next(loop for loop in map(_WHILE.match, bodies[entry]) if loop)
    made = re.compile(rf"^\s*(?:ROOT )?%?(\S+) = {re.escape(shape)}\S* ([\w\-]+)\(")
    return [f"{found.group(1)} ({found.group(2)})"
            for found in map(made.match, bodies[outer.group(3)])
            if found and found.group(2) not in (
                "while", "get-tuple-element", "parameter", "bitcast")]


_SORT = re.compile(
    r"^\s*(?:ROOT )?%?(\S+) = \(?(.*?)\)? sort\(([^)]*)\), dimensions=\{(\d+)\}")
_DEFINED = re.compile(r"^\s*(?:ROOT )?%?(\S+) = .*? ([\w\-]+)\(")
_SCOPED = re.compile(r'"used_scoped_memory_configs":\[\{[^}]*"size":"(\d+)"')


def sorts(text: str) -> list:
    """(instruction, operand shapes, sorted dimension, each operand's
    name and the opcode that made it, scoped memory in bytes, `op_name`)
    of every `sort` of a compiled module: the layout a sort got, and
    whether a `copy` stands in front of it (PERF.md section 6, PR 48:
    under `vmap` the router's sorts lay `[B, N]` with the machines in a
    tile's sublanes, until the ranking sorted a machine at a time)."""
    found = []
    for lines in computations(text)[0].values():
        made = {m.group(1): m.group(2) for m in map(_DEFINED.match, lines) if m}
        for line in lines:
            sort = _SORT.match(line)
            if not sort:
                continue
            operands = [o.strip().lstrip("%") for o in sort.group(3).split(",")]
            scoped = _SCOPED.search(line)
            found.append((
                sort.group(1), _SHAPE.findall(sort.group(2)), int(sort.group(4)),
                [f"{o} ({made.get(o, '?')})" for o in operands],
                int(scoped.group(1)) if scoped else 0, op_name_of(line)))
    return found


def nested_whiles(text: str) -> list:
    """(depth, computation, instruction, carried shapes, `op_name`) of
    every `while` of a compiled module that lies more than two loops
    deep: `run_loop` is the loop over chunks and, in its body, the scan
    over a chunk's steps, so a third loop runs inside every step."""
    bodies, entry = computations(text)
    found, seen = [], set()

    def walk(name: str, depth: int) -> None:
        if (name, depth) in seen or name not in bodies:
            return
        seen.add((name, depth))
        for line in bodies[name]:
            loop = _WHILE.match(line)
            if loop:  # its condition holds no loop
                if depth >= 2:
                    found.append((depth + 1, name, loop.group(1),
                                  _SHAPE.findall(loop.group(2)), op_name_of(line)))
                walk(loop.group(3), depth + 1)
                continue
            for called in _CALLED.finditer(line):
                for callee in (called.group(1) or called.group(2)).split(","):
                    walk(callee.strip().lstrip("%"), depth)

    walk(entry, 0)
    return found


def main(conf_path: str, out_path: str, devices: int | None = None,
         trace_len: int = 546, has_sync: bool = False, fleet: int = 0) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
    from jax.sharding import PartitionSpec as P

    from primesim_tpu.parallel import sharding
    from primesim_tpu.sim.engine import run_loop
    from primesim_tpu.sim.fleet import fleet_run_loop
    from primesim_tpu.sim.state import init_state
    from primesim_tpu.trace.device import DeviceTrace

    # a compile for a described device is written to the persistent cache
    # and cannot be read back without the chip
    jax.config.update("jax_enable_compilation_cache", False)
    cfg, chunk_steps, conf_devices = load_config(conf_path)
    devices = devices or conf_devices
    topo = topologies.get_topology_desc(topology_name="v5e:2x2", platform="tpu")
    # as `build_state` lays the block out: no stat rows on a mesh; a fleet
    # stacks solo `init_state`s and places the stack, so its block has them
    st = jax.eval_shape(lambda: init_state(cfg, stat_rows=bool(fleet) or devices == 1))
    # the trace as `Engine` hands it over: `DeviceTrace`'s blocks
    ev = jax.eval_shape(lambda: DeviceTrace.of(
        jnp.zeros((cfg.n_cores, trace_len, 4), jnp.int32), cfg.local_run_len))
    loop = run_loop
    st_specs, ev_spec = sharding.state_pspecs(), sharding.events_pspec()
    if fleet:  # the batch is the leading axis of every leaf, as `FleetEngine` stacks them
        loop, cfg = fleet_run_loop, cfg.timing_normalized()
        st, ev = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((fleet, *s.shape), s.dtype), (st, ev))
        st_specs, ev_spec = sharding.fleet_state_pspecs(), sharding.fleet_events_pspec()
    if devices > 1:
        mesh = Mesh(np.asarray(topo.devices[:devices]), (sharding.AXIS,))
        place = lambda spec: NamedSharding(mesh, spec)  # noqa: E731
        st = jax.tree.map(
            lambda s, spec: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=place(spec)),
            st, st_specs)
        events, scalar = place(ev_spec), place(P())
    else:
        events = scalar = SingleDeviceSharding(topo.devices[0])
        st = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=events), st)
    ev = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=events), ev)
    t0 = time.perf_counter()
    compiled = loop.lower(
        cfg, chunk_steps, ev, st,
        jax.ShapeDtypeStruct((), jnp.int32, sharding=scalar), has_sync=has_sync).compile()
    text = compiled.as_text()
    with open(out_path, "w") as f:
        f.write(text)
    print(f"{out_path}: {len(text)} bytes, {devices} described device(s), "
          f"{f'a fleet of {fleet}' if fleet else 'one machine'}, "
          f"compiled in {time.perf_counter() - t0:.1f} s")
    mem = compiled.memory_analysis()
    print(f"a chip: arguments {mem.argument_size_in_bytes / 1e9:.3f} GB, outputs "
          f"{mem.output_size_in_bytes / 1e9:.3f} GB, of them aliased to an argument "
          f"{mem.alias_size_in_bytes / 1e9:.3f} GB, temporaries "
          f"{mem.temp_size_in_bytes / 1e9:.3f} GB, generated code "
          f"{mem.generated_code_size_in_bytes} bytes")
    for line in text.splitlines():
        found = _COLLECTIVE.match(line)
        if found:
            print(f"  {found.group(1)} {found.group(2)[:80]} | "
                  f"{op_name_of(line)[-60:]}")
    if fleet:
        rows = list(st.dirm.shape)
        rows[0] //= devices  # a chip's own machines' directories, whole
        dirm = f"s32[{','.join(str(n) for n in rows)}]"
        ops = chunk_loop_ops(text, dirm)
        print(f"ops of `dirm`'s shape {dirm} in the loop over chunks, outside "
              f"its scan: {len(ops)} {' '.join(ops)}")
    found = sorts(text)
    print(f"sorts: {len(found)}")
    for name, shapes, dim, operands, scoped, op_name in found:
        print(f"  {name} {len(shapes)} x {shapes[0]} dimensions={{{dim}}} scoped "
              f"{scoped / 1e6:.1f} MB of {' '.join(operands)} | {op_name[-48:]}")
    loops = nested_whiles(text)
    print(f"loops inside the step: {len(loops)}")
    for depth, inside, name, shapes, op_name in loops:
        print(f"  {name} (depth {depth}, in {inside}) carries {' '.join(shapes)} | "
              f"{op_name[-60:]}")


if __name__ == "__main__":
    args = [a for a in sys.argv[1:] if a != "--sync"]
    fleet = 0
    if "--fleet" in args[:-1]:
        at = args.index("--fleet")
        fleet = int(args[at + 1])
        del args[at:at + 2]
    if len(args) not in (2, 3):
        print(__doc__, file=sys.stderr)
        raise SystemExit(2)
    main(args[0], args[1], int(args[2]) if len(args) == 3 else None,
         has_sync="--sync" in sys.argv[1:], fleet=fleet)
