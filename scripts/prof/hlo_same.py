"""Did a change alter the program the chip executes, or only its metadata?

    PYTHONPATH=<checkout> python scripts/prof/hlo_same.py dump <config .json> <out.txt> [--sync]
    python scripts/prof/hlo_same.py compare <a.txt> <b.txt>

`dump` compiles the `run_loop` of the `primesim_tpu` it imports on the
present default device (one device; shapes only, nothing runs) and writes
the compiled module's text. The file is a benchmark configuration
(`benchmark/configs/*.json`: its machine and `chunk_steps`; its
`run.step_impl`, which the files still state, is handed to
`MachineConfig.from_dict` as `benchmark/measure.py` hands it: ROADMAP D15)
or a plain machine file (`configs/*.json`: every static selector is a
field of it; chunks of 8 steps); `--sync` compiles the step for a trace
with locks and barriers (`has_sync` true). Run it once for each checkout, then `compare` the
two files with everything that is only
metadata removed: every instruction's `metadata={...}` (`op_name`, source
line, stack frame) and the file/function/stack-frame tables those point
into. It says whether the rest is byte-identical and, where it is not,
whether the two texts still agree in everything but instruction names
(XLA numbers the instructions it creates late after the names the front
end gave, and a `jax.named_scope` reaches a few of those), naming what
differs. Exit code 0: identical, or identical up to names; 1: not.
"""

from __future__ import annotations

import collections
import json
import re
import sys

_METADATA = re.compile(r",? ?metadata=\{[^}]*\}")
_TABLES = re.compile(
    r"\n(?:FileNames|FunctionNames|FileLocations|StackFrames)\n(?:\d+ .*\n)*")
# a name where it is used (`%add.7`) and where a computation's header
# declares it as a parameter (`(reduce_sum.108: s32[], ...)`)
_NAME = re.compile(r"%[\w.\-]+|\b[\w.\-]+(?=: )")


def strip(text: str) -> str:
    return _TABLES.sub("\n", _METADATA.sub("", text))


def load_config(config_path: str):
    """(`MachineConfig`, `chunk_steps`, `devices`) of a benchmark
    configuration, or of a plain machine file (chunks of 8, one device)."""
    from primesim_tpu.config.machine import MachineConfig

    with open(config_path) as f:
        conf = json.load(f)
    if "machine" not in conf:
        return MachineConfig.from_dict(conf), 8, 1
    run = conf["run"]
    machine = {**conf["machine"], "step_impl": run["step_impl"]}
    return (MachineConfig.from_dict(machine), int(run["chunk_steps"]),
            int(run.get("devices") or 1))


def dump(config_path: str, out_path: str, has_sync: bool = False,
         trace_len: int = 546) -> None:
    import jax
    import jax.numpy as jnp

    from primesim_tpu.sim.engine import run_loop
    from primesim_tpu.sim.state import init_state

    # what THIS checkout compiles: a persistent cache keyed without
    # metadata would hand back another checkout's text
    jax.config.update("jax_enable_compilation_cache", False)
    cfg, chunk_steps, _ = load_config(config_path)
    st = jax.eval_shape(lambda: init_state(cfg))
    ev = jax.ShapeDtypeStruct((cfg.n_cores, trace_len, 4), jnp.int32)
    text = run_loop.lower(
        cfg, chunk_steps, ev, st,
        jax.ShapeDtypeStruct((), jnp.int32), has_sync=has_sync).compile().as_text()
    with open(out_path, "w") as f:
        f.write(text)
    print(f"{out_path}: {len(text)} bytes, has_sync {has_sync}, "
          f"{jax.devices()[0].device_kind}")


def compare(a_path: str, b_path: str) -> int:
    with open(a_path) as fa, open(b_path) as fb:
        a, b = strip(fa.read()), strip(fb.read())
    if a == b:
        print(f"byte-identical without metadata ({len(a)} bytes)")
        return 0
    la, lb = a.splitlines(), b.splitlines()
    renamed: collections.Counter = collections.Counter()
    same = len(la) == len(lb)
    for x, y in zip(la, lb):
        if not same or x == y:
            continue
        if _NAME.sub("%", x) != _NAME.sub("%", y):
            same = False
            print(f"differs beyond names:\n  {x[:200]}\n  {y[:200]}")
            break
        for p, q in zip(_NAME.findall(x), _NAME.findall(y)):
            if p != q:
                renamed[re.sub(r"\.\d+", "", p), re.sub(r"\.\d+", "", q)] += 1
    fusions = [set(re.findall(r"%([\w.\-]*fusion[\w.\-]*) = ", t)) for t in (a, b)]
    print(f"lines {len(la)} / {len(lb)}; identical up to instruction names: {same}; "
          f"fusion names identical: {fusions[0] == fusions[1]} ({len(fusions[0])}); "
          f"renamed uses by stem: {dict(renamed)}")
    return 0 if same and fusions[0] == fusions[1] else 1


def main(argv: list) -> int:
    if argv[:1] == ["dump"] and (
            len(argv) == 3 or (len(argv) == 4 and argv[3] == "--sync")):
        dump(argv[1], argv[2], has_sync=len(argv) == 4)
        return 0
    if len(argv) == 3 and argv[0] == "compare":
        return compare(argv[1], argv[2])
    print(__doc__, file=sys.stderr)
    return 2


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
