"""The named scopes of `step` and `run_loop` (sim/step.py::PHASES,
DESIGN.md §15) reach the compiled program: every phase a machine enables
is in some instruction's `op_name`, a phase it lacks is in none, and each
`rank` scope sits under its own phase. Read from the compiled text, which
is what a profiler trace and the benchmark's per-phase metrics read. And
every equation under a phase was written by one of that phase's
functions (sim/step.py::PHASE_FUNCTIONS), read from the jaxpr.
"""

import dataclasses
import functools
import glob
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest

from primesim_tpu.config.machine import MachineConfig
from primesim_tpu.sim.engine import Engine, run_loop
from primesim_tpu.sim.fleet import FleetEngine, fleet_run_loop
from primesim_tpu.sim.state import dirm_width
from primesim_tpu.sim.step import PHASE_FUNCTIONS, PHASES, step
from primesim_tpu.trace import synth

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N = 16
_MESH = {"mesh_x": 4, "mesh_y": 4}

# machine -> (config, has_sync, the optional phases it enables); every
# machine runs local, probe, arb, dir, commit and chunk
ALWAYS = {"s.local", "s.probe", "s.probe/stat", "s.arb", "s.dir", "s.commit",
          "s.chunk"}
MACHINES = {
    # no contention model, no DRAM queue, a trace without locks or barriers
    "plain": (dict(n_cores=N, n_banks=N, noc=_MESH, local_run_len=8), False,
              set()),
    # rung 3's selectors at 16 cores, and the sync phase with it
    "rung3": (
        dict(n_cores=N, n_banks=N, local_run_len=8, dram_queue=True,
             core={"cpi": 1, "o3_overlap_256": 128},
             noc=dict(_MESH, contention=True, contention_model="router",
                      contention_lat=1)),
        True, {"s.noc", "s.noc/rank", "s.noc/stat", "s.dram", "s.dram/rank",
               "s.sync", "s.sync/lock", "s.sync/barrier"},
    ),
    # the tile-count contention model (no ranking) on a faulty machine
    "faulty": (
        dict(n_cores=N, n_banks=N, local_run_len=8,
             noc=dict(_MESH, contention=True, contention_model="tile",
                      contention_lat=1)),
        False, {"s.fault", "s.noc"},
    ),
    # the coarse sharer vector (Dir-G): the only machine with group work
    "coarse": (dict(n_cores=N, n_banks=N, noc=_MESH, local_run_len=8,
                    sharer_group=4), False, {"s.dir/grp"}),
    # rung 4's selectors at 64 cores: a CPI a core and the full sharer map
    # (two words) reduced in blocks of one word
    "chunked": (dict(n_cores=64, n_banks=16, noc={"mesh_x": 8, "mesh_y": 8},
                     local_run_len=8, sharer_chunk_words=1,
                     core={"cpi": 1, "cpi_pattern": [1, 1, 3, 3],
                           "o3_overlap_256": 64}),
                False, {"s.dir/chunk"}),
}


def build(machine: str):
    """The machine's config and an `Engine` on the files' one trace."""
    cfg = MachineConfig.from_dict(MACHINES[machine][0])
    if machine == "faulty":
        cfg = dataclasses.replace(cfg, faults_enabled=True)
    return cfg, Engine(
        cfg, synth.fft_like(cfg.n_cores, n_phases=2, points_per_core=8, seed=3),
        chunk_steps=8)


@functools.lru_cache(maxsize=None)
def compiled_text(machine: str) -> str:
    """The machine's compiled `run_loop`, as text."""
    has_sync = MACHINES[machine][1]
    cfg, eng = build(machine)
    return run_loop.lower(
        cfg, 8, eng.events, eng.state, jnp.asarray(1, jnp.int32),
        has_sync=has_sync).compile().as_text()


def scope_paths(machine: str) -> frozenset:
    """Every `op_name` of the machine's compiled `run_loop`."""
    return _op_names(compiled_text(machine))


def _op_names(text: str) -> frozenset:
    return frozenset(re.findall(r'op_name="([^"]*)"', text))


FLEET = "rung3 x2"  # two of rung 3's small machines in one `fleet_run_loop`
_FLEET_OVERRIDES = [{}, {"link_lat": 2, "dram_service": 25}]


def build_fleet():
    """`build("rung3")`'s machine twice, the second with the NoC's and the
    DRAM controller's knobs turned, as `primetpu sweep` stacks them."""
    cfg, eng = build("rung3")
    return FleetEngine(cfg, [eng.trace] * 2, _FLEET_OVERRIDES, chunk_steps=8,
                       force_sync=MACHINES["rung3"][1])


@functools.lru_cache(maxsize=None)
def program_paths(program: str) -> frozenset:
    """Every `op_name` of a compiled loop: a machine's `run_loop`, or
    (`FLEET`) the `fleet_run_loop` of two small router machines."""
    if program != FLEET:
        return scope_paths(program)
    fleet = build_fleet()
    return _op_names(fleet_run_loop.lower(
        fleet.geom_cfg, 8, fleet.events, fleet.state, jnp.asarray(1, jnp.int32),
        has_sync=fleet.has_sync).compile().as_text())


def _has(paths, name: str) -> bool:
    return any(f"/{name}/" in p for p in paths)


def test_phases_are_short_and_shallow():
    assert len(set(PHASES)) == len(PHASES)
    for name in PHASES:
        parts = name.split("/")
        assert len(parts) <= 2 and all(len(p) <= 8 for p in parts), name
        assert parts[0].startswith("s."), name
    assert ALWAYS | set().union(*(m[2] for m in MACHINES.values())) == set(PHASES)


@pytest.mark.parametrize("name", PHASES)
@pytest.mark.parametrize("machine", sorted(MACHINES))
def test_scope_in_compiled_program_iff_enabled(machine, name):
    enabled = name in ALWAYS | MACHINES[machine][2]
    assert _has(scope_paths(machine), name) == enabled


@pytest.mark.parametrize("machine", sorted(MACHINES))
def test_every_op_under_a_phase_comes_from_that_phases_functions(machine):
    """Every equation of `run_loop` whose name stack holds a phase was
    written by one of the functions PHASE_FUNCTIONS gives that phase: it
    is on the equation's traceback (helpers such as `_pick` are inner
    frames, so the whole stack is looked up, not its top). A phase's work
    written into another phase's function fails here and not in a
    metric's reader. Read from the jaxpr: in the compiled text XLA has
    merged equal instructions of different phases and kept one's frames.
    Entered: the loops and branches, whose bodies are traced where they
    stand. Not entered: what JAX traces once and caches (a `jnp` function
    such as `jit(remainder)`, a scatter's or a reduction's combiner),
    since its inside keeps the frames of whichever phase called it first;
    the call itself is looked at."""
    _, has_sync, optional = MACHINES[machine]
    cfg, eng = build(machine)
    checked = set()

    def walk(jaxpr, prefix):
        for eqn in jaxpr.eqns:
            path = f"{prefix}/{eqn.source_info.name_stack}"
            found = re.search(r"/(s\.\w+)", path)
            if found:
                fns = [f.function_name for f in eqn.source_info.traceback.frames]
                assert any(f == own or f.startswith(own + ".") for f in fns
                           for own in PHASE_FUNCTIONS[found.group(1)]), (
                    path, eqn.primitive.name, fns)
                checked.add(found.group(1))
            if eqn.primitive.name in ("while", "scan", "cond") or (
                    eqn.primitive.name == "jit"
                    and eqn.params["name"] == "run_loop"):
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub, path)

    walk(jax.make_jaxpr(functools.partial(run_loop, cfg, 8, has_sync=has_sync))(
        eng.events, eng.state, jnp.asarray(1, jnp.int32)).jaxpr, "")
    assert checked == {p for p in ALWAYS | optional if "/" not in p}


def test_group_scope_holds_the_coarse_vectors_reductions_and_only_there():
    """`s.dir/grp` sits under `s.dir` and holds the group-table gathers and
    the masked reductions; a machine with `sharer_group` 1 compiles to a
    text without it (the parametrised test above, for every other machine)."""
    paths = scope_paths("coarse")
    grp = [p.split("/s.dir/grp/", 1)[1] for p in paths if "/s.dir/grp/" in p]
    assert any(p.startswith("reduce_max") for p in grp)
    assert any(p.startswith("reduce_sum") for p in grp)
    assert not any("/grp/" in p and "s.dir/grp/" not in p for p in paths)


def test_chunk_scope_holds_the_full_maps_blockwise_reductions_and_only_there():
    """`s.dir/chunk` sits under `s.dir` and holds the scan over blocks of
    sharer words with its masked reductions; a machine without
    `sharer_chunk_words` compiles to a text without it (the parametrised
    test above, for every other machine), and `s.chunk`, run_loop's own
    scope, is another thing."""
    paths = scope_paths("chunked")
    chunk = [p.split("/s.dir/chunk/", 1)[1] for p in paths if "/s.dir/chunk/" in p]
    assert any(p.startswith("while/body/") for p in chunk)  # the scan over the blocks
    assert any(p.endswith("reduce_max") for p in chunk)
    assert any(p.endswith("reduce_sum") for p in chunk)
    assert not any("/chunk/" in p and "s.dir/chunk/" not in p for p in paths)
    assert not any("/s.chunk/" in p and "/s.dir/" in p for p in paths)


@pytest.mark.parametrize("program", ["rung3", FLEET])
def test_rank_scopes_hold_the_ranking_under_their_own_phase(program):
    paths = program_paths(program)
    for phase in ("s.noc", "s.dram"):
        assert any(f"/{phase}/rank/sort" in p for p in paths)
    # nothing of the ranking outside a rank scope, no scope inside another
    assert not any(p.endswith("/sort") and "/rank/" not in p for p in paths)
    assert not any(len(re.findall(r"/s\.\w+", p)) > 1 for p in paths)


@pytest.mark.parametrize("program", ["rung3", FLEET])
def test_rung3_loop_ranks_without_a_search(program):
    """Each entry's rank is read from the sort's own output: no
    `searchsorted` anywhere, and no loop (a bisection is a `while` where
    it is not unrolled) under a rank scope; under a batch axis too."""
    paths = program_paths(program)
    ranked = [p.split("/rank/", 1)[1] for p in paths if "/rank/" in p]
    assert any(p.startswith("sort") for p in ranked)
    assert not [p for p in paths if "searchsorted" in p]
    assert not [p for p in ranked if "while" in p.split("/")]


def indexed_ops(machine: str) -> list:
    """Every `gather` and `scatter*` equation of the machine's `step`
    (`FLEET`: of the two machines' `fleet_run_loop`, where the batching
    rules have given each its batch axis), through every sub-jaxpr:
    (primitive, scope path, operand shape, number of indices, a gather's
    slice sizes)."""
    found = []

    def walk(jaxpr, prefix):
        for eqn in jaxpr.eqns:
            path = f"{prefix}/{eqn.source_info.name_stack}"
            name = eqn.primitive.name
            if name == "gather" or name.startswith("scatter"):
                operand, indices = (v.aval.shape for v in eqn.invars[:2])
                found.append((name, path, operand, math.prod(indices[:-1]),
                              eqn.params.get("slice_sizes")))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub, path)

    if machine == FLEET:
        fleet = build_fleet()
        traced = jax.make_jaxpr(lambda ev, st: fleet_run_loop(
            fleet.geom_cfg, 8, ev, st, jnp.asarray(1, jnp.int32),
            has_sync=fleet.has_sync))(fleet.events, fleet.state)
    else:
        has_sync = MACHINES[machine][1]
        cfg, eng = build(machine)
        traced = jax.make_jaxpr(
            lambda ev, st: step(cfg, ev, st, has_sync=has_sync))(eng.events, eng.state)
    walk(traced.jaxpr, "")
    return found


@pytest.mark.parametrize("machine", ["plain", "coarse"])
def test_step_picks_out_of_the_l1_row_without_a_gather(machine):
    """The core's own L1 row is read whole and the set selected
    (`_l1_set_read`), and the local run's way and word picks are selects
    (`_pick`): no `gather` of `step` has the L1 array as its operand, with
    four planes or, under the coarse vector, five; and `s.local` holds two
    gathers: of the two whole blocks of the core's trace that hold its
    run's candidates (`DeviceTrace.window`: 2 C rows of 128 words, no
    slice of one record), and of the home sets' directory rows, which the
    core does not hold. Every gather of the directory takes whole rows
    (`_validate_ways` reads the rows its way pointers name and selects:
    `_way_record`), and `s.probe` holds exactly two of them: the home
    rows, C of them, and the way rows, W1 * C."""
    cfg, eng = build(machine)
    shapes = {"l1": eng.state.l1.shape, "events": eng.events.blocks.shape,
              "dirm": eng.state.dirm.shape}
    assert len(set(shapes.values())) == 3
    indexed = [op for op in indexed_ops(machine) if op[0] == "gather"]
    gathers = [(path, shape) for _, path, shape, _, _ in indexed]
    assert any("s.probe" in p for p, _ in gathers)  # the walk sees scopes
    assert not [p for p, shape in gathers if shape == shapes["l1"]]
    assert sorted(shape for p, shape in gathers if "s.local" in p) == sorted(
        [shapes["events"], shapes["dirm"]])
    assert [(n, sizes) for _, _, shape, n, sizes in indexed
            if shape == shapes["events"]] == [(2 * cfg.n_cores, (1, 1, 128))]
    of_dirm = [(path, n, sizes) for _, path, shape, n, sizes in indexed
               if shape == shapes["dirm"]]
    assert shapes["dirm"][1] == dirm_width(cfg)
    assert {sizes for _, _, sizes in of_dirm} == {(1, dirm_width(cfg))}, of_dirm
    assert sorted(n for path, n, _ in of_dirm if "s.probe" in path) == [
        cfg.n_cores, cfg.l1.ways * cfg.n_cores]


# the local run's row read on the three kinds of directory row: the full
# sharer map (two words at 64 cores), rung 5's coarse vector (one bit to 64
# cores, an epoch a way) and moesi (the run also counts a line's sharers)
LOCAL_RUN_MACHINES = {
    "full_map": dict(n_cores=64, n_banks=16, local_run_len=8,
                     noc={"mesh_x": 8, "mesh_y": 8}),
    "coarse_64": dict(n_cores=128, n_banks=32, local_run_len=8,
                      sharer_group=64, noc={"mesh_x": 16, "mesh_y": 8}),
    "moesi": dict(n_cores=64, n_banks=16, local_run_len=8, coherence="moesi",
                  noc={"mesh_x": 8, "mesh_y": 8}),
}


@pytest.mark.parametrize("machine", sorted(LOCAL_RUN_MACHINES))
def test_local_run_holds_its_home_rows_candidates_first(machine):
    """`_local` reads its K = `local_run_len` + 1 candidate home rows a core
    as `[K, C]` slots (`sharding.read_rows`' one order), on one device too:
    the `dirm` gather of its jaxpr yields `(K, C, DW)`, which is the
    gathered `[K*C, DW]` bytes as they lie, and nothing in the phase has
    the shape `(C, K, DW)`, the copy that padded K = 9 to the tile's 16
    rows on the chip (a seventh of rung 4's step on one chip; PERF.md
    section 6, PR 56)."""
    from primesim_tpu.sim.step import _local

    cfg = MachineConfig.from_dict(LOCAL_RUN_MACHINES[machine])
    C, K, DW = cfg.n_cores, cfg.local_run_len + 1, dirm_width(cfg)
    assert K not in (C, DW) and C != DW
    eng = Engine(
        cfg, synth.fft_like(C, n_phases=1, points_per_core=4, seed=3),
        chunk_steps=8)
    traced = jax.make_jaxpr(lambda ev, st: _local(
        cfg, ev, st, jnp.arange(C, dtype=jnp.int32), None, {}))(
            eng.events, eng.state)
    shapes, gathered = set(), []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            outs = [v.aval.shape for v in eqn.outvars]
            shapes.update(outs)
            if (eqn.primitive.name == "gather"
                    and eqn.invars[0].aval.shape == eng.state.dirm.shape):
                gathered.extend(outs)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(traced.jaxpr)
    assert gathered == [(K, C, DW)]
    assert (C, K, DW) not in shapes
    assert (K, C) in shapes and (C, K) in shapes  # the record, turned for the lanes


@pytest.mark.parametrize("machine", ["plain", "coarse", "rung3"])
def test_step_edits_the_l1_row_without_a_scatter(machine):
    """The write-side twin: each core edits its own L1 row by a select over
    the row's planes (`_l1_row_write`), with four planes read or, under the
    coarse vector, five, with and without sync events: no `scatter*` of
    `step` has the L1 array as its operand. What phase 4.A still scatters
    is the directory, a true cross-row write: exactly one row `scatter-add`
    with C indices."""
    cfg, eng = build(machine)
    shapes = {"l1": eng.state.l1.shape, "dirm": eng.state.dirm.shape}
    assert shapes["l1"] != shapes["dirm"]
    scatters = [op for op in indexed_ops(machine) if op[0].startswith("scatter")]
    assert any("s.commit" in p for _, p, _, _, _ in scatters)  # the walk sees scopes
    assert not [(name, p) for name, p, shape, _, _ in scatters
                if shape == shapes["l1"]]
    assert [(name, n) for name, p, shape, n, _ in scatters
            if shape == shapes["dirm"]] == [("scatter-add", cfg.n_cores)]


@pytest.mark.parametrize("program, machines", [("rung3", 1), (FLEET, len(_FLEET_OVERRIDES))])
def test_rung3_walk_indexes_no_table_entry_by_entry(program, machines):
    """The router walk's per-link state rides the rank's sorted order
    (`segmented_rank_floor`, `segmented_table_max`): under `s.noc` no
    `gather` and no `scatter` of any kind has more indices than the
    machine has links. The element forms over all C * legs * H slots (a
    scatter-min for `base`, a gather pair, the departures' scatter-max)
    were three quarters of the rung-3 step on the chip (PERF.md section 6,
    PR 31); what stays reads or writes NL words or fewer. In a fleet's
    loop the batching rules give each such op its batch axis and no more:
    NL words or fewer a machine."""
    from primesim_tpu.noc.mesh import n_links

    cfg, _ = build("rung3")
    n_slots = cfg.n_cores * 2 * 6  # two legs of a 4x4 mesh's 6 hops
    assert n_links(cfg) < n_slots
    indexed = indexed_ops(program)
    assert any("s.noc" in p for _, p, _, _, _ in indexed)  # the walk sees scopes
    # and counts indices: the probe's way rows, W1 a core
    assert any(n == machines * cfg.l1.ways * cfg.n_cores for _, p, _, n, _ in indexed
               if "s.probe" in p)
    assert not [(name, p, n) for name, p, _, n, _ in indexed
                if "s.noc" in p and n > machines * n_links(cfg)]


def test_benchmark_needles_are_phase_names():
    """Every scope a per-phase metric reader spells is a name in PHASES."""
    files = sorted(
        glob.glob(os.path.join(ROOT, "benchmark", "metrics", "ph_*.py"))
        + [os.path.join(ROOT, "benchmark", "metrics", "rank_noc_ms_step.py"),
           os.path.join(ROOT, "benchmark", "metrics", "collective_dirm_ms_step.py"),
           os.path.join(ROOT, "benchmark", "phase_ops.py")])
    spelled = {}
    for path in files:
        with open(path) as f:
            for needle in re.findall(r'"/?(s\.[\w./]*?)/?"', f.read()):
                spelled.setdefault(needle, path)
    assert spelled, "no reader spells a phase"
    assert not {n: p for n, p in spelled.items() if n not in PHASES}
