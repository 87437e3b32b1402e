"""The shipped lint rules (DESIGN.md §19 invariant catalog).

Each rule checks one load-bearing, mechanically-checkable contract the
repo has converged on over PRs 1-11:

  PT-TRACED-BRANCH  traced TimingKnobs/FaultState values never reach
                    Python control flow or host casts inside the
                    simulator (they are jax-traced; branching on them
                    either crashes under jit or silently bakes one
                    knob value into the compiled program)
  PT-JIT-KEY        every jax.jit site is review-gated (the jit key
                    must stay the timing-normalized geometry), and no
                    knob-derived name appears in static_argnames
  PT-DURABLE        no raw write-mode open() and no shared
                    deterministic "<path>.tmp" names on durability
                    paths — atomic_save_npz / journal append or bust
                    (the PR 10 hedged-twin bug class)
  PT-TYPED-ERR      no bare ValueError/RuntimeError on CLI-reachable
                    paths: errors users can hit must be typed with a
                    .location() so `main()` can structure them
  PT-OBS-HOOK       any function calling a self.obs.* hook keeps a
                    `self.obs is None` comparison in (an enclosing)
                    function — the obs-off path must stay fused and
                    bit-exact

Rules yield (lineno, col, message); framework mechanics (suppression,
baseline, scoping) live in lint.py.
"""

from __future__ import annotations

import ast

from .lint import rule

# Traced-pytree field names. Mirrored literally (rather than imported
# from sim.state / faults.schedule) so linting never needs jax in the
# process; test_analysis.py asserts the mirror stays in sync.
KNOB_FIELDS = frozenset({
    "quantum", "cpi", "l1_lat", "llc_lat", "link_lat", "router_lat",
    "dram_lat", "dram_service", "contention_lat", "prefetch_degree",
    "prefetch_lat",
})
FAULT_FIELDS = frozenset({
    "seed", "core_dead", "link_dead", "link_extra", "ev_step",
    "ev_kind", "ev_a", "ev_b", "flip_l1", "flip_llc", "due_rate",
})
TRACED_FIELDS = KNOB_FIELDS | FAULT_FIELDS

# Static zoo selectors (DESIGN.md §25): string-valued config fields that
# pick a compiled variant and ride the jit/exec-cache key via
# timing_normalized. The inverse contract of TRACED_FIELDS — these must
# branch in PYTHON (`if cfg.coherence == ...`), never inside traced
# select ops, or both variants compile into one program and the static
# key stops meaning anything.
SELECTOR_FIELDS = frozenset({
    "topology", "coherence", "prefetcher", "contention_model",
})
_TRACED_SELECTS = {"where", "select", "select_n", "cond", "switch"}

_HOST_CASTS = {"bool", "float", "int"}


def _traced_attrs(node: ast.AST):
    """Attribute accesses that look like traced knob/fault fields:
    the attr is a TimingKnobs/FaultState field AND the base expression
    mentions knobs or faults (so `cfg.seed`-ish lookalikes on foreign
    objects don't fire)."""
    for n in ast.walk(node):
        if isinstance(n, ast.Attribute) and n.attr in TRACED_FIELDS:
            base = ast.unparse(n.value).lower()
            if "knob" in base or "fault" in base:
                yield n


@rule(
    "PT-TRACED-BRANCH",
    "no Python control flow / host casts on traced knob or fault fields",
    scope=("/sim/", "/faults/"),
)
def check_traced_branch(tree, ctx):
    hits: dict = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.If, ast.While)):
            for a in _traced_attrs(node.test):
                hits[(a.lineno, a.col_offset)] = (
                    f"Python `{type(node).__name__.lower()}` on traced "
                    f"field `.{a.attr}` — traced TimingKnobs/FaultState "
                    "values must stay in jax ops (lax.cond/jnp.where), "
                    "never host control flow"
                )
        elif isinstance(node, ast.Call) and isinstance(node.func, ast.Name):
            if node.func.id in _HOST_CASTS:
                for arg in node.args:
                    for a in _traced_attrs(arg):
                        hits[(a.lineno, a.col_offset)] = (
                            f"host cast `{node.func.id}()` on traced "
                            f"field `.{a.attr}` — forces a device sync "
                            "and bakes the knob into host state"
                        )
        elif (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr in _TRACED_SELECTS
            and ast.unparse(node.func.value)
            in ("jnp", "np", "jax.numpy", "lax", "jax.lax")
        ):
            for arg in node.args:
                for a in ast.walk(arg):
                    if (
                        isinstance(a, ast.Attribute)
                        and a.attr in SELECTOR_FIELDS
                    ):
                        hits[(a.lineno, a.col_offset)] = (
                            f"static selector `.{a.attr}` inside traced "
                            f"`{node.func.attr}` — zoo selectors are jit-"
                            "key statics; branch in Python so only the "
                            "selected variant compiles"
                        )
    for (lineno, col), msg in sorted(hits.items()):
        yield lineno, col, msg


def _is_jax_jit(node: ast.AST) -> bool:
    """`jax.jit`, or `mesh_jit` (parallel/sharding.py: `jax.jit` with the
    mesh as one more static argument): a use of either is a jit site."""
    return (
        isinstance(node, ast.Attribute)
        and node.attr == "jit"
        and isinstance(node.value, ast.Name)
        and node.value.id == "jax"
    ) or (isinstance(node, ast.Name) and node.id == "mesh_jit")


@rule(
    "PT-JIT-KEY",
    "jit sites are review-gated; no knob-derived static_argnames",
)
def check_jit_key(tree, ctx):
    for node in ast.walk(tree):
        if _is_jax_jit(node):
            yield (
                node.lineno, node.col_offset,
                "jax.jit site — the jit key must stay the timing-"
                "normalized geometry (knobs ride traced state, never "
                "static args); baseline this site once reviewed",
            )
        elif isinstance(node, ast.ImportFrom) and node.module == "jax":
            for alias in node.names:
                if alias.name == "jit":
                    yield (
                        node.lineno, node.col_offset,
                        "`from jax import jit` hides jit sites from "
                        "review — use `jax.jit` so sites stay greppable",
                    )
        elif isinstance(node, ast.Call) and any(
            _is_jax_jit(n) for n in ast.walk(node.func)
        ) or (
            isinstance(node, ast.Call)
            and any(_is_jax_jit(n) for a in node.args for n in ast.walk(a))
        ):
            for kw in node.keywords:
                if kw.arg != "static_argnames":
                    continue
                for c in ast.walk(kw.value):
                    if isinstance(c, ast.Constant) and isinstance(
                        c.value, str
                    ):
                        s = c.value.lower()
                        if s in TRACED_FIELDS or "knob" in s or (
                            "fault" in s
                        ):
                            yield (
                                c.lineno, c.col_offset,
                                f"knob-derived name '{c.value}' in "
                                "static_argnames — a traced timing/"
                                "fault value in the jit key recompiles "
                                "per knob variant",
                            )


def _open_write_mode(call: ast.Call) -> str | None:
    """The mode string if this is a write-mode builtin open(), else
    None."""
    if not (isinstance(call.func, ast.Name) and call.func.id == "open"):
        return None
    mode = None
    if len(call.args) >= 2 and isinstance(call.args[1], ast.Constant):
        mode = call.args[1].value
    for kw in call.keywords:
        if kw.arg == "mode" and isinstance(kw.value, ast.Constant):
            mode = kw.value.value
    if isinstance(mode, str) and any(ch in mode for ch in "wax"):
        return mode
    return None


@rule(
    "PT-DURABLE",
    "durable writes are atomic with writer-unique temp names",
    scope=("/serve/", "/pool/", "checkpoint.py", "exec_cache.py"),
)
def check_durable(tree, ctx):
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            mode = _open_write_mode(node)
            if mode is not None:
                yield (
                    node.lineno, node.col_offset,
                    f"raw write-mode open(..., '{mode}') on a "
                    "durability-scoped path — route durable bytes "
                    "through atomic_save_npz / JobJournal.append "
                    "(mkstemp + fsync + os.replace)",
                )
        elif (
            isinstance(node, ast.BinOp)
            and isinstance(node.op, ast.Add)
            and isinstance(node.right, ast.Constant)
            and isinstance(node.right.value, str)
            and node.right.value.endswith(".tmp")
        ):
            yield (
                node.lineno, node.col_offset,
                "deterministic '<path>.tmp' temp name — two writers "
                "racing the same name can rename each other's work "
                "away (the PR 10 bug); use tempfile.mkstemp",
            )
        elif isinstance(node, ast.JoinedStr):
            parts = node.values
            if parts and isinstance(parts[-1], ast.Constant) and (
                isinstance(parts[-1].value, str)
                and parts[-1].value.endswith(".tmp")
            ):
                yield (
                    node.lineno, node.col_offset,
                    "deterministic f'...tmp' temp name — two writers "
                    "racing the same name can rename each other's "
                    "work away (the PR 10 bug); use tempfile.mkstemp",
                )


@rule(
    "PT-CHAOS-SITE",
    "durable writes and socket sends stay behind chaos fault sites",
    scope=("/serve/", "/pool/", "checkpoint.py", "exec_cache.py"),
)
def check_chaos_site(tree, ctx):
    """A function that fsyncs or sendalls on the serve/pool paths must
    also call a registered chaos hook (`chaos.durable`, `chaos.
    socket_send`, `chaos.crashpoint`, ...) so the fault-injection
    coverage of DESIGN.md §20 cannot silently rot as I/O paths are
    added. Maintenance-only paths (tail repair, dir fsync) baseline
    with a `why`."""
    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        risky = []   # (lineno, col, what)
        covered = False
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if not isinstance(f, ast.Attribute):
                continue
            if (
                f.attr == "fsync"
                and isinstance(f.value, ast.Name)
                and f.value.id == "os"
            ):
                risky.append((node.lineno, node.col_offset, "os.fsync"))
            elif f.attr == "sendall":
                risky.append((node.lineno, node.col_offset, "sendall"))
            elif (
                isinstance(f.value, ast.Name) and f.value.id == "chaos"
            ):
                covered = True
        if covered:
            continue
        for lineno, col, what in risky:
            yield (
                lineno, col,
                f"{what} in {fn.name}() without a chaos fault site — "
                "thread chaos.durable/chaos.socket_send/chaos."
                "crashpoint through this path (chaos/sites.py) or "
                "baseline it with a why",
            )


@rule(
    "PT-TYPED-ERR",
    "no bare ValueError/RuntimeError on CLI-reachable paths",
    scope=("/cli/", "/serve/", "/pool/"),
)
def check_typed_err(tree, ctx):
    for node in ast.walk(tree):
        if not isinstance(node, ast.Raise) or node.exc is None:
            continue
        exc = node.exc
        name = None
        if isinstance(exc, ast.Call) and isinstance(exc.func, ast.Name):
            name = exc.func.id
        elif isinstance(exc, ast.Name):
            name = exc.id
        if name in ("ValueError", "RuntimeError"):
            yield (
                node.lineno, node.col_offset,
                f"bare {name} on a CLI-reachable path — raise a typed "
                "error carrying .location() (TraceError grammar) so "
                "main() can emit the structured exit-2 JSON, or "
                "baseline with the boundary that converts it",
            )


@rule(
    "PT-OBS-HOOK",
    "obs hook callers keep the dead `self.obs is None` branch",
    scope=("/sim/", "/ingest/"),
)
def check_obs_hook(tree, ctx):
    funcs = []  # (lineno, end_lineno, has_guard)
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            guard = any(
                isinstance(n, ast.Compare)
                and ast.unparse(n.left) == "self.obs"
                and any(isinstance(op, (ast.Is, ast.IsNot)) for op in n.ops)
                for n in ast.walk(node)
            )
            funcs.append((node.lineno, node.end_lineno, guard))
    for node in ast.walk(tree):
        if (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and isinstance(node.func.value, ast.Attribute)
            and node.func.value.attr == "obs"
            and isinstance(node.func.value.value, ast.Name)
            and node.func.value.value.id == "self"
        ):
            covered = any(
                lo <= node.lineno <= hi and guard
                for lo, hi, guard in funcs
            )
            if not covered:
                yield (
                    node.lineno, node.col_offset,
                    f"self.obs.{node.func.attr}() without a `self.obs "
                    "is None` branch in an enclosing function — the "
                    "obs-off path must stay fused/bit-exact (DESIGN.md "
                    "§14 dead-branch contract)",
                )
