"""Look a cell up by name: `BENCHMARK.json` names it, and its
configuration, traffic mix and per-layer metrics are files of their own
under `benchmark/`, found by those names. Adding a cell, a configuration,
a traffic mix, a trace shape or a metric is adding files and one entry;
nothing here knows any of them."""

from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class CellError(ValueError):
    """`BENCHMARK.json` or a file it names is missing or inconsistent."""


def _json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        raise CellError(f"{path}: {e}") from e


def load_cell(name: str, root: str = ROOT) -> dict:
    """Everything one run needs, as plain data: the cell's entry, its
    configuration and traffic files, and the metric entries it reports."""
    bench = _json(os.path.join(root, "BENCHMARK.json"))
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:
        raise CellError(
            f"workload {name!r} is not in BENCHMARK.json "
            f"(have {[w['name'] for w in bench['workloads']]})")
    entry = next((c for c in bench["configs"] if c["name"] == cell["config"]), None)
    if entry is None:
        raise CellError(f"config {cell['config']!r} is not in BENCHMARK.json")
    bdir = os.path.join(root, "benchmark")
    config = _json(os.path.join(root, entry["file"]))
    traffic = _json(os.path.join(bdir, "traffic", cell["traffic"] + ".json"))
    if config["run"]["devices"] != cell["chips"]:
        raise CellError(
            f"{name}: the cell asks for {cell['chips']} chip(s), its "
            f"configuration runs on {config['run']['devices']}")

    def reported(m: dict) -> bool:
        return name in m["workloads"] if "workloads" in m else True

    return {
        "name": name,
        "root": root,
        "cell": cell,
        "config": config,
        "traffic": traffic,
        "end_to_end": [m for m in bench["end_to_end"] if reported(m)],
        "per_layer": [m for m in bench["per_layer"] if reported(m)],
        "peaks": _json(os.path.join(bdir, "peaks.json")),
    }


def _load(kind: str, name: str, root: str, function: str):
    """`function` of `benchmark/<kind>/<name>.py`, found by name."""
    path = os.path.join(root, "benchmark", kind, name + ".py")
    if not os.path.isfile(path):
        raise CellError(f"{kind[:-1]} {name!r} has no file at {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    if not callable(getattr(mod, function, None)):
        raise CellError(f"{path} defines no {function}()")
    return getattr(mod, function)


def load_metric(name: str, root: str = ROOT):
    """The reader of one per-layer metric: `benchmark/metrics/<name>.py`,
    one function `read(run, trace)` that returns a number, or None where
    it finds nothing to read."""
    return _load("metrics", name, root, "read")


def load_generator(name: str, root: str = ROOT):
    """One trace shape: `benchmark/generators/<name>.py`, one function
    `generate(n_cores, seed, **args)` that returns folded events."""
    return _load("generators", name, root, "generate")


def peak_for(peaks: dict, device_kind: str) -> dict:
    """The published peaks of a device kind; an unknown kind is an error,
    never a default."""
    if device_kind not in peaks["devices"]:
        raise CellError(
            f"device kind {device_kind!r} is not in benchmark/peaks.json "
            f"(have {sorted(peaks['devices'])})")
    return peaks["devices"][device_kind]
