"""A checkout in a temporary directory that gains two cells, two
configurations (one of them on four devices), two traffic mixes, a trace
shape and a per-layer metric purely as new files and new entries: the
by-data requirement, as the tests exercise it."""

import json
import os
import shutil

from conftest import BENCH, ROOT

CELL = "tiny16.fft-tiny"
CELL_X4 = "tiny16-x4.stride-tiny"

STRIDE = '''"""A trace shape added as a file: every core walks its own lines at a
stride, with a store every fourth access."""
import numpy as np

from trafficgen import EV_LD, EV_ST, LINE, finish


def generate(n_cores, seed, n_mem_ops, stride_lines, ins_per_mem):
    rng = np.random.default_rng(seed)
    k = np.arange(n_mem_ops, dtype=np.int64)
    base = (1 + np.arange(n_cores, dtype=np.int64)) * (1 << 16)
    addrs = base[:, None] + (k * stride_lines * LINE)[None, :] % (1 << 15)
    types = np.where(k % 4 == 3, EV_ST, EV_LD)[None, :].repeat(n_cores, 0)
    pre = rng.integers(1, 2 * ins_per_mem + 1, size=(n_cores, n_mem_ops))
    return finish(types, 8, addrs, pre)
'''


def make_root(dst: str) -> str:
    shutil.copytree(BENCH, os.path.join(dst, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    os.symlink(os.path.join(ROOT, "primesim_tpu"), os.path.join(dst, "primesim_tpu"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(BENCH, "configs", "rung3.json")) as f:
        config = json.load(f)
    config["name"] = "tiny16"
    config["machine"].update(
        n_cores=16, n_banks=16,
        l1={"size": 1024, "ways": 2, "line": 64, "latency": 2},
        llc={"size": 4096, "ways": 4, "line": 64, "latency": 12})
    config["machine"]["noc"].update(mesh_x=4, mesh_y=4)
    config_x4 = json.loads(json.dumps(config))
    config_x4["name"] = "tiny16-x4"
    config_x4["run"]["devices"] = 4
    traffic = {"name": "fft-tiny", "generator": "fft_like",
               "args": {"n_phases": 3, "points_per_core": 16, "ins_per_mem": 4},
               "parity_args": {"n_phases": 2, "points_per_core": 4},
               "panel_seeds": [11, 12], "fold": True}
    stride = {"name": "stride-tiny", "generator": "stride_walk",
              "args": {"n_mem_ops": 40, "stride_lines": 3, "ins_per_mem": 2},
              "parity_args": {"n_mem_ops": 12},
              "panel_seeds": [5, 6], "fold": True}
    files = {
        "benchmark/configs/tiny16.json": json.dumps(config),
        "benchmark/configs/tiny16-x4.json": json.dumps(config_x4),
        "benchmark/traffic/fft-tiny.json": json.dumps(traffic),
        "benchmark/traffic/stride-tiny.json": json.dumps(stride),
        "benchmark/generators/stride_walk.py": STRIDE,
        "benchmark/metrics/n_jobs.py":
            "def read(run, trace):\n    return len(run['jobs'])\n",
    }
    for rel, text in files.items():
        with open(os.path.join(dst, rel), "w") as f:
            f.write(text)
    bench["configs"].append({"name": "tiny16", "source": "test", "reduced": [],
                             "file": "benchmark/configs/tiny16.json", "why": "test"})
    bench["configs"].append({"name": "tiny16-x4", "source": "test", "reduced": [],
                             "file": "benchmark/configs/tiny16-x4.json", "why": "test"})
    bench["workloads"].append({"name": CELL, "config": "tiny16", "traffic": "fft-tiny",
                               "chips": 1, "why": "test"})
    bench["workloads"].append({"name": CELL_X4, "config": "tiny16-x4",
                               "traffic": "stride-tiny", "chips": 4, "why": "test"})
    bench["per_layer"].append({"name": "n_jobs", "unit": "jobs", "better": "higher",
                               "source": "program_counter", "layer": "host driver",
                               "moves": "sim_mips", "workloads": [CELL, CELL_X4]})
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return dst
