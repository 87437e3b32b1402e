"""CLI + shipped configs + report writer (SURVEY.md §2 #12/#14/#15)."""

import glob
import json
import os

import pytest

from primesim_tpu.cli import main
from primesim_tpu.config.machine import MachineConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = sorted(glob.glob(os.path.join(REPO, "configs", "*.json")))


def test_ladder_configs_ship_and_validate():
    ladder = [p for p in CONFIGS if os.path.basename(p).startswith("rung")]
    # the five BASELINE rungs, and rung 5 with its DRAM controllers' queues on
    variant = os.path.join(REPO, "configs", "rung5_16384core_wafer_dramq.json")
    assert len(ladder) == 6 and variant in ladder, ladder
    names = [os.path.basename(p) for p in ladder if p != variant]
    for n, cores in zip(
        sorted(names), [64, 256, 1024, 4096, 16384]
    ):
        assert str(cores) in n, (n, cores)
    for p in ladder:
        with open(p) as f:
            cfg = MachineConfig.from_json(f.read())  # __post_init__ validates
        assert cfg.n_cores in (64, 256, 1024, 4096, 16384)
        # round trip through to_json preserves the machine
        assert MachineConfig.from_json(cfg.to_json()) == cfg


def test_zoo_and_calib_configs_ship_and_validate():
    zoo = [p for p in CONFIGS if os.path.basename(p).startswith("zoo_")]
    assert len(zoo) == 2, zoo
    for p in zoo:
        with open(p) as f:
            cfg = MachineConfig.from_json(f.read())
        assert cfg.noc.topology in ("mesh", "torus", "ring")
        assert MachineConfig.from_json(cfg.to_json()) == cfg
    from primesim_tpu.calib.table import parse_table

    with open(os.path.join(REPO, "configs", "calib_ipu_microbench.json")) as f:
        table = parse_table(f.read())
    assert table.entries and all(e.metric for e in table.entries)


def test_biglittle_pattern_tiles():
    with open(os.path.join(REPO, "configs", "rung4_4096core_biglittle.json")) as f:
        cfg = MachineConfig.from_json(f.read())
    v = cfg.core.cpi_vector(cfg.n_cores)
    assert len(v) == 4096 and v[0] == 1 and v[4] == 3 and v[8] == 1


def test_cli_run_golden_and_report(tmp_path, capsys):
    cfg = os.path.join(REPO, "configs", "rung1_64core_fft.json")
    rpt = str(tmp_path / "report.txt")
    rc = main(
        [
            "run", cfg,
            "--synth", "fft_like:n_phases=2,points_per_core=8",
            "--engine", "golden",
            "--report", rpt,
            "--per-core-limit", "2",
        ]
    )
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    summary = json.loads(out[-1])
    assert summary["unit"] == "MIPS" and summary["detail"]["n_cores"] == 64
    text = open(rpt).read()
    assert "AGGREGATE" in text and "PER-CORE" in text
    assert f"{summary['detail']['instructions']:,}" in text


def test_cli_synth_roundtrip_run_jax(tmp_path, capsys):
    cfg_path = str(tmp_path / "m.json")
    with open(cfg_path, "w") as f:
        f.write(MachineConfig(n_cores=8, n_banks=8).to_json())
    tr_path = str(tmp_path / "t.ptpu")
    rc = main(
        ["synth", "lock_contention:n_critical=4", "--cores", "8",
         "--out", tr_path, "--fold"]
    )
    assert rc == 0 and os.path.exists(tr_path)
    rc = main(["run", cfg_path, "--trace", tr_path, "--engine", "jax",
               "--chunk-steps", "32"])
    assert rc == 0
    summary = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert summary["detail"]["engine"] == "jax"
    assert summary["detail"]["instructions"] > 0


def test_cli_engines_agree(tmp_path, capsys):
    cfg_path = str(tmp_path / "m.json")
    with open(cfg_path, "w") as f:
        f.write(MachineConfig(n_cores=8, n_banks=8).to_json())
    results = {}
    for eng in ("golden", "jax"):
        rc = main(
            ["run", cfg_path, "--synth", "false_sharing:n_mem_ops=40",
             "--engine", eng]
        )
        assert rc == 0
        out = capsys.readouterr().out.strip().splitlines()[-1]
        d = json.loads(out)["detail"]
        results[eng] = (d["instructions"], d["max_core_cycles"], d["noc_msgs"])
    assert results["golden"] == results["jax"]


def test_cli_rejects_bad_input(tmp_path):
    cfg_path = str(tmp_path / "m.json")
    with open(cfg_path, "w") as f:
        f.write(MachineConfig(n_cores=8, n_banks=8).to_json())
    with pytest.raises(SystemExit):
        main(["run", cfg_path])  # no trace source
    with pytest.raises(SystemExit):
        main(["run", cfg_path, "--synth", "nonsense_gen"])
    with pytest.raises(SystemExit):
        main(["run", cfg_path, "--synth", "fft_like:bogus"])  # bad k=v


def test_cli_xprof_writes_trace(tmp_path, capsys):
    cfg_path = str(tmp_path / "m.json")
    with open(cfg_path, "w") as f:
        f.write(MachineConfig(n_cores=4, n_banks=4).to_json())
    prof = str(tmp_path / "prof")
    rc = main(
        ["run", cfg_path, "--synth", "stream:n_mem_ops=10",
         "--chunk-steps", "16", "--xprof", prof]
    )
    assert rc == 0
    capsys.readouterr()
    found = [p for p in glob.glob(prof + "/**/*", recursive=True)
             if os.path.isfile(p)]
    assert found, "profiler trace directory is empty"


def test_cli_info(capsys):
    cfg = os.path.join(REPO, "configs", "rung3_1024core_o3.json")
    assert main(["info", cfg]) == 0
    d = json.loads(capsys.readouterr().out)
    assert d["n_cores"] == 1024 and d["core"]["o3_overlap_256"] == 128


def test_cli_devices_runs_sharded(tmp_path, capsys):
    # --devices N shards the machine over N (virtual CPU) devices and
    # still produces the exact single-device result (VERDICT r4 #8)
    cfg_path = str(tmp_path / "m.json")
    with open(cfg_path, "w") as f:
        f.write(MachineConfig(n_cores=16, n_banks=8).to_json())
    args = ["run", cfg_path, "--synth", "false_sharing:n_mem_ops=20",
            "--chunk-steps", "16"]
    assert main(args) == 0
    single = json.loads(capsys.readouterr().out)
    assert main(args + ["--devices", "8"]) == 0
    sharded = json.loads(capsys.readouterr().out)
    assert sharded["detail"]["instructions"] == single["detail"]["instructions"]
    assert (
        sharded["detail"]["max_core_cycles"]
        == single["detail"]["max_core_cycles"]
    )
    # golden engine has no device loop to shard
    with pytest.raises(SystemExit):
        main(args + ["--devices", "8", "--engine", "golden"])


def test_cli_capture_online(tmp_path, capsys):
    # one-command execution-driven mode: build the example binary, run it
    # under `primetpu capture`, simulating WHILE it executes
    import shutil
    import subprocess

    if shutil.which("gcc") is None:
        import pytest

        pytest.skip("no native toolchain")
    frontend = os.path.join(REPO, "primesim_tpu", "frontend")
    binary = str(tmp_path / "ocean_like")
    subprocess.run(
        ["gcc", "-O2", "-fno-builtin", "-o", binary,
         os.path.join(frontend, "examples", "ocean_like.c"), "-lpthread"],
        check=True, capture_output=True,
    )
    cfg_path = str(tmp_path / "m.json")
    with open(cfg_path, "w") as f:
        f.write(
            MachineConfig(
                n_cores=3, n_banks=4, quantum=10_000
            ).to_json()
        )
    rc = main(["capture", cfg_path, "--window", "256", "--",
               binary, "2", "1", "2"])
    assert rc == 0
    d = json.loads(capsys.readouterr().out)
    assert d["detail"]["engine"] == "online"
    assert d["detail"]["instructions"] > 0
    assert d["detail"]["events"] > 0
