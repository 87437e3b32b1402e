"""Analysis subsystem (ISSUE 12): lint rules on good/bad fixtures,
suppression + baseline semantics, fsck over clean/torn/tampered durable
state, the recompile sentinel, and the CLI's structured exit-2 contract
for AnalysisError/FsckCorrupt."""

import json
import os

import numpy as np
import pytest

from primesim_tpu.analysis.errors import (
    AnalysisError,
    FsckCorrupt,
    RecompileError,
)
from primesim_tpu.analysis.fsck import run_fsck
from primesim_tpu.analysis.lint import run_lint
from primesim_tpu.analysis.recompile import recompile_sentinel
from primesim_tpu.serve.journal import JobJournal, _frame

# ---- lint fixtures ------------------------------------------------------


def _lint(tmp_path, relpath, src, select=None):
    p = tmp_path / relpath
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(src)
    return run_lint(
        paths=[str(tmp_path)], root=str(tmp_path),
        baseline_path=str(tmp_path / "absent_baseline.json"),
        select=select,
    )


def _rules_of(res):
    return sorted({f.rule for f in res.findings})


def test_traced_branch_bad_and_good(tmp_path):
    bad = (
        "def f(st):\n"
        "    if st.knobs.cpi > 1:\n"
        "        return 1\n"
        "    while st.faults.due_rate:\n"
        "        pass\n"
        "    return float(st.knobs.dram_lat)\n"
    )
    res = _lint(tmp_path, "primesim_tpu/sim/x.py", bad,
                select=["PT-TRACED-BRANCH"])
    assert len(res.findings) == 3
    assert _rules_of(res) == ["PT-TRACED-BRANCH"]
    good = (
        "import jax.numpy as jnp\n"
        "def f(st, cfg):\n"
        "    y = jnp.where(st.knobs.cpi > 1, 1, 0)\n"
        "    if cfg.fault_seed:\n"  # config field, not a traced leaf
        "        y = y + 1\n"
        "    return y\n"
    )
    res = _lint(tmp_path, "primesim_tpu/sim/x.py", good,
                select=["PT-TRACED-BRANCH"])
    assert res.clean


def test_traced_branch_out_of_scope_silent(tmp_path):
    # same code in stats/ (host-side folding) is not in the rule's scope
    bad = "def f(st):\n    return bool(st.knobs.cpi)\n"
    res = _lint(tmp_path, "primesim_tpu/stats/x.py", bad,
                select=["PT-TRACED-BRANCH"])
    assert res.clean


def test_jit_key_bad_and_good(tmp_path):
    bad = (
        "import functools\n"
        "import jax\n"
        "@functools.partial(jax.jit, static_argnames=('quantum',))\n"
        "def f(quantum):\n"
        "    return quantum\n"
        "from jax import jit\n"
    )
    res = _lint(tmp_path, "primesim_tpu/sim/y.py", bad,
                select=["PT-JIT-KEY"])
    msgs = "\n".join(f.message for f in res.findings)
    assert "jax.jit site" in msgs
    assert "static_argnames" in msgs  # the knob-derived static name
    assert "from jax import jit" in msgs or "hides jit sites" in msgs
    assert len(res.findings) == 3
    good = "import jax.numpy as jnp\ndef f(x):\n    return jnp.sum(x)\n"
    res = _lint(tmp_path, "primesim_tpu/sim/y.py", good,
                select=["PT-JIT-KEY"])
    assert res.clean


def test_durable_shared_tmp_regression_pr10(tmp_path):
    # the exact PR 10 bug shape: deterministic shared temp name + raw
    # write-mode open on a checkpoint path
    bad = (
        "import os, json\n"
        "def save_meta(meta_path, meta):\n"
        "    tmp = meta_path + '.tmp'\n"
        "    with open(tmp, 'w') as f:\n"
        "        json.dump(meta, f)\n"
        "    os.replace(tmp, meta_path)\n"
        "def save_meta2(meta_path, meta):\n"
        "    tmp = f'{meta_path}.tmp'\n"
        "    return tmp\n"
    )
    res = _lint(tmp_path, "primesim_tpu/serve/w.py", bad,
                select=["PT-DURABLE"])
    assert len(res.findings) == 3  # BinOp .tmp, open 'w', f-string .tmp
    good = (
        "import os, json, tempfile\n"
        "def save_meta(root, meta_path, meta):\n"
        "    fd, tmp = tempfile.mkstemp(dir=root, suffix='.tmp')\n"
        "    with os.fdopen(fd, 'w') as f:\n"
        "        json.dump(meta, f)\n"
        "    os.replace(tmp, meta_path)\n"
    )
    res = _lint(tmp_path, "primesim_tpu/serve/w.py", good,
                select=["PT-DURABLE"])
    assert res.clean


def test_typed_err_bad_and_good(tmp_path):
    bad = "def f():\n    raise ValueError('nope')\n"
    res = _lint(tmp_path, "primesim_tpu/cli/z.py", bad,
                select=["PT-TYPED-ERR"])
    assert len(res.findings) == 1
    good = (
        "class SpecError(ValueError):\n"
        "    def location(self):\n"
        "        return {}\n"
        "def f():\n"
        "    raise SpecError('typed')\n"
    )
    res = _lint(tmp_path, "primesim_tpu/cli/z.py", good,
                select=["PT-TYPED-ERR"])
    assert res.clean


def test_obs_hook_bad_and_good(tmp_path):
    bad = (
        "class E:\n"
        "    def step(self):\n"
        "        self.obs.chunk_committed(1)\n"
    )
    res = _lint(tmp_path, "primesim_tpu/sim/o.py", bad,
                select=["PT-OBS-HOOK"])
    assert len(res.findings) == 1
    good = (
        "class E:\n"
        "    def step(self):\n"
        "        if self.obs is None:\n"
        "            return\n"
        "        self.obs.chunk_committed(1)\n"
    )
    res = _lint(tmp_path, "primesim_tpu/sim/o.py", good,
                select=["PT-OBS-HOOK"])
    assert res.clean


def test_suppression_comment(tmp_path):
    src = (
        "def f(st):\n"
        "    return bool(st.knobs.cpi)  # ptlint: allow=PT-TRACED-BRANCH\n"
        "def g(st):\n"
        "    # ptlint: allow=*\n"
        "    return bool(st.knobs.cpi)\n"
    )
    res = _lint(tmp_path, "primesim_tpu/sim/s.py", src,
                select=["PT-TRACED-BRANCH"])
    assert res.clean and res.suppressed == 2


def test_baseline_count_and_stale(tmp_path):
    src = (
        "def f():\n"
        "    raise ValueError('nope')\n"
        "def g():\n"
        "    raise ValueError('nope')\n"
    )
    p = tmp_path / "primesim_tpu/cli/z.py"
    p.parent.mkdir(parents=True)
    p.write_text(src)
    bl = tmp_path / "LINT_BASELINE.json"

    def run(entries):
        bl.write_text(json.dumps({"entries": entries}))
        return run_lint(paths=[str(tmp_path)], root=str(tmp_path),
                        baseline_path=str(bl), select=["PT-TYPED-ERR"])

    entry = {"rule": "PT-TYPED-ERR", "path": "primesim_tpu/cli/z.py",
             "line_text": "raise ValueError('nope')", "why": "test"}
    # count=1 absorbs one of the two identical findings
    res = run([dict(entry, count=1)])
    assert len(res.findings) == 1 and res.baselined == 1
    # count=2 absorbs both
    res = run([dict(entry, count=2)])
    assert res.clean and res.baselined == 2
    # an entry matching nothing is reported stale (debt already paid)
    res = run([dict(entry, count=2),
               dict(entry, line_text="raise ValueError('gone')",
                    count=1)])
    assert res.clean and len(res.stale) == 1


def test_baseline_malformed_raises(tmp_path):
    bl = tmp_path / "LINT_BASELINE.json"
    bl.write_text("{not json")
    with pytest.raises(AnalysisError):
        run_lint(paths=[str(tmp_path)], root=str(tmp_path),
                 baseline_path=str(bl))
    bl.write_text(json.dumps({"entries": [{"rule": "PT-X"}]}))
    with pytest.raises(AnalysisError):
        run_lint(paths=[str(tmp_path)], root=str(tmp_path),
                 baseline_path=str(bl))


def test_unknown_rule_select_raises(tmp_path):
    with pytest.raises(AnalysisError):
        run_lint(paths=[str(tmp_path)], root=str(tmp_path),
                 select=["PT-NOPE"])


def test_traced_field_mirror_in_sync():
    # rules.py mirrors the pytree field names so linting never imports
    # jax; this test is the tripwire that keeps the mirror honest
    from primesim_tpu.analysis import rules
    from primesim_tpu.faults.schedule import FaultState
    from primesim_tpu.sim.state import TimingKnobs

    assert rules.KNOB_FIELDS == frozenset(TimingKnobs._fields)
    assert rules.FAULT_FIELDS == frozenset(FaultState._fields)


def test_repo_lints_clean():
    # the S1 acceptance bar: the shipped tree + committed baseline has
    # zero findings (new debt must be fixed or explicitly baselined)
    res = run_lint()
    assert res.clean, "\n".join(
        f"{f.path}:{f.line}: {f.rule} {f.message}" for f in res.findings
    )
    assert not res.stale, res.stale


# ---- fsck: journals -----------------------------------------------------


def _serve_journal(d, n_jobs=4, segment_records=3):
    j = JobJournal(str(d), segment_records=segment_records)
    for i in range(n_jobs):
        j.append({"t": "accept",
                  "job": {"job_id": f"j{i}", "synth": "stream:n_mem_ops=5"}})
        j.append({"t": "state", "job_id": f"j{i}", "state": "RUNNING"})
        j.append({"t": "state", "job_id": f"j{i}", "state": "DONE",
                  "result": {"x": i}})
    j.close()
    return d


def test_fsck_clean_journal(tmp_path):
    _serve_journal(tmp_path / "sj")
    res = run_fsck(str(tmp_path))
    assert res.clean and not res.findings
    assert res.checked["journals"] == 1 and res.checked["records"] == 12


def test_fsck_torn_tail_is_a_note_not_corruption(tmp_path):
    _serve_journal(tmp_path / "sj")
    with open(tmp_path / "sj" / "journal.jsonl", "a") as f:
        f.write('{"c": 1, "r": {"t":"state","job_id"')  # torn append
    res = run_fsck(str(tmp_path))
    assert res.clean  # kill -9 debris: replay drops it, fsck exits 0
    assert len(res.findings) == 1 and "torn tail" in res.findings[0].detail


def test_fsck_closed_segment_rot(tmp_path):
    _serve_journal(tmp_path / "sj")
    segs = sorted(p for p in os.listdir(tmp_path / "sj")
                  if p.startswith("journal-"))
    sp = tmp_path / "sj" / segs[0]
    b = sp.read_bytes()
    sp.write_bytes(b[:40] + bytes([b[40] ^ 0xFF]) + b[41:])
    res = run_fsck(str(tmp_path))
    assert any(f.kind == "journal-record" and f.corrupt
               for f in res.findings)


def test_fsck_tampered_segment_chain(tmp_path):
    _serve_journal(tmp_path / "sj", n_jobs=5, segment_records=2)
    segs = sorted(p for p in os.listdir(tmp_path / "sj")
                  if p.startswith("journal-"))
    sp = tmp_path / "sj" / segs[1]
    # rewrite a middle segment with VALID frames but different content:
    # per-line CRCs pass, so only the next segment's prev back-link can
    # catch the transplant
    header = json.loads(sp.read_text().splitlines()[0])["r"]
    sp.write_text(_frame(header) + "\n"
                  + _frame({"t": "note", "msg": "tampered"}) + "\n")
    res = run_fsck(str(tmp_path))
    assert any("back-link" in f.detail for f in res.corrupt)


def test_fsck_missing_middle_segment(tmp_path):
    _serve_journal(tmp_path / "sj", n_jobs=5, segment_records=2)
    segs = sorted(p for p in os.listdir(tmp_path / "sj")
                  if p.startswith("journal-"))
    os.remove(tmp_path / "sj" / segs[1])
    res = run_fsck(str(tmp_path))
    assert any("missing from the chain" in f.detail for f in res.corrupt)


def test_fsck_illegal_job_transition(tmp_path):
    j = JobJournal(str(tmp_path / "sj"), segment_records=None)
    j.append({"t": "accept", "job": {"job_id": "ja", "synth": "s"}})
    j.append({"t": "state", "job_id": "ja", "state": "DONE"})  # skip RUN
    # tolerated shapes must NOT fire: post-terminal echo + crash requeue
    j.append({"t": "state", "job_id": "ja", "state": "RUNNING"})
    j.append({"t": "accept", "job": {"job_id": "jb", "synth": "s"}})
    j.append({"t": "state", "job_id": "jb", "state": "RUNNING"})
    j.append({"t": "state", "job_id": "jb", "state": "PENDING"})
    j.append({"t": "state", "job_id": "jb", "state": "RUNNING"})
    j.close()
    res = run_fsck(str(tmp_path))
    bad = [f for f in res.corrupt if f.kind == "job-transition"]
    assert len(bad) == 1 and "PENDING -> DONE" in bad[0].detail


def test_fsck_state_without_accept(tmp_path):
    j = JobJournal(str(tmp_path / "sj"), segment_records=None)
    j.append({"t": "state", "job_id": "ghost", "state": "RUNNING"})
    j.close()
    res = run_fsck(str(tmp_path))
    assert any("no accept record" in f.detail for f in res.corrupt)


def test_fsck_pool_unit_key_consistency(tmp_path):
    from primesim_tpu.pool.units import unit_key

    spec = {"unit_id": "u1", "index": 0, "config": "{}", "synth": "s",
            "trace_path": None, "fold": True, "overrides": {},
            "chunk_steps": 16, "max_steps": 100}
    spec["key"] = unit_key(spec)
    # clean ledger passes
    p = JobJournal(str(tmp_path / "ok"), segment_records=None)
    p.append({"t": "unit", "unit": dict(spec)})
    p.append({"t": "lease", "unit_id": "u1", "worker": "w", "epoch": 1,
              "key": spec["key"]})
    p.append({"t": "ack", "unit_id": "u1", "worker": "w", "epoch": 1,
              "key": spec["key"], "result": {}})
    p.close()
    assert run_fsck(str(tmp_path / "ok")).clean
    # conflicting lease key fails
    p = JobJournal(str(tmp_path / "bad"), segment_records=None)
    p.append({"t": "unit", "unit": dict(spec)})
    p.append({"t": "lease", "unit_id": "u1", "worker": "w", "epoch": 1,
              "key": "deadbeefdeadbeef"})
    p.close()
    res = run_fsck(str(tmp_path / "bad"))
    assert any("conflicting unit keys" in f.detail for f in res.corrupt)
    # edited spec: content no longer hashes to its stamped key
    p = JobJournal(str(tmp_path / "edit"), segment_records=None)
    edited = dict(spec, max_steps=999_999)
    p.append({"t": "unit", "unit": edited})
    p.close()
    res = run_fsck(str(tmp_path / "edit"))
    assert any("stamped key" in f.detail for f in res.corrupt)


# ---- fsck: checkpoints + warm cache ------------------------------------


def _solo_npz(path, rows=None):
    from primesim_tpu.sim.checkpoint import _FORMAT, atomic_save_npz
    from primesim_tpu.stats.counters import N_BLOCK_ROWS

    atomic_save_npz(
        str(path),
        format=np.int64(_FORMAT),
        cycle_base=np.int64(0),
        steps_run=np.int64(0),
        config_json=np.frombuffer(b"{}", dtype=np.uint8),
        trace_sha=np.frombuffer(b"ab" * 32, dtype=np.uint8),
        state_counters=np.zeros(
            (rows if rows is not None else N_BLOCK_ROWS, 4),
            np.int32,
        ),
    )


def test_fsck_checkpoint_crc_tamper(tmp_path):
    _solo_npz(tmp_path / "ck.npz")
    assert run_fsck(str(tmp_path)).clean
    b = (tmp_path / "ck.npz").read_bytes()
    (tmp_path / "ck.npz").write_bytes(
        b[:len(b) // 2] + bytes([b[len(b) // 2] ^ 0xFF])
        + b[len(b) // 2 + 1:]
    )
    res = run_fsck(str(tmp_path))
    assert any(f.kind == "checkpoint" for f in res.corrupt)


def test_fsck_checkpoint_counter_rows(tmp_path):
    _solo_npz(tmp_path / "ck.npz", rows=3)
    res = run_fsck(str(tmp_path))
    assert any("counter rows" in f.detail for f in res.corrupt)


def test_fsck_warm_entry_and_sidecar(tmp_path):
    from primesim_tpu.sim.checkpoint import _FORMAT, atomic_save_npz
    from primesim_tpu.stats.counters import N_BLOCK_ROWS

    key = "ab" * 32
    atomic_save_npz(
        str(tmp_path / f"{key}.npz"),
        format=np.int64(_FORMAT), warm=np.int64(1),
        steps=np.int64(512), cycle_base=np.int64(0),
        steps_run=np.int64(512),
        trace_sha=np.frombuffer(b"cd" * 32, dtype=np.uint8),
        state_counters=np.zeros((N_BLOCK_ROWS, 4), np.int32),
        host_counters=np.zeros((N_BLOCK_ROWS, 4), np.int64),
    )
    meta = {"cfg_key": "ef" * 32, "key": key, "trace_sha": "cd" * 32,
            "steps": 512}
    (tmp_path / f"{key}.json").write_text(json.dumps(meta))
    assert run_fsck(str(tmp_path)).clean
    # sidecar claiming different steps = key/content disagreement
    (tmp_path / f"{key}.json").write_text(
        json.dumps(dict(meta, steps=1024))
    )
    res = run_fsck(str(tmp_path))
    assert any("steps" in f.detail for f in res.corrupt)
    # orphan sidecar (npz pruned) is a note, not corruption
    os.remove(tmp_path / f"{key}.npz")
    (tmp_path / f"{key}.json").write_text(json.dumps(meta))
    res = run_fsck(str(tmp_path))
    assert res.clean and any(f.kind == "orphan" for f in res.findings)


def test_fsck_quarantine_moves_never_deletes(tmp_path):
    (tmp_path / "ck.npz").write_bytes(b"garbage, not a zip")
    (tmp_path / "leftover.npz.k3j2.tmp").write_bytes(b"partial")
    res = run_fsck(str(tmp_path), repair="quarantine")
    assert sorted(res.quarantined) == [
        "ck.npz", "leftover.npz.k3j2.tmp"
    ]
    q = tmp_path / ".fsck-quarantine"
    assert (q / "ck.npz").read_bytes() == b"garbage, not a zip"
    assert (q / "leftover.npz.k3j2.tmp").exists()
    assert not (tmp_path / "ck.npz").exists()
    # quarantined files are not re-scanned
    assert run_fsck(str(tmp_path)).clean


# ---- recompile sentinel -------------------------------------------------


def test_recompile_sentinel_allows_one_compile():
    from primesim_tpu.config.machine import small_test_config
    from primesim_tpu.sim.engine import Engine
    from primesim_tpu.trace import synth

    cfg = small_test_config(4, n_banks=4)
    tr = synth.stream(4, n_mem_ops=10, seed=7)
    with recompile_sentinel(allowed=1, watch=("engine",),
                            label="fresh geometry") as s:
        Engine(cfg, tr, chunk_steps=8).run()
    assert s.active
    assert all(g <= 1 for g in s.growth().values())
    # warm re-run compiles nothing
    with recompile_sentinel(allowed=0, watch=("engine",)) as s:
        Engine(cfg, tr, chunk_steps=8).run()
    assert all(g == 0 for g in s.growth().values())


def test_recompile_sentinel_raises_on_breach():
    from primesim_tpu.config.machine import small_test_config
    from primesim_tpu.sim.engine import Engine
    from primesim_tpu.trace import synth

    cfg = small_test_config(4, n_banks=4)
    tr = synth.stream(4, n_mem_ops=10, seed=8)
    Engine(cfg, tr, chunk_steps=8).run()  # warm this geometry
    with pytest.raises(RecompileError) as ei:
        with recompile_sentinel(allowed=0, watch=("engine",),
                                label="guard"):
            # a NEW chunk size is a new static key -> forced compile
            Engine(cfg, tr, chunk_steps=16).run()
    assert any(g > 0 for g in ei.value.growth.values())
    assert "location" not in ei.value.location() or True
    assert ei.value.location()["growth"] == ei.value.growth


def test_recompile_sentinel_unknown_preset():
    with pytest.raises(RecompileError):
        with recompile_sentinel(watch=("gpu",)):
            pass


# ---- CLI contract (S6) --------------------------------------------------


def test_cli_lint_exit_codes(tmp_path, capsys):
    from primesim_tpu.cli import main

    bad = tmp_path / "primesim_tpu" / "cli" / "z.py"
    bad.parent.mkdir(parents=True)
    bad.write_text("def f():\n    raise ValueError('nope')\n")
    rc = main(["lint", str(tmp_path), "--root", str(tmp_path),
               "--select", "PT-TYPED-ERR", "--format", "json"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 1 and out["summary"]["findings"] == 1
    assert out["findings"][0]["rule"] == "PT-TYPED-ERR"
    bad.write_text("def f():\n    return 0\n")
    rc = main(["lint", str(tmp_path), "--root", str(tmp_path),
               "--select", "PT-TYPED-ERR"])
    assert rc == 0


def test_cli_lint_analysis_error_is_structured(tmp_path, capsys):
    from primesim_tpu.cli import main

    bl = tmp_path / "LINT_BASELINE.json"
    bl.write_text("{not json")
    rc = main(["lint", str(tmp_path), "--root", str(tmp_path),
               "--baseline", str(bl)])
    err = capsys.readouterr().err.strip().splitlines()[-1]
    obj = json.loads(err)
    assert rc == 2 and obj["error"]["type"] == "AnalysisError"
    assert obj["error"]["location"]["path"] == str(bl)


def test_cli_fsck_exit_2_structured_on_tamper(tmp_path, capsys):
    from primesim_tpu.cli import main

    _serve_journal(tmp_path / "sj")
    segs = sorted(p for p in os.listdir(tmp_path / "sj")
                  if p.startswith("journal-"))
    sp = tmp_path / "sj" / segs[0]
    b = sp.read_bytes()
    sp.write_bytes(b[:40] + bytes([b[40] ^ 0xFF]) + b[41:])
    rc = main(["fsck", str(tmp_path), "--format", "json"])
    cap = capsys.readouterr()
    assert rc == 2
    obj = json.loads(cap.err.strip().splitlines()[-1])
    assert obj["error"]["type"] == "FsckCorrupt"
    assert obj["error"]["location"]["n_corrupt"] >= 1
    # the json report still went to stdout before the error
    rep = json.loads(cap.out)
    assert rep["summary"]["corrupt"] >= 1


def test_cli_fsck_clean_exit_0(tmp_path, capsys):
    from primesim_tpu.cli import main

    _serve_journal(tmp_path / "sj")
    rc = main(["fsck", str(tmp_path)])
    assert rc == 0
    assert "0 corrupt" in capsys.readouterr().out
