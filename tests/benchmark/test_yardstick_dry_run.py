"""Every cell's whole run rehearsed on XLA:CPU at toy size — every phase,
every child, the reference — and never a result: no result line, exit 3.
A measuring run that finds no TPU fails; so does one with no program."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
MANIFEST = os.path.join(REPO, "BENCHMARK.json")
RUN = os.path.join(REPO, "benchmark", "run.py")

with open(MANIFEST) as _fh:
    CELLS = [w["name"] for w in json.load(_fh)["workloads"]]


def bench(args, tmp_path, timeout=600, cwd=REPO, run=RUN):
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", TMPDIR=str(tmp_path), PYTHONPATH="",
               BENCH_RUN="ignored", JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jc"))
    return subprocess.run([sys.executable, run, *args], capture_output=True,
                          text=True, env=env, cwd=cwd, timeout=timeout)


@pytest.mark.parametrize("cell", CELLS)
def test_dry_run_runs_every_phase_and_prints_no_result(cell, tmp_path):
    trace = "1" if cell.endswith("saturated") else "0"
    proc = bench(["--workload", cell, "--seed", str(2**31 + 11),
                  "--seconds", "3", "--trace", trace, "--dry-run-cpu"], tmp_path)
    assert proc.returncode == 3, proc.stdout[-3000:] + proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    assert lines[-1].startswith("dry run on cpu: every phase passed")
    assert not lines[-1].startswith("{")  # never a result line
    checks = [json.loads(ln[7:]) for ln in lines if ln.startswith("check: ")]
    assert checks and all(c["pass"] for c in checks if not c.get("informs"))
    assert all("limit" in c for c in checks)  # each number beside its limit
    would = json.loads(next(ln for ln in lines if ln.startswith("would print: "))[13:])
    assert would["device"]["platform"] == "cpu" and would["failed"] == 0
    assert would["attempted"] > 0 and would["metrics"]
    assert os.listdir(tmp_path) in ([], ["jc"])  # the work directory is gone


def test_a_measuring_run_without_a_tpu_fails(tmp_path):
    """Not a rehearsal: the run measures, finds its server on the CPU and
    fails. (The configuration cut to its toy sizes, so the test is short.)"""
    with open(MANIFEST) as fh:
        m = json.load(fh)
    with open(os.path.join(REPO, m["configs"][0]["file"])) as fh:
        cfg = json.load(fh)
    cfg.update(cfg["toy"])
    (tmp_path / "small.json").write_text(json.dumps(cfg))
    m["configs"][0]["file"] = str(tmp_path / "small.json")
    (tmp_path / "manifest.json").write_text(json.dumps(m))
    proc = bench(["--manifest", str(tmp_path / "manifest.json"), "--workload", CELLS[0],
                  "--seed", "1", "--seconds", "1", "--trace", "0"], tmp_path)
    assert proc.returncode not in (0, 3)
    assert "not on 1 TPU chip" in proc.stderr
    assert not proc.stdout.strip().splitlines()[-1:] or not proc.stdout.strip().splitlines()[-1].startswith("{")


def test_without_the_program_there_is_nothing_to_measure(tmp_path):
    """A directory that holds only BENCHMARK.json and the files under paths."""
    root = tmp_path / "bare"
    shutil.copytree(os.path.join(REPO, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root / "BENCHMARK.json")
    proc = bench(["--workload", "retrieval-yambda.serve-steady", "--seed", "1",
                  "--seconds", "1", "--trace", "0"], tmp_path, cwd=root,
                 run=str(root / "benchmark" / "run.py"))
    assert proc.returncode not in (0, 3)
    assert proc.stdout.strip() == ""
    assert "nothing to measure" in proc.stderr
