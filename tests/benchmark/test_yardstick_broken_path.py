"""The rest of a run with the timed path broken underneath: an answer
altered where it is produced. `correct` has to come out false."""

from __future__ import annotations

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
MANIFEST = os.path.join(REPO, "BENCHMARK.json")
RUN = os.path.join(REPO, "benchmark", "run.py")

BROKEN_SERVER = '''
import sys
from predictionio_tpu.models import recommendation as r
_sound = r.ALSAlgorithm.batch_predict
def batch_predict(self, model, queries):
    out = _sound(self, model, queries)
    for _, res in out:
        if res.itemScores:
            res.itemScores[-1].score *= 0.999  # one score altered where it is produced
    return out
r.ALSAlgorithm.batch_predict = batch_predict
from predictionio_tpu.cli.main import main
sys.exit(main(sys.argv[1:]))
'''

def _run_with(tmp_path, cell, config_name, extra):
    with open(MANIFEST) as fh:
        m = json.load(fh)
    with open(os.path.join(REPO, "benchmark", "configs", config_name + ".json")) as fh:
        cfg = json.load(fh)
    cfg.update(extra)
    cfg_path = tmp_path / "broken.json"
    cfg_path.write_text(json.dumps(cfg))
    for c in m["configs"]:
        if c["name"] == config_name:
            c["file"] = str(cfg_path)
    man = tmp_path / "manifest.json"
    man.write_text(json.dumps(m))
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", TMPDIR=str(tmp_path), PYTHONPATH="",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jc"))
    return subprocess.run(
        [sys.executable, RUN, "--manifest", str(man), "--workload", cell, "--seed", "77",
         "--seconds", "2", "--trace", "0", "--dry-run-cpu"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=600)


def _checks(proc):
    return {c["name"]: c for c in (
        json.loads(ln[7:]) for ln in proc.stdout.splitlines() if ln.startswith("check: "))}


def test_an_altered_answer_is_not_correct(tmp_path):
    entry = tmp_path / "broken_server.py"
    entry.write_text(BROKEN_SERVER)
    proc = _run_with(tmp_path, "retrieval-yambda.serve-steady", "retrieval-yambda",
                     {"server_entry": [str(entry)]})
    assert proc.returncode == 1, proc.stdout[-2000:] + proc.stderr[-2000:]
    assert "dry run on cpu: NOT correct" in proc.stdout
    would = json.loads(next(ln for ln in proc.stdout.splitlines()
                            if ln.startswith("would print: "))[13:])
    assert would["correct"] is False
    checks = _checks(proc)
    assert not checks["score_gap_max"]["pass"]
    assert checks["overlap_min"]["pass"]  # the items were right: one number fails
