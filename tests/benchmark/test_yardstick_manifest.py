"""BENCHMARK.json keeps the contract; a configuration, a traffic mix, a cell
and a per-layer metric are each found by name, with no harness edit; the
lower-precision control comes out as not correct."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.join(REPO, "benchmark"))

import manifest as manifest_rules  # noqa: E402
import reference  # noqa: E402

MANIFEST = os.path.join(REPO, "BENCHMARK.json")


def _load(path):
    with open(path) as fh:
        return json.load(fh)


@pytest.mark.parametrize("path", [MANIFEST], ids=["BENCHMARK.json"])
class TestManifest:
    def test_keeps_the_contract(self, path):
        assert manifest_rules.validate(_load(path), REPO) == []

    def test_every_config_file_states_its_cut(self, path):
        for c in _load(path)["configs"]:
            cfg = _load(os.path.join(REPO, c["file"]))
            assert cfg["name"] == c["name"]
            assert cfg["reduced"] == c["reduced"]
            assert set(c["reduced"]) <= set(cfg["assumed"])  # each cut says why
            for width in ("rank", "factor_dtype"):
                assert width not in c["reduced"]
            assert cfg["limits"] and cfg["guarantees"]

    def test_size_under_64k(self, path):
        assert os.path.getsize(path) < 64 * 1024


class TestRulesCatchFaults:
    def _good(self):
        return _load(MANIFEST)

    @pytest.mark.parametrize("breakit,needle", [
        (lambda m: m["workloads"][0].update(name="has space"), "name"),
        (lambda m: m["end_to_end"][0].update(unit="tokens per second"), "unit"),
        (lambda m: m["per_layer"][6].update(moves="serve_qps"), "does not report"),
        (lambda m: m["configs"].append({**m["configs"][0], "name": "unused", "file": "benchmark/configs/unused.json"}), "no cell uses it"),
        (lambda m: m["end_to_end"][0].update(bound=0.2), "bound"),
        (lambda m: m["per_layer"][0].update(why="x"), "keys"),
        (lambda m: m["workloads"][0].update(traffic="no-such-mix"), "traffic file"),
    ])
    def test_fault_is_found(self, breakit, needle):
        m = self._good()
        breakit(m)
        faults = manifest_rules.validate(m, REPO)
        assert any(needle in f for f in faults), faults


def test_discovery_needs_no_harness_edit(tmp_path):
    """A later PR's configuration, traffic mix, cell and per-layer metric:
    new files and new entries only, found by name."""
    root = tmp_path / "repo"
    shutil.copytree(os.path.join(REPO, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    cfg = _load(os.path.join(REPO, "benchmark", "configs", "retrieval-yambda.json"))
    cfg["name"] = "als-new"
    (root / "benchmark" / "configs" / "als-new.json").write_text(json.dumps(cfg))
    mix = _load(os.path.join(REPO, "benchmark", "traffic", "yambda-steady.json"))
    mix["rate_qps"] = 77.0
    (root / "benchmark" / "traffic" / "new-mix.json").write_text(json.dumps(mix))
    (root / "benchmark" / "metrics" / "new_metric.py").write_text(
        "SPEC = {'scale': 2}\n"
        "def read(raw, spec, cell):\n"
        "    return spec['scale'] * raw['x'] * cell['traffic']['rate_qps'] if 'x' in raw else None\n")
    m = _load(MANIFEST)
    m["configs"].append({"name": "als-new", "source": "s", "file": "benchmark/configs/als-new.json",
                         "reduced": [], "why": "w"})
    m["workloads"].append({"name": "als-new.cell", "config": "als-new", "traffic": "new-mix",
                           "chips": 1, "why": "w"})
    for e in m["end_to_end"]:
        if e["name"] == "query_p50_ms":
            e["workloads"].append("als-new.cell")
    m["per_layer"].append({"name": "new_metric", "unit": "x", "better": "lower",
                           "source": "program_counter", "layer": "score",
                           "moves": "query_p50_ms", "workloads": ["als-new.cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(m))
    code = (
        "import json, sys; sys.argv=['run.py']; import run, manifest\n"
        f"m = run.load_json({str(root / 'BENCHMARK.json')!r})\n"
        f"assert manifest.validate(m, {str(root)!r}) == [], manifest.validate(m, {str(root)!r})\n"
        f"cell = run.resolve(m, 'als-new.cell', {str(root)!r})\n"
        "defs = run.metrics_for(m, 'als-new.cell', True)\n"
        "assert 'new_metric' in [d['name'] for d in defs]\n"
        "assert 'shortlist_ms' not in [d['name'] for d in defs]\n"
        "print(json.dumps([cell['config']['name'], cell['traffic']['rate_qps'], cell['traffic']['driver'],"
        " run.evaluate(defs, {'x': 1.5, 'late_ms': [1.0]}, cell)]))\n"
    )
    env = {**os.environ, "PYTHONPATH": REPO}
    proc = subprocess.run([sys.executable, "-c", code], cwd=root / "benchmark",
                          capture_output=True, text=True, env=env, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    name, rate, driver, metrics = json.loads(proc.stdout.strip().splitlines()[-1])
    assert (name, rate, driver) == ("als-new", 77.0, "serve")
    assert metrics["new_metric"] == {"value": 2 * 1.5 * 77.0, "unit": "x"}
    assert metrics["gen_late_ms_p99"]["value"] == 1.0
    assert "dispatch_ms" not in metrics  # nothing to read: left out


class TestControl:
    """The reference one precision down, in the program's place, at a size a
    test run can hold: it has to fail the limit the configurations state,
    and the sound f32 answer has to pass it."""

    def _tables(self, seed):
        sys.path.insert(0, os.path.join(REPO, "benchmark"))
        import factors

        return factors.user_factors(seed, 64, 64), factors.item_factors(seed, 50_000, 64)

    @pytest.mark.parametrize("seed", [1, 2, 2**31 + 7])
    def test_bf16_scores_fail_and_f32_pass(self, seed):
        limit = _load(os.path.join(REPO, "benchmark", "configs", "retrieval-yambda.json"))[
            "limits"]["score_gap_max"]["limit"]
        U, V = self._tables(seed)
        ref_s, ref_i = reference.top_k_scan(U, V, 10, block=8192)
        # a sound server: f32 in another summation order (f64 accumulate)
        sound = np.take_along_axis((U.astype(np.float64) @ V.T.astype(np.float64)), ref_i, 1)
        low_s, low_i = reference.top_k_scan(U, V, 10, precision="bfloat16", block=8192)
        sound_gap, low_gap = [], []
        for r in range(len(U)):
            sound_gap.append(np.abs(sound[r].astype(np.float32) - reference.score_items(U[r], V, ref_i[r])).max())
            low_gap.append(np.abs(low_s[r] - reference.score_items(U[r], V, low_i[r])).max())
        assert max(sound_gap) < limit / 3
        assert min(low_gap) > 3 * limit  # every answer of the control fails
        # the scan is exact: it agrees with a full sort
        full = U @ V.T
        assert np.array_equal(ref_i, np.argsort(-full, axis=1, kind="stable")[:, :10])


class TestModelWriter:
    """The vectorised model-file writer against the program's serializer."""

    def test_byte_identical_to_the_programs_serializer(self):
        import factors
        import modelwriter
        from predictionio_tpu.data.bimap import BiMap
        from predictionio_tpu.models import modelfile
        from predictionio_tpu.models.recommendation import ALSModel

        U, V = factors.user_factors(9, 1234, 64), factors.item_factors(9, 10_007, 64)
        model = ALSModel(
            user_index=BiMap.from_dense([f"u{n}" for n in range(len(U))]),
            item_index=BiMap.from_dense([f"i{n}" for n in range(len(V))]),
            user_factors=U, item_factors=V)
        want = modelfile.serialize([("arrays", model)], "abc")
        got = modelwriter.factor_model_blob(
            modelfile, "abc", (ALSModel.__module__, ALSModel.__qualname__), b"u", b"i", U, V)
        assert bytes(got) == want

    def test_dense_ids(self):
        import modelwriter

        blob, offs = modelwriter.dense_id_blob(b"i", 1002)
        ids = [bytes(blob[offs[j]:offs[j + 1]]).decode() for j in range(1002)]
        assert ids == [f"i{n}" for n in range(1002)]
