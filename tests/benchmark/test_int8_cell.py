"""The int8 cell's own pieces: the manifest with it (every list it joined, by
MEMBERSHIP), its configuration, writer, driver and readers found by name with
no harness edit, the quantize rule against the configuration's words, the
chunked quantized tables and the chunked reference against whole-table ones,
both controls, the readers against hand sums, the writer's refusal of a
program that cannot serve the model as stored, a CPU rehearsal of every phase,
and the rest of a run whose rescore rounds the dequantized rows to bf16:
`correct` has to come out false."""

from __future__ import annotations

import importlib
import importlib.util
import io
import json
import os
import subprocess
import sys
from contextlib import redirect_stdout

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.join(REPO, "benchmark"))

import costs  # noqa: E402
import factors  # noqa: E402
import manifest as manifest_rules  # noqa: E402
import readers  # noqa: E402
import reference  # noqa: E402
import reference_int8  # noqa: E402
import run as bench_run  # noqa: E402
import write_int8  # noqa: E402
import write_sharded  # noqa: E402

MANIFEST = os.path.join(REPO, "BENCHMARK.json")
RUN = os.path.join(REPO, "benchmark", "run.py")
CELL = "recommendation-amazon23-int8.serve-onechip-steady"
CONFIG = "recommendation-amazon23-int8"
TWIN = "recommendation-amazon23"
METRICS = os.path.join(REPO, "benchmark", "metrics")
OWN = {"coarse_int8_dot_share.int8", "rescore_device_ms.int8", "resident_gb.int8"}
JOINED = {"shortlist_ms", "rescore_ms", "fetch_ms", "shortlist_roofline",
          "worker_busy_share", "dispatch_cpu_ms"}
CHAIN = {"http_handoff_ms", "serve_submit_ms", "serve_wake_ms", "serve_tail_ms",
         "http_write_ms", "dispatch_self_ms", "batch_useful_rows_share", "batch_small_share"}
LISTLESS = {"gen_late_ms_p99", "query_p95_ms.steady", "query_p99_ms.steady",
            "batch_queue_wait_ms", "batch_size_mean", "dispatch_ms", "device_idle_share"}
STREAM = factors.STREAM_ITEM_FACTORS


def _manifest() -> dict:
    with open(MANIFEST) as fh:
        return json.load(fh)


def _cell():
    return bench_run.resolve(_manifest(), CELL, REPO)


def _metric_module(name: str):
    spec = importlib.util.spec_from_file_location(
        "m_" + name.replace(".", "_"), os.path.join(METRICS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestManifest:
    def test_the_manifest_with_the_cell_keeps_the_rules(self):
        assert manifest_rules.validate(_manifest(), REPO) == []

    def test_one_one_chip_cell_of_one_new_configuration(self):
        m = _manifest()
        cell = next(w for w in m["workloads"] if w["name"] == CELL)
        assert (cell["chips"], cell["config"], cell["traffic"]) == \
            (1, CONFIG, "amazon23-int8-steady")
        assert [w["name"] for w in m["workloads"] if w["config"] == CONFIG] == [CELL]
        assert len(cell["why"]) <= 200 and "int8" in cell["why"]
        conf = next(c for c in m["configs"] if c["name"] == CONFIG)
        assert conf["reduced"] == ["users"] and len(conf["source"]) <= 200
        twin = next(c for c in m["configs"] if c["name"] == TWIN)
        assert conf["source"] != twin["source"] and conf["file"] != twin["file"]

    @pytest.mark.parametrize("name", sorted(JOINED | CHAIN))
    def test_the_cell_is_in_every_list_it_joined(self, name):
        """By membership, never by position: a later PR appends behind it."""
        m = _manifest()
        metric = next(p for p in m["per_layer"] if p["name"] == name)
        assert CELL in metric["workloads"] and metric["moves"] == "query_p50_ms"
        assert metric["workloads"].count(CELL) == 1

    def test_the_cell_reports_query_p50_and_setup(self):
        m = _manifest()
        p50 = next(e for e in m["end_to_end"] if e["name"] == "query_p50_ms")
        assert CELL in p50["workloads"] and p50["bound"] == 0.05
        assert [d["name"] for d in bench_run.metrics_for(m, CELL, trace=False)] \
            == ["query_p50_ms", "setup_s"]
        assert all(CELL not in e.get("workloads", []) for e in m["end_to_end"]
                   if e["name"] == "serve_qps")

    @pytest.mark.parametrize("name", sorted(OWN))
    def test_its_own_metrics_list_it_alone(self, name):
        metric = next(p for p in _manifest()["per_layer"] if p["name"] == name)
        assert metric["workloads"] == [CELL] and metric["moves"] == "query_p50_ms"
        assert metric["layer"] == "score"

    def test_everything_is_found_by_name(self):
        """Configuration, traffic mix, driver and every reader, through the
        harness as it stands."""
        cell = _cell()
        assert cell["traffic"]["driver"] == "quantized"
        assert importlib.import_module("drivers.quantized").run
        defs = bench_run.metrics_for(_manifest(), CELL, trace=True)
        assert {d["name"] for d in defs} == OWN | JOINED | CHAIN | LISTLESS
        for d in defs:
            assert callable(readers.load_metric(METRICS, d["name"]))

    def test_the_published_shapes_are_uncut(self):
        cfg = _cell()["config"]
        with open(os.path.join(REPO, "benchmark", "configs", TWIN + ".json")) as fh:
            twin = json.load(fh)
        assert (cfg["num_items"], cfg["rank"], cfg["factor_dtype"]) == (48_190_000, 64, "int8")
        assert cfg["published"] == twin["published"]
        assert cfg["published"]["items"] == cfg["num_items"]
        assert (cfg["num_users"], cfg["rank"]) == (twin["num_users"], twin["rank"])
        assert cfg["reduced"] == ["users"] and cfg["architecture"] is None
        assert cfg["retrieval"] == {"threshold": 100000, "oversample": 8, "tile": 262144,
                                    "coarse_dtype": "int8"}
        algo = cfg["variant"]["algorithms"][0]["params"]
        assert algo["storage_dtype"] == "int8" and algo["rank"] == 64
        assert "sharded_serving" not in algo
        assert cfg["deploy_flags"] == [f for f in twin["deploy_flags"]
                                       if f not in ("--mesh", "data=4")]
        assert cfg["chips"] == 1 and "mesh" not in cfg

    def test_what_one_chip_holds(self):
        cfg = _cell()["config"]
        values = cfg["num_items"] * cfg["rank"]
        scales = cfg["num_items"] * 4
        assert values == 3_084_160_000 and scales == 192_760_000
        stored = costs.coarse_tiles(cfg["num_items"], 262144) * 262144
        assert stored == 184 * 262144 == 48_234_496
        resident = values + scales + stored * (cfg["rank"] + 4 + 4)
        assert 0.25 * 16e9 < resident < 0.5 * 16e9  # tiles and table both: over the floor
        assert values + scales + stored * 4 < 0.25 * 16e9  # one array would be under it
        assert values * 4 + values * 2 > 16e9  # the f32 twin fits no single chip

    def test_the_limits_are_stated_with_their_reasons(self):
        lim = _cell()["config"]["limits"]
        assert lim["overlap_min"]["limit"] == 0.9 and lim["overlap_mean_min"]["limit"] == 0.999
        assert 1e-5 <= lim["score_gap_max"]["limit"] <= 1e-3
        for v in lim.values():
            assert len(v["why"]) > 20 and "TO BE" not in v["why"]

    def test_the_rate_is_a_tenth_of_the_knee(self):
        mix = _cell()["traffic"]
        assert mix["loop"] == "open" and mix["num"] == 10 and mix["connections"] == 256
        assert mix["rate_qps"] == int(mix["rate_qps"]) and "TO BE" not in mix["why_rate"]
        assert mix["rate_qps"] == round(0.1 * mix["knee_qps"])
        assert mix["warm_clients"] == [3, 5, 9, 16] and mix["trace_seconds"] == 1.0
        assert mix["late_limit_ms"] == 5.0 and mix["users"] == "uniform-distinct"


class TestTheQuantizeRule:
    def test_the_rule_is_the_configurations(self):
        rule = _cell()["config"]["quantize"]
        assert "max|row| / 127" in rule["rule"] and "rint(row / scale)" in rule["rule"]
        assert "reference_int8.py quantize_rows" in rule["where"]
        block = np.asarray([[1.0, -2.54, 0.5, 0.0], [0.0, 0.0, 0.0, 0.0],
                            [-127.0, 63.4, 63.6, 1e-3]], np.float32)
        v, s = reference_int8.quantize_rows(block)
        assert v.dtype == np.int8 and s.dtype == np.float32
        np.testing.assert_array_equal(s, np.asarray([2.54 / 127, 1.0, 1.0], np.float32))
        np.testing.assert_array_equal(v, [[50, -127, 25, 0], [0, 0, 0, 0], [-127, 63, 64, 0]])
        np.testing.assert_array_equal(
            reference_int8.dequantize(v, s), v.astype(np.float32) * s[:, None])

    def test_it_is_the_programs_rule_without_the_programs_code(self):
        """The stored form is the one `pio train` with storage_dtype int8
        leaves; the benchmark does not call the program to make it."""
        import jax.numpy as jnp

        from predictionio_tpu.ops import als

        block = factors.factor_table(3, STREAM, 500, 64)
        v, s = reference_int8.quantize_rows(block)
        pv, ps = als.quantize_rows(jnp.asarray(block))
        np.testing.assert_array_equal(np.asarray(ps), s)
        np.testing.assert_array_equal(np.asarray(pv), v)
        with open(os.path.join(REPO, "benchmark", "reference_int8.py")) as fh:
            assert "predictionio_tpu" not in fh.read().replace("the program", "")

    def test_a_requantized_query_is_the_stored_row(self):
        v, s = reference_int8.quantize_rows(factors.factor_table(5, 1, 2000, 64))
        np.testing.assert_array_equal(
            reference_int8.requantized(reference_int8.dequantize(v, s)), v)


class TestChunkedTables:
    @pytest.fixture(autouse=True)
    def _small_chunks(self, monkeypatch):
        monkeypatch.setattr(factors, "CHUNK_ROWS", 1000)

    @pytest.mark.parametrize("part", ["values", "scales"])
    def test_a_row_source_is_the_whole_tables_slices(self, part):
        v, s = reference_int8.quantize_rows(factors.item_factors(5, 4321, 16))
        whole = v if part == "values" else s
        src = reference_int8.QuantizedRows(5, STREAM, 4321, 16, part, workers=3)
        assert src.shape == whole.shape and src.dtype == whole.dtype
        for lo, hi in [(0, 4321), (999, 3003), (4000, 9999), (1000, 2000), (7, 7)]:
            np.testing.assert_array_equal(src.rows(lo, hi), whole[lo:hi])

    @pytest.mark.parametrize("quantized", [True, False])
    def test_rows_by_index_out_of_their_chunks(self, quantized):
        f = factors.item_factors(5, 4321, 16)
        want = reference_int8.dequantize(*reference_int8.quantize_rows(f)) if quantized else f
        ixs = np.asarray([4320, 0, 999, 1000, 2500, 0])
        np.testing.assert_array_equal(
            reference_int8.table_rows(5, STREAM, 4321, 16, ixs, quantized), want[ixs])

    def test_the_chunked_reference_is_the_one_table_reference(self):
        deq = reference_int8.dequantize(
            *reference_int8.quantize_rows(factors.item_factors(9, 4321, 16)))
        q = reference_int8.table_rows(9, factors.STREAM_USER_FACTORS, 50, 16, np.arange(7))
        s, i = reference.top_k_scan(q, deq, 10)
        served = i.copy()
        served[0, 3] = -1
        s2, i2, own, controls = reference_int8.scan(9, 4321, 16, q, 10, served=served, workers=3)
        np.testing.assert_array_equal(i2, i)
        np.testing.assert_array_equal(s2, s)
        assert controls == {} and np.isnan(own[0, 3])
        for row in range(7):
            live = served[row] >= 0
            np.testing.assert_allclose(
                own[row][live], reference.score_items(q[row], deq, served[row][live]),
                rtol=0, atol=1e-6)

    def test_a_last_chunk_shorter_than_k(self):
        deq = reference_int8.dequantize(
            *reference_int8.quantize_rows(factors.item_factors(3, 2003, 8)))
        q = factors.user_factors(3, 4, 8)
        s, i, _, _ = reference_int8.scan(3, 2003, 8, q, 10)
        rs, ri = reference.top_k_scan(q, deq, 10)
        np.testing.assert_array_equal(i, ri)
        np.testing.assert_array_equal(s, rs)

    @pytest.mark.parametrize("name", ["bfloat16", "unquantized"])
    def test_a_control_is_its_own_top_k_held_to_the_exact_scores(self, name):
        from drivers import quantized

        src = factors.item_factors(9, 4321, 64)
        deq = reference_int8.dequantize(*reference_int8.quantize_rows(src))
        users = np.arange(6)
        q = reference_int8.table_rows(9, factors.STREAM_USER_FACTORS, 50, 64, users)
        q_src = reference_int8.table_rows(9, factors.STREAM_USER_FACTORS, 50, 64, users, False)
        table, precision = quantized.CONTROLS[name]
        cq = q if table == "int8" else q_src
        _, _, _, got = reference_int8.scan(
            9, 4321, 64, q, 10, controls={name: (cq, table, precision)}, workers=3)
        c_s, c_i, exact = got[name]
        ws, wi = reference.top_k_scan(cq, deq if table == "int8" else src, 10, precision)
        np.testing.assert_array_equal(c_i, wi)
        np.testing.assert_array_equal(c_s, ws)
        for row in range(6):
            np.testing.assert_allclose(
                exact[row], reference.score_items(q[row], deq, wi[row]), rtol=0, atol=1e-6)
        # one step away is not correct: over the cell's limit on every query
        limit = _cell()["config"]["limits"]["score_gap_max"]["limit"]
        assert np.abs(c_s - exact).max(axis=1).min() > limit


class TestReaders:
    def test_int8_dot_share_from_the_counter(self):
        mod = _metric_module("coarse_int8_dot_share.int8")
        series = "pio_retrieval_coarse_mode_total"
        d = {series + '{mode="int8"}': 30.0, series + '{mode="int8_dot"}': 10.0,
             series + '{mode="bf16"}': 0.0}
        assert mod.read({"counters_delta": d}, {}, {}) == pytest.approx(25.0)
        d[series + '{mode="int8"}'] = 0.0
        assert mod.read({"counters_delta": d}, {}, {}) == 100.0
        # the parent has no such counter; a window without a shortlist call
        assert mod.read({"counters_delta": {"pio_batch_size_count": 4.0}}, {}, {}) is None
        assert mod.read({"counters_delta": {k: 0.0 for k in d}}, {}, {}) is None
        assert mod.read({}, {}, {}) is None

    def test_resident_gb_sums_the_gauge_as_scraped(self):
        mod = _metric_module("resident_gb.int8")
        g = {'pio_model_resident_bytes{part="table"}': 3_084_160_000.0,
             'pio_model_resident_bytes{part="table_scales"}': 192_760_000.0,
             'pio_model_resident_bytes{part="coarse"}': 3_087_007_744.0,
             'pio_model_resident_bytes{part="coarse_scales"}': 192_937_984.0,
             'pio_model_resident_bytes{part="coarse_ids"}': 192_937_984.0,
             'pio_model_resident_bytes{part="users"}': 68_000_000.0}
        assert mod.read({"gauges_close": g}, {}, {}) == pytest.approx(6.818, abs=1e-3)
        assert mod.read({"gauges_close": {}}, {}, {}) is None  # the parent
        assert mod.read({"counters_delta": g}, {}, {}) is None  # a delta of a gauge is 0
        assert mod.read({"gauges_close": {k: 0.0 for k in g}}, {}, {}) is None

    def test_rescore_device_ms_is_a_calls_mean(self):
        mod = _metric_module("rescore_device_ms.int8")
        raw = {"trace": {"programs": {mod.PROGRAM: 0.004, "jit__coarse_topk": 1.0},
                         "program_calls": {mod.PROGRAM: 80, "jit__coarse_topk": 80}}}
        assert mod.PROGRAM == "jit__rescore_gather"
        assert mod.read(raw, {}, {}) == pytest.approx(0.05)
        assert mod.read({"trace": {"programs": {}, "program_calls": {}}}, {}, {}) is None
        assert mod.read({}, {}, {}) is None

    def test_the_roofline_reads_the_int8_bytes_of_the_configuration(self):
        """shortlist_roofline needs no new counting code: costs.shortlist_bytes
        already counts a stored int8 row as 64 values + its scale + its id."""
        cell = _cell()
        need = costs.shortlist_bytes(48_190_000, 64, 262144, "int8")
        assert need == 184 * 262144 * (64 + 4 + 4) + 64 * 4 == 3_472_883_968
        read = readers.load_metric(METRICS, "shortlist_roofline")
        raw = {"device": {"kind": "TPU v5 lite"}, "trace": {
            "programs": {"jit__coarse_topk": 40 * 2 * need / 819e9},  # half the memory's speed
            "program_calls": {"jit__coarse_topk": 40}}}
        assert read(raw, cell) == pytest.approx(50.0)
        assert read({"device": raw["device"], "trace": {"programs": {}}}, cell) is None


class TestWriterAndDriver:
    def test_the_writer_refuses_a_program_that_cannot_serve_the_pair_as_stored(
            self, monkeypatch, capsys):
        from predictionio_tpu.ops import retrieval

        write_int8.quantized_serving()  # this program can
        monkeypatch.delattr(retrieval, "put_rows")
        with pytest.raises(SystemExit) as e:
            write_int8.quantized_serving()
        assert e.value.code == 2
        assert "put_rows" in capsys.readouterr().err

    def test_the_models_bytes_reckoned_before_anything_is_written(self):
        n = write_int8.model_bytes(1_000_000, 48_190_000, 64)
        assert 4.0e9 < n < 4.4e9  # a third of the twin's 13.4 GB
        assert n < write_sharded.model_bytes(1_000_000, 48_190_000, 64) / 3

    def test_a_written_model_is_the_quantized_tables_in_segments(self, tmp_path, monkeypatch):
        monkeypatch.setattr(factors, "CHUNK_ROWS", 4096)
        env = {"PIO_FS_BASEDIR": str(tmp_path / "store"),
               "PIO_STORAGE_SOURCES_DB_TYPE": "sqlite",
               "PIO_STORAGE_SOURCES_DB_PATH": str(tmp_path / "pio.db"),
               "PIO_STORAGE_SOURCES_FS_TYPE": "localfs",
               "PIO_STORAGE_SOURCES_FS_PATH": str(tmp_path / "models"),
               "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "DB",
               "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "FS"}
        for k, v in env.items():
            monkeypatch.setenv(k, v)
        spec = {"seed": 11, "num_users": 500, "num_items": 70_000, "rank": 64,
                "variant": _cell()["config"]["variant"], "variant_label": "engine.json",
                "segment_bytes": 1 << 20, "workers": 3}
        (tmp_path / "spec.json").write_text(json.dumps(spec))
        out = io.StringIO()
        with redirect_stdout(out):
            assert write_int8.main(["write_int8.py", str(tmp_path / "spec.json")]) == 0
        told = json.loads(out.getvalue().strip().splitlines()[-1])
        segs = [p for p in (tmp_path / "models").iterdir() if ".seg" in p.name]
        assert told["segments"] == len(segs) >= 4
        assert all(p.stat().st_size <= 1 << 20 for p in segs)
        from predictionio_tpu.models import modelfile

        head = next(p for p in (tmp_path / "models").iterdir() if p.name.endswith(".bin"))
        f = modelfile.load_path(head).fields(0)
        v, s = reference_int8.quantize_rows(factors.item_factors(11, 70_000, 64))
        assert isinstance(f["item_factors"], modelfile.SpannedArray)
        assert f["item_factors"].dtype == np.int8 and f["item_scales"].dtype == np.float32
        np.testing.assert_array_equal(np.asarray(f["item_factors"]), v)
        np.testing.assert_array_equal(np.asarray(f["item_scales"]), s)
        uv, us = reference_int8.quantize_rows(factors.user_factors(11, 500, 64))
        np.testing.assert_array_equal(np.asarray(f["user_factors"]), uv)
        np.testing.assert_array_equal(np.asarray(f["user_scales"]), us)
        assert f["item_index"].inverse[69_999] == "i69999" and f["user_index"]["u499"] == 499


BROKEN = '''
import sys
import jax.numpy as jnp
from predictionio_tpu.ops import retrieval
_sound = retrieval._table_rows
def _table_rows(table, ixs):
    # the dequantized rows rounded to bf16 before the product
    return _sound(table, ixs).astype(jnp.bfloat16).astype(jnp.float32)
retrieval._table_rows = _table_rows
from predictionio_tpu.cli.main import main
sys.exit(main(sys.argv[1:]))
'''


def _bench(tmp_path, *args, manifest=None):
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", TMPDIR=str(tmp_path), PYTHONPATH="", BENCH_RUN="ignored",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jc"))
    cmd = [sys.executable, RUN, "--workload", CELL, "--dry-run-cpu", *args]
    if manifest:
        cmd += ["--manifest", str(manifest)]
    return subprocess.run(cmd, capture_output=True, text=True, env=env, cwd=REPO, timeout=600)


def _checks(proc):
    return {c["name"]: c for c in (
        json.loads(ln[7:]) for ln in proc.stdout.splitlines() if ln.startswith("check: "))}


def test_a_cpu_rehearsal_passes_every_phase_and_both_controls_fail(tmp_path):
    proc = _bench(tmp_path, "--seed", "2147483999", "--seconds", "3", "--trace", "1",
                  "--control", "1")
    assert proc.returncode == 3, proc.stdout[-2000:] + proc.stderr[-2000:]
    assert "dry run on cpu: every phase passed" in proc.stdout
    checks = _checks(proc)
    for name in ("score_gap_max", "overlap_min", "overlap_mean_min", "answers_compared",
                 "compiles_in_window", "exact_path_queries", "table_bytes_a_value",
                 "table_resident"):
        assert checks[name]["pass"], checks[name]
    assert checks["overlap_min"]["value"] == 1.0
    assert checks["table_bytes_a_value"]["value"] == 1.0
    for name in ("bfloat16", "unquantized"):
        c = checks[f"control.score_gap_max({name})"]
        assert c["control"] and not c["pass"] and c["smallest"] > c["limit"]
    line = next(ln for ln in proc.stdout.splitlines() if ln.startswith("would print: "))
    for name in ("query_p95_ms.steady", "dispatch_ms", "shortlist_ms", "rescore_ms",
                 "fetch_ms", "worker_busy_share", "http_handoff_ms",
                 "coarse_int8_dot_share.int8", "resident_gb.int8"):
        assert f'"{name}": {{"value"' in line, name
    times = json.loads(next(ln for ln in proc.stdout.splitlines()
                            if ln.startswith("times: "))[7:])
    assert times["resident_bytes"]["table"] == 40_000 * 64
    assert times["model_segments"] >= 3 and "memory_by_device" in times


def test_a_rescore_one_precision_down_is_not_correct(tmp_path):
    entry = tmp_path / "broken_server.py"
    entry.write_text(BROKEN)
    m = _manifest()
    with open(os.path.join(REPO, "benchmark", "configs", CONFIG + ".json")) as fh:
        cfg = json.load(fh)
    cfg["server_entry"] = [str(entry)]
    (tmp_path / "broken.json").write_text(json.dumps(cfg))
    for c in m["configs"]:
        if c["name"] == CONFIG:
            c["file"] = str(tmp_path / "broken.json")
    (tmp_path / "manifest.json").write_text(json.dumps(m))
    proc = _bench(tmp_path, "--seed", "77", "--seconds", "2", "--trace", "0",
                  manifest=tmp_path / "manifest.json")
    assert proc.returncode == 1, proc.stdout[-2000:] + proc.stderr[-2000:]
    assert "dry run on cpu: NOT correct" in proc.stdout
    checks = _checks(proc)
    assert not checks["score_gap_max"]["pass"]
    assert checks["table_bytes_a_value"]["pass"]  # what was held was the pair
