"""The sharded cell's own pieces: the manifest with it, its configuration,
writer, driver and readers found by name with no harness edit, the chunked
factor tables and the chunked reference against the whole-table ones, the
readers against hand sums, the driver's refusal of a program without the
spanning model format, and the rest of a run with one shard's answers dropped
before the merge: `correct` has to come out false."""

from __future__ import annotations

import importlib
import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.join(REPO, "benchmark"))

import factor_blocks  # noqa: E402
import factors  # noqa: E402
import manifest as manifest_rules  # noqa: E402
import readers  # noqa: E402
import reference  # noqa: E402
import reference_sharded  # noqa: E402
import run as bench_run  # noqa: E402
import write_sharded  # noqa: E402
import xplane_sharded  # noqa: E402

MANIFEST = os.path.join(REPO, "BENCHMARK.json")
RUN = os.path.join(REPO, "benchmark", "run.py")
CELL = "recommendation-amazon23.serve-sharded-steady"
CONFIG = "recommendation-amazon23"
METRICS = os.path.join(REPO, "benchmark", "metrics")
OWN = {"shard_scan_roofline", "shard_merge_ms", "shard_busy_skew", "shortlist_ms",
       "fetch_ms", "shortlist_size_mean"}
CHAIN = {"http_handoff_ms", "serve_submit_ms", "serve_wake_ms", "serve_tail_ms",
         "http_write_ms", "dispatch_self_ms", "batch_useful_rows_share", "batch_small_share"}
LISTLESS = {"gen_late_ms_p99", "query_p95_ms.steady", "query_p99_ms.steady",
            "batch_queue_wait_ms", "batch_size_mean", "dispatch_ms", "device_idle_share"}


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

    def test_one_four_chip_cell_on_query_p50(self):
        m = _manifest()
        cell = next(w for w in m["workloads"] if w["name"] == CELL)
        assert cell["chips"] == 4
        assert [w["name"] for w in m["workloads"] if w["chips"] == 4] == [CELL]
        p50 = next(e for e in m["end_to_end"] if e["name"] == "query_p50_ms")
        assert p50["workloads"][-1] == CELL
        conf = next(c for c in m["configs"] if c["name"] == CONFIG)
        assert conf["reduced"] == ["users"] and len(conf["source"]) <= 200

    def test_everything_is_found_by_name(self):
        """Configuration, traffic mix, driver and every reader, through the
        harness as it stands."""
        cell = _cell()
        assert cell["traffic"]["driver"] == "sharded"
        assert importlib.import_module("drivers.sharded").run
        defs = bench_run.metrics_for(_manifest(), CELL, trace=True)
        names = {d["name"] for d in defs}
        assert names == {n + ".sharded" for n in OWN} | CHAIN | LISTLESS
        for d in defs:
            assert callable(readers.load_metric(METRICS, d["name"]))
        assert [d["name"] for d in bench_run.metrics_for(_manifest(), CELL, trace=False)] \
            == ["query_p50_ms", "setup_s"]

    def test_the_published_shapes_are_uncut(self):
        cfg = _cell()["config"]
        assert (cfg["num_items"], cfg["rank"], cfg["factor_dtype"]) == (48_190_000, 64, "float32")
        assert cfg["published"]["items"] == cfg["num_items"]
        assert cfg["published"]["users"] == 54_510_000 and cfg["num_users"] == 1_000_000
        assert cfg["reduced"] == ["users"] and cfg["architecture"] is None
        assert cfg["retrieval"] == {"threshold": 100000, "oversample": 8, "tile": 262144,
                                    "coarse_dtype": "bfloat16"}
        algo = cfg["variant"]["algorithms"][0]["params"]
        assert algo["sharded_serving"] is True and algo["rank"] == 64
        assert cfg["deploy_flags"][-2:] == ["--mesh", "data=4"]
        # the tables at the stated precision fit no single chip
        table = cfg["num_items"] * cfg["rank"] * 4
        assert table + table // 2 > 16e9


class TestChunkedTables:
    @pytest.fixture(autouse=True)
    def _small_chunks(self, monkeypatch):
        monkeypatch.setattr(factors, "CHUNK_ROWS", 1000)

    def test_blocks_are_the_whole_tables_slices_bit_for_bit(self):
        V = factors.item_factors(5, 4321, 16)
        stream = factors.STREAM_ITEM_FACTORS
        for lo, hi in [(0, 4321), (999, 3003), (4000, 9999), (1000, 2000), (7, 7)]:
            got = factor_blocks.rows(5, stream, 4321, 16, lo, hi)
            assert got.dtype == np.float32
            np.testing.assert_array_equal(got, V[lo:hi])
        src = factor_blocks.SeededRows(5, stream, 4321, 16)
        assert src.shape == (4321, 16) and src.dtype == np.float32
        np.testing.assert_array_equal(src.rows(2500, 4321), V[2500:])

    @pytest.mark.parametrize("precision", reference.PRECISIONS)
    def test_the_chunked_reference_is_the_one_table_reference(self, precision):
        V = factors.item_factors(9, 4321, 16)
        q = factors.user_factors(9, 50, 16)[:7]
        s, i = reference.top_k_scan(q, V, 10, precision)
        served = i.copy()
        served[0, 3] = -1
        s2, i2, own = reference_sharded.scan(9, 4321, 16, q, 10, served=served,
                                             precision=precision, workers=3)
        np.testing.assert_array_equal(i2, i)
        np.testing.assert_array_equal(s2, s)
        assert np.isnan(own[0, 3])
        for row in range(7):
            live = served[row] >= 0
            # the same rows against the same query; a matvec's summation order
            # moves with how many rows it is handed
            np.testing.assert_allclose(
                own[row][live], reference.score_items(q[row], V, served[row][live], precision),
                rtol=0, atol=1e-6)

    def test_a_last_chunk_shorter_than_k(self):
        V = factors.item_factors(3, 2003, 8)
        q = factors.user_factors(3, 4, 8)
        s, i, _ = reference_sharded.scan(3, 2003, 8, q, 10)
        rs, ri = reference.top_k_scan(q, V, 10)
        np.testing.assert_array_equal(i, ri)
        np.testing.assert_array_equal(s, rs)


class TestReaders:
    def test_scan_bytes_against_a_hand_sum(self):
        mod = _metric_module("shard_scan_roofline.sharded")
        # 48.19 M rows over 4 chips: 12,047,500 a chip -> 46 tiles of 2^18 rows,
        # each row 64 bf16 values + one int32 id
        assert mod.sharded_scan_bytes(48_190_000, 64, 262144, 4) \
            == 4 * 46 * 262144 * (64 * 2 + 4) == 6_366_953_472
        # a shard smaller than a tile is one tile of its own power of two
        assert mod.sharded_scan_bytes(40_000, 64, 8192, 4) == 4 * 2 * 8192 * 132
        assert mod.sharded_scan_bytes(1000, 8, 8192, 4) == 4 * 256 * (8 * 2 + 4)

    def test_roofline_counts_bytes_and_seconds_over_the_same_chips(self):
        mod = _metric_module("shard_scan_roofline.sharded")
        cell = _cell()
        per_chip_s = 46 * 262144 * 132 / 819e9  # a chip's own least time a dispatch
        raw = {"device": {"kind": "TPU v5 lite"}, "trace": {
            "device_planes": 4,
            "programs": {mod.PROGRAM: 100 * 4 * 2 * per_chip_s},  # every chip at half speed
            "program_calls": {mod.PROGRAM: 400}}}
        assert mod.read(raw, {}, cell) == pytest.approx(50.0)
        assert mod.read({"device": raw["device"], "trace": {
            "device_planes": 4, "programs": {}, "program_calls": {}}}, {}, cell) is None
        assert mod.read({"device": raw["device"]}, {}, cell) is None

    def test_merge_ms_is_a_chips_mean_over_the_runs_it_was_found_in(self):
        mod = _metric_module("shard_merge_ms.sharded")
        raw = {"trace": {"shard_ops_s": 0.040, "shard_ops_calls": 400}}
        assert mod.read(raw, {}, {}) == pytest.approx(0.1)  # 40 ms over 100 dispatches x 4 chips
        assert mod.read({"trace": {"programs": {}}}, {}, {}) is None  # the parent's trace
        assert mod.read({"trace": {"shard_ops_s": 0.0, "shard_ops_calls": 0}}, {}, {}) is None

    def test_busy_skew_is_largest_over_mean(self):
        mod = _metric_module("shard_busy_skew.sharded")
        raw = {"trace": {"busy_by_plane": {"a": 1.0, "b": 1.0, "c": 1.0, "d": 2.0}}}
        assert mod.read(raw, {}, {}) == pytest.approx(1.6)
        assert mod.read({"trace": {}}, {}, {}) is None and mod.read({}, {}, {}) is None

    def test_the_tail_of_a_run_runs_from_its_collective_to_its_end(self):
        """Two runs of the program on one plane, a run of another program, and
        a run with no collective (one device): the tail is found by the
        collective's op NAME inside each run."""
        ag = "%all-gather.2 = s32[4,2,1,16]{3,2,1,0} all-gather(%fusion.24), channel_id=2"
        sort = "%sort.5 = (f32[1,64], s32[1,64]) sort(%a, %b)"
        scan = "%while.4 = (s32[], f32[1,128]) while(%t)"
        assert xplane_sharded.is_collective(ag)
        assert xplane_sharded.is_collective("%all-gather-start.1 = (s32[2,1,16]) all-gather-start(%x)")
        assert not xplane_sharded.is_collective(sort)
        lines = [
            ("XLA Modules", [("jit__sharded_topk(123)", 0.0, 10.0),
                             ("jit__other(9)", 10.0, 20.0),
                             ("jit__sharded_topk(123)", 20.0, 30.0),
                             ("jit__sharded_topk(123)", 40.0, 50.0)]),
            ("XLA Ops", [(scan, 0.0, 6.0), (sort, 6.0, 7.0), (ag, 7.0, 9.0), (sort, 9.0, 9.5),
                         (ag, 12.0, 13.0),  # another program's: not counted
                         (scan, 20.0, 26.0), (ag, 26.0, 29.0), (sort, 29.0, 30.0),
                         (scan, 40.0, 49.0), (sort, 49.0, 50.0)]),  # no collective
        ]
        total, runs, by_op = xplane_sharded.shard_tail(lines)
        assert runs == 2 and total == pytest.approx(2.5 + 4.0)
        assert by_op["all-gather.2 s32[4,2,1,16]"] == pytest.approx(5.0)
        assert xplane_sharded.shard_tail([("XLA Ops", [])]) == (0.0, 0, {})


class TestWriterAndDriver:
    def test_the_driver_refuses_a_program_without_the_spanning_format(self, monkeypatch):
        from drivers import sharded
        from drivers.common import BenchFailure
        from predictionio_tpu.models import modelfile

        sharded.require_spanning_format()  # this program has it
        monkeypatch.delattr(modelfile, "write_spanning")
        with pytest.raises(BenchFailure, match="write_spanning.*nothing was written"):
            sharded.require_spanning_format()
        with pytest.raises(SystemExit) as e:
            write_sharded.spanning_format()
        assert e.value.code == 2

    def test_the_writer_fails_by_name_where_the_model_does_not_fit(self, tmp_path):
        with pytest.raises(SystemExit, match="bytes free"):
            write_sharded.probe_directory(str(tmp_path), 1 << 60, 1 << 20)
        write_sharded.probe_directory(str(tmp_path), 1 << 20, 1 << 20)
        assert os.listdir(tmp_path) == []  # the probe file is gone
        # the model's bytes, reckoned before anything is written
        assert 12.6e9 < write_sharded.model_bytes(1_000_000, 48_190_000, 64) < 13.6e9

    @pytest.mark.parametrize("n", [0, 1, 9, 10, 11, 1000, 1001, 123_457])
    def test_ids_by_spans_are_the_one_calls_ids(self, n):
        import modelwriter

        want = modelwriter.dense_id_blob(b"i", n)
        got = write_sharded.dense_ids(b"i", n, workers=3, span=1000)
        for a, b in zip(got, want):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(a, b)

    def test_a_written_model_is_the_seeded_tables_in_segments(self, tmp_path, monkeypatch):
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
        cfg = _cell()["config"]
        spec = {"seed": 11, "num_users": 500, "num_items": 30_000, "rank": 16,
                "variant": cfg["variant"], "variant_label": "engine.json",
                "segment_bytes": 1 << 20, "workers": 3}
        (tmp_path / "spec.json").write_text(json.dumps(spec))
        import io
        from contextlib import redirect_stdout

        out = io.StringIO()
        with redirect_stdout(out):
            assert write_sharded.main(["write_sharded.py", str(tmp_path / "spec.json")]) == 0
        told = json.loads(out.getvalue().strip().splitlines()[-1])
        segs = [p for p in (tmp_path / "models").iterdir() if ".seg" in p.name]
        assert told["segments"] == len(segs) >= 3
        assert all(p.stat().st_size <= 1 << 20 for p in segs)
        from predictionio_tpu.models import modelfile

        head = next(p for p in (tmp_path / "models").iterdir() if p.name.endswith(".bin"))
        f = modelfile.load_path(head).fields(0)
        np.testing.assert_array_equal(
            np.asarray(f["item_factors"]), factors.item_factors(11, 30_000, 16))
        np.testing.assert_array_equal(f["user_factors"], factors.user_factors(11, 500, 16))
        assert f["item_index"].inverse[29_999] == "i29999" and f["user_index"]["u499"] == 499


BROKEN = '''
import sys
from predictionio_tpu.parallel import shard_topk
_sound = shard_topk._merge
def _merge(all_s, all_i, k):
    # one shard's answers dropped before the merge
    return _sound(all_s.at[1].set(-1e30), all_i, k)
shard_topk._merge = _merge
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


def test_a_dropped_shard_is_not_correct(tmp_path):
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
    assert not checks["overlap_min"]["pass"]
    assert checks["score_gap_max"]["pass"]  # what was served was scored right


def test_the_sound_cell_rehearses_on_the_cpu_with_its_control(tmp_path):
    proc = _bench(tmp_path, "--seed", str(2**31 + 5), "--seconds", "3", "--trace", "1",
                  "--control", "1")
    assert proc.returncode == 3, proc.stdout[-3000:] + proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    checks = _checks(proc)
    assert checks["score_gap_max"]["pass"] and checks["overlap_min"]["value"] == 1.0
    assert checks["unsharded_queries"]["value"] == 0
    assert checks["extra_host_reads"]["pass"]
    assert not checks["control.score_gap_max(bfloat16)"]["pass"]
    times = json.loads(next(ln for ln in lines if ln.startswith("times: "))[7:])
    assert times["model_segments"] >= 8  # 1 MiB segments at toy size
    assert len(times["memory_by_device"]) == 4
    assert times["model_load"]["stage_to_device"]["count"] == 4  # a shard a device
    would = json.loads(next(ln for ln in lines if ln.startswith("would print: "))[13:])
    assert would["device"]["count"] == 4
    # the three device-trace metrics read a TPU's planes: nothing on the CPU
    assert set(would["metrics"]) >= {"shortlist_ms.sharded", "fetch_ms.sharded",
                                     "shortlist_size_mean.sharded", "dispatch_ms"} | CHAIN
    assert would["metrics"]["shortlist_size_mean.sharded"]["value"] == 128.0
