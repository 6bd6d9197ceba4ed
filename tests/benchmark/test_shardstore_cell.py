"""The sharded storefront cell's own pieces: the manifest with it (two four-chip
cells of eight), its configuration's shapes and what it says it cut and
assumed, the traffic's parameters, configuration / traffic / driver / every
reader found by name with no harness edit, the chunked reference under rules
against the one-table one, the readers against hand sums, the writer's refusal
of a program that cannot serve the deployment, and the toy rehearsal of the
whole cell: sound it ends `correct`, with a shard that ignores the unavailable
rule it does not."""

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

import ecomm_data  # noqa: E402
import factors  # noqa: E402
import manifest as manifest_rules  # noqa: E402
import readers  # noqa: E402
import reference_ecommerce as ref  # noqa: E402
import reference_ecommerce_sharded as ref_sharded  # noqa: E402
import run as bench_run  # noqa: E402
import shardstore_costs  # noqa: E402
import write_shardstore  # noqa: E402
import xplane_shardstore  # noqa: E402

MANIFEST = os.path.join(REPO, "BENCHMARK.json")
RUN = os.path.join(REPO, "benchmark", "run.py")
CELL = "ecommerce-amazon23.serve-storefront-sharded"
TWIN = "recommendation-amazon23.serve-sharded-steady"
CONFIG = "ecommerce-amazon23"
METRICS = os.path.join(REPO, "benchmark", "metrics")
OWN = {"masked_shard_scan_roofline", "shortlist_ms", "fetch_ms", "rules_build_ms",
       "seen_read_ms", "shard_merge_ms", "shard_busy_skew", "shortlist_size_mean",
       "exact_path_share"}
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

    def test_two_four_chip_cells_of_eight(self):
        m = _manifest()
        four = {w["name"] for w in m["workloads"] if w["chips"] == 4}
        assert four == {CELL, TWIN} and len(m["workloads"]) == 8
        assert len(four) <= max(1, len(m["workloads"]) // 4)
        cell = next(w for w in m["workloads"] if w["name"] == CELL)
        assert (cell["config"], cell["traffic"]) == (CONFIG, "amazon23-storefront-sharded")
        assert len(cell["why"]) <= 200
        p50 = next(e for e in m["end_to_end"] if e["name"] == "query_p50_ms")
        assert CELL in p50["workloads"]
        conf = next(c for c in m["configs"] if c["name"] == CONFIG)
        assert conf["reduced"] == ["users", "events"] and len(conf["source"]) <= 200
        assert conf["source"] == _cell()["config"]["source"]

    def test_the_cells_metrics_list_it(self):
        m = _manifest()
        by_name = {p["name"]: p for p in m["per_layer"]}
        for name in OWN:
            assert by_name[name + ".shardstore"]["workloads"] == [CELL]
            assert by_name[name + ".shardstore"]["moves"] == "query_p50_ms"
        for name in CHAIN:
            assert CELL in by_name[name]["workloads"]
        for name in LISTLESS:
            assert "workloads" not in by_name[name]
        roof = by_name["masked_shard_scan_roofline.shardstore"]
        assert (roof["unit"], roof["source"], roof["better"]) == ("%", "device_trace", "higher")

    def test_everything_is_found_by_name(self):
        """Configuration, traffic mix, driver, generator and every reader,
        through the harness as it stands."""
        cell = _cell()
        assert cell["traffic"]["driver"] == "shardstore"
        assert importlib.import_module("drivers.shardstore").run
        assert os.path.exists(os.path.join(REPO, "benchmark", cell["traffic"]["generator"]))
        defs = bench_run.metrics_for(_manifest(), CELL, trace=True)
        assert {d["name"] for d in defs} == \
            {n + ".shardstore" for n in OWN} | CHAIN | LISTLESS
        for d in defs:
            assert callable(readers.load_metric(METRICS, d["name"]))
        assert [d["name"] for d in bench_run.metrics_for(_manifest(), CELL, trace=False)] \
            == ["query_p50_ms", "setup_s"]


class TestConfiguration:
    def test_the_published_shapes_are_uncut(self):
        cfg = _cell()["config"]
        assert (cfg["num_items"], cfg["rank"], cfg["factor_dtype"]) == (48_190_000, 64, "float32")
        assert cfg["num_categories"] == 33 == cfg["published"]["categories"]
        assert cfg["categories_per_item"] == 1
        assert cfg["published"]["items"] == cfg["num_items"]
        assert cfg["published"]["users"] == 54_510_000
        assert cfg["published"]["reviews"] == 571_540_000
        assert cfg["architecture"] is None and cfg["chips"] == 4 and cfg["mesh"] == {"data": 4}
        assert cfg["retrieval"] == {"threshold": 100000, "oversample": 8, "tile": 262144,
                                    "coarse_dtype": "bfloat16"}
        # the tables at the stated precision fit no single chip
        table = cfg["num_items"] * cfg["rank"] * 4
        assert table + table // 2 > 16e9

    def test_reduced_and_assumed_say_what_was_cut(self):
        cfg = _cell()["config"]
        assert cfg["reduced"] == ["users", "events"]
        assert cfg["num_users"] == 1_000_000
        assert cfg["events"] == {"active_users": 100_000, "count": 1_050_000, "buy_share": 1.0}
        # the source's mean, reviews over users, is what a seen set follows
        assert cfg["events"]["count"] / cfg["events"]["active_users"] == pytest.approx(
            cfg["published"]["reviews"] / cfg["published"]["users"], rel=2e-3)
        assert cfg["unavailable_items"] == cfg["num_items"] // 1000
        assert set(cfg["assumed"]) >= {"users", "events", "rank", "factors", "category_sizes",
                                       "unavailable_items", "weights", "training"}
        assert "buy" in cfg["assumed"]["events"] and "Zipf" in cfg["assumed"]["category_sizes"]
        sizes = ecomm_data.category_sizes(cfg["num_items"], cfg["num_categories"])
        assert f"{sizes[0]:,}" in cfg["assumed"]["category_sizes"]
        assert f"{sizes[-1]:,}" in cfg["assumed"]["category_sizes"]

    def test_the_template_is_deployed_as_documented_on_the_mesh(self):
        cfg = _cell()["config"]
        assert cfg["variant"]["engineFactory"] == "predictionio_tpu.models.ecommerce.engine"
        algo = cfg["variant"]["algorithms"][0]["params"]
        assert algo["unseenOnly"] is True and algo["seenEvents"] == ["buy", "view"]
        assert algo["shardedServing"] is True and algo["rank"] == 64
        assert "weights" not in algo
        assert cfg["deploy_flags"] == ["--batch-window-ms", "2", "--no-warmup",
                                       "--mesh", "data=4"]
        assert "--query-cache-mb" not in cfg["deploy_flags"]
        from predictionio_tpu.models.ecommerce import ECommAlgorithmParams

        params = ECommAlgorithmParams.from_dict(algo)
        assert params.sharded_serving and params.unseen_only
        assert tuple(params.seen_events) == ("buy", "view")

    def test_guarantees_and_limits_are_stated(self):
        cfg = _cell()["config"]
        assert set(cfg["guarantees"]) == {"rescore", "rules", "live", "merge", "checked"}
        assert {k: v["limit"] for k, v in cfg["limits"].items()} == {
            "excluded_served": 0, "score_gap_max": 1e-4, "overlap_min": 0.9,
            "overlap_mean_min": 0.999}
        assert all(v["why"] for v in cfg["limits"].values())
        toy = cfg["toy"]
        assert "device_count=4" in toy["server_env"]["XLA_FLAGS"]
        assert toy["num_items"] < 100_000

    def test_the_traffic_is_the_issues(self):
        mix = _cell()["traffic"]
        assert mix["shares"] == {"home": 0.7, "category": 0.2, "cart": 0.1}
        assert (mix["num"], mix["loop"], mix["users"]) == (10, "open", "uniform-distinct")
        assert (mix["warm_in_s"], mix["trace_seconds"], mix["connections"]) == (3.0, 1.0, 256)
        assert mix["generator"] == "loadgen_storefront.py"
        assert mix["rate_qps"] == pytest.approx(0.1 * mix["knee_qps"])
        assert "->" in mix["why_rate"]  # the ladder's rungs are written down


class TestReference:
    @pytest.fixture(autouse=True)
    def _small_chunks(self, monkeypatch):
        monkeypatch.setattr(factors, "CHUNK_ROWS", 1000)

    def _world(self, seed=9, items=4321, rank=16, cats=7, n=9):
        V = factors.item_factors(seed, items, rank)
        q = factors.user_factors(seed, 50, rank)[:n]
        rng = np.random.default_rng(seed)
        rules = dict(
            unavailable=np.sort(rng.choice(items, 400, replace=False)),
            excluded=[np.sort(rng.choice(items, int(m), replace=False))
                      for m in rng.integers(0, 30, n)],
            item_category=rng.integers(0, cats, items).astype(np.int32),
            query_category=[None if j % 3 else int(j % cats) for j in range(n)])
        return V, q, rules

    @pytest.mark.parametrize("precision", ["float32", "bfloat16"])
    @pytest.mark.parametrize("group", [256, 4])
    def test_the_chunked_reference_is_the_one_table_reference(self, precision, group):
        V, q, rules = self._world()
        s, i = ref.top_k_allowed(q, V, 10, precision=precision, **rules)
        served = i.copy()
        served[0, 3] = -1
        s2, i2, own = ref_sharded.scan(9, len(V), 16, q, 10, served=served,
                                       precision=precision, workers=3, group=group, **rules)
        np.testing.assert_array_equal(i2, i)
        # (a product's summation order moves with how many queries it is handed)
        np.testing.assert_allclose(s2, s, rtol=0, atol=1e-6)
        assert np.isnan(own[0, 3])
        for row in range(len(q)):
            live = served[row] >= 0
            np.testing.assert_allclose(own[row][live], V[served[row][live]] @ q[row],
                                       rtol=0, atol=1e-6)

    def test_a_small_category_gives_a_short_answer(self):
        V, q, rules = self._world()
        rules["item_category"][:] = 0
        rules["item_category"][[5, 1500, 4000]] = 6  # one in a chunk, chunks apart
        rules["query_category"] = [6] * len(q)
        rules["unavailable"] = np.asarray([1500])
        rules["excluded"] = [np.asarray([], np.int64)] * len(q)
        s, i, _ = ref_sharded.scan(9, len(V), 16, q, 10, **rules)
        assert (np.sort(i[:, :2], axis=1) == [5, 4000]).all() and (i[:, 2:] == -1).all()
        assert np.isinf(s[:, 2:]).all()

    def test_the_control_drops_the_rule_on_a_range_of_rows_only(self):
        V, q, rules = self._world(n=40)
        lo, hi = 1080, 2160
        _, i, _ = ref_sharded.scan(9, len(V), 16, q, 10, no_unavailable_rows=(lo, hi), **rules)
        flags = np.zeros(len(V), bool)
        flags[rules["unavailable"]] = True
        served = i[i >= 0]
        bad = served[flags[served]]
        assert len(bad) and ((bad >= lo) & (bad < hi)).all()
        _, sound, _ = ref_sharded.scan(9, len(V), 16, q, 10, **rules)
        assert not flags[sound[sound >= 0]].any()


class TestReaders:
    def test_scan_bytes_against_a_hand_sum(self):
        # 48.19 M rows over 4 chips: 12,047,500 a chip -> 46 tiles of 2^18 rows;
        # a row: 64 bf16 values, an int32 id, an int32 category, an availability
        # byte, and a mask byte a query
        assert shardstore_costs.shard_stored_rows(48_190_000, 262144, 4) == 46 * 262144
        assert shardstore_costs.masked_shard_scan_bytes(48_190_000, 64, 262144, 4) \
            == 46 * 262144 * (64 * 2 + 4 + 4 + 1 + 1) + 64 * 4 == 1_664_090_368
        assert shardstore_costs.masked_shard_scan_bytes(
            40_000, 64, 8192, 4, category_columns=2, batch=3) \
            == 2 * 8192 * (128 + 4 + 8 + 1 + 3) + 3 * 256

    def test_roofline_is_one_shards_bytes_over_the_slowest_shards_seconds(self):
        mod = _metric_module("masked_shard_scan_roofline.shardstore")
        cell = _cell()
        least = 1_664_090_368 / 819e9  # one shard's least time a dispatch
        raw = {"device": {"kind": "TPU v5 lite"}, "trace": {
            "program_calls": {mod.PROGRAM: 400},  # 100 dispatches on 4 planes
            "program_s_by_plane": {"a": 100 * 2 * least, "b": 100 * 4 * least,
                                   "c": 100 * 2 * least, "d": 100 * 3 * least}}}
        assert mod.read(raw, {}, cell) == pytest.approx(25.0)  # the slowest: b
        # a program without the masked sharded program: nothing, and no raise
        parent = {"device": raw["device"], "trace": {
            "programs": {"jit__sharded_topk": 1.0}, "program_calls": {"jit__sharded_topk": 4},
            "program_s_by_plane": {"a": 0.0, "b": 0.0}}}
        assert mod.read(parent, {}, cell) is None
        assert mod.read({"device": raw["device"], "trace": {}}, {}, cell) is None
        assert mod.read({"device": raw["device"]}, {}, cell) is None

    def test_merge_ms_and_busy_skew(self):
        merge = _metric_module("shard_merge_ms.shardstore")
        assert merge.read({"trace": {"shard_ops_s": 0.040, "shard_ops_calls": 400}}, {}, {}) \
            == pytest.approx(0.1)
        assert merge.read({"trace": {"shard_ops_s": 0.0, "shard_ops_calls": 0}}, {}, {}) is None
        assert merge.read({}, {}, {}) is None
        skew = _metric_module("shard_busy_skew.shardstore")
        raw = {"trace": {"busy_by_plane": {"a": 1.0, "b": 1.0, "c": 1.0, "d": 2.0}}}
        assert skew.read(raw, {}, {}) == pytest.approx(1.6)
        assert skew.read({"trace": {}}, {}, {}) is None and skew.read({}, {}, {}) is None

    def test_exact_path_share_reads_the_sharded_counters(self):
        mod = _metric_module("exact_path_share.shardstore")
        d = {'pio_retrieval_queries_total{path="exact"}': 1.0,
             'pio_retrieval_queries_total{path="sharded"}': 399.0,
             "pio_retrieval_sharded_masked_total": 400.0}
        assert mod.read({"counters_delta": d}, {}, {}) == pytest.approx(0.25)
        d['pio_retrieval_queries_total{path="exact"}'] = 0.0
        assert mod.read({"counters_delta": d}, {}, {}) == 0.0
        d.pop("pio_retrieval_sharded_masked_total")  # the parent has no such counter
        assert mod.read({"counters_delta": d}, {}, {}) is None
        assert mod.read({}, {}, {}) is None

    @pytest.mark.parametrize("name,series", [
        ("shortlist_ms", "pio_retrieval_shortlist_seconds"),
        ("fetch_ms", "pio_retrieval_fetch_seconds"),
        ("rules_build_ms", "pio_ecomm_rules_seconds"),
        ("seen_read_ms", "pio_ecomm_seen_read_seconds"),
    ])
    def test_the_span_readers_read_their_histograms_in_ms(self, name, series):
        read = readers.load_metric(METRICS, name + ".shardstore")
        d = {series + "_sum": 0.5, series + "_count": 100.0}
        assert read({"counters_delta": d}, {}) == pytest.approx(5.0)
        assert read({"counters_delta": {}}, {}) is None

    def test_the_masked_programs_seconds_are_taken_a_plane_at_a_time(self):
        lines = [("XLA Modules", [("jit__sharded_topk_masked(7)", 0.0, 2.0),
                                  ("jit__sharded_topk(7)", 2.0, 9.0),
                                  ("jit__sharded_topk_masked(7)", 10.0, 13.0)])]
        assert xplane_shardstore.program_seconds(lines) == pytest.approx(5.0)
        assert xplane_shardstore.program_seconds([("XLA Ops", [])]) == 0.0


class TestWriter:
    def test_a_program_without_sharded_storefront_serving_is_refused_at_once(
            self, monkeypatch, capsys):
        import dataclasses

        from predictionio_tpu.models import ecommerce

        assert write_shardstore.require_sharded_storefront()  # this program has it

        @dataclasses.dataclass
        class ParentParams:  # the parent's template: no such parameter
            app_name: str = ""

        monkeypatch.setattr(ecommerce, "ECommAlgorithmParams", ParentParams)
        with pytest.raises(SystemExit) as e:
            write_shardstore.require_sharded_storefront()
        assert e.value.code == 2
        assert "sharded_serving" in capsys.readouterr().err

    def test_a_written_model_loads_as_the_template_model_in_segments(
            self, tmp_path, monkeypatch):
        monkeypatch.setattr(factors, "CHUNK_ROWS", 4096)
        env = {"PIO_FS_BASEDIR": str(tmp_path / "store"),
               "PIO_STORAGE_SOURCES_DB_TYPE": "sqlite",
               "PIO_STORAGE_SOURCES_DB_PATH": str(tmp_path / "pio.db"),
               "PIO_STORAGE_SOURCES_FS_TYPE": "localfs",
               "PIO_STORAGE_SOURCES_FS_PATH": str(tmp_path / "models"),
               "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "DB",
               "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "DB",
               "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "FS"}
        for k, v in env.items():
            monkeypatch.setenv(k, v)
        cfg = _cell()["config"]
        spec = {"seed": 11, "num_users": 500, "num_items": 30_000, "num_categories": 12,
                "rank": 16, "unavailable_items": 30, "app_name": "Shop",
                "events": {"active_users": 100, "count": 1050, "buy_share": 1.0},
                "variant": cfg["variant"], "variant_label": "engine.json",
                "segment_bytes": 1 << 20, "workers": 3}
        (tmp_path / "spec.json").write_text(json.dumps(spec))
        import io
        from contextlib import redirect_stdout

        from predictionio_tpu.data.storage import set_storage

        out = io.StringIO()
        try:
            with redirect_stdout(out):
                assert write_shardstore.main(
                    ["write_shardstore.py", str(tmp_path / "spec.json")]) == 0
        finally:
            set_storage(None)
        told = json.loads(out.getvalue().strip().splitlines()[-1])
        segs = [p for p in (tmp_path / "models").iterdir() if ".seg" in p.name]
        assert told["segments"] == len(segs) >= 3 and told["events"] == 1050
        from predictionio_tpu.models import modelfile
        from predictionio_tpu.models.ecommerce import ECommModel

        head = next(p for p in (tmp_path / "models").iterdir() if p.name.endswith(".bin"))
        (kind, model), = modelfile.load_path(head).entries()
        assert kind == "arrays" and isinstance(model, ECommModel)
        assert isinstance(model.item_factors, modelfile.SpannedArray)  # never one array
        np.testing.assert_array_equal(
            np.asarray(model.item_factors), factors.item_factors(11, 30_000, 16))
        np.testing.assert_array_equal(
            np.asarray(model.item_categories)[:, 0], ecomm_data.item_categories(11, 30_000, 12))
        assert model.category_ids(["c3", "nope"]) == [model.category_index["c3"]]
        assert model.item_index.inverse[29_999] == "i29999"


FAULTY = '''
import sys
from predictionio_tpu.parallel import shard_topk
_sound = shard_topk.ShardedCatalog.row_vector
def row_vector(self, fill, dtype, pad, *rest):
    # shard 1 lost its availability vector: every row it holds reads available
    def faulty(lo, hi):
        v = fill(lo, hi)
        return v * 0 + 1 if dtype.__name__ == "uint8" and lo == self.rows_per_shard else v
    return _sound(self, faulty, dtype, pad, *rest)
shard_topk.ShardedCatalog.row_vector = row_vector
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


def test_a_shard_without_its_availability_vector_is_not_correct(tmp_path):
    """The fault the second control stands for, made in the program: `correct`
    comes out false by `excluded_served`, and by nothing else."""
    entry = tmp_path / "faulty_server.py"
    entry.write_text(FAULTY)
    m = _manifest()
    with open(os.path.join(REPO, "benchmark", "configs", CONFIG + ".json")) as fh:
        cfg = json.load(fh)
    cfg["server_entry"] = [str(entry)]
    cfg["toy"]["unavailable_items"] = 8000  # a fifth of the toy catalog: it shows at once
    (tmp_path / "faulty.json").write_text(json.dumps(cfg))
    for c in m["configs"]:
        if c["name"] == CONFIG:
            c["file"] = str(tmp_path / "faulty.json")
    (tmp_path / "manifest.json").write_text(json.dumps(m))
    proc = _bench(tmp_path, "--seed", "77", "--seconds", "2", "--trace", "0",
                  manifest=tmp_path / "manifest.json")
    assert proc.returncode == 1, proc.stdout[-2000:] + proc.stderr[-2000:]
    assert "dry run on cpu: NOT correct" in proc.stdout
    checks = _checks(proc)
    assert not checks["excluded_served"]["pass"] and checks["excluded_served"]["value"] > 0
    assert checks["score_gap_max"]["pass"]  # what was served was scored right


def test_the_sound_cell_rehearses_on_the_cpu_with_its_controls(tmp_path):
    proc = _bench(tmp_path, "--seed", str(2**31 + 5), "--seconds", "3", "--trace", "1",
                  "--control", "1")
    assert proc.returncode == 3, proc.stdout[-3000:] + proc.stderr[-3000:]
    assert "dry run on cpu: every phase passed" in proc.stdout
    lines = proc.stdout.strip().splitlines()
    checks = _checks(proc)
    assert checks["excluded_served"]["value"] == 0
    for kind in ("", ".home", ".category", ".cart"):
        assert checks["score_gap_max" + kind]["pass"]
        assert checks["overlap_min" + kind]["pass"]
    assert checks["live_probe.removed_items_served"]["value"] == 0
    assert checks["live_probe.score_gap_max"]["pass"]
    assert checks["unsharded_queries"]["value"] == 0
    assert checks["unmasked_sharded_queries"]["value"] == 0
    assert checks["extra_host_reads"]["pass"] and checks["compiles_in_window"]["pass"]
    assert not checks["control.score_gap_max(bfloat16)"]["pass"]
    assert "control.excluded_served(no unavailable rule on one shard)" in checks
    times = json.loads(next(ln for ln in lines if ln.startswith("times: "))[7:])
    assert times["model_segments"] >= 8  # 1 MiB segments at toy size
    assert len(times["memory_by_device"]) == 4
    assert times["model_load"]["stage_to_device"]["count"] == 4  # a shard a device
    assert times["resident_bytes"]["rules"] > 0
    would = json.loads(next(ln for ln in lines if ln.startswith("would print: "))[13:])
    assert would["correct"] is True and would["device"]["count"] == 4
    # the device-trace metrics read a TPU's planes: nothing on the CPU
    assert set(would["metrics"]) >= {
        "shortlist_ms.shardstore", "fetch_ms.shardstore", "rules_build_ms.shardstore",
        "seen_read_ms.shardstore", "shortlist_size_mean.shardstore",
        "exact_path_share.shardstore", "dispatch_ms"} | CHAIN
    assert would["metrics"]["shortlist_size_mean.shardstore"]["value"] == 128.0
    assert would["metrics"]["exact_path_share.shardstore"]["value"] == 0.0
