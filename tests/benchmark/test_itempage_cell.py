"""The item-page cell's own pieces: the manifest with it, the seeded traffic,
the plain reference of the Similar Product template, the model-file writer
against the program's serializer, the new readers against hand sums, and the
rest of a run with the served path broken underneath: `correct` has to come
out false."""

from __future__ import annotations

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
import itempage_data  # noqa: E402
import manifest as manifest_rules  # noqa: E402
import readers  # noqa: E402
import reference_similarproduct as ref  # noqa: E402
import run as bench_run  # noqa: E402

MANIFEST = os.path.join(REPO, "BENCHMARK.json")
RUN = os.path.join(REPO, "benchmark", "run.py")
CELL = "similarproduct-taobao.serve-itempage"
CONFIG = "similarproduct-taobao"
METRICS = os.path.join(REPO, "benchmark", "metrics")
SHARES = {"similar": 0.6, "same_category": 0.25, "session": 0.15}
OWN = {"similar_build_ms", "shortlist_ms", "rescore_ms", "fetch_ms", "shortlist_size_mean",
       "exact_path_share", "masked_shortlist_roofline", "sumrows_rescore_roofline"}
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
    def test_it_validates_with_the_cell(self):
        assert manifest_rules.validate(_manifest(), REPO) == []

    def test_one_configuration_and_one_cell_were_added(self):
        m = _manifest()
        conf = [c for c in m["configs"] if c["name"] == CONFIG]
        assert len(conf) == 1 and m["configs"][-1] is conf[0] and conf[0]["reduced"] == []
        assert "Taobao UserBehavior" in conf[0]["source"] and "Similar Product" in conf[0]["source"]
        cells = [w for w in m["workloads"] if w["config"] == CONFIG]
        assert cells == [m["workloads"][-1]] and cells[0]["name"] == CELL
        assert cells[0]["chips"] == 1 and cells[0]["traffic"] == "taobao-itempage"

    def test_the_cells_traced_metrics(self):
        """Eight of its own (.itempage), the span chain's eight with the cell
        appended to their lists, and the seven that list no cells."""
        m = _manifest()
        names = {d["name"] for d in bench_run.metrics_for(m, CELL, True)}
        assert names == {n + ".itempage" for n in OWN} | CHAIN | LISTLESS
        assert {d["name"] for d in bench_run.metrics_for(m, CELL, False)} == \
            {"query_p50_ms", "setup_s"}
        for other in (w["name"] for w in m["workloads"] if w["name"] != CELL):
            assert not any(d["name"].endswith(".itempage")
                           for d in bench_run.metrics_for(m, other, True))
        for p in m["per_layer"]:  # the cell is LAST in every list it was appended to
            if p["name"] in CHAIN:
                assert p["workloads"][-1] == CELL
            if p["name"].endswith(".itempage"):
                assert p["workloads"] == [CELL] and p["moves"] == "query_p50_ms"
        cell = _cell()
        for n in OWN:  # every reader is there and finds nothing in an empty run
            assert readers.load_metric(METRICS, n + ".itempage")(
                {"device": {"kind": "TPU v5 lite"}}, cell) is None

    def test_the_traffic_is_the_issues(self):
        mix = _cell()["traffic"]
        assert (mix["loop"], mix["num"], mix["connections"]) == ("open", 10, 256)
        assert mix["shares"] == SHARES and mix["driver"] == "itempage"
        assert mix["rate_qps"] == 0.2 * mix["knee_qps"] == 100.0  # ISSUE 30: 0.2 x the ladder's knee
        cfg = _cell()["config"]
        assert cfg["num_items"] == 4162024 and cfg["rank"] == 128 and cfg["reduced"] == []
        assert cfg["retrieval"] == {"threshold": 100000, "oversample": 8, "tile": 262144,
                                    "coarse_dtype": "bfloat16"}
        assert cfg["deploy_flags"] == ["--batch-window-ms", "2", "--no-warmup"]
        assert set(cfg["limits"]) == {"excluded_served", "score_gap_max", "overlap_min",
                                      "overlap_mean_min"}


class TestTraffic:
    def test_request_i_reads_the_same_whatever_the_count_drawn(self):
        few = itempage_data.requests(9, 50, 40000, SHARES)
        many = itempage_data.requests(9, 5000, 40000, SHARES)
        for key in few:
            assert (few[key] == many[key][:50]).all(), key
        share = np.bincount(many["kind"], minlength=3) / 5000.0
        assert np.allclose(share, [0.6, 0.25, 0.15], atol=0.03)
        session = many["kind"] == itempage_data.SESSION
        assert (many["n_items"][~session] == 1).all()
        assert many["n_items"][session].max() == 8 and many["n_items"][session].min() >= 1
        assert np.mean(many["n_items"][session] >= 2) > 0.97  # 2..8 unless two draws met
        assert many["n_black"].min() == 1 and many["n_black"].max() == 5
        for i in np.flatnonzero(session)[:200].tolist():
            own = many["items"][i, : many["n_items"][i]]
            assert len(set(own.tolist())) == len(own)  # a strip holds an item once

    def test_query_items_follow_zipf_popularity(self):
        r = itempage_data.requests(3, 20000, 40000, SHARES)
        ranked = itempage_data.by_popularity(3, 40000)
        rank_of = np.empty(40000, np.int64)
        rank_of[ranked] = np.arange(40000)
        lead = rank_of[r["items"][:, 0]]
        # P(rank < r) = log(r + 1) / log(N + 1): half the draws in the top ~200
        assert abs(np.mean(lead < 199) - np.log(200) / np.log(40001)) < 0.02
        assert abs(np.mean(lead == 0) - np.log(2) / np.log(40001)) < 0.01

    @pytest.mark.parametrize("kind,needles", [
        (itempage_data.SIMILAR, [b'{"items":["i', b'"num":10}']),
        (itempage_data.SAME_CATEGORY, [b'"categories":["c']),
        (itempage_data.SESSION, [b'"blackList":["i']),
    ])
    def test_request_bodies(self, kind, needles):
        r = itempage_data.requests(3, 400, 40000, SHARES)
        cat = ecomm_data.item_categories(3, 40000, 60)
        i = int(np.flatnonzero(r["kind"] == kind)[0])
        body = itempage_data.request_body(10, r, i, cat)
        assert all(n in body for n in needles)
        parsed = json.loads(body)
        items, black, c = itempage_data.query_of(r, i, cat)
        assert parsed["items"] == [f"i{x}" for x in items] and parsed["num"] == 10
        assert parsed.get("blackList", []) == [f"i{x}" for x in black]
        if kind == itempage_data.SAME_CATEGORY:
            assert parsed["categories"] == [f"c{cat[items[0]]}"] and c == cat[items[0]]
        else:
            assert "categories" not in parsed and c is None
        assert ("blackList" in parsed) == (kind == itempage_data.SESSION)


class TestReference:
    def test_unit_rows_and_query_vectors(self):
        raw = np.asarray([[3, 4], [0, 2], [0, 0], [1, 0]], np.float32)
        unit = ref.unit_rows(raw, block=3)
        assert np.allclose(unit, [[0.6, 0.8], [0, 1], [0, 0], [1, 0]])
        q = ref.query_vectors(unit, [[0], [0, 1, 3], [3, 3]])
        assert np.allclose(q, [[0.6, 0.8], [1.6, 1.8], [2, 0]])  # listed twice counts twice

    @pytest.mark.parametrize("columns", [1, 3])
    @pytest.mark.parametrize("block", [512, 1 << 18])
    def test_top_k_allowed_against_brute_force(self, block, columns):
        rng = np.random.default_rng(0)
        n, d, s, k = 5000, 16, 12, 10
        unit = ref.unit_rows(rng.standard_normal((n, d)).astype(np.float32))
        cat = rng.integers(0, 40, (n, columns)).astype(np.int32)
        cat[np.flatnonzero((cat == 39).any(axis=1))[3:]] = 0  # category 39 holds three items
        if columns == 1:
            cat = cat[:, 0]
        own = [rng.choice(n, rng.integers(1, 9), replace=False) for _ in range(s)]
        ex = [np.union1d(o, rng.choice(n, rng.integers(0, 6), replace=False)) for o in own]
        qc = [None if r % 3 else [int(rng.integers(0, 40)), 5][: 1 + r % 2] for r in range(s)]
        qc[0], qc[3] = [39], []
        q = ref.query_vectors(unit, own)
        bs, bi = ref.top_k_allowed(q, unit, k, excluded=ex, item_category=cat,
                                   query_categories=qc, block=block)
        for r in range(s):
            sc = unit @ q[r]
            ok = np.ones(n, bool)
            ok[ex[r]] = False
            if qc[r] is not None:
                ok &= np.isin(cat, qc[r]).reshape(n, -1).any(axis=1)
            idx = np.flatnonzero(ok)
            want = idx[np.lexsort((idx, -sc[idx]))][:k]
            assert (bi[r, :len(want)] == want).all() and (bi[r, len(want):] == -1).all()
            assert np.allclose(bs[r, :len(want)], sc[want], atol=1e-5)
            assert ref.allowed_count(n, excluded=ex[r], item_category=cat,
                                     query_categories=qc[r]) == len(idx)
            assert ref.excluded_served(want, excluded=ex[r], item_category=cat,
                                       query_categories=qc[r]) == 0
        assert (bi[0] >= 0).sum() <= 3 and (bi[3] >= 0).sum() == 0  # short and empty answers

    def test_the_controls_move_what_they_should(self):
        rng = np.random.default_rng(1)
        unit = ref.unit_rows(rng.standard_normal((3000, 128)).astype(np.float32))
        own = [[5], [7, 9, 11], [13], [17]]
        q = ref.query_vectors(unit, own)
        cat = (np.arange(3000) % 3).astype(np.int32)
        kw = dict(excluded=[np.asarray(o) for o in own], item_category=cat,
                  query_categories=[[0], None, [2], [1]])
        _, sound = ref.top_k_allowed(q, unit, 10, **kw)
        _, without = ref.top_k_allowed(q, unit, 10, apply_category=False, **kw)

        def served(ids):
            return sum(ref.excluded_served(row, excluded=kw["excluded"][n], item_category=cat,
                                           query_categories=kw["query_categories"][n])
                       for n, row in enumerate(ids))

        assert served(sound) == 0 and served(without) > 0
        s16, i16 = ref.top_k_allowed(q, unit, 10, precision="bfloat16", **kw)
        assert np.abs(s16[0] - unit[i16[0]] @ q[0]).max() > 1e-4

    def test_excluded_served_and_allowed_count_by_hand(self):
        cat = np.asarray([0, 0, 1, 1, 0, 0, 1, 1, 0, 0], np.int32)
        ex = np.asarray([2, 7])  # the query's own item 2 and a black-listed 7
        kw = dict(excluded=ex, item_category=cat)
        assert ref.excluded_served([0, 1, 5], query_categories=None, **kw) == 0
        assert ref.excluded_served([2, 4, 7, 0], query_categories=None, **kw) == 2
        assert ref.excluded_served([0, 3, 6], query_categories=[1], **kw) == 1  # 0 is outside
        assert ref.excluded_served([2, 3], query_categories=[1], **kw) == 1  # own item served back
        assert ref.excluded_served([], query_categories=[1], **kw) == 0
        assert ref.allowed_count(10, query_categories=[1], **kw) == 2  # {3, 6}
        assert ref.allowed_count(10, query_categories=None, **kw) == 8
        assert ref.allowed_count(10, query_categories=[], **kw) == 0
        two = np.asarray([[0, -1], [1, 0], [2, -1]], np.int32)
        assert ref.allowed_count(3, excluded=np.asarray([0]), item_category=two,
                                 query_categories=[0]) == 1  # row 1, by its second column


class TestWriter:
    def test_the_model_file_is_the_programs_own_bytes(self):
        import write_similar
        from predictionio_tpu.data.bimap import BiMap
        from predictionio_tpu.models import modelfile
        from predictionio_tpu.models.similarproduct import SimilarProductModel

        V = np.random.default_rng(2).standard_normal((1017, 8)).astype(np.float32)
        cat = ecomm_data.item_categories(2, 1017, 12)
        fast = write_similar.model_blob(modelfile, "m1", V, cat, 12)
        model = SimilarProductModel(
            item_index=BiMap.from_dense([f"i{n}" for n in range(1017)]), item_factors=V,
            category_index=BiMap.from_dense([f"c{n}" for n in range(12)]),
            item_categories=cat.reshape(-1, 1),
        )
        assert bytes(fast) == modelfile.serialize([("arrays", model)], "m1")
        back = modelfile.deserialize(bytes(fast))[0][1]
        assert back.item_categories[:, 0].tolist() == cat.tolist()
        assert back.category_index["c11"] == 11 and back.item_index["i1016"] == 1016
        assert back.categories is None and back.item_scales is None


class TestReaders:
    def test_masked_shortlist_bytes_are_the_storefronts(self):
        m = _metric_module("masked_shortlist_roofline.itempage")
        # 16 tiles x 262,144 rows; a row: 128 bf16 values, an int32 id, an
        # availability byte, one int32 category id = 265 B; one f32 query of 128
        assert m.masked_shortlist_bytes(4162024, 128, 262144, "bfloat16") == \
            4194304 * 265 + 512 == 1111491072
        assert m.PROGRAM == "jit__coarse_topk_masked"
        read = readers.load_metric(METRICS, "masked_shortlist_roofline.itempage")
        raw = {"device": {"kind": "TPU v5 lite"},
               "trace": {"programs": {"jit__coarse_topk_masked": 0.5, "jit__coarse_topk": 9.0},
                         "program_calls": {"jit__coarse_topk_masked": 100}}}
        assert read(raw, _cell()) == pytest.approx(100.0 * 100 * (1111491072 / 819e9) / 0.5)
        raw["trace"] = {"programs": {"jit__coarse_topk": 9.0}, "program_calls": {}}
        assert read(raw, _cell()) is None  # a program without the masked scan

    def test_sumrows_rescore_bytes_against_a_hand_sum(self):
        m = _metric_module("sumrows_rescore_roofline.itempage")
        # one query: 1 summed row + 128 candidates, each 128 f32 values and an int32 id
        assert m.sumrows_rescore_bytes(128, 128, 1) == 129 * (512 + 4) == 66564
        assert m.sumrows_rescore_bytes(128, 128, 8, batch=2) == 2 * 136 * 516
        assert m.sumrows_rescore_flops(128, 128, 1) == 2 * 128 * 128 + 128

    def test_sumrows_rescore_roofline_reads_its_program_and_counters(self):
        read = readers.load_metric(METRICS, "sumrows_rescore_roofline.itempage")
        delta = {"pio_retrieval_shortlist_size_sum": 12800.0,
                 "pio_retrieval_shortlist_size_count": 100.0,
                 "pio_similar_query_rows_sum": 200.0, "pio_similar_query_rows_count": 100.0,
                 "pio_batch_size_sum": 100.0, "pio_batch_size_count": 100.0}
        raw = {"device": {"kind": "TPU v5 lite"}, "counters_delta": delta,
               "trace": {"programs": {"jit__rescore_sum_rows_masked": 0.004},
                         "program_calls": {"jit__rescore_sum_rows_masked": 100}}}
        least = 130 * 516 / 819e9  # bandwidth binds: 82 ns a call
        assert read(raw, _cell()) == pytest.approx(100.0 * 100 * least / 0.004)
        assert 0 < read(raw, _cell()) < 100
        # the parent has no such program and no such counter: nothing to read
        raw["trace"] = {"programs": {"jit__rescore_sum_rows": 0.004},
                        "program_calls": {"jit__rescore_sum_rows": 100}}
        assert read(raw, _cell()) is None
        assert read({"device": raw["device"], "counters_delta": {}, "trace": {}}, _cell()) is None
        assert read({"device": raw["device"]}, _cell()) is None

    def test_exact_path_share_is_the_storefronts_reader(self):
        read = readers.load_metric(METRICS, "exact_path_share.itempage")
        two, exact = ('pio_retrieval_queries_total{path="two_stage"}',
                      'pio_retrieval_queries_total{path="exact"}')
        assert read({"counters_delta": {two: 90.0, exact: 10.0}}, {}) == pytest.approx(10.0)
        assert read({"counters_delta": {two: 90.0, exact: 0.0}}, {}) == 0.0
        assert read({"counters_delta": {}}, {}) is None

    def test_similar_build_reads_its_histogram_or_nothing(self):
        read = readers.load_metric(METRICS, "similar_build_ms.itempage")
        delta = {"pio_similar_build_seconds_sum": 0.12, "pio_similar_build_seconds_count": 100.0}
        assert read({"counters_delta": delta}, {}) == pytest.approx(1.2)
        assert read({"counters_delta": {"pio_ecomm_rules_seconds_sum": 1.0}}, {}) is None


BROKEN = '''
import sys
import numpy as np
from predictionio_tpu.models import similarproduct
from predictionio_tpu.ops import retrieval, topk
%s
from predictionio_tpu.cli.main import main
sys.exit(main(sys.argv[1:]))
'''
ALTERED_SCORE = '''
_sound = retrieval._score_candidates
def _score_candidates(qvecs, item_factors, cand_ids, k, rules=None):
    s, ids = _sound(qvecs, item_factors, cand_ids, k, rules)
    return s * 0.999, ids  # the served scores altered where they are produced
retrieval._score_candidates = _score_candidates
'''
NO_CATEGORY_RULE = '''
_sound = topk.rows_allowed
def rows_allowed(av, cs, hit, qcat, has_cat):
    return _sound(av, cs, hit, qcat, has_cat & False)  # the category rule skipped
topk.rows_allowed = retrieval.rows_allowed = rows_allowed
'''
OWN_ITEM_SERVED = '''
_sound = topk.rows_allowed
def rows_allowed(av, cs, hit, qcat, has_cat):
    return _sound(av, cs, hit & False, qcat, has_cat)  # a query's own items served back
topk.rows_allowed = retrieval.rows_allowed = rows_allowed
'''


@pytest.mark.parametrize("fault,failing,sound", [
    (ALTERED_SCORE, "score_gap_max", "excluded_served"),
    (NO_CATEGORY_RULE, "excluded_served", "score_gap_max"),
    (OWN_ITEM_SERVED, "excluded_served", "score_gap_max"),
], ids=["score", "category", "own_item"])
def test_the_served_path_broken_underneath_is_not_correct(tmp_path, fault, failing, sound):
    entry = tmp_path / "broken_server.py"
    entry.write_text(BROKEN % fault)
    m = _manifest()
    with open(os.path.join(REPO, "benchmark", "configs", CONFIG + ".json")) as fh:
        cfg = json.load(fh)
    cfg["server_entry"] = [str(entry)]
    (tmp_path / "broken.json").write_text(json.dumps(cfg))
    for c in m["configs"]:
        if c["name"] == CONFIG:
            c["file"] = str(tmp_path / "broken.json")
    (tmp_path / "manifest.json").write_text(json.dumps(m))
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", TMPDIR=str(tmp_path), PYTHONPATH="",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jc"))
    proc = subprocess.run(
        [sys.executable, RUN, "--manifest", str(tmp_path / "manifest.json"), "--workload", CELL,
         "--seed", "77", "--seconds", "2", "--trace", "0", "--dry-run-cpu"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=600)
    assert proc.returncode == 1, proc.stdout[-2000:] + proc.stderr[-2000:]
    assert "dry run on cpu: NOT correct" in proc.stdout
    checks = {c["name"]: c for c in (
        json.loads(ln[7:]) for ln in proc.stdout.splitlines() if ln.startswith("check: "))}
    assert not checks[failing]["pass"]
    assert checks[sound]["pass"]  # one number fails, and it is the right one


def test_the_sound_cell_rehearses_on_the_cpu(tmp_path):
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", TMPDIR=str(tmp_path), PYTHONPATH="", BENCH_RUN="ignored",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jc"))
    proc = subprocess.run(
        [sys.executable, RUN, "--workload", CELL, "--seed", str(2**31 + 5), "--seconds", "3",
         "--trace", "1", "--control", "1", "--dry-run-cpu"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=600)
    assert proc.returncode == 3, proc.stdout[-3000:] + proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    checks = {c["name"]: c for c in (json.loads(ln[7:]) for ln in lines if ln.startswith("check: "))}
    for kind in itempage_data.KINDS:  # every kind was asked, and held per kind
        assert checks["score_gap_max." + kind]["pass"]
    assert checks["excluded_served"]["value"] == 0 and checks["exact_path_queries"]["value"] == 0
    assert not checks["control.score_gap_max(bfloat16)"]["pass"]
    assert not checks["control.excluded_served(no category rule)"]["pass"]
    would = json.loads(next(ln for ln in lines if ln.startswith("would print: "))[13:])
    got = set(would["metrics"])
    # the two rooflines read a TPU's device trace: nothing on the CPU
    assert got >= {n + ".itempage" for n in OWN - {"masked_shortlist_roofline",
                                                   "sumrows_rescore_roofline"}} | CHAIN
    assert would["metrics"]["shortlist_size_mean.itempage"]["value"] == 128.0
    assert would["metrics"]["exact_path_share.itempage"]["value"] == 0.0
