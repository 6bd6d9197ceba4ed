"""The storefront cell's own pieces: the seeded deployment data, the plain
reference of the business rules, the model-file writer against the program's
serializer, the new readers against hand sums, and the rest of a run with a
rule skipped where it is applied: `correct` has to come out false."""

from __future__ import annotations

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
import readers  # noqa: E402
import reference_ecommerce as ref  # noqa: E402
import run as bench_run  # noqa: E402

MANIFEST = os.path.join(REPO, "BENCHMARK.json")
RUN = os.path.join(REPO, "benchmark", "run.py")
CELL = "ecommerce-taobao.serve-storefront"
METRICS = os.path.join(REPO, "benchmark", "metrics")
SHARES = {"home": 0.7, "category": 0.2, "cart": 0.1}


def _cell():
    with open(MANIFEST) as fh:
        return bench_run.resolve(json.load(fh), CELL, REPO)


class TestDeploymentData:
    def test_category_sizes_are_zipf_and_cover_the_catalog(self):
        sizes = ecomm_data.category_sizes(4162024, 9439)
        assert sizes.sum() == 4162024 and sizes.min() == 45 and len(sizes) == 9439
        assert (np.diff(sizes) <= 0).all() and 1.9 < sizes[0] / sizes[1] < 2.1

    def test_every_item_has_one_category_from_the_seed(self):
        a = ecomm_data.item_categories(5, 4000, 60)
        assert a.dtype == np.int32 and len(a) == 4000 and set(a.tolist()) == set(range(60))
        assert (a == ecomm_data.item_categories(5, 4000, 60)).all()
        assert (a != ecomm_data.item_categories(6, 4000, 60)).any()
        assert (np.bincount(a) == ecomm_data.category_sizes(4000, 60)).all()

    def test_events_keep_the_source_ratio_and_fit_the_bucket(self):
        who, item, buy = ecomm_data.user_events(5, 40000, 400, 40560, 0.02)
        assert len(who) == 40560 and (np.diff(who) >= 0).all()
        counts = np.bincount(who)
        assert set(counts.tolist()) <= {101, 102}  # the source's 101.4 a user
        assert 0.01 < buy.mean() < 0.03
        seen = ecomm_data.seen_sets(who, item, 400)
        assert len(seen) == 400 and max(len(s) for s in seen) <= 102
        # with a cart's five items still inside the program's bucket of 128
        assert max(len(s) for s in seen) + ecomm_data.MAX_LIST <= 128

    def test_request_i_reads_the_same_whatever_the_count_drawn(self):
        few = ecomm_data.requests(9, 50, 40000, 60, SHARES)
        many = ecomm_data.requests(9, 5000, 40000, 60, SHARES)
        for key in few:
            assert (few[key] == many[key][:50]).all(), key
        share = np.bincount(many["kind"], minlength=3) / 5000.0
        assert np.allclose(share, [0.7, 0.2, 0.1], atol=0.03)
        assert many["list_len"].min() == 1 and many["list_len"].max() == 5

    def test_categories_are_asked_for_by_their_item_count(self):
        r = ecomm_data.requests(3, 20000, 40000, 60, SHARES)
        sizes = ecomm_data.category_sizes(40000, 60)
        got = np.bincount(r["category"], minlength=60) / 20000.0
        assert np.allclose(got, sizes / 40000.0, atol=0.01)

    @pytest.mark.parametrize("kind,needle", [
        (ecomm_data.HOME, b'{"user":"u7","num":10}'),
        (ecomm_data.CATEGORY, b'"categories":["c'),
        (ecomm_data.CART, b'"blackList":["i'),
    ])
    def test_request_bodies(self, kind, needle):
        r = ecomm_data.requests(3, 400, 40000, 60, SHARES)
        i = int(np.flatnonzero(r["kind"] == kind)[0])
        body = ecomm_data.request_body(7, 10, r, i)
        assert needle in body
        parsed = json.loads(body)
        assert parsed["user"] == "u7" and parsed["num"] == 10
        if kind == ecomm_data.CART:
            assert len(parsed["blackList"]) == r["list_len"][i]


class TestReference:
    @pytest.mark.parametrize("block", [512, 1 << 18])
    def test_top_k_allowed_against_brute_force(self, block):
        rng = np.random.default_rng(0)
        n, d, s, k = 5000, 16, 12, 10
        V = rng.standard_normal((n, d)).astype(np.float32)
        q = rng.standard_normal((s, d)).astype(np.float32)
        cat = rng.integers(0, 40, n).astype(np.int32)
        cat[np.flatnonzero(cat == 39)[3:]] = 0  # category 39 holds three items
        un = np.sort(rng.choice(n, 50, replace=False))
        ex = [np.sort(rng.choice(n, rng.integers(0, 30), replace=False)) for _ in range(s)]
        qc = [None if r % 3 else int(rng.integers(0, 40)) for r in range(s)]
        qc[0] = 39
        bs, bi = ref.top_k_allowed(q, V, k, unavailable=un, excluded=ex,
                                   item_category=cat, query_category=qc, block=block)
        for r in range(s):
            sc = V @ q[r]
            ok = np.ones(n, bool)
            ok[un] = False
            ok[ex[r]] = False
            if qc[r] is not None:
                ok &= cat == qc[r]
            idx = np.flatnonzero(ok)
            want = idx[np.lexsort((idx, -sc[idx]))][:k]
            assert (bi[r, :len(want)] == want).all() and (bi[r, len(want):] == -1).all()
            assert np.allclose(bs[r, :len(want)], sc[want], atol=1e-5)
        assert (bi[0] >= 0).sum() <= 3  # a small category gives a short answer

    def test_the_controls_move_what_they_should(self):
        rng = np.random.default_rng(1)
        V = rng.standard_normal((3000, 32)).astype(np.float32)
        q = rng.standard_normal((4, 32)).astype(np.float32)
        kw = dict(unavailable=np.arange(0, 3000, 3), excluded=[np.zeros(0, np.int64)] * 4,
                  item_category=np.zeros(3000, np.int32), query_category=[None] * 4)
        _, sound = ref.top_k_allowed(q, V, 10, **kw)
        _, without = ref.top_k_allowed(q, V, 10, apply_unavailable=False, **kw)
        flags = np.zeros(3000, bool)
        flags[kw["unavailable"]] = True
        count = lambda ids: sum(ref.excluded_served(
            row, excluded=np.zeros(0, np.int64), unavailable_flags=flags,
            item_category=kw["item_category"], query_category=None) for row in ids)
        assert count(sound) == 0 and count(without) > 0
        s16, i16 = ref.top_k_allowed(q, V, 10, precision="bfloat16", **kw)
        assert np.abs(s16[0] - V[i16[0]] @ q[0]).max() > 1e-3

    def test_excluded_served_counts_each_rule(self):
        flags = np.zeros(10, bool)
        flags[4] = True
        cat = np.asarray([0, 0, 1, 1, 0, 0, 1, 1, 0, 0], np.int32)
        kw = dict(excluded=np.asarray([2, 7]), unavailable_flags=flags, item_category=cat)
        assert ref.excluded_served([0, 1, 5], query_category=None, **kw) == 0
        assert ref.excluded_served([2, 4, 7, 0], query_category=None, **kw) == 3
        assert ref.excluded_served([0, 3, 6], query_category=1, **kw) == 1
        assert ref.excluded_served([], query_category=1, **kw) == 0
        assert ref.allowed_count(10, query_category=1, **kw) == 2  # {3, 6}
        assert ref.allowed_count(10, query_category=None, **kw) == 7


class TestWriter:
    def test_the_model_file_is_the_programs_own_bytes(self):
        import write_ecomm
        from predictionio_tpu.data.bimap import BiMap
        from predictionio_tpu.models import modelfile
        from predictionio_tpu.models.ecommerce import ECommModel

        rng = np.random.default_rng(2)
        U = rng.standard_normal((123, 8)).astype(np.float32)
        V = rng.standard_normal((1017, 8)).astype(np.float32)
        cat = ecomm_data.item_categories(2, 1017, 12)
        fast = write_ecomm.model_blob(modelfile, "m1", U, V, cat, 12)
        model = ECommModel(
            user_index=BiMap.from_dense([f"u{n}" for n in range(123)]),
            item_index=BiMap.from_dense([f"i{n}" for n in range(1017)]),
            user_factors=U, item_factors=V,
            category_index=BiMap.from_dense([f"c{n}" for n in range(12)]),
            item_categories=cat.reshape(-1, 1),
        )
        assert bytes(fast) == modelfile.serialize([("arrays", model)], "m1")
        back = modelfile.deserialize(bytes(fast))[0][1]
        assert back.item_categories[:, 0].tolist() == cat.tolist()
        assert back.category_index["c11"] == 11 and back.item_index["i1016"] == 1016

    def test_event_rows_are_the_tables_twelve_columns(self):
        import write_ecomm

        active = np.asarray([3, 9])
        rows = list(write_ecomm.event_rows(
            active, np.asarray([0, 0, 1]), np.asarray([5, 6, 7]),
            np.asarray([False, True, False])))
        assert [r[1] for r in rows] == ["view", "buy", "view"]
        assert [(r[3], r[5]) for r in rows] == [("u3", "i5"), ("u3", "i6"), ("u9", "i7")]
        assert all(len(r) == 12 for r in rows) and len({r[0] for r in rows}) == 3


class TestReaders:
    def test_masked_shortlist_bytes_against_a_hand_sum(self):
        mod = readers.load_metric(METRICS, "masked_shortlist_roofline.storefront")
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "msr", os.path.join(METRICS, "masked_shortlist_roofline.storefront.py"))
        m = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(m)
        # 16 tiles x 262,144 rows; a row: 128 bf16 values, an int32 id, an
        # availability byte, one int32 category id = 256 + 4 + 1 + 4 = 265 B;
        # one f32 query of 128
        assert m.masked_shortlist_bytes(4162024, 128, 262144, "bfloat16") == \
            4194304 * 265 + 512 == 1111491072
        assert m.masked_shortlist_bytes(1000, 8, 256, "int8", category_columns=2, batch=4) == \
            1024 * (8 + 4 + 1 + 8 + 4) + 4 * 8 * 4
        assert callable(mod)

    def test_masked_shortlist_roofline_reads_the_masked_program_only(self):
        read = readers.load_metric(METRICS, "masked_shortlist_roofline.storefront")
        cell = _cell()
        raw = {"device": {"kind": "TPU v5 lite"},
               "trace": {"programs": {"jit__coarse_topk_masked": 0.5, "jit__coarse_topk": 9.0},
                         "program_calls": {"jit__coarse_topk_masked": 100, "jit__coarse_topk": 3}}}
        least = 1111491072 / 819e9  # bandwidth binds: 1.36 ms a call
        assert read(raw, cell) == pytest.approx(100.0 * 100 * least / 0.5)
        assert 0 < read(raw, cell) < 100
        # a program that has no masked scan (the parent): nothing to read
        raw["trace"] = {"programs": {"jit__coarse_topk": 9.0},
                        "program_calls": {"jit__coarse_topk": 3}}
        assert read(raw, cell) is None
        assert read({"device": raw["device"]}, cell) is None

    def test_exact_path_share(self):
        read = readers.load_metric(METRICS, "exact_path_share.storefront")
        two, exact = ('pio_retrieval_queries_total{path="two_stage"}',
                      'pio_retrieval_queries_total{path="exact"}')
        assert read({"counters_delta": {two: 90.0, exact: 10.0}}, {}) == pytest.approx(10.0)
        assert read({"counters_delta": {two: 90.0, exact: 0.0}}, {}) == 0.0
        assert read({"counters_delta": {two: 0.0, exact: 0.0}}, {}) is None
        assert read({"counters_delta": {}}, {}) is None

    def test_the_cells_traced_metrics(self):
        """Seven of its own (.storefront), the span chain's eight with the cell
        appended to their lists, and the seven that list no cells."""
        with open(MANIFEST) as fh:
            m = json.load(fh)
        names = {d["name"] for d in bench_run.metrics_for(m, CELL, True)}
        own = {n for n in names if n.endswith(".storefront")}
        assert len(own) == 7 and len(names) == 7 + 8 + 7
        assert {"dispatch_ms", "http_handoff_ms", "gen_late_ms_p99"} <= names
        assert "shortlist_ms" not in names and "shortlist_roofline" not in names
        assert {d["name"] for d in bench_run.metrics_for(m, CELL, False)} == \
            {"query_p50_ms", "setup_s"}
        steady = {d["name"] for d in bench_run.metrics_for(m, "retrieval-yambda.serve-steady", True)}
        assert not any(n.endswith(".storefront") for n in steady) and "dispatch_ms" in steady
        for n in own:  # every reader is there and finds nothing in an empty run
            assert readers.load_metric(METRICS, n)({"device": {"kind": "TPU v5 lite"}}, _cell()) is None


BROKEN = '''
import sys
import numpy as np
from predictionio_tpu.models import ecommerce
from predictionio_tpu.ops import retrieval, topk
%s
from predictionio_tpu.cli.main import main
sys.exit(main(sys.argv[1:]))
'''
NO_CATEGORY_RULE = '''
_sound = topk.rows_allowed
def rows_allowed(av, cs, hit, qcat, has_cat):
    return _sound(av, cs, hit, qcat, has_cat & False)  # the category rule skipped
topk.rows_allowed = retrieval.rows_allowed = rows_allowed
'''
NO_UNAVAILABLE_RULE = '''
ecommerce.ECommAlgorithm._unavailable_rows = \\
    lambda self, model, cache: np.zeros(0, np.int32)  # the constraint never read
'''


@pytest.mark.parametrize("fault,failing", [
    (NO_CATEGORY_RULE, "excluded_served"),
    (NO_UNAVAILABLE_RULE, "live_probe.removed_items_served"),
], ids=["category", "unavailable"])
def test_a_rule_skipped_where_it_is_applied_is_not_correct(tmp_path, fault, failing):
    entry = tmp_path / "broken_server.py"
    entry.write_text(BROKEN % fault)
    with open(MANIFEST) as fh:
        m = json.load(fh)
    with open(os.path.join(REPO, "benchmark", "configs", "ecommerce-taobao.json")) as fh:
        cfg = json.load(fh)
    cfg["server_entry"] = [str(entry)]
    (tmp_path / "broken.json").write_text(json.dumps(cfg))
    for c in m["configs"]:
        if c["name"] == "ecommerce-taobao":
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
    assert checks["score_gap_max"]["pass"]  # the scores were right: the rule fails
