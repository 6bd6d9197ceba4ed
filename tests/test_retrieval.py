"""Two-stage retrieval: coarse shortlist + exact rescore vs the exact ops.

The contract under test (ops/retrieval.py): the rescore stage rebuilds
query vectors and scores exactly like the exact path, so a two-stage
result equals the exact result whenever the shortlist covers the exact
top-k — and the shortlist's oversampling buys that coverage across
storage precisions (f32/bf16/int8), single chip and the virtual 8-device
mesh. Sub-threshold catalogs must never route through this module at
all (the byte-parity regression).
"""

from __future__ import annotations

import functools
from types import SimpleNamespace

import numpy as np
import pytest

from predictionio_tpu.data.bimap import BiMap
from predictionio_tpu.ops import retrieval
from predictionio_tpu.ops.als import quantize_rows
from predictionio_tpu.ops.retrieval import CoarseCatalog
from predictionio_tpu.ops.topk import (
    catalog_norms,
    Rules,
    gather_top_k_batch,
    sum_rows_top_k_batch_masked,
    top_k_similar,
)

PAST = 1 << 30  # rows of a catalog whose stored scores are past retrieval._UNCUT


def _dense(i, d, seed=0):
    rng = np.random.default_rng(seed)
    return rng.normal(size=(i, d)).astype(np.float32)


def _int8(i, d, seed=0):
    f = _dense(i, d, seed)
    q, s = quantize_rows(f)
    return np.asarray(q), np.asarray(s)


def _open_rules(stored, b):
    """Rules over ``stored`` rows that rule nothing out for ``b``
    queries: what a sum-of-rows call carries where the test is about
    something else (the form has no rule-less program)."""
    import jax.numpy as jnp

    return retrieval.device_rules(Rules(
        avail=jnp.ones(stored, jnp.uint8), cats=(),
        qcat=np.full((b, 1), -2, np.int32), has_cat=np.zeros(b, bool),
        ex=np.full((b, 1), -1, np.int32),
    ))


def _host(rules):
    """``rules`` as a template hands them to ``top_k``: the per-query
    parts host arrays (``top_k`` packs them into its one upload)."""
    return rules._replace(
        qcat=np.asarray(rules.qcat), has_cat=np.asarray(rules.has_cat),
        ex=np.asarray(rules.ex),
    )


def _device(rules):
    """The ``device_rules`` of a form's host rules, for the host-facing
    calls a test compares ``top_k`` with (None stays None)."""
    return rules and retrieval.device_rules(rules)


def _exact_top(q, v, scales, k):
    """Numpy exact reference: ids of the top-k dequantized dot scores."""
    vf = v.astype(np.float32)
    if scales is not None:
        vf = vf * scales[:, None]
    sc = q @ vf.T
    return np.argsort(-sc, axis=1, kind="stable")[:, :k]


def _recall(cand, exact):
    hits = sum(
        len(set(cand[b].tolist()) & set(exact[b].tolist()))
        for b in range(exact.shape[0])
    )
    return hits / exact.size


class TestShortlistRecall:
    """Coarse pass coverage across storage modes; tile=256 on a 4096-row
    catalog forces the scan through 16 tiles (merge path exercised)."""

    @pytest.mark.parametrize("mode", ["bf16", "int8", "int8_dot"])
    def test_recall_at_default_oversample(self, mode):
        v, s = _int8(4096, 16, seed=1)
        q = _dense(8, 16, seed=2)
        exact = _exact_top(q, v, s, 8)
        cat = CoarseCatalog((v, s), tile=256, mode=mode)
        _, cand = cat.shortlist(q, 64)  # 8x oversample of k=8
        assert cand.shape == (8, 64)
        assert _recall(cand, exact) >= 0.999

    def test_dense_catalog_bf16_copy(self):
        v = _dense(2048, 12, seed=3)
        q = _dense(4, 12, seed=4)
        exact = _exact_top(q, v, None, 8)
        cat = CoarseCatalog(v, tile=512)
        assert cat.mode == "bf16"
        _, cand = cat.shortlist(q, 64)
        assert _recall(cand, exact) >= 0.999

    def test_pad_tile_ids_never_returned(self):
        # 200 rows pad to one 256-wide tile; a 256-wide shortlist has
        # only 200 eligible rows, so 56 slots per row must come back -1
        v = _dense(200, 8, seed=5)
        cat = CoarseCatalog(v, tile=256)
        _, cand = cat.shortlist(_dense(3, 8, seed=6), 256)
        valid = cand[cand >= 0]
        assert valid.max() < 200
        assert (cand < 0).sum() == 3 * 56
        for row in cand:
            vr = row[row >= 0]
            assert len(set(vr.tolist())) == vr.size  # no duplicates

    def test_shortlist_k_bucketing(self, monkeypatch):
        monkeypatch.setenv("PIO_RETRIEVAL_TILE", str(1 << 18))
        # pow2(8 * pow2(k)); capped by the catalog's pow2 envelope
        assert retrieval.shortlist_k(5, 1 << 20) == 64
        assert retrieval.shortlist_k(8, 1 << 20) == 64
        assert retrieval.shortlist_k(9, 1 << 20) == 128
        assert retrieval.shortlist_k(8, 100) == 64  # pow2(100) = 128 > 64
        assert retrieval.shortlist_k(64, 80) == 128  # catalog envelope

    def test_engagement_threshold(self, monkeypatch):
        monkeypatch.setenv("PIO_RETRIEVAL_THRESHOLD", "1000")
        assert not retrieval.engaged(999)
        assert retrieval.engaged(1000)
        monkeypatch.setenv("PIO_RETRIEVAL_THRESHOLD", "0")
        assert not retrieval.engaged(10**9)  # <= 0 disables entirely


class TestRescoreExactness:
    """The rescore stage restricted to a full-coverage candidate set
    must reproduce the exact ops' ranking."""

    def test_rescore_gather_matches_exact(self):
        for table in (_dense(256, 8, seed=7), _int8(256, 8, seed=7)):
            quantized = isinstance(table, tuple)
            U = _dense(32, 8, seed=8)
            uixs = np.arange(4, dtype=np.int32)
            es, ei = gather_top_k_batch(uixs, U, table, k=8)
            # candidates = the whole catalog, shuffled per row
            rng = np.random.default_rng(9)
            cand = np.stack([rng.permutation(256) for _ in range(4)]).astype(
                np.int32
            )
            s, ids = retrieval.rescore_gather_top_k_batch(
                uixs, U, table, cand, k=8
            )
            np.testing.assert_array_equal(ids, np.asarray(ei))
            np.testing.assert_allclose(
                s, np.asarray(es), rtol=1e-5, atol=1e-6,
                err_msg=f"quantized={quantized}",
            )

    def test_rescore_sum_rows_matches_exact(self):
        table = _int8(200, 8, seed=10)
        ixs = np.array([[0, 3, 7, 0], [5, 5, 9, 0]], np.int32)
        w = np.array([[1, 1, 1, 0], [1, 0.5, 1, 0]], np.float32)
        rules = _open_rules(200, 2)
        es, ei = sum_rows_top_k_batch_masked(ixs, w, table, rules, k=8)
        cand = np.tile(np.arange(200, dtype=np.int32), (2, 1))
        s, ids = retrieval.rescore_sum_rows_top_k_batch(
            ixs, w, table, cand, k=8, rules=rules
        )
        np.testing.assert_array_equal(ids, np.asarray(ei))
        np.testing.assert_allclose(s, np.asarray(es), rtol=1e-5, atol=1e-6)

    def test_padded_candidates_report_minus_one(self):
        v = _dense(64, 4, seed=11)
        q = _dense(2, 4, seed=12)
        cand = np.full((2, 16), -1, np.int32)
        cand[:, :3] = [[1, 2, 3], [10, 11, 12]]
        s, ids = retrieval.rescore_top_k_batch(q, v, cand, k=8)
        assert (ids[:, 3:] == -1).all()
        assert set(ids[0, :3].tolist()) == {1, 2, 3}

    def test_near_ties_preserve_score_multiset(self):
        """Adversarial near-ties: 512 rows drawn from 16 archetypes plus
        1e-6 noise. Ids may legitimately differ between paths at equal
        scores, so compare the sorted score arrays instead."""
        rng = np.random.default_rng(15)
        arch = rng.normal(size=(16, 8)).astype(np.float32)
        v = (
            arch[rng.integers(0, 16, size=512)]
            + rng.normal(scale=1e-6, size=(512, 8))
        ).astype(np.float32)
        q = _dense(4, 8, seed=16)
        cat = CoarseCatalog(v, tile=128, mode="bf16")
        _, cand = cat.shortlist(q, 256)
        s, _ = retrieval.rescore_top_k_batch(q, v, cand, k=16)
        full = np.tile(np.arange(512, dtype=np.int32), (4, 1))
        es, _ = retrieval.rescore_top_k_batch(q, v, full, k=16)
        np.testing.assert_allclose(
            np.sort(s, axis=1), np.sort(np.asarray(es), axis=1),
            rtol=1e-4, atol=1e-5,
        )


def _exact_tables(storage, rows, d, seed):
    """A (host table, its f32 rows) pair whose every product and sum is
    exact in f32 — eighths of small integers; int8 values under
    power-of-two scales — so that any order of accumulation gives the
    same bits and a NumPy reference can be held to equality."""
    rng = np.random.default_rng(seed)
    if storage == "int8":
        vq = rng.integers(-127, 128, size=(rows, d)).astype(np.int8)
        vs = (2.0 ** rng.integers(-8, -6, size=rows)).astype(np.float32)
        return (vq, vs), vq.astype(np.float32) * vs[:, None]
    f = (rng.integers(-16, 17, size=(rows, d)) / 8.0).astype(np.float32)
    if storage == "bfloat16":
        import ml_dtypes

        return f.astype(ml_dtypes.bfloat16), f
    return f, f


def _numpy_rescore(qvecs, rows_f32, cand, k):
    """Plain f32 gather-dot-top-k: the reference of every rescore."""
    sc = np.einsum(
        "bd,bsd->bs", qvecs.astype(np.float32),
        rows_f32[np.maximum(cand, 0)],
    ).astype(np.float32)
    sc[cand < 0] = retrieval.NEG_INF
    order = np.argsort(-sc, axis=1, kind="stable")[:, :k]
    s = np.take_along_axis(sc, order, axis=1)
    ids = np.take_along_axis(cand, order, axis=1)
    return s, np.where(s > retrieval.NEG_INF / 2, ids, -1)


class TestResidentTables:
    """The three rescore programs over tables resident as
    ``device_factors()`` leaves them (ops/retrieval.py module
    docstring): D = 64 goes through ``_gather_rows``' view, 20 and 100
    through the plain gather. 1,003 rows: no multiple of 8 or of 128."""

    ROWS, USERS, S, K = 1003, 37, 128, 16

    @pytest.mark.parametrize("b", [1, 3, 16])
    @pytest.mark.parametrize("d", [20, 64, 100])
    @pytest.mark.parametrize("storage", ["float32", "bfloat16", "int8"])
    @pytest.mark.parametrize("entry", ["gather", "vectors", "sum_rows"])
    def test_rescore_bit_identical_to_numpy(self, entry, storage, d, b):
        import jax
        import jax.numpy as jnp

        host, rows = _exact_tables(storage, self.ROWS, d, seed=d)
        table = jax.tree.map(jnp.asarray, host)
        rng = np.random.default_rng(100 * d + b)
        cand = np.stack([
            rng.permutation(self.ROWS)[: self.S] for _ in range(b)
        ]).astype(np.int32)
        cand[:, -5:] = -1  # padding slots
        cand[0, 9:] = -1  # fewer live candidates than k
        if entry == "gather":
            uhost, urows = _exact_tables(storage, self.USERS, d, seed=d + 1)
            uixs = rng.integers(0, self.USERS, size=b).astype(np.int32)
            qvecs = urows[uixs]
            s, ids = retrieval.rescore_gather_top_k_batch(
                uixs, jax.tree.map(jnp.asarray, uhost), table, cand, k=self.K
            )
        elif entry == "vectors":
            qvecs = (rng.integers(-16, 17, size=(b, d)) / 8.0).astype(
                np.float32
            )
            s, ids = retrieval.rescore_top_k_batch(
                qvecs, table, cand, k=self.K
            )
        else:
            ixs = rng.integers(0, self.ROWS, size=(b, 4)).astype(np.int32)
            w = np.ones((b, 4), np.float32)
            w[:, -1] = 0.0  # a padding row
            qvecs = np.sum(rows[ixs] * w[..., None], axis=1)
            s, ids = retrieval.rescore_sum_rows_top_k_batch(
                ixs, w, table, cand, k=self.K,
                rules=_open_rules(self.ROWS, b),
            )
        want_s, want_ids = _numpy_rescore(qvecs, rows, cand, self.K)
        np.testing.assert_array_equal(ids, want_ids)
        np.testing.assert_array_equal(s, want_s)
        assert (ids[0, 9:] == -1).all() and (ids[0, :9] >= 0).all()

    def test_temp_bytes_reach_the_gauge_and_stats(self):
        """Each compiled rescore program needs far less temporary
        memory than its table holds, and says so on ``/metrics`` and in
        ``/stats.json``. XLA:CPU has one layout and re-lays nothing, so
        this holds the reader to account; on the chip ``chip_smoke.py``
        and the benchmark's list of device ops hold the layout."""
        import jax.numpy as jnp

        from predictionio_tpu.obs import metrics as obs_metrics

        host = _dense(200_000, 64, seed=42)
        table, users = jnp.asarray(host), jnp.asarray(host[:500])
        cand = np.arange(self.S, dtype=np.int32)[None, :]
        retrieval.rescore_gather_top_k_batch(
            np.zeros(1, np.int32), users, table, cand, k=self.K
        )
        retrieval.rescore_top_k_batch(host[:1], table, cand, k=self.K)
        retrieval.rescore_sum_rows_top_k_batch(
            np.zeros((1, 2), np.int32), np.ones((1, 2), np.float32),
            table, cand, k=self.K, rules=_open_rules(len(host), 1),
        )
        temp = retrieval.stats_block()["rescore_temp_bytes"]
        # (another test file on this worker may have compiled the masked
        # vectors program too: it reports like the three called here)
        assert {
            "retrieval.rescore_gather", "retrieval.rescore_vectors",
            "retrieval.rescore_sum_rows_masked",
        } <= set(temp) <= {p.name for p in retrieval._RESCORE_PROGRAMS}
        scraped = obs_metrics.parse_prometheus(obs_metrics.render_prometheus())
        for fn, n in temp.items():
            assert 0 < n < host.nbytes / 10, (fn, n)
            assert scraped[
                f'pio_retrieval_rescore_temp_bytes{{fn="{fn}"}}'
            ] == n


class TestSatelliteOps:
    def test_sum_rows_accepts_int8_pair(self):
        vq, vs = _int8(96, 8, seed=17)
        dense = vq.astype(np.float32) * vs[:, None]
        ixs = np.array([[0, 5], [9, 9]], np.int32)
        w = np.ones((2, 2), np.float32)
        rules = _open_rules(96, 2)
        ds, di = sum_rows_top_k_batch_masked(ixs, w, dense, rules, k=8)
        qs, qi = sum_rows_top_k_batch_masked(ixs, w, (vq, vs), rules, k=8)
        np.testing.assert_array_equal(np.asarray(qi), np.asarray(di))
        np.testing.assert_allclose(
            np.asarray(qs), np.asarray(ds), rtol=1e-5, atol=1e-6
        )

    def test_top_k_similar_precomputed_norms(self):
        v = _dense(80, 8, seed=18)
        norms = catalog_norms(v)
        np.testing.assert_allclose(
            np.asarray(norms), np.linalg.norm(v, axis=1), rtol=1e-6
        )
        s0, i0 = top_k_similar(v[3], v, 8)
        s1, i1 = top_k_similar(v[3], v, 8, norms=norms)
        np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
        np.testing.assert_allclose(
            np.asarray(s0), np.asarray(s1), rtol=1e-6
        )

    def test_cosine_model_tables_stay_quantized(self):
        from predictionio_tpu.models.similarproduct import SimilarProductModel

        vq, vs = _int8(64, 8, seed=19)
        m = SimilarProductModel(
            item_index=BiMap.from_dense([f"i{j}" for j in range(64)]),
            item_factors=vq, categories={}, item_scales=vs,
        )
        table = m.device_factors()
        assert isinstance(table, tuple)  # int8 catalog not densified
        assert table[0].dtype == np.int8
        rows = np.asarray(table[0], np.float32) * np.asarray(table[1])[:, None]
        np.testing.assert_allclose(
            np.linalg.norm(rows, axis=1), 1.0, rtol=1e-5
        )
        assert m.device_norms().shape == (64,)


@pytest.fixture()
def mesh():
    from predictionio_tpu.parallel.mesh import make_mesh

    return make_mesh([("data", 8)])


class TestMeshCoarse:
    def test_sharded_two_stage_template_parity(self, mesh, monkeypatch):
        from predictionio_tpu.models import recommendation as rec

        monkeypatch.setenv("PIO_RETRIEVAL_PROBE_EVERY", "1")
        model = _rec_model(int8=True)
        algo = rec.ALSAlgorithm(
            rec.ALSAlgorithmParams(sharded_serving=True)
        )
        queries = [(i, rec.Query(user=f"u{i}", num=5)) for i in range(3)]
        exact = algo.batch_predict(model, queries)
        monkeypatch.setenv("PIO_RETRIEVAL_THRESHOLD", "64")
        two = algo.batch_predict(model, queries)
        _assert_same_results(exact, two)


def _rec_model(i=512, d=8, users=16, int8=False, seed=22):
    from predictionio_tpu.models.recommendation import ALSModel

    U = _dense(users, d, seed=seed)
    if int8:
        vq, vs = _int8(i, d, seed=seed + 1)
        V, S = vq, vs
    else:
        V, S = _dense(i, d, seed=seed + 1), None
    return ALSModel(
        user_index=BiMap.from_dense([f"u{j}" for j in range(users)]),
        item_index=BiMap.from_dense([f"i{j}" for j in range(i)]),
        user_factors=U, item_factors=V, item_scales=S,
    )


def _assert_same_results(exact, two_stage):
    assert len(exact) == len(two_stage)
    for (ix_a, ra), (ix_b, rb) in zip(
        sorted(exact, key=lambda t: t[0]),
        sorted(two_stage, key=lambda t: t[0]),
    ):
        assert ix_a == ix_b
        la = getattr(ra, "itemScores", None) or getattr(ra, "userScores", [])
        lb = getattr(rb, "itemScores", None) or getattr(rb, "userScores", [])
        assert [getattr(x, "item", None) or getattr(x, "user", None)
                for x in la] == \
               [getattr(x, "item", None) or getattr(x, "user", None)
                for x in lb]
        np.testing.assert_allclose(
            [x.score for x in la], [x.score for x in lb],
            rtol=1e-4, atol=1e-5,
        )


class TestTemplateTwoStage:
    """Each template's batch_predict, exact vs two-stage (threshold
    forced below the fixture catalogs): identical ids, matching scores.
    Oversampling at the default factor must cover every exact top-k on
    these catalogs, so any divergence is a routing/rescore bug."""

    @pytest.mark.parametrize("int8", [False, True])
    def test_recommendation(self, monkeypatch, int8):
        from predictionio_tpu.models import recommendation as rec

        model = _rec_model(int8=int8)
        algo = rec.ALSAlgorithm(rec.ALSAlgorithmParams())
        queries = [
            (0, rec.Query(user="u0", num=5)),
            (1, rec.Query(user="u3", num=3)),
            (2, rec.Query(user="zz", num=4)),  # unknown user in batch
            (3, rec.Query(user="u7", num=8)),
        ]
        exact = algo.batch_predict(model, queries)
        monkeypatch.setenv("PIO_RETRIEVAL_THRESHOLD", "64")
        monkeypatch.setenv("PIO_RETRIEVAL_TILE", "128")  # multi-tile
        monkeypatch.setenv("PIO_RETRIEVAL_PROBE_EVERY", "1")
        before = retrieval.stats_block()["two_stage_queries"]
        two = algo.batch_predict(model, queries)
        assert retrieval.stats_block()["two_stage_queries"] > before
        _assert_same_results(exact, two)

    def test_resident_tables_serve_the_exact_path_and_the_probe(
        self, monkeypatch
    ):
        """The readers of ``device_factors()`` other than the rescore,
        over the same resident tables at D = 64 (the rescore's view
        path): the exact program below the threshold and the recall
        probe at its dispatch return what the exact path returns, and a
        second probe compiles nothing — the rescore programs compile
        ahead of time and are counted like any jit."""
        from predictionio_tpu.models import recommendation as rec
        from predictionio_tpu.obs import device as obs_device

        def compiles():
            return sum(
                f["compiles"] for f in obs_device.compile_snapshot().values()
            )

        algo = rec.ALSAlgorithm(rec.ALSAlgorithmParams())
        queries = [(0, rec.Query(user="u0", num=5)),
                   (1, rec.Query(user="u3", num=3))]
        model = _rec_model(d=64)
        exact = algo.batch_predict(model, queries)  # below the threshold
        tables = model.device_factors()
        monkeypatch.setenv("PIO_RETRIEVAL_THRESHOLD", "64")
        monkeypatch.setenv("PIO_RETRIEVAL_TILE", "128")
        monkeypatch.setenv("PIO_RETRIEVAL_PROBE_EVERY", "2")
        probes = retrieval.stats_block()["probes"]
        before = compiles()
        for _ in range(2):  # one of any two dispatches probes
            _assert_same_results(exact, algo.batch_predict(model, queries))
        assert retrieval.stats_block()["probes"] == probes + 1
        warm = compiles()
        assert warm > before  # the rescore program's compile was counted
        for _ in range(2):
            _assert_same_results(exact, algo.batch_predict(model, queries))
        assert retrieval.stats_block()["probes"] == probes + 2
        assert retrieval.stats_block()["probe_recall"] == 1.0
        assert compiles() == warm
        assert model.device_factors() is tables  # placed once, read by all

    @pytest.mark.parametrize("int8", [False, True])
    def test_similarproduct_with_boundary_exclusions(self, monkeypatch, int8):
        from predictionio_tpu.models import similarproduct as sp

        n = 512
        if int8:
            vq, vs = _int8(n, 8, seed=23)
        else:
            vq, vs = _dense(n, 8, seed=23), None
        model = sp.SimilarProductModel(
            item_index=BiMap.from_dense([f"i{j}" for j in range(n)]),
            item_factors=vq, categories={}, item_scales=vs,
        )
        algo = sp.ALSAlgorithm(sp.ALSAlgorithmParams())
        # blackList the exact top results so the answer must come from
        # DEEPER in the shortlist than the unfiltered top-num
        probe = algo.batch_predict(
            model, [(0, sp.Query(items=["i0"], num=6))]
        )[0][1]
        top_ids = [x.item for x in probe.itemScores]
        queries = [
            (0, sp.Query(items=["i0"], num=4, blackList=top_ids)),
            (1, sp.Query(items=["i1", "i2"], num=5)),
            (2, sp.Query(items=["i3"], num=3, whiteList=[f"i{j}" for j in range(40)])),
        ]
        exact = algo.batch_predict(model, queries)
        monkeypatch.setenv("PIO_RETRIEVAL_THRESHOLD", "64")
        monkeypatch.setenv("PIO_RETRIEVAL_TILE", "128")
        two = algo.batch_predict(model, queries)
        _assert_same_results(exact, two)
        # the blackListed query's answers must avoid the exact top ids
        got = [x.item for x in dict(two)[0].itemScores]
        assert not set(got) & set(top_ids)

    def test_recommendeduser(self, monkeypatch):
        from predictionio_tpu.models import recommendeduser as ru

        n = 512
        vq, vs = _int8(n, 8, seed=24)
        model = ru.RecommendedUserModel(
            followed_index=BiMap.from_dense([f"u{j}" for j in range(n)]),
            followed_factors=vq, followed_scales=vs,
        )
        algo = ru.ALSAlgorithm(ru.ALSAlgorithmParams())
        queries = [
            (0, ru.Query(users=["u0", "u1"], num=5)),
            (1, ru.Query(users=["u2"], num=4, blackList=["u5", "u6"])),
        ]
        exact = algo.batch_predict(model, queries)
        monkeypatch.setenv("PIO_RETRIEVAL_THRESHOLD", "64")
        monkeypatch.setenv("PIO_RETRIEVAL_TILE", "128")
        two = algo.batch_predict(model, queries)
        _assert_same_results(exact, two)

    def test_ecommerce(self, monkeypatch):
        from predictionio_tpu.models import ecommerce as ec

        n = 512
        model = ec.ECommModel(
            user_index=BiMap.from_dense([f"u{j}" for j in range(8)]),
            item_index=BiMap.from_dense([f"i{j}" for j in range(n)]),
            user_factors=_dense(8, 8, seed=25),
            item_factors=_dense(n, 8, seed=26),
            categories={f"i{j}": ["c0"] for j in range(0, n, 2)},
        )
        algo = ec.ECommAlgorithm(
            ec.ECommAlgorithmParams(unseen_only=False)
        )
        queries = [
            (0, ec.Query(user="u0", num=5)),
            (1, ec.Query(user="u1", num=4, blackList=["i3"])),
            (2, ec.Query(user="u2", num=3, categories=["c0"])),
            (3, ec.Query(user="u3", num=3, whiteList=["i5", "i9", "i40", "i77"])),
        ]
        exact = algo.batch_predict(model, queries)
        monkeypatch.setenv("PIO_RETRIEVAL_THRESHOLD", "64")
        monkeypatch.setenv("PIO_RETRIEVAL_TILE", "128")
        before = retrieval.stats_block()
        two = algo.batch_predict(model, queries)
        # every kind stays on the two-stage path: the rules are applied
        # inside the scan and the rescore, no query leaves for the exact one
        after = retrieval.stats_block()
        assert after["exact_queries"] == before["exact_queries"]
        assert after["two_stage_queries"] >= before["two_stage_queries"] + 4
        _assert_same_results(exact, two)
        assert all(int(s.item[1:]) % 2 == 0 for s in dict(two)[2].itemScores)
        assert {s.item for s in dict(two)[3].itemScores} <= {"i5", "i9", "i40", "i77"}


class TestSubThresholdParity:
    def test_small_catalogs_never_touch_two_stage(self):
        """Regression pin for the byte-parity suites: below the default
        threshold the two-stage counter must not move and results flow
        through the unchanged exact ops."""
        from predictionio_tpu.models import recommendation as rec

        model = _rec_model(i=128)
        algo = rec.ALSAlgorithm(rec.ALSAlgorithmParams())
        before = retrieval.stats_block()["two_stage_queries"]
        out = algo.batch_predict(
            model, [(0, rec.Query(user="u0", num=4))]
        )
        assert retrieval.stats_block()["two_stage_queries"] == before
        assert len(out[0][1].itemScores) == 4

    def test_stats_block_shape(self):
        block = retrieval.stats_block()
        assert {"threshold", "oversample", "two_stage_queries",
                "exact_queries", "shortlist_size", "probe_recall"} <= set(block)
        assert set(block["coarse_mode"]) == {"bf16", "int8", "int8_dot"}
        assert tuple(block["resident_bytes"]) == retrieval.RESIDENT_PARTS

    @pytest.mark.parametrize("mode", ["bf16", "int8", "int8_dot"])
    def test_a_shortlist_call_counts_its_coarse_mode(self, mode):
        v, s = _int8(2048, 16, seed=31)
        cat = retrieval.CoarseCatalog((v, s), tile=512, mode=mode)
        before = dict(retrieval.stats_block()["coarse_mode"])
        cat.shortlist(_dense(3, 16, seed=32), 8)
        after = retrieval.stats_block()["coarse_mode"]
        assert {m: after[m] - before[m] for m in after} == {
            m: float(m == mode) for m in after}

    @pytest.mark.parametrize("mode", ["bf16", "int8"])
    def test_a_catalog_publishes_its_resident_bytes(self, mode):
        cat = retrieval.CoarseCatalog(_int8(1000, 16, seed=33), tile=512, mode=mode)
        got = retrieval.stats_block()["resident_bytes"]
        assert got["coarse"] + got["coarse_scales"] + got["coarse_ids"] == cat.nbytes()
        assert got["coarse"] == 1024 * 16 * (2 if mode == "bf16" else 1)
        assert got["coarse_scales"] == (0 if mode == "bf16" else 1024 * 4)


class TestStageSpans:
    def test_stages_record_themselves_and_nothing_carries_over(self, monkeypatch):
        """The two stages land on the current trace as spans at their
        true start and end, children of the enclosing region — and a
        call made with no trace leaves nothing behind for the next one
        (the thread-local side channel this replaced added up)."""
        from predictionio_tpu.obs import trace as obs_trace

        # the read is told apart on one call in CPU_EVERY: here on every one
        monkeypatch.setattr(obs_trace, "CPU_EVERY", 1)
        v = _dense(300, 8, seed=27)
        cat = CoarseCatalog(v, tile=256)
        q = _dense(2, 8, seed=28)
        for _ in range(3):  # untraced calls on this thread, first
            _, cand = cat.shortlist(q, 32)
            retrieval.rescore_top_k_batch(q, v, cand, k=8)
        tr = obs_trace.Trace("t")
        with obs_trace.use_trace(tr):
            with obs_trace.region("batch.dispatch[2]") as outer:
                _, cand = cat.shortlist(q, 32)
                retrieval.rescore_top_k_batch(q, v, cand, k=8)
        # each host-facing stage is its launch, then its own read; below
        # a stage, its crossings: every upload, the launch, the wait and
        # the copy back, each a child of its stage
        assert [(name, parent) for name, _, _, parent in tr.spans] == [
            ("xfer.h2d[serve.dispatch]", "dispatch.shortlist"),
            ("launch[retrieval.coarse_topk]", "dispatch.shortlist"),
            ("dispatch.shortlist", "batch.dispatch[2]"),
            ("fetch.wait", "dispatch.fetch"),
            ("xfer.d2h[serve.answers]", "dispatch.fetch"),
            ("dispatch.fetch", "batch.dispatch[2]"),
            ("xfer.h2d[serve.dispatch]", "dispatch.rescore"),  # the ids
            ("xfer.h2d[serve.dispatch]", "dispatch.rescore"),  # the vectors
            ("launch[retrieval.rescore_vectors]", "dispatch.rescore"),
            ("dispatch.rescore", "batch.dispatch[2]"),
            ("fetch.wait", "dispatch.fetch"),
            ("xfer.d2h[serve.answers]", "dispatch.fetch"),
            ("dispatch.fetch", "batch.dispatch[2]"),
            ("batch.dispatch[2]", None),
        ]
        stages = [sp for sp in tr.spans if sp[3] in ("batch.dispatch[2]", None)]
        (s_off, s_dur), (f1_off, f1_dur), (r_off, r_dur), (f2_off, f2_dur), \
            (d_off, d_dur) = [(off, dur) for _, off, dur, _ in stages]
        assert min(s_dur, f1_dur, r_dur, f2_dur) > 0
        # inside the parent, in order, not overlapping
        assert d_off <= s_off and s_off + s_dur <= f1_off
        assert f1_off + f1_dur <= r_off and r_off + r_dur <= f2_off
        assert f2_off + f2_dur <= d_off + d_dur
        assert outer.self_seconds == pytest.approx(
            d_dur - s_dur - f1_dur - r_dur - f2_dur
        )
        assert obs_trace.current_trace() is None


# -- the tile's top-k' in two exact levels ------------------------------------


def _eqns(jaxpr):
    """Every equation of ``jaxpr``, sub-jaxprs (the scan's body)
    included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else (v,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


def _operands(jaxpr, primitive):
    """The first operand's aval of every ``primitive`` equation in
    ``jaxpr``."""
    return [e.invars[0].aval for e in _eqns(jaxpr)
            if e.primitive.name == primitive]


def _top_k_eqns(jaxpr):
    """Operand shapes of every ``top_k`` in ``jaxpr``."""
    return [tuple(a.shape) for a in _operands(jaxpr, "top_k")]


def _scan_args(b, nt, t, d, mode="bf16"):
    import jax.numpy as jnp

    return (
        jnp.ones((b, d), jnp.float32),
        jnp.ones((nt, t, d), jnp.bfloat16 if mode == "bf16" else jnp.int8),
        None if mode == "bf16" else jnp.ones(
            retrieval.side_shape(nt, t), jnp.float32
        ),
        jnp.arange(nt * t, dtype=jnp.int32).reshape(retrieval.side_shape(nt, t)),
    )


def _scan_jaxpr(b, nt, t, d, k, rules=None, mode="bf16"):
    import jax

    return jax.make_jaxpr(
        lambda q, tiles, scales, ids: retrieval._coarse_scan(
            q, tiles, scales, ids, k, mode, rules
        )
    )(*_scan_args(b, nt, t, d, mode)).jaxpr


def _tie_rows(kind, b, t, k, rng):
    sc = rng.normal(size=(b, t)).astype(np.float32)
    if kind == "heavy_ties":
        sc = rng.integers(0, 6, size=(b, t)).astype(np.float32)
    elif kind == "neg_inf_band":
        sc[:, t // 4: 3 * t // 4] = retrieval.NEG_INF
    elif kind == "few_finite":
        keep = rng.permutation(t)[: k // 2]
        few = np.full(t, retrieval.NEG_INF, np.float32)
        few[keep] = sc[0, keep]
        sc[0] = few
    elif kind == "all_neg_inf":
        sc[-1] = retrieval.NEG_INF
    return sc


class TestTileSelect:
    """``_two_level_top_k`` against ``jax.lax.top_k``: the values are
    read, not recomputed, so they are bit-equal; ids may differ only
    among exactly equal scores."""

    @staticmethod
    def _group(t, k):
        # a row of lanes where the shape allows it, else the widest
        # that leaves k groups
        return min(128, t // k)

    @pytest.mark.parametrize("k", [16, 128, 1024])
    @pytest.mark.parametrize("t", [1 << 12, 1 << 14, 1 << 16])
    @pytest.mark.parametrize("b", [1, 4, 16])
    def test_equals_lax_top_k(self, b, t, k):
        import jax

        # every row a permutation of t distinct values: the ids too
        rng = np.random.default_rng(b * t + k)
        sc = np.stack([rng.permutation(t) for _ in range(b)])
        sc = (sc.astype(np.float32) - t / 3) * np.float32(0.37)
        assert all(len(np.unique(r)) == t for r in sc)
        want_s, want_i = jax.lax.top_k(sc, k)
        got_s, got_i = retrieval._two_level_top_k(sc, k, self._group(t, k))
        np.testing.assert_array_equal(np.asarray(got_s), np.asarray(want_s))
        np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))

    @pytest.mark.parametrize("b", [1, 4])
    @pytest.mark.parametrize(
        "kind", ["heavy_ties", "neg_inf_band", "few_finite", "all_neg_inf"]
    )
    def test_ties_and_masked_rows(self, kind, b):
        import jax

        t, k = 1 << 14, 128
        sc = _tie_rows(kind, b, t, k, np.random.default_rng(len(kind) + b))
        want_s, _ = jax.lax.top_k(sc, k)
        got_s, got_i = retrieval._two_level_top_k(sc, k, self._group(t, k))
        got_s, got_i = np.asarray(got_s), np.asarray(got_i)
        np.testing.assert_array_equal(got_s, np.asarray(want_s))
        # every returned position holds the returned value, none repeats
        np.testing.assert_array_equal(
            np.take_along_axis(sc, got_i, axis=1), got_s
        )
        assert all(len(set(r.tolist())) == k for r in got_i)

    @pytest.mark.parametrize("t,k,nt,width", [
        (1 << 18, 128, 1, 128),      # every benchmark configuration's tile
        (1 << 18, 16, 1, 128),
        (1 << 18, 1024, 1, 16),
        (1 << 18, 1 << 14, 1, 0),    # k' nears the row
        (1 << 18, 1 << 18, 1, 0),
        (1 << 16, 128, 1, 32),
        (1 << 13, 128, 1, 8),
        (1 << 12, 16, 1, 0),         # under _MIN_SPLIT
        (256, 64, 1, 0),             # the tiles of the tests above
        (16, 4, 1, 0),
        (3 * (1 << 16) + 1, 128, 1, 0),  # no whole number of groups
        # a scan's row is the CATALOG's: NT pieces of T
        (1 << 18, 128, 36, 128),     # yambda; 16 / 46 / 184 tiles likewise
        (1 << 18, 1024, 36, 128),    # one tile would take groups of 16
        (1 << 18, 1 << 13, 36, 128),  # ... and split nothing from here on
        (1 << 18, 1 << 16, 36, 16),
        (1 << 18, 1 << 18, 36, 0),
        (1 << 12, 16, 3, 128),       # three tiles under _MIN_SPLIT make a row over it
        (256, 16, 40, 128),
        (256, 64, 3, 0),
        (96, 16, 3, 0),              # the CPU fixtures'
        (64, 4, 1 << 10, 0),         # groups wider than a tile divide none
    ])
    def test_shape_rule(self, t, k, nt, width):
        g = retrieval.select_group(t, k, nt)
        assert g == width
        n = nt * t
        if g:
            assert t % g == 0 and n // g >= k
            assert 4 * (n // g + k * g) <= n
        if nt == 1:
            assert g == retrieval.select_group(t, k)
        elif retrieval.select_group(t, k) == 128:
            assert g == 128  # what a tile takes in lanes its catalog does too

    @pytest.mark.parametrize("masked", [False, True])
    def test_engaged_scan_never_sorts_a_whole_tile(self, masked):
        """The jaxpr of an engaged scan holds no ``top_k`` whose operand
        is T wide: the group maxima of all tiles and the candidates.
        (Eight queries of rank 8: four chunks of two.)"""
        b, nt, t, d, k = 8, 2, 1 << 13, 8, 16
        g = retrieval.select_group(t, k, nt)
        c = retrieval.scan_chunk(b, d, "bf16", PAST)
        assert g and c == 2
        rules = None
        if masked:
            rules = _rules(nt * t, b)
        shapes = _top_k_eqns(_scan_jaxpr(b, nt, t, d, k, rules))
        assert sorted(shapes) == sorted([(c, nt * t // g), (c, k * g)] * 4)

    def test_the_benchmark_tile_is_selected_in_small_sorts(self):
        """2^18 rows at k' = 128, both configurations' shape: [B, 2048]
        group maxima, then the [B, 16384] candidates through the same
        helper ([B, 1024] maxima, [B, 2048] candidates)."""
        b, t, k = 16, 1 << 18, 128
        assert retrieval.select_group(t, k) == 128
        assert retrieval.select_group(k * 128, k) == 16
        assert sorted(_top_k_eqns(_scan_jaxpr(b, 1, t, 64, k))) == [
            (b, 1024), (b, 2048), (b, 2048)
        ]

    def test_a_catalog_that_splits_nothing_is_one_top_k_of_the_row(self):
        """Three tiles of 256 rows: the steps keep their scores alone
        and the one ``top_k`` stands after the loop, over [B, NT x T]."""
        b, nt, t, d, k = 2, 3, 256, 8, 64
        assert not retrieval.select_group(t, k, nt)
        jaxpr = _scan_jaxpr(b, nt, t, d, k)
        assert _top_k_eqns(jaxpr) == [(b, nt * t)]
        assert not _top_k_eqns(_scan_body(jaxpr))

    @pytest.mark.parametrize("b", [1, 2])
    def test_the_programs_keep_their_names(self, b):
        """The benchmark's roofline readers find the two programs in a
        device trace as ``jit__coarse_topk`` / ``jit__coarse_topk_masked``,
        the single's (whose score is the three-row dot) like the batch's."""
        nt, t, d, k = 2, 1 << 13, 8, 16
        assert retrieval.score_form(b, d) == ("dot" if b == 1 else "rows")
        q, tiles, _, ids = _scan_args(b, nt, t, d)
        text = retrieval._coarse_topk.lower(
            q, tiles, None, ids, k=k, mode="bf16"
        ).as_text()
        assert "module @jit__coarse_topk " in text
        text = retrieval._coarse_topk_masked.lower(
            q, tiles, None, ids, _rules(nt * t, b), k=k, mode="bf16"
        ).as_text()
        assert "module @jit__coarse_topk_masked " in text


def _rules(stored, b, *, small_cat=(), ex=None, qcat=None):
    """Rules over ``stored`` rows for ``b`` queries: every 97th row
    unavailable, category 1 = ``small_cat`` rows, category 0 the rest."""
    import jax.numpy as jnp

    from predictionio_tpu.ops.topk import Rules

    avail = np.ones(stored, np.uint8)
    avail[::97] = 0
    cat = np.zeros(stored, np.int32)
    cat[list(small_cat)] = 1
    return retrieval.device_rules(Rules(
        avail=jnp.asarray(avail), cats=(jnp.asarray(cat),),
        qcat=np.full((b, 1), -2, np.int32) if qcat is None else qcat,
        has_cat=np.zeros(b, bool) if qcat is None else (qcat[:, 0] >= 0),
        ex=np.full((b, 4), -1, np.int32) if ex is None else ex,
    ))


def _coarse_scores(cat, table, q):
    """NumPy's copy of the coarse scores ``cat`` ranks by: [B, I] f32."""
    import jax.numpy as jnp

    vals, scales = table
    if cat.mode == "bf16":
        f = vals.astype(np.float32) * scales[:, None]
        return q @ np.asarray(
            jnp.asarray(f).astype(jnp.bfloat16).astype(jnp.float32)
        ).T
    if cat.mode == "int8":
        return (q @ vals.astype(np.float32).T) * scales[None, :]
    qs = np.abs(q).max(axis=1, keepdims=True) / np.float32(127.0)
    qi = np.clip(np.round(q / np.maximum(qs, 1e-12)), -127, 127)
    dots = qi.astype(np.int64) @ vals.astype(np.int64).T
    return dots.astype(np.float32) * scales[None, :]


class TestTwoLevelShortlist:
    """``CoarseCatalog.shortlist`` at a tile wide enough to engage two
    levels: the shortlist is the top-k' of the coarse scores, as a set."""

    I, D, T, K = 40_000, 16, 1 << 14, 64

    @pytest.mark.parametrize("n", [4, 16])
    @pytest.mark.parametrize("masked", [False, True])
    @pytest.mark.parametrize("mode", ["bf16", "int8", "int8_dot"])
    def test_shortlist_is_the_numpy_selection(self, mode, masked, n):
        """Four queries of rank 16 over a bf16 copy are one pass over
        the tiles; sixteen, or four over int8 values, would store more
        than half of what a step reads and are scanned in chunks of four
        or two (``scan_chunk``). Either way the numpy selection."""
        assert retrieval.select_group(self.T, self.K, 3)
        chunk = retrieval.scan_chunk(n, self.D, mode, PAST)
        assert chunk == (4 if mode == "bf16" else 2)
        table = _int8(self.I, self.D, seed=31)
        q = _dense(n, self.D, seed=32)
        cat = CoarseCatalog(table, tile=self.T, mode=mode)
        assert cat.tile == self.T and cat.stored_rows == 3 * self.T
        sc = _coarse_scores(cat, table, q)
        allowed = np.ones((n, self.I), bool)
        rules = None
        if masked:
            small = np.random.default_rng(33).permutation(self.I)[:40]
            small = small[small % 97 != 0]  # available ones
            ex = np.full((n, 4), -1, np.int32)
            ex[2, :3] = np.argsort(-sc[2])[:3]  # the query's own best
            qcat = np.full((n, 1), -2, np.int32)
            qcat[1], qcat[3] = 1, 0
            rules = _rules(cat.stored_rows, n, small_cat=small, ex=ex,
                           qcat=qcat)
            allowed[:, ::97] = False
            in_small = np.zeros(self.I, bool)
            in_small[small] = True
            allowed[1] &= in_small
            allowed[3] &= ~in_small
            allowed[2, ex[2, :3]] = False
            assert 0 < allowed[1].sum() < self.K
        s, ids = cat.shortlist(q, self.K, rules)
        for b in range(n):
            ranked = np.argsort(-np.where(allowed[b], sc[b], -np.inf),
                                kind="stable")
            want = ranked[: min(self.K, int(allowed[b].sum()))]
            got = ids[b][ids[b] >= 0]
            assert len(got) == len(set(got.tolist()))
            assert set(got.tolist()) == set(want.tolist()), (mode, b)
            # a short answer's tail is -1, at the end
            assert (ids[b][len(want):] == -1).all()
            np.testing.assert_allclose(
                s[b][: len(want)], sc[b][ids[b][: len(want)]],
                rtol=1e-5, atol=1e-5,
            )

    def test_a_small_tile_is_the_numpy_selection_too(self):
        """Three tiles of 256 rows split nothing: one ``lax.top_k`` of
        the stored row."""
        table = _int8(600, 8, seed=34)
        cat = CoarseCatalog(table, tile=256, mode="bf16")
        assert not retrieval.select_group(256, 32, 3)
        q = _dense(2, 8, seed=35)
        sc = _coarse_scores(cat, table, q)
        s, ids = cat.shortlist(q, 32)
        for b in range(2):
            want = np.argsort(-sc[b], kind="stable")[:32]
            assert set(ids[b].tolist()) == set(want.tolist())
            np.testing.assert_allclose(s[b], sc[b][ids[b]], rtol=1e-5, atol=1e-5)


# -- a single's score through the batched scans' dot ---------------------------


def _split_rows(kind, d=64):
    rng = np.random.default_rng(len(kind))
    q = rng.normal(size=(1, d)).astype(np.float32)
    if kind == "tiny":
        q *= np.float32(1e-24)  # the smallest term stays a normal number
    elif kind == "large":
        q *= np.float32(3e30)
    elif kind == "negative":
        q = -np.abs(q)
    elif kind == "zero":
        q[:] = 0.0
    elif kind == "mixed":
        q[0, ::3] = 0.0
        q[0, 1::3] *= np.float32(1e-12)
        q[0, 2::3] *= np.float32(-4e6)
    return q


class TestScoreForm:
    """One f32 query under 128 columns is scored as three bf16 rows
    through one dot (``score_form`` -> "dot"); every other shape keeps
    the program it had. The split is exact, so the shortlist is the
    selection it was."""

    I, D, T, K = 40_000, 64, 1 << 14, 64

    @pytest.mark.parametrize("d", [8, 64, 127, 128, 256])
    @pytest.mark.parametrize("b", [1, 2, 16])
    def test_shape_rule(self, b, d):
        want = "dot" if b == 1 and d < 128 else "rows"
        assert retrieval.score_form(b, d) == want

    @pytest.mark.parametrize(
        "kind", ["normal", "tiny", "large", "negative", "zero", "mixed"]
    )
    def test_the_three_terms_sum_back_bit_for_bit(self, kind):
        import jax.numpy as jnp

        q = _split_rows(kind)
        terms = retrieval._split_bf16(jnp.asarray(q))
        assert terms.dtype == jnp.bfloat16 and terms.shape == (3, q.shape[1])
        hi, mid, lo = np.asarray(terms.astype(jnp.float32))
        # smallest term first, in f32: every partial sum is representable
        back = (lo + mid) + hi
        np.testing.assert_array_equal(back.view(np.uint32),
                                      q[0].view(np.uint32))
        np.testing.assert_array_equal(
            hi, np.asarray(jnp.asarray(q[0]).astype(jnp.bfloat16)
                           .astype(jnp.float32))
        )
        assert (np.abs(mid) <= np.abs(hi)).all()
        assert (np.abs(lo) <= np.abs(mid)).all()

    def test_the_split_rounds_by_reduce_precision_not_by_a_cast_and_back(self):
        """XLA:TPU computes ``q.astype(bf16).astype(f32)`` inside a
        fusion in f32, so ``q - hi`` would be 0 and the query ONE bf16
        term (seen on the chip, PR 31; XLA:CPU does not show it): the
        only cast is the last one, of terms that are bf16 numbers."""
        import jax
        import jax.numpy as jnp

        jaxpr = jax.make_jaxpr(retrieval._split_bf16)(
            jnp.ones((1, 64), jnp.float32)
        ).jaxpr
        names = [e.primitive.name for e in jaxpr.eqns]
        assert names.count("reduce_precision") == 2
        assert names.count("convert_element_type") == 1
        assert names[-1] == "convert_element_type"
        q = _split_rows("normal")
        eager = np.asarray(retrieval._split_bf16(q).astype(jnp.float32))
        jitted = np.asarray(
            jax.jit(retrieval._split_bf16)(q).astype(jnp.float32)
        )
        np.testing.assert_array_equal(eager, jitted)

    def _allowed(self, sc, masked):
        allowed = np.ones((1, self.I), bool)
        if not masked:
            return None, allowed
        ex = np.full((1, 4), -1, np.int32)
        ex[0, :3] = np.argsort(-sc[0])[:3]  # the query's own best
        allowed[:, ::97] = False
        allowed[0, ex[0, :3]] = False
        return ex, allowed

    @pytest.mark.parametrize("masked", [False, True])
    @pytest.mark.parametrize("mode", ["bf16", "int8"])
    def test_a_single_is_the_numpy_selection_and_its_row_in_a_pair(
        self, mode, masked
    ):
        assert retrieval.score_form(1, self.D) == "dot"
        assert retrieval.select_group(self.T, self.K)
        table = _int8(self.I, self.D, seed=41)
        q = _dense(1, self.D, seed=42)
        cat = CoarseCatalog(table, tile=self.T, mode=mode)
        sc = _coarse_scores(cat, table, q.astype(np.float64))
        ex, allowed = self._allowed(sc, masked)
        rules = pair_rules = None
        if masked:
            rules = _rules(cat.stored_rows, 1, ex=ex)
            pair_rules = _rules(cat.stored_rows, 2, ex=np.repeat(ex, 2, 0))
        s, ids = cat.shortlist(q, self.K, rules)
        assert s.shape == ids.shape == (1, self.K)
        want = np.argsort(-np.where(allowed[0], sc[0], -np.inf),
                          kind="stable")[: self.K]
        assert set(ids[0].tolist()) == set(want.tolist())
        np.testing.assert_allclose(
            s[0], sc[0][ids[0]], rtol=0, atol=2e-6 * np.abs(sc[0]).max()
        )
        # the same query as row 0 of a batch of two: the "rows" program
        assert retrieval.score_form(2, self.D) == "rows"
        s2, ids2 = cat.shortlist(np.repeat(q, 2, 0), self.K, pair_rules)
        np.testing.assert_array_equal(ids2[0], ids[0])
        np.testing.assert_array_equal(ids2[1], ids[0])
        np.testing.assert_allclose(
            s2[0], s[0], rtol=0, atol=2e-6 * np.abs(sc[0]).max()
        )

    @pytest.mark.parametrize("masked", [False, True])
    @pytest.mark.parametrize("mode", ["bf16", "int8"])
    def test_the_engaged_scan_is_one_dot_and_the_rest_stays_one_row(
        self, mode, masked
    ):
        """One ``dot_general``, its query operand the three bf16 terms,
        the tile in no wider dtype than bf16; every ``top_k`` operand —
        the group maxima of all tiles and the candidates, once, after
        the loop (PR 33) — still has ONE row."""
        import jax.numpy as jnp

        nt, t, d, k = 2, 1 << 13, 64, 16
        g = retrieval.select_group(t, k, nt)
        rules = _rules(nt * t, 1) if masked else None
        jaxpr = _scan_jaxpr(1, nt, t, d, k, rules, mode)
        (query,) = _operands(jaxpr, "dot_general")
        assert query.shape[0] >= 3 and query.shape[1] == d
        assert query.dtype == jnp.bfloat16
        assert sorted(_top_k_eqns(jaxpr)) == sorted(
            [(1, nt * t // g), (1, k * g)]
        )

    @pytest.mark.parametrize("b,d", [(2, 64), (16, 64), (64, 64), (1, 128),
                                     (2, 128)])
    @pytest.mark.parametrize("masked", [False, True])
    def test_every_other_shape_keeps_its_score(self, b, d, masked):
        """B >= 2 and D >= 128: the f32 queries as they are against the
        tile cast to f32 — all of them, or a chunk at a time
        (``scan_chunk``: 64 queries of rank 64 are four chunks of 16) —
        and the selections after the loop, of as many rows."""
        import jax.numpy as jnp

        nt, t, k = 2, 1 << 13, 16
        assert retrieval.score_form(b, d) == "rows"
        g = retrieval.select_group(t, k, nt)
        rules = _rules(nt * t, b) if masked else None
        jaxpr = _scan_jaxpr(b, nt, t, d, k, rules)
        c = retrieval.scan_chunk(b, d, "bf16", PAST)
        assert c == min(b, 16 if d == 64 else 32)
        queries = _operands(jaxpr, "dot_general")
        assert len(queries) == b // c
        for query in queries:
            assert query.shape == (c, d) and query.dtype == jnp.float32
        assert sorted(_top_k_eqns(jaxpr)) == sorted(
            [(c, nt * t // g), (c, k * g)] * (b // c)
        )

    def test_int8_dot_has_no_f32_query_to_split(self):
        import jax.numpy as jnp

        assert retrieval.score_form(1, 64, "int8_dot") == "rows"
        assert retrieval.score_form(1, 64, "int8") == "dot"
        jaxpr = _scan_jaxpr(1, 2, 256, 64, 16, mode="int8_dot")
        (query,) = _operands(jaxpr, "dot_general")
        assert query.shape == (1, 64) and query.dtype == jnp.int8

    @pytest.mark.parametrize("b,d,mode,form", [
        (1, 64, "bf16", "dot"),
        (1, 64, "int8", "dot"),
        (1, 8, "bf16", "dot"),
        (2, 64, "bf16", "rows"),
        (3, 64, "int8", "rows"),      # pads to a batch of four
        (1, 128, "bf16", "rows"),
        (1, 64, "int8_dot", "rows"),
    ])
    def test_the_counter_counts_one_a_call(self, b, d, mode, form):
        from predictionio_tpu.obs import metrics as obs_metrics

        cat = CoarseCatalog(_int8(600, d, seed=43), tile=256, mode=mode)
        before = retrieval.stats_block()["score_form"]
        cat.shortlist(_dense(b, d, seed=44), 32)
        after = retrieval.stats_block()["score_form"]
        other = "rows" if form == "dot" else "dot"
        assert after[form] == before[form] + 1
        assert after[other] == before[other]
        scraped = obs_metrics.parse_prometheus(obs_metrics.render_prometheus())
        for f, n in after.items():
            assert scraped[
                f'pio_retrieval_score_form_total{{form="{f}"}}'
            ] == n


# -- one query selects once, after the tile loop -------------------------------


def _tile_loops(jaxpr):
    """The ``scan`` s over the tiles in a ``_coarse_scan``'s ``jaxpr``:
    one a chunk, none inside another."""
    loops = [e for e in jaxpr.eqns if e.primitive.name == "scan"]
    assert loops and not [
        e for loop in loops for e in _eqns(loop.params["jaxpr"].jaxpr)
        if e.primitive.name in ("scan", "while")
    ]
    return loops


def _scan_body(jaxpr):
    """The body of the (first) tile loop in ``jaxpr``."""
    return _tile_loops(jaxpr)[0].params["jaxpr"].jaxpr


def _tile_rules(rules, i, t):
    """``rules`` cut to tile ``i`` of ``t`` rows: its own vectors, the
    queries' lists in the tile's positions."""
    import jax.numpy as jnp

    ex = np.asarray(rules.ex)
    inside = (ex >= i * t) & (ex < (i + 1) * t)
    return rules._replace(
        avail=rules.avail[i * t: (i + 1) * t],
        cats=tuple(c[i * t: (i + 1) * t] for c in rules.cats),
        ex=jnp.asarray(np.where(inside, ex - i * t, -1).astype(np.int32)),
    )


class TestDeferredSelect:
    """A scan selects once, after the loop (one query since PR 33, a
    batch since PR 36, every batch and every tile since PR 44): against
    ``lax.top_k`` over the whole guarded score row — the scores
    bit-equal, the ids equal wherever the scores are distinct."""

    T, K = 1 << 13, 128

    @staticmethod
    def _scan(cat, q, k, rules):
        import jax

        return jax.device_get(jax.jit(
            lambda q, tiles, scales, ids, rules: retrieval._coarse_scan(
                q, tiles, scales, ids, k, cat.mode, rules
            )
        )(q, cat._tiles, cat._scales, cat._ids, rules))

    def _row(self, cat, q, rules):
        """The whole guarded (and masked) [B, stored] score row, a tile
        at a time: a one-tile catalog at k' = T splits nothing, so its
        scan is the step and one ``lax.top_k`` that keeps every score of
        the tile. Positions of rows that may not be served hold
        ``NEG_INF``."""
        nt, t = cat._ids.shape[0], cat.tile
        row = np.full((len(q), nt * t), retrieval.NEG_INF, np.float32)
        for i in range(nt):
            one = SimpleNamespace(
                mode=cat.mode, _tiles=cat._tiles[i: i + 1],
                _scales=None if cat._scales is None else cat._scales[i: i + 1],
                # positions, not ids: padding keeps its place in the row
                _ids=np.arange(i * t, (i + 1) * t, dtype=np.int32)[None],
            )
            guard = np.asarray(cat._ids[i]).reshape(-1) >= 0
            s, pos = self._scan(
                one, q, t, None if rules is None else _tile_rules(rules, i, t),
            )
            for b in range(len(q)):
                keep = pos[b] >= 0
                row[b, pos[b][keep]] = s[b][keep]
            row[:, i * t: (i + 1) * t][:, ~guard] = retrieval.NEG_INF
        return row

    def _catalog(self, mode, d, rows, seed, twin_tiles=False, tile=T):
        table = _int8(rows, d, seed=seed)
        if twin_tiles:  # tile 1 repeats tile 0: every score comes twice
            vals, scales = table = tuple(np.array(a) for a in table)
            vals[tile: 2 * tile] = vals[:tile]
            scales[tile: 2 * tile] = scales[:tile]
        return CoarseCatalog(table, tile=tile, mode=mode)

    # a batched case: (queries, what the single's case of that name has)
    BATCHED = {
        "pair": (2, "whole_tiles"),
        "four": (4, "whole_tiles"),
        "eight_padded_last_tile": (8, "padded_last_tile"),
        "sixteen": (16, "whole_tiles"),
        "pair_small_category": (2, "small_category"),
        "eight_own_rows_excluded": (8, "own_rows_excluded"),
        "sixteen_unavailable_padded": (16, "unavailable_rows"),
        "eight_equal_scores_across_tiles": (8, "equal_scores_across_tiles"),
    }

    @pytest.mark.parametrize("case", [
        "whole_tiles", "padded_last_tile", "one_tile", "five_tiles",
        "small_category", "own_rows_excluded", "unavailable_rows",
        "equal_scores_across_tiles", *BATCHED,
        # PR 43: what a single's one pass after its score has to keep
        "minus_one_ids_inside_a_tile", "a_tile_of_padding",
        "a_shortlist_of_1024",
    ])
    @pytest.mark.parametrize("d", [64, 128])
    @pytest.mark.parametrize("mode", ["bf16", "int8", "int8_dot"])
    def test_the_shortlist_it_was(self, mode, d, case):
        """The scan — a single and batches of 2, 4, 8 and 16, with and
        without ``Rules`` — against the whole row."""
        import jax

        # k' = 1,024 over tiles of 2^16 rows: groups of 8, as here at 128
        t, k = (1 << 16, 1024) if case == "a_shortlist_of_1024" else (
            self.T, self.K
        )
        padded = case == "sixteen_unavailable_padded"
        b, case = self.BATCHED.get(case, (1, case))
        rows = {"padded_last_tile": 2 * t + 1000, "one_tile": t,
                "five_tiles": 5 * t, "a_shortlist_of_1024": 2 * t + 1000,
                }.get(case, 3 * t - 1000 * padded)
        cat = self._catalog(mode, d, rows, seed=61, tile=t,
                            twin_tiles=case == "equal_scores_across_tiles")
        nt = cat._ids.shape[0]
        assert nt == -(-rows // t) and cat.tile == t
        assert retrieval.select_group(t, k, nt) == {1: 8, 5: 32}.get(nt, 16)
        # one pass in every case here but sixteen queries over rank-64
        # int8 values (more than half of what a step reads): two of 8
        beyond = b == 16 and d == 64 and mode != "bf16"
        assert retrieval.scan_chunk(b, d, mode, PAST) == (8 if beyond else b)
        assert retrieval.score_form(b, d, mode) == (
            "dot" if b == 1 and d == 64 and mode != "int8_dot" else "rows"
        )
        q = _dense(b, d, seed=62)
        rules = None
        gone = np.zeros(0, np.int64)  # rows whose stored id is made -1
        if case == "minus_one_ids_inside_a_tile":
            # the eight best rows and one row in 53, all over the tiles
            first = self._scan(cat, q, k, None)[1]
            gone = np.union1d(first[0, :8], np.arange(5, rows, 53))
        elif case == "a_tile_of_padding":  # its group maxima: all NEG_INF
            gone = np.arange(t, 2 * t)
        if len(gone):
            ids = np.array(cat._ids)
            ids.reshape(-1)[gone] = -1
            cat._ids = jax.numpy.asarray(ids)
        if case == "small_category":
            small = np.random.default_rng(63).permutation(rows)[:40]
            rules = _rules(cat.stored_rows, b, small_cat=small,
                           qcat=np.full((b, 1), 1, np.int32))
        elif case == "own_rows_excluded":
            first = self._scan(cat, q, k, None)[1]
            rules = _rules(cat.stored_rows, b, ex=first[:, :4].astype(np.int32))
        elif case == "unavailable_rows":
            rules = _rules(cat.stored_rows, b)
        got_s, got_i = self._scan(cat, q, k, rules)
        row = self._row(cat, q, rules)
        ref_s, ref_pos = jax.device_get(jax.lax.top_k(row, k))
        np.testing.assert_array_equal(
            got_s.view(np.uint32), ref_s.view(np.uint32)
        )
        flat_ids = np.asarray(cat._ids).reshape(-1)
        ref_i = flat_ids[ref_pos]
        if rules is not None:
            ref_i = np.where(ref_s > retrieval.NEG_INF / 2, ref_i, -1)
        twins = 0
        for r in range(b):
            live = got_s[r] > retrieval.NEG_INF / 2
            if len(np.unique(got_s[r][live])) == live.sum():
                np.testing.assert_array_equal(got_i[r], ref_i[r])
                continue
            # another choice among equals (tiles that repeat; a batch's
            # int8 products): every id holds its score, once
            twins += 1
            served = got_i[r][live]
            assert len(set(served.tolist())) == live.sum()
            assert (served >= 0).all() and (got_i[r][~live] == -1).all()
            np.testing.assert_array_equal(row[r][served], got_s[r][live])
        if case == "equal_scores_across_tiles":
            assert twins == b
        else:
            # (1,024 of 132,072 scores hold a pair of equals now and then)
            assert twins == 0 or (b > 1 and mode == "int8_dot") or k == 1024
        if case == "small_category":
            for r in range(b):
                live = int((got_i[r] >= 0).sum())
                assert 0 < live < k and (got_i[r, live:] == -1).all()
                assert (got_s[r, live:] == np.float32(retrieval.NEG_INF)).all()
        elif case == "own_rows_excluded":
            for r in range(b):
                assert not set(got_i[r].tolist()) & set(first[r, :4].tolist())
        elif case == "unavailable_rows":
            assert (got_i % 97 != 0).all() and got_i.max() < rows
        elif case in ("padded_last_tile", "a_shortlist_of_1024"):
            assert (got_i >= 0).all() and got_i.max() < rows
        elif len(gone):
            assert (got_i >= 0).all() and not set(got_i[0].tolist()) & set(
                gone.tolist()
            )

    @pytest.mark.parametrize("mode", ["bf16", "int8", "int8_dot"])
    def test_a_shortlist_too_wide_to_split_is_one_top_k_of_the_row(self, mode):
        """k' = T/2 over three tiles: ``select_group`` splits nothing,
        the steps keep their scores alone and the served selection is
        the ``lax.top_k`` of the stored row."""
        import jax

        t, k = self.T, self.T // 2
        assert retrieval.select_group(t, k, 3) == 0
        cat = self._catalog(mode, 64, 2 * t + 1000, seed=64)
        q = _dense(1, 64, seed=65)
        s, ids = cat.shortlist(q, k)
        ref_s, ref_pos = jax.device_get(
            jax.lax.top_k(self._row(cat, q, None), k)
        )
        np.testing.assert_array_equal(s.view(np.uint32), ref_s.view(np.uint32))
        np.testing.assert_array_equal(
            ids, np.asarray(cat._ids).reshape(-1)[ref_pos]
        )

    @pytest.mark.parametrize("b", [1, 4])
    @pytest.mark.parametrize("mode", ["bf16", "int8"])
    @pytest.mark.parametrize("t,nt,k,group", [
        (1 << 13, 8, 96, 128),    # a tile alone has 64 groups of 128 lanes
        (1 << 13, 16, 128, 128),
        (1 << 12, 8, 64, 32),     # a tile alone is under _MIN_SPLIT
        (1 << 12, 3, 16, 128),
    ])
    def test_the_catalog_offers_the_groups_a_tile_alone_would_not(
            self, t, nt, k, group, mode, b):
        """k' above T/128 (or a tile too small to split) over several
        tiles: the group width is the catalog's (``select_group`` of
        NT x T), the selection still the ``lax.top_k`` of the row."""
        import jax

        assert retrieval.select_group(t, k, nt) == group
        assert retrieval.select_group(t, k) != group
        rows = nt * t - 700
        cat = self._catalog(mode, 64, rows, seed=68, tile=t)
        assert cat._ids.shape[0] == nt
        q = _dense(b, 64, seed=69)
        rules = _rules(cat.stored_rows, b) if b > 1 else None
        got_s, got_i = self._scan(cat, q, k, rules)
        row = self._row(cat, q, rules)
        ref_s, ref_pos = jax.device_get(jax.lax.top_k(row, k))
        np.testing.assert_array_equal(
            got_s.view(np.uint32), ref_s.view(np.uint32)
        )
        ref_i = np.asarray(cat._ids).reshape(-1)[ref_pos]
        for r in range(b):
            if len(np.unique(got_s[r])) == k:
                np.testing.assert_array_equal(got_i[r], ref_i[r])
        assert (got_i >= 0).all() and got_i.max() < rows

    @pytest.mark.parametrize("b,nt,t,k,d,mode,chunk,group", [
        (1, 36, 1 << 18, 128, 64, "bf16", 1, 128),    # yambda
        (1, 16, 1 << 18, 128, 128, "bf16", 1, 128),   # both Taobao ones
        (1, 46, 1 << 18, 128, 64, "bf16", 1, 128),    # a sharded chip
        (1, 1, 1 << 18, 128, 64, "bf16", 1, 128),
        (1, 3, 1 << 13, 16, 64, "bf16", 1, 128),
        (1, 36, 1 << 18, 1024, 64, "bf16", 1, 128),
        # every batch the cells dispatch (B = 2 .. 16), all four shapes
        (2, 36, 1 << 18, 128, 64, "bf16", 2, 128),
        (4, 36, 1 << 18, 128, 64, "bf16", 4, 128),
        (8, 36, 1 << 18, 128, 64, "bf16", 8, 128),
        (16, 36, 1 << 18, 128, 64, "bf16", 16, 128),
        (2, 16, 1 << 18, 128, 128, "bf16", 2, 128),
        (8, 16, 1 << 18, 128, 128, "bf16", 8, 128),
        (16, 16, 1 << 18, 128, 128, "bf16", 16, 128),
        (2, 46, 1 << 18, 128, 64, "bf16", 2, 128),
        (16, 46, 1 << 18, 128, 64, "bf16", 16, 128),
        # the bound: B x 4 <= D x itemsize / 2, whatever the tiles' number
        (32, 36, 1 << 18, 128, 64, "bf16", 16, 128),  # 1.2 GB of scores
        (64, 36, 1 << 18, 128, 64, "bf16", 16, 128),
        (32, 46, 1 << 18, 128, 64, "bf16", 16, 128),
        (32, 16, 1 << 18, 128, 128, "bf16", 32, 128),
        (64, 16, 1 << 18, 128, 128, "bf16", 32, 128),
        (8, 184, 1 << 18, 128, 64, "int8", 8, 128),   # a byte a value
        (16, 184, 1 << 18, 128, 64, "int8", 8, 128),
        (16, 184, 1 << 18, 128, 64, "int8_dot", 8, 128),
        (2, 3, 1 << 13, 16, 8, "bf16", 2, 128),
        (4, 3, 1 << 13, 16, 8, "bf16", 2, 128),
        (1, 36, 1 << 18, 1 << 14, 64, "bf16", 1, 128),  # k' nears the TILE
        (1, 3, 1 << 12, 16, 64, "bf16", 1, 128),      # tiles under _MIN_SPLIT
        (1, 3, 256, 64, 64, "bf16", 1, 0),            # the CPU fixtures'
        (16, 3, 256, 64, 64, "bf16", 16, 0),
        # the smallest chunk is one query, whatever the rank (at the
        # parent a single of rank 2 "did not fit")
        (1, 3, 256, 16, 2, "bf16", 1, 0),
        (4, 3, 256, 16, 2, "bf16", 1, 0),
        (1, 3, 256, 16, 4, "int8", 1, 0),
        (2, 3, 256, 16, 7, "int8_dot", 1, 0),
        # a bound that is no power of two; a batch that is none (its
        # last chunk is what is left)
        (25, 3, 256, 16, 100, "bf16", 25, 0),
        (32, 3, 256, 16, 100, "bf16", 16, 0),
        (24, 3, 256, 16, 64, "bf16", 16, 0),
        (17, 3, 256, 16, 64, "bf16", 16, 0),
        # under rank 64 the tiles are small, not the scores large: what
        # stores at most _UNCUT is one pass whatever the rank — the ALS
        # templates' default rank 10 and bench.py's rank 32 over int8
        # values at its 1 M rows, any rank up to 9 tiles' 2.36 M rows
        (64, 4, 1 << 18, 128, 10, "bf16", 64, 128),
        (8, 4, 1 << 18, 128, 32, "int8", 8, 128),
        (64, 4, 1 << 18, 128, 32, "int8", 64, 128),
        (64, 9, 1 << 18, 128, 10, "int8", 64, 128),
        (64, 10, 1 << 18, 128, 10, "int8", 32, 128),
        # 16 queries over yambda's 36 tiles at any rank, 8 up to 72 tiles
        (16, 36, 1 << 18, 128, 10, "bf16", 16, 128),
        (64, 36, 1 << 18, 128, 10, "bf16", 16, 128),
        (64, 36, 1 << 18, 128, 32, "int8", 16, 128),
        (64, 72, 1 << 18, 128, 10, "bf16", 8, 128),
        (64, 73, 1 << 18, 128, 10, "bf16", 4, 128),
        # rank 10 over the int8 cell's 184 tiles: the first bound's two
        (1, 184, 1 << 18, 128, 10, "bf16", 1, 128),
        (8, 184, 1 << 18, 128, 10, "bf16", 2, 128),
        (8, 184, 1 << 18, 128, 10, "int8", 2, 128),    # three fit _UNCUT
    ])
    def test_the_rule(self, monkeypatch, b, nt, t, k, d, mode, chunk, group):
        """How many queries a pass takes (``scan_chunk``) and the group
        width its steps keep maxima of (``select_group``). The tiles of
        2^18 rows are read with the bytes no pass is cut under
        (``_UNCUT``: 604 MB, what 16 queries store over 36 such tiles);
        the small ones stand for a catalog past them, as everywhere in
        the tests (conftest.py)."""
        uncut = 16 * 36 * (1 << 18) * 4
        monkeypatch.setattr(retrieval, "_UNCUT", uncut)
        rows = nt * t if t == 1 << 18 else PAST
        assert retrieval.scan_chunk(b, d, mode, rows) == chunk
        assert retrieval.select_group(t, k, nt) == group
        # the stored scores against the tiles' bytes and the floor
        half = rows * d * (2 if mode == "bf16" else 1) / 2
        assert chunk * rows * 4 <= max(half, uncut) or chunk == 1
        if chunk < b:  # a power of two, and twice it would fit neither
            assert chunk & (chunk - 1) == 0
            assert 2 * chunk * rows * 4 > max(half, uncut)

    @pytest.mark.parametrize("masked", [False, True])
    @pytest.mark.parametrize("mode,d", [
        ("bf16", 64), ("bf16", 128), ("int8", 64), ("int8_dot", 64),
    ])
    def test_a_step_selects_nothing_and_carries_nothing_at_any_batch(
        self, mode, d, masked
    ):
        """The jaxpr: the tile loop of a single, a pair, a batch of
        eight, the batch at the bound's edge and the loops of the two
        beyond it (a loop a chunk) have no carry and their bodies no
        ``sort`` / ``top_k`` — score, guard, mask, one maximum a group —
        and every selection stands after its loop, a chunk's rows wide."""
        nt, t, k = 2, 1 << 13, 16
        g = retrieval.select_group(t, k, nt)
        groups = nt * t // g
        assert g == 128 and not retrieval.select_group(groups, k)
        assert retrieval.select_group(k * g, k) == 0
        edge = d // 4 if mode == "bf16" else d // 8  # the bound's edge
        for b in (1, 2, 8, edge, 2 * edge, 4 * edge):
            c = retrieval.scan_chunk(b, d, mode, PAST)
            assert c == min(b, edge)
            jaxpr = _scan_jaxpr(b, nt, t, d, k, _rules(nt * t, b) if masked
                                else None, mode)
            loops = _tile_loops(jaxpr)
            assert len(loops) == b // c  # one after another, in one program
            for loop in loops:
                assert loop.params["num_carry"] == 0
                assert loop.params["length"] == nt
                body = loop.params["jaxpr"].jaxpr
                inside = [e.primitive.name for e in _eqns(body)]
                assert not {"sort", "top_k", "gather", "concatenate"} & set(inside)
                assert inside.count("reduce_max") == 1
            assert sorted(_top_k_eqns(jaxpr)) == sorted(
                [(c, k * g), (c, groups)] * (b // c)
            )

    @pytest.mark.parametrize("b", [1, 2, 8, 16])
    def test_the_scores_are_stored_query_major_at_every_batch(self, b):
        """The scan's stacked outputs: [NT, B, T/G, G] scores and
        [NT, B, T/G] maxima, whatever B. (On the chip a dot leaves a
        batch of 8 in blocks of 8 queries x 128 lanes, group after
        group; storing it in that order was tried and lost: PERF.md
        section 6, PR 36.)"""
        nt, t, k = 2, 1 << 13, 128
        assert retrieval.select_group(t, k, nt) == 16
        (scan,) = [e for e in _scan_jaxpr(b, nt, t, 64, k).eqns
                   if e.primitive.name == "scan"]
        scores, maxima = (v.aval.shape for v in scan.outvars)
        assert scores == (nt, b, 512, 16) and maxima == (nt, b, 512)

    def test_the_benchmark_shapes_select_in_small_sorts_once(self):
        """36 tiles of 2^18 at k' = 128: [1, 73728] maxima through the
        tiles' helper ([1, 576] maxima, [1, 16384] candidates as
        [1, 1024] + [1, 2048]), then the [1, 16384] scores of the chosen
        groups the same way: five selections of at most 2,048 a call."""
        jaxpr = _scan_jaxpr(1, 36, 1 << 18, 8, 128)
        assert not _top_k_eqns(_scan_body(jaxpr))
        assert sorted(_top_k_eqns(jaxpr)) == [
            (1, 576), (1, 1024), (1, 1024), (1, 2048), (1, 2048)
        ]

    @pytest.mark.parametrize("b,chunks", [(1, 1), (2, 1), (3, 1), (4, 1),
                                          (5, 2), (16, 4)])
    def test_a_call_is_one_upload_and_one_read_whatever_its_chunks(
            self, b, chunks):
        """Rank 16 in bf16: up to 4 queries are one pass; 5 pad to 8 and
        are two chunks, 16 are four — of ONE program: one upload, one
        blocking read, one count a call, and no counter of the choice."""
        from predictionio_tpu.obs import metrics as obs_metrics

        cat = self._catalog("bf16", 16, 2 * self.T + 5, seed=66)
        bp = retrieval._pow2(b)
        assert bp // retrieval.scan_chunk(bp, 16, "bf16", PAST) == chunks
        q = _dense(b, 16, seed=67)
        cat.shortlist(q, 64)  # compiled
        before = retrieval.stats_block()
        s, ids = cat.shortlist(q, 64)
        after = retrieval.stats_block()
        assert after["uploads"] == before["uploads"] + 1
        assert after["host_reads"] == before["host_reads"] + 1
        form = "dot" if b == 1 else "rows"
        assert after["score_form"][form] == before["score_form"][form] + 1
        assert "tile_select" not in after
        assert b"tile_select" not in obs_metrics.render_prometheus()
        # a chunk's rows are the rows of the same queries sent alone
        for lo in range(0, b if chunks > 1 else 0, 4):
            n = min(4, b - lo)  # the last chunk's other rows: copies of row 0
            piece = np.concatenate([q[lo: lo + n], np.repeat(q[:1], 4 - n, 0)])
            one_s, one_i = cat.shortlist(piece, 64)
            np.testing.assert_array_equal(
                s[lo: lo + n].view(np.uint32), one_s[:n].view(np.uint32)
            )
            np.testing.assert_array_equal(ids[lo: lo + n], one_i[:n])


# -- the chain: one owner of "exact or two-stage, shortlist -> rescore, probe" --


# -- the per-row side arrays lie [NT, T/128, 128] --------------------------------


@functools.lru_cache(maxsize=None)
def _sides_catalog(mode, tile):
    """A catalog of rank 64 whose third tile holds 300 rows and
    padding, its table, and NumPy's coarse scores' inputs."""
    table = _int8(2 * tile + min(300, tile // 3), 64, seed=91)
    return CoarseCatalog(table, tile=tile, mode=mode), table


class TestSideArrays:
    """A catalog stores its row ids and an int8 pair's row scales
    [NT, T/L, L] (``side_shape``: L = 128 lanes where they divide the
    tile, else the tile), and the scan takes them as they lie: against
    NumPy's selection over NumPy's coarse scores, and against the SAME
    scan handed the same values [NT, T] — the form every catalog stored
    before PR 42 — bit for bit."""

    K = 16
    # the catalog's shape -> its tile: 2^14 rows split into 128-lane
    # groups at k' = 16 (the served structure: g = L = 128); 96 rows
    # split nothing and are no whole number of lanes (L = T)
    TILES = {"lane_groups": 1 << 14, "no_split": 96}

    @pytest.mark.parametrize("t,want", [
        (1 << 18, (5, 2048, 128)), (1 << 14, (5, 128, 128)),
        (128, (5, 1, 128)), (96, (5, 1, 96)), (200, (5, 1, 200)),
        (1, (5, 1, 1)),
    ])
    def test_the_shape_comes_from_the_tile_alone(self, t, want):
        assert retrieval.side_shape(5, t) == want

    @pytest.mark.parametrize("mode", ["bf16", "int8", "int8_dot"])
    @pytest.mark.parametrize("tile", [1 << 14, 96])
    def test_a_catalog_stores_them_so(self, mode, tile):
        cat, (vals, scales) = _sides_catalog(mode, tile)
        shape = retrieval.side_shape(3, tile)
        assert shape[2] == (128 if tile % 128 == 0 else tile)
        assert cat._ids.shape == shape and cat.stored_rows == 3 * tile
        flat = np.asarray(cat._ids).reshape(-1)
        np.testing.assert_array_equal(flat[: len(vals)], np.arange(len(vals)))
        assert (flat[len(vals):] == -1).all() and len(vals) < 2.4 * tile
        if mode == "bf16":
            assert cat._scales is None
            assert cat.nbytes() == 3 * tile * (64 * 2 + 4)
            return
        assert cat._scales.shape == shape
        got = np.asarray(cat._scales).reshape(-1)
        np.testing.assert_array_equal(got[: len(vals)], scales)
        assert (got[len(vals):] == 1.0).all()
        assert cat.nbytes() == 3 * tile * (64 + 4 + 4)

    @pytest.mark.parametrize("ruled", [False, True], ids=["open", "rules"])
    @pytest.mark.parametrize("shape", ["lane_groups", "no_split"])
    @pytest.mark.parametrize("b", [1, 2, 8, 16, 32])
    @pytest.mark.parametrize("mode", ["bf16", "int8", "int8_dot"])
    def test_parity(self, mode, b, shape, ruled):
        """32 queries are beyond the bound of every mode at rank 64
        (``scan_chunk``: two chunks of 16, four of 8), 16 beyond the
        int8 modes'."""
        import jax

        t, k = self.TILES[shape], self.K
        cat, table = _sides_catalog(mode, t)
        rows, stored = len(table[0]), cat.stored_rows
        assert retrieval.scan_chunk(32, 64, mode, PAST) == (16 if mode == "bf16" else 8)
        assert retrieval.select_group(t, k, 3) == (
            128 if shape == "lane_groups" else 0
        )
        q = _dense(b, 64, seed=92 + b)
        sc = _coarse_scores(cat, table, q)
        allowed = np.ones((b, rows), bool)
        rules = None
        if ruled:
            small = np.random.default_rng(93).permutation(rows)[:10]
            ex = np.full((b, 4), -1, np.int32)
            ex[-1, :3] = np.argsort(-sc[-1])[:3]  # the last query's own best
            ex[0, 3] = rows - 1  # a row of the mostly padded tile
            qcat = np.full((b, 1), -2, np.int32)
            qcat[b // 2] = 1
            rules = _rules(stored, b, small_cat=small, ex=ex, qcat=qcat)
            allowed[:, ::97] = False
            in_small = np.zeros(rows, bool)
            in_small[small] = True
            allowed[b // 2] &= in_small
            allowed[-1, ex[-1, :3]] = False
            allowed[0, rows - 1] = False

        def scan(ids, scales):
            return jax.device_get(jax.jit(
                lambda q, tiles, scales, ids, rules: retrieval._coarse_scan(
                    q, tiles, scales, ids, k, mode, rules,
                )
            )(q, cat._tiles, scales, ids, rules))

        s, ids = scan(cat._ids, cat._scales)
        flat_s, flat_ids = scan(
            cat._ids.reshape(3, t),
            None if cat._scales is None else cat._scales.reshape(3, t),
        )
        np.testing.assert_array_equal(s.view(np.uint32), flat_s.view(np.uint32))
        np.testing.assert_array_equal(ids, flat_ids)
        for r in range(b):
            n = min(k, int(allowed[r].sum()))
            got = ids[r][:n]
            assert (ids[r][n:] == -1).all() and (got >= 0).all()
            assert len(set(got.tolist())) == n and allowed[r][got].all()
            np.testing.assert_allclose(
                s[r][:n], sc[r][got], rtol=1e-5, atol=1e-5
            )
            # nothing better was left out: NumPy's selection, to rounding
            rest = allowed[r].copy()
            rest[got] = False
            if rest.any():
                assert sc[r][rest].max() <= s[r][:n].min() + 1e-4
        if ruled:
            assert 0 < int((ids[b // 2] >= 0).sum()) == int(allowed[b // 2].sum()) < k


class TestChunkedBatches:
    """A batch beyond the stored-scores bound is scanned a chunk of the
    queries at a time inside the one program (``scan_chunk``): the
    answer of the same queries sent as calls within the bound, bit for
    bit, with ``Rules`` whose per-query rows differ from chunk to
    chunk; and the smallest chunk is one query."""

    K = 16
    TILES = TestSideArrays.TILES

    @staticmethod
    def _scan(cat, q, k, rules):
        import jax

        return jax.device_get(jax.jit(
            lambda q, tiles, scales, ids, rules: retrieval._coarse_scan(
                q, tiles, scales, ids, k, cat.mode, rules
            )
        )(q, cat._tiles, cat._scales, cat._ids, rules))

    @staticmethod
    def _own_rules(stored, rows, b, seed):
        """Rules whose every per-query row is its own: a category for
        every third query, two excluded rows a query."""
        rng = np.random.default_rng(seed)
        qcat = np.full((b, 1), -2, np.int32)
        qcat[::3] = 1
        ex = np.full((b, 4), -1, np.int32)
        ex[:, :2] = rng.integers(0, rows, (b, 2))
        return _rules(stored, b, small_cat=rng.permutation(rows)[: rows // 3],
                      ex=ex, qcat=qcat)

    _rows = staticmethod(retrieval._query_rows)

    @pytest.mark.parametrize("ruled", [False, True], ids=["open", "rules"])
    @pytest.mark.parametrize("shape", ["lane_groups", "no_split"])
    @pytest.mark.parametrize("chunks", [2, 4])
    @pytest.mark.parametrize("mode", ["bf16", "int8", "int8_dot"])
    def test_a_batch_beyond_the_bound_answers_as_calls_within_it(
            self, mode, chunks, shape, ruled):
        """B = 32 and 64 in bf16, 16 and 32 over int8 values, at rank 64."""
        t = self.TILES[shape]
        cat, table = _sides_catalog(mode, t)
        c = 16 if mode == "bf16" else 8
        b = chunks * c
        assert retrieval.scan_chunk(b, 64, mode, PAST) == c
        q = _dense(b, 64, seed=95 + b)
        rules = self._own_rules(cat.stored_rows, len(table[0]), b, 96) if ruled \
            else None
        s, ids = self._scan(cat, q, self.K, rules)
        assert s.shape == ids.shape == (b, self.K)
        for lo in range(0, b, c):
            one_s, one_i = self._scan(
                cat, q[lo: lo + c], self.K, self._rows(rules, lo, lo + c)
            )
            np.testing.assert_array_equal(
                s[lo: lo + c].view(np.uint32), one_s.view(np.uint32)
            )
            np.testing.assert_array_equal(ids[lo: lo + c], one_i)
        if ruled:  # the rows differ between the chunks, and they bind
            assert (ids % 97 != 0).all()
            assert not (ids[:, :, None] == np.asarray(rules.ex)[:, None, :]).any()
            assert len({tuple(r) for r in ids.tolist()}) == b

    @pytest.mark.parametrize("ruled", [False, True], ids=["open", "rules"])
    @pytest.mark.parametrize("b", [17, 33])
    def test_chunks_of_padding_cost_no_row_its_answer(self, b, ruled):
        """17 queries pad to 32 and 33 to 64 with copies of row 0: the
        last chunk of 64 is ALL copies. Every served row is the row of
        the same query in a call within the bound."""
        cat, table = _sides_catalog("bf16", 1 << 14)
        bp = retrieval._pow2(b)
        assert retrieval.scan_chunk(bp, 64, "bf16", PAST) == 16 and (bp - b) >= 15
        q = retrieval._pad_rows(_dense(b, 64, seed=97), bp)
        rules = self._own_rules(cat.stored_rows, len(table[0]), b, 98) if ruled \
            else None
        if ruled:
            rules = rules._replace(**{
                part: retrieval._pad_rows(np.asarray(getattr(rules, part)), bp)
                for part in ("qcat", "has_cat", "ex")
            })
            rules = retrieval.device_rules(rules)
        before = retrieval.stats_block()
        s, ids = cat.shortlist(q[:b] if not ruled else q, self.K, rules)
        after = retrieval.stats_block()
        assert after["host_reads"] == before["host_reads"] + 1
        assert after["uploads"] == before["uploads"] + 1
        for lo in range(0, b, 16):
            one_s, one_i = self._scan(
                cat, q[lo: lo + 16], self.K, self._rows(rules, lo, lo + 16)
            )
            n = min(16, b - lo)
            np.testing.assert_array_equal(
                s[lo: lo + n].view(np.uint32), one_s[:n].view(np.uint32)
            )
            np.testing.assert_array_equal(ids[lo: lo + n], one_i[:n])

    @pytest.mark.parametrize("ruled", [False, True], ids=["open", "rules"])
    @pytest.mark.parametrize("b", [17, 24, 40])
    def test_a_batch_that_is_no_power_of_two_ends_in_what_is_left(
            self, b, ruled):
        """No served caller sends one (``shortlist``, ``pack`` and the
        sharded path pad to a power of two); a direct call is cut into
        chunks of 16 and a last one of 1 or 8, each the answer of the
        same queries alone."""
        cat, table = _sides_catalog("bf16", 1 << 14)
        assert retrieval.scan_chunk(b, 64, "bf16", PAST) == 16
        q = _dense(b, 64, seed=102 + b)
        rules = self._own_rules(cat.stored_rows, len(table[0]), b, 103) if ruled \
            else None
        s, ids = self._scan(cat, q, self.K, rules)
        assert s.shape == ids.shape == (b, self.K)
        for lo in range(0, b, 16):
            one_s, one_i = self._scan(
                cat, q[lo: lo + 16], self.K, self._rows(rules, lo, lo + 16)
            )
            np.testing.assert_array_equal(
                s[lo: lo + 16].view(np.uint32), one_s.view(np.uint32)
            )
            np.testing.assert_array_equal(ids[lo: lo + 16], one_i)

    @pytest.mark.parametrize("mode,d,b", [
        ("bf16", 10, 64), ("int8", 16, 64), ("int8", 32, 8), ("int8_dot", 32, 16),
    ])
    def test_a_catalog_under_the_uncut_bytes_is_one_pass_at_any_rank(
            self, monkeypatch, mode, d, b):
        """The ALS templates' default rank 10 and bench.py's rank 32:
        beyond the first bound (2 queries at rank 10, and at rank 16
        over int8 values, 4 at rank 32), and ONE loop over the tiles wherever the
        stored scores are under ``_UNCUT`` — 64 queries of a 5,000-row
        catalog are 1.5 MB — with the answer of the chunks."""
        rows = 5_000
        table = _int8(rows, d, seed=104)
        cat = CoarseCatalog(table, tile=1 << 11, mode=mode)
        q = _dense(b, d, seed=105)
        c = retrieval.scan_chunk(b, d, mode, PAST)
        assert c == max(1, d * (2 if mode == "bf16" else 1) // 8) < b
        cut = self._scan(cat, q, self.K, None)  # a loop a chunk (conftest)
        monkeypatch.setattr(retrieval, "_UNCUT", 16 * 36 * (1 << 18) * 4)
        assert retrieval.scan_chunk(b, d, mode, cat.stored_rows) == b
        nt, t, _ = cat._tiles.shape
        jaxpr = _scan_jaxpr(b, nt, t, d, self.K, None, mode)
        assert len(_tile_loops(jaxpr)) == 1
        s, ids = self._scan(cat, q, self.K, None)
        np.testing.assert_array_equal(s.view(np.uint32), cut[0].view(np.uint32))
        np.testing.assert_array_equal(ids, cut[1])

    @pytest.mark.parametrize("ruled", [False, True], ids=["open", "rules"])
    @pytest.mark.parametrize("mode,d", [("bf16", 2), ("int8", 4), ("int8_dot", 7)])
    def test_the_smallest_chunk_is_one_query(self, mode, d, ruled):
        """Rank 2 in bf16 (4, 7 over int8 values): four bytes of stored
        values a row against four of one query's score — at the parent
        a SINGLE "did not fit". One query is a pass; four are four
        passes of one, each the single's answer bit for bit."""
        assert retrieval.scan_chunk(1, d, mode, PAST) == 1
        assert retrieval.scan_chunk(4, d, mode, PAST) == 1
        rows = 2 * 96 + 30
        table = _int8(rows, d, seed=99)
        cat = CoarseCatalog(table, tile=96, mode=mode)
        q = _dense(4, d, seed=100)
        rules = self._own_rules(cat.stored_rows, rows, 4, 101) if ruled else None
        s, ids = self._scan(cat, q, self.K, rules)
        sc = _coarse_scores(cat, table, q)
        for r in range(4):
            one_s, one_i = self._scan(
                cat, q[r: r + 1], self.K, self._rows(rules, r, r + 1)
            )
            np.testing.assert_array_equal(
                s[r: r + 1].view(np.uint32), one_s.view(np.uint32)
            )
            np.testing.assert_array_equal(ids[r: r + 1], one_i)
            live = ids[r] >= 0
            assert live.sum() == len(set(ids[r][live].tolist())) > 0
            np.testing.assert_allclose(
                s[r][live], sc[r][ids[r][live]], rtol=1e-5, atol=1e-5
            )
            if not ruled:  # NumPy's selection, to rounding
                rest = np.ones(rows, bool)
                rest[ids[r]] = False
                assert live.all() and sc[r][rest].max() <= s[r].min() + 1e-4


class TestServingChain:
    """``retrieval.top_k`` returns what the explicit sequence returns —
    the form's exact op below the threshold, ``shortlist`` then the
    form's ``rescore_*`` above it — and probes row 0 alone on every
    ``PIO_RETRIEVAL_PROBE_EVERY``-th two-stage dispatch, never below."""

    I, D, B, K = 500, 8, 4, 8  # 500 rows in 4 tiles of 128: 12 rows of padding

    def _form(self, form, table, host, stored):
        """(the query form, its exact call, its rescore call given the
        candidates) over ``table``; ``stored`` sizes the rules."""
        import jax.numpy as jnp

        from predictionio_tpu.ops import topk

        if form == "user_rows":
            users = _dense(32, self.D, seed=42)
            ixs, U = np.asarray([3, 7, 1, 30], np.int32), jnp.asarray(users)
            return (
                retrieval.UserRows(ixs, U, lambda i: users[i]),
                lambda: gather_top_k_batch(ixs, U, table, k=self.K),
                lambda cand: retrieval.rescore_gather_top_k_batch(
                    ixs, U, table, cand, k=self.K),
            )
        if form == "sum_rows":
            ixs = np.asarray([[5, 9], [2, 0], [7, 7], [40, 41]], np.int32)
            weights = np.asarray([[1, 1], [1, 0], [1, 1], [1, 1]], np.float32)
            rules = _open_rules(stored, self.B)
            return (
                retrieval.SumRows(
                    ixs, weights,
                    lambda i, w: (host[i] * w[..., None]).sum(axis=1),
                    _host(rules),
                ),
                lambda: sum_rows_top_k_batch_masked(
                    ixs, weights, table, rules, k=self.K),
                lambda cand: retrieval.rescore_sum_rows_top_k_batch(
                    ixs, weights, table, cand, k=self.K, rules=rules),
            )
        v = _dense(self.B, self.D, seed=43)
        if form == "vectors":
            return (
                retrieval.Vectors(v),
                lambda: topk.top_k_items_batch(v, table, k=self.K),
                lambda cand: retrieval.rescore_top_k_batch(
                    v, table, cand, self.K),
            )
        ex = np.full((self.B, 4), -1, np.int32)
        ex[2, :3] = np.argsort(-(v[2] @ host.T))[:3]  # the query's own best
        rules = _rules(
            stored, self.B, small_cat=range(0, self.I, 5), ex=ex,
            qcat=np.asarray([[-2], [1], [-2], [0]], np.int32),
        )
        return (
            retrieval.Vectors(v, _host(rules)),
            lambda: topk.top_k_items_batch_masked(v, table, rules, k=self.K),
            lambda cand: retrieval.rescore_top_k_batch(
                v, table, cand, self.K, rules),
        )

    @pytest.mark.parametrize("int8", [False, True], ids=["f32", "int8"])
    @pytest.mark.parametrize(
        "two_stage", [False, True], ids=["below_threshold", "two_stage"]
    )
    @pytest.mark.parametrize(
        "form", ["user_rows", "vectors", "vectors_rules", "sum_rows"]
    )
    def test_chain_is_the_explicit_sequence(
        self, monkeypatch, form, two_stage, int8
    ):
        import itertools

        import jax.numpy as jnp

        monkeypatch.setenv(
            "PIO_RETRIEVAL_THRESHOLD", "64" if two_stage else "100000"
        )
        monkeypatch.setenv("PIO_RETRIEVAL_TILE", "128")
        monkeypatch.setenv("PIO_RETRIEVAL_PROBE_EVERY", "3")
        monkeypatch.setattr(retrieval, "_probe_clock", itertools.count(1))
        if int8:
            vals, scales = _int8(self.I, self.D, seed=41)
            table = (jnp.asarray(vals), jnp.asarray(scales))
            host = vals.astype(np.float32) * scales[:, None]
        else:
            host = _dense(self.I, self.D, seed=41)
            table = jnp.asarray(host)
        coarse = CoarseCatalog(table)
        assert coarse.stored_rows == 512
        kp = retrieval.two_stage_k(self.K, self.I)
        assert kp == (64 if two_stage else 0)
        query, exact, rescore = self._form(
            form, table, host, coarse.stored_rows if two_stage else self.I
        )
        if two_stage:
            _, cand = coarse.shortlist(
                query.coarse_vectors(), kp, _device(query.rules)
            )
            want = rescore(cand)
        else:
            want = exact()
        want_s, want_ids = np.asarray(want[0]), np.asarray(want[1])
        assert (want_ids[:, 0] >= 0).all()

        lookups, shortlists, exact_rows, probed = [], [], [], []
        real_launch, real_exact = CoarseCatalog.launch, type(query).exact
        monkeypatch.setattr(
            CoarseCatalog, "launch",
            lambda self, q, k, *rest: shortlists.append(k)
            or real_launch(self, q, k, *rest),
        )
        monkeypatch.setattr(
            type(query), "exact",
            lambda self, table, k: exact_rows.append(len(self[0]))
            or real_exact(self, table, k),
        )
        real_recall = retrieval.probe_recall
        monkeypatch.setattr(
            retrieval, "probe_recall",
            lambda got, want: probed.append((len(got), len(want)))
            or real_recall(got, want),
        )
        before = retrieval.stats_block()
        for _ in range(6):
            s, ids = retrieval.top_k(
                query, table, self.I, lambda: lookups.append(1) or coarse,
                self.K, probe_n=5,
            )
            assert isinstance(s, np.ndarray) and isinstance(ids, np.ndarray)
            np.testing.assert_array_equal(ids, want_ids)
            np.testing.assert_array_equal(s, want_s)
        after = retrieval.stats_block()
        assert after["exact_queries"] == before["exact_queries"]
        if two_stage:
            # every dispatch shortlists k' and rescores; the 3rd and the
            # 6th also run the exact program, on the first query alone
            assert shortlists == [kp] * 6 and len(lookups) == 6
            assert exact_rows == [1, 1] and probed == [(5, 5)] * 2
            assert after["probes"] == before["probes"] + 2
            assert after["probe_recall"] == 1.0
            assert after["two_stage_queries"] == (
                before["two_stage_queries"] + 6 * self.B
            )
        else:
            assert not shortlists and not lookups and not probed
            assert exact_rows == [self.B] * 6
            assert after["probes"] == before["probes"]
            assert after["two_stage_queries"] == before["two_stage_queries"]

    @pytest.mark.parametrize(
        "engaged", [False, True], ids=["below_threshold", "engaged"]
    )
    def test_a_filter_that_rules_out_most_of_the_catalog_is_rules(
        self, monkeypatch, engaged
    ):
        """What the dense ``exclude_mask`` of a sum-of-rows query was
        (PR 30 removed it with ``exact_only``): a filter that leaves
        three rows travels as ``Rules``, shortlists at retrieval scale
        like any query and is answered by the masked exact program below
        it; ``path="exact"`` counts only a k that leaves a shortlist no
        room."""
        import jax.numpy as jnp

        from predictionio_tpu.ops.topk import sum_rows_top_k_batch_masked

        monkeypatch.setenv(
            "PIO_RETRIEVAL_THRESHOLD", "64" if engaged else "100000"
        )
        monkeypatch.setenv("PIO_RETRIEVAL_TILE", "128")
        monkeypatch.setenv("PIO_RETRIEVAL_PROBE_EVERY", "0")
        host = _dense(self.I, self.D, seed=44)
        table = jnp.asarray(host)
        coarse = CoarseCatalog(table)
        stored = coarse.stored_rows if engaged else self.I
        rules = _rules(
            stored, 1, small_cat=[4, 17, 300], qcat=np.asarray([[1]], np.int32)
        )
        ixs, weights = np.asarray([[5]], np.int32), np.ones((1, 1), np.float32)
        query = retrieval.SumRows(
            ixs, weights, lambda i, w: (host[i] * w[..., None]).sum(axis=1),
            _host(rules),
        )
        before = retrieval.stats_block()
        s, ids = retrieval.top_k(query, table, self.I, coarse, self.K)
        after = retrieval.stats_block()
        es, ei = sum_rows_top_k_batch_masked(
            ixs, weights, table, rules, k=self.K
        )
        np.testing.assert_array_equal(ids, np.asarray(ei))
        np.testing.assert_allclose(s, np.asarray(es), atol=2e-6, rtol=0)
        assert set(ids[0][ids[0] >= 0].tolist()) == {4, 17, 300}
        assert after["exact_queries"] == before["exact_queries"]
        assert after["two_stage_queries"] == (
            before["two_stage_queries"] + int(engaged)
        )
        # a k the shortlist cannot oversample: the exact program, counted
        s, ids = retrieval.top_k(query, table, self.I, coarse, 256)
        assert set(ids[0][ids[0] >= 0].tolist()) == {4, 17, 300}
        assert retrieval.stats_block()["exact_queries"] == (
            after["exact_queries"] + int(engaged)
        )


class TestOneCrossing:
    """A two-stage dispatch through ``top_k`` crosses the host boundary
    once each way: the scan's ids reach the rescore as the device array
    they are, at the scan's power-of-two rows, and one read ends it."""

    I, D, K = 500, 8, 8  # 4 tiles of 128; k' = 64

    @pytest.fixture(autouse=True)
    def _two_stage(self, monkeypatch):
        monkeypatch.setenv("PIO_RETRIEVAL_THRESHOLD", "64")
        monkeypatch.setenv("PIO_RETRIEVAL_TILE", "128")
        monkeypatch.setenv("PIO_RETRIEVAL_PROBE_EVERY", "0")

    def _table(self, int8, seed=51, rows=None, d=None):
        import jax.numpy as jnp

        rows, d = rows or self.I, d or self.D
        if int8:
            vals, scales = _int8(rows, d, seed=seed)
            return ((jnp.asarray(vals), jnp.asarray(scales)),
                    vals.astype(np.float32) * scales[:, None])
        host = _dense(rows, d, seed=seed)
        return jnp.asarray(host), host

    def _form(self, form, b, table, host, stored, seed=52):
        """(the query form for ``b`` queries, the host-facing rescore of
        candidates read back from ``shortlist``)."""
        import jax.numpy as jnp

        rng = np.random.default_rng(seed + b)
        d = host.shape[1]
        if form == "user_rows":
            users = _dense(40, d, seed=seed)
            ixs, U = rng.integers(0, 40, b).astype(np.int32), jnp.asarray(users)
            return (
                retrieval.UserRows(ixs, U, lambda i: users[i]),
                lambda cand: retrieval.rescore_gather_top_k_batch(
                    ixs, U, table, cand, k=self.K),
            )
        if form == "sum_rows":
            # always under rules, so padded to the power of two like
            # ``vectors_rules`` below (the cosine templates do)
            bp = retrieval._pow2(b)
            ixs = rng.integers(0, len(host), (bp, 2)).astype(np.int32)
            weights = np.ones((bp, 2), np.float32)
            weights[::3, 1] = 0.0
            rules = _open_rules(stored, bp)
            return (
                retrieval.SumRows(
                    ixs, weights,
                    lambda i, w: (host[i] * w[..., None]).sum(axis=1),
                    _host(rules),
                ),
                lambda cand: retrieval.rescore_sum_rows_top_k_batch(
                    ixs, weights, table, cand, k=self.K, rules=rules),
            )
        if form == "vectors":
            v = _dense(b, d, seed=seed + b)
            return (
                retrieval.Vectors(v),
                lambda cand: retrieval.rescore_top_k_batch(
                    v, table, cand, self.K),
            )
        # under rules the caller pads to the power of two, as the
        # E-Commerce template does: the rules hold that many rows
        bp = retrieval._pow2(b)
        v = _dense(b, d, seed=seed + b)
        v = np.concatenate([v, np.repeat(v[:1], bp - b, axis=0)])
        ex = np.full((bp, 4), -1, np.int32)
        ex[-1, :3] = np.argsort(-(v[-1] @ host.T))[:3]  # the query's own best
        qcat = np.full((bp, 1), -2, np.int32)
        qcat[::2] = 1
        rules = _rules(stored, bp, small_cat=range(0, len(host), 5),
                       ex=ex, qcat=qcat)
        return (
            retrieval.Vectors(v, _host(rules)),
            lambda cand: retrieval.rescore_top_k_batch(
                v, table, cand, self.K, rules),
        )

    @pytest.mark.parametrize("int8", [False, True], ids=["f32", "int8"])
    @pytest.mark.parametrize("b", [1, 3, 16])
    @pytest.mark.parametrize(
        "form", ["user_rows", "vectors", "vectors_rules", "sum_rows"]
    )
    def test_one_read_a_dispatch_and_the_host_chains_answer(
        self, monkeypatch, form, b, int8
    ):
        import jax

        table, host = self._table(int8)
        coarse = CoarseCatalog(table)
        kp = retrieval.two_stage_k(self.K, self.I)
        assert kp == 64
        query, host_rescore = self._form(
            form, b, table, host, coarse.stored_rows
        )
        n = len(query[0])
        _, cand = coarse.shortlist(
            query.coarse_vectors(), kp, _device(query.rules)
        )
        assert isinstance(cand, np.ndarray) and cand.shape == (n, kp)
        want_s, want_ids = host_rescore(cand)
        assert want_ids.shape == (n, self.K) and (want_ids[:, 0] >= 0).all()

        # the whole dispatch under a guard that refuses a device-to-host
        # read, lifted for the one read alone (XLA:CPU has no boundary to
        # guard: there the candidates' type and the counter carry the
        # proof; on the chip the guard does, PERF.md section 6, PR 29)
        handed = []
        real_rescore, real_fetch = type(query).rescore, retrieval._fetch

        def guarded_rescore(self, table, scan, k):
            handed.append(scan.ids)
            # what went up for the scan is on the device for the rescore
            assert isinstance(scan.queries, jax.Array)
            assert (scan.layout is not None) == (query.rules is not None)
            return real_rescore(self, table, scan, k)

        def fetch(out, rows):
            with jax.transfer_guard_device_to_host("allow"):
                return real_fetch(out, rows)

        monkeypatch.setattr(type(query), "rescore", guarded_rescore)
        monkeypatch.setattr(retrieval, "_fetch", fetch)
        before = retrieval.stats_block()
        with jax.transfer_guard_device_to_host("disallow"):
            s, ids = retrieval.top_k(query, table, self.I, coarse, self.K)
        after = retrieval.stats_block()
        assert after["host_reads"] == before["host_reads"] + 1
        for stage in ("shortlist_seconds", "rescore_seconds", "fetch_seconds"):
            assert after[stage]["count"] == before[stage]["count"] + 1, stage
        assert after["two_stage_queries"] == before["two_stage_queries"] + n
        (cand_dev,) = handed
        assert isinstance(cand_dev, jax.Array)
        assert cand_dev.shape == (retrieval._pow2(n), kp)
        assert isinstance(s, np.ndarray) and isinstance(ids, np.ndarray)
        np.testing.assert_array_equal(ids, want_ids)
        np.testing.assert_allclose(s, want_s, rtol=0, atol=2e-6)

    def test_rescore_programs_exist_per_power_of_two_bucket(self):
        """B = 1..16 through ``top_k`` compiles a rescore program for 1,
        2, 4, 8 and 16 rows and no other; a second sweep compiles none."""
        table, host = self._table(False, seed=61, rows=777, d=12)
        coarse = CoarseCatalog(table)
        programs = {p.name: p for p in retrieval._RESCORE_PROGRAMS}
        for form, name in (("user_rows", "retrieval.rescore_gather"),
                           ("vectors", "retrieval.rescore_vectors"),
                           ("sum_rows", "retrieval.rescore_sum_rows_masked")):
            program = programs[name]
            for want in (5, 0):
                before = program._cache_size()
                for b in range(1, 17):
                    query, _ = self._form(
                        form, b, table, host, coarse.stored_rows, seed=62
                    )
                    s, ids = retrieval.top_k(query, table, 777, coarse, self.K)
                    assert ids.shape == (len(query[0]), self.K)
                    assert (ids >= 0).all()
                assert program._cache_size() - before == want, (name, want)

    @pytest.mark.parametrize("int8", [False, True], ids=["f32", "int8"])
    def test_fewer_allowed_rows_than_the_shortlist_answers_short_and_exact(
        self, int8
    ):
        table, host = self._table(int8, seed=71)
        coarse = CoarseCatalog(table)
        allowed = [3, 110, 257, 258, 499]  # the whole of category 1
        v = _dense(2, self.D, seed=72)
        qcat = np.asarray([[1], [-2]], np.int32)
        rules = _rules(coarse.stored_rows, 2, small_cat=allowed, qcat=qcat)
        s, ids = retrieval.top_k(
            retrieval.Vectors(v, _host(rules)), table, self.I, coarse, self.K
        )
        order = np.argsort(-(v[0] @ host[allowed].T), kind="stable")
        assert ids[0].tolist() == [allowed[i] for i in order] + [-1] * 3
        np.testing.assert_allclose(
            s[0, :5], (v[0] @ host[allowed].T)[order], rtol=0, atol=2e-5
        )
        assert (s[0, 5:] < -1e29).all()
        assert (ids[1] >= 0).all()  # the unrestricted batchmate is full


# -- a dispatch under rules goes up once ----------------------------------------


def _packed_case(b, c, e, rows, d, seed=81):
    """Host parts of ``b`` queries: f32 vectors and weights that hold
    the bit patterns a bit-cast has to carry (signed zero, a subnormal,
    infinities, a NaN with a payload), category ids with -2 pads,
    ``has_cat`` of both kinds, exclusion lists with -1 pads."""
    rng = np.random.default_rng(seed + b)
    odd = np.asarray(
        [0x80000000, 0x00000001, 0x7F800000, 0xFF800000, 0x7FC01234],
        np.uint32,
    ).view(np.float32)
    vectors = rng.standard_normal((b, d)).astype(np.float32)
    vectors[0, : len(odd)] = odd
    qcat = np.full((b, c), -2, np.int32)
    qcat[::2, : max(1, c - 1)] = rng.integers(0, 9000, (len(qcat[::2]), max(1, c - 1)))
    ex = np.full((b, e), -1, np.int32)
    ex[:, : e - 3] = rng.integers(0, 1 << 22, (b, e - 3))
    has_cat = (np.arange(b) % 2 == 0)
    ixs = weights = None
    if rows:
        ixs = rng.integers(0, 1 << 22, (b, rows)).astype(np.int32)
        weights = (rng.random((b, rows)) < 0.7).astype(np.float32)
        weights[0, : len(odd)] = odd
    return vectors, qcat, has_cat, ex, ixs, weights


def _bits(a):
    return np.ascontiguousarray(a).view(np.int32)


class TestPackedDispatch:
    """Under rules everything a dispatch puts on the device is ONE
    buffer (``retrieval.pack``) that the masked programs take apart
    (``retrieval._unpack``): every part comes back bit for bit, the
    chain answers as the separate arrays do, and the uploads are
    counted."""

    @pytest.mark.parametrize("d", [64, 128])
    @pytest.mark.parametrize("rows", [0, 8, 16])
    @pytest.mark.parametrize("e", [16, 32], ids=["bucket", "outgrown"])
    @pytest.mark.parametrize("c", [1, 2, 4])
    @pytest.mark.parametrize("b", [1, 2, 5, 11])
    def test_the_layout_round_trips(self, b, c, e, rows, d):
        import jax
        import jax.numpy as jnp

        vectors, qcat, has_cat, ex, ixs, weights = _packed_case(b, c, e, rows, d)
        avail = jnp.ones(7, jnp.uint8)
        rules = Rules(avail, (avail,), qcat, has_cat, ex)
        packed, layout = retrieval.pack(vectors, rules, ixs, weights)
        bp = retrieval._pow2(b)
        assert layout == retrieval.Layout(d, c, e, rows)
        assert packed.dtype == np.int32
        assert packed.shape == (bp, d + c + 1 + e + 2 * rows)
        np.testing.assert_array_equal(packed[b:], np.repeat(packed[:1], bp - b, 0))
        got_v, got_r, got_ixs, got_w = jax.jit(
            retrieval._unpack, static_argnames="layout"
        )(jnp.asarray(packed), layout=layout, rules=retrieval._resident(rules))
        assert got_r.avail.shape == (7,) and len(got_r.cats) == 1
        assert got_v.dtype == jnp.float32 and got_w.dtype == jnp.float32
        assert got_r.has_cat.dtype == jnp.bool_
        for got, want in (
            (_bits(got_v), _bits(vectors)), (got_r.qcat, qcat),
            (got_r.has_cat, has_cat), (got_r.ex, ex),
        ):
            got = np.asarray(got)
            np.testing.assert_array_equal(got[:b], want)
            np.testing.assert_array_equal(got[b:], np.repeat(want[:1], bp - b, 0))
        assert got_ixs.shape == got_w.shape == (bp, rows)
        if rows:
            np.testing.assert_array_equal(np.asarray(got_ixs)[:b], ixs)
            np.testing.assert_array_equal(_bits(np.asarray(got_w))[:b], _bits(weights))

    def test_parts_of_different_lengths_make_no_batch(self):
        vectors, qcat, has_cat, ex, _, _ = _packed_case(4, 1, 16, 0, 8)
        with pytest.raises(ValueError, match="do not make one batch"):
            retrieval.pack(vectors[:3], Rules(None, (), qcat, has_cat, ex))

    I, D, K = 500, 8, 8  # 4 tiles of 128; k' = 64

    @pytest.fixture()
    def chain(self, monkeypatch):
        import jax.numpy as jnp

        monkeypatch.setenv("PIO_RETRIEVAL_THRESHOLD", "64")
        monkeypatch.setenv("PIO_RETRIEVAL_TILE", "128")
        monkeypatch.setenv("PIO_RETRIEVAL_PROBE_EVERY", "0")
        host = _dense(self.I, self.D, seed=91)
        host /= np.linalg.norm(host, axis=1, keepdims=True)
        table = jnp.asarray(host)
        return host, table, CoarseCatalog(table)

    def _query(self, form, case, b, host, stored):
        """(the form for ``top_k``, its ``shortlist`` + host-facing
        rescore on separate arrays) of ``b`` queries padded to a power
        of two, as the templates pad."""
        bp = retrieval._pow2(b)
        rng = np.random.default_rng(92 + b)
        small = [3, 110, 257, 258, 499]  # the whole of category 1
        qcat = np.full((bp, 2), -2, np.int32)
        ex = np.full((bp, 16), -1, np.int32)
        if case == "category":
            qcat[::2, 0], qcat[1::2, :] = 0, (1, 7)
        elif case == "fewer_than_kp":
            qcat[:, 0] = 1  # five rows allowed, k' = 64
        if form == "vectors":
            v = _dense(b, self.D, seed=93 + b)
            v = np.concatenate([v, np.repeat(v[:1], bp - b, axis=0)])
            sums, rows = v, None
        else:
            ixs = rng.integers(0, self.I, (bp, 8)).astype(np.int32)
            weights = (np.arange(8)[None, :] < rng.integers(1, 9, (bp, 1)))
            weights = weights.astype(np.float32)
            ixs[b:], weights[b:] = ixs[0], weights[0]
            ex[:, :8] = np.where(weights > 0, ixs, -1)  # a query's own rows
            sums, rows = (host[ixs] * weights[..., None]).sum(axis=1), (ixs, weights)
        if case == "blacklist":
            best = np.argsort(-(sums @ host.T), axis=1)[:, :5]
            ex[:, 8:13] = best  # the query's own best, ruled out
        rules = _host(_rules(stored, bp, small_cat=small, ex=ex, qcat=qcat))
        rules = rules._replace(has_cat=qcat[:, 0] >= 0)
        if rows is None:
            return retrieval.Vectors(sums, rules), (
                lambda dev, cand: retrieval.rescore_top_k_batch(
                    sums, self.table, cand, self.K, dev))
        return retrieval.SumRows(*rows, lambda i, w: sums, rules), (
            lambda dev, cand: retrieval.rescore_sum_rows_top_k_batch(
                *rows, self.table, cand, self.K, dev))

    @pytest.mark.parametrize("b", [1, 2, 5, 11])
    @pytest.mark.parametrize(
        "case", ["plain", "category", "blacklist", "fewer_than_kp"]
    )
    @pytest.mark.parametrize("form", ["vectors", "sum_rows"])
    def test_the_packed_chain_answers_as_the_separate_arrays(
        self, chain, form, case, b
    ):
        host, self.table, coarse = chain
        query, rescore = self._query(form, case, b, host, coarse.stored_rows)
        dev = retrieval.device_rules(query.rules)
        kp = retrieval.two_stage_k(self.K, self.I)
        cs, cand = coarse.shortlist(query.coarse_vectors(), kp, dev)
        want_s, want_ids = rescore(dev, cand)
        before = retrieval.stats_block()
        s, ids = retrieval.top_k(query, self.table, self.I, coarse, self.K)
        after = retrieval.stats_block()
        np.testing.assert_array_equal(ids, want_ids)
        np.testing.assert_array_equal(_bits(s), _bits(want_s))
        assert after["uploads"] == before["uploads"] + 1
        assert after["host_reads"] == before["host_reads"] + 1
        if case == "fewer_than_kp":
            assert (np.sort(ids[:, :5], axis=1) == [3, 110, 257, 258, 499]).all()
            assert (ids[:, 5:] == -1).all() and (cand[:, 5:] == -1).all()
        elif case == "blacklist":
            assert not (ids[:, :, None] == query.rules.ex[:, None, :]).any()
        else:  # a category case's odd rows are held to the small one
            assert (ids[:: 2 if case == "category" else 1] >= 0).all()

    @pytest.mark.parametrize("b", [3, 16])
    @pytest.mark.parametrize("form,uploads", [
        ("user_rows", 2), ("vectors", 1), ("vectors_rules", 1), ("sum_rows", 1),
    ])
    def test_uploads_a_dispatch(self, chain, form, uploads, b):
        """A form without rules goes up as it always did — its vectors,
        then (``UserRows``) its indices behind the running scan — and a
        form under rules in one buffer; the reference path on separate
        arrays counts each of its own. Sixteen queries are scanned in
        chunks (``scan_chunk``) of the one program: as many uploads."""
        host, table, coarse = chain
        assert retrieval.scan_chunk(retrieval._pow2(b), self.D, "bf16", PAST) == min(
            4, self.D // 4)
        one = TestOneCrossing()
        query, host_rescore = one._form(form, b, table, host, coarse.stored_rows)
        before = retrieval.stats_block()["uploads"]
        retrieval.top_k(query, table, self.I, coarse, self.K)
        assert retrieval.stats_block()["uploads"] == before + uploads
        if query.rules is not None:
            dev = retrieval.device_rules(query.rules)
            assert retrieval.stats_block()["uploads"] == before + uploads + 3
            coarse.launch(query.coarse_vectors(), 64, dev)
            assert retrieval.stats_block()["uploads"] == before + uploads + 4

    def test_the_masked_programs_keep_their_names_packed(self, chain):
        """The benchmark's roofline readers find the packed programs by
        the names the separate ones had."""
        import jax.numpy as jnp

        host, table, coarse = chain
        vectors, qcat, has_cat, ex, ixs, weights = _packed_case(2, 1, 16, 8, self.D)
        rules = _host(_rules(coarse.stored_rows, 2))
        packed, layout = retrieval.pack(vectors, rules, ixs % self.I, weights)
        resident = retrieval._resident(rules)
        text = retrieval._coarse_topk_masked.lower(
            jnp.asarray(packed), coarse._tiles, None, coarse._ids, resident,
            k=64, mode="bf16", layout=layout,
        ).as_text()
        assert "module @jit__coarse_topk_masked " in text
        cand = jnp.zeros((2, 64), jnp.int32)
        for name, args in (
            ("_rescore_vectors_masked", (jnp.asarray(packed), table)),
            ("_rescore_sum_rows_masked", (jnp.asarray(packed), None, table)),
        ):
            text = getattr(retrieval, name).lower(
                *args, cand, resident, k=self.K, layout=layout
            ).as_text()
            assert f"module @jit_{name} " in text


def test_the_templates_leave_the_decision_to_the_chain():
    """No module under models/ decides exact-or-two-stage, sizes a
    shortlist or runs the probe's clock itself (``retrieval.top_k`` and,
    for the mesh branch, ``two_stage_k`` / ``probe`` own that)."""
    import pathlib
    import re

    import predictionio_tpu.models as models

    owned = re.compile(
        r"\b(shortlist_k|engaged|probe_due|probe_recall|note_exact)\b"
    )
    for path in sorted(pathlib.Path(models.__file__).parent.glob("*.py")):
        hits = [
            f"{path.name}:{n}: {line.strip()}"
            for n, line in enumerate(path.read_text().splitlines(), 1)
            if owned.search(line)
        ]
        assert not hits, hits
