"""Device-resident evaluation fast path: parity and fallback gating.

Pins the contract from docs/evaluation.md — the batched top-k +
vectorized-metric path (core/fast_eval.py eval_device) must produce the
SAME numbers as the per-query Python path (atol 1e-6) on a single chip
and on the virtual 8-device mesh, including empty actual sets
(Option-skip) and out-of-vocabulary actual ids; anything the fast path
cannot express (metric subclasses, custom Serving, no eval_topk) must
fall back silently rather than diverge.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np
import pytest

from predictionio_tpu.core import EngineParams, WorkflowContext
from predictionio_tpu.core.base import (
    Algorithm,
    DataSource,
    FirstServing,
    Serving,
)
from predictionio_tpu.core.engine import Engine
from predictionio_tpu.core.evaluation import MetricEvaluator
from predictionio_tpu.core.fast_eval import FastEvalEngineWorkflow
from predictionio_tpu.core.params import Params
from predictionio_tpu.core.ranking import (
    ACTUAL_PAD,
    MAPAtK,
    NDCGAtK,
    PrecisionAtK,
    average_precision_at_k,
    encode_actuals,
    ndcg_at_k,
    precision_at_k,
)
from predictionio_tpu.models.recommendation import (
    ALSAlgorithm,
    ALSAlgorithmParams,
    Query,
    RecommendationPreparator,
    TrainingData,
)

CTX = WorkflowContext(mode="FastEvalTest")


# -- the vectorized kernel vs the per-query reference functions -------------


def _random_eval_points(seed: int, n_queries: int, vocab: int, k: int):
    """(pred id rows [Q, k], actual raw-id lists, index) with the messy
    cases mixed in: empty actuals, out-of-vocab actuals, short pred rows
    (-1 padding after a query's num cap)."""
    rng = np.random.default_rng(seed)
    index = {f"i{j}": j for j in range(vocab)}
    pred = np.full((n_queries, k), -1, dtype=np.int32)
    actuals: list[list[str]] = []
    for qi in range(n_queries):
        n_pred = int(rng.integers(0, k + 1))
        pred[qi, :n_pred] = rng.choice(vocab, size=n_pred, replace=False)
        if qi % 7 == 3:
            actuals.append([])  # empty actual set -> Option-skip
            continue
        ids = [f"i{j}" for j in rng.choice(vocab, size=rng.integers(1, 6),
                                           replace=False)]
        if qi % 5 == 0:
            ids.append(f"oov{qi}")  # relevant id outside the catalog
        actuals.append(ids)
    return pred, actuals, index


class TestRankingKernel:
    K = 8

    def test_kernel_matches_per_query_functions(self):
        from predictionio_tpu.ops.topk import ranking_metrics_batch

        pred, actuals, index = _random_eval_points(0, 200, 40, self.K)
        enc, counts = encode_actuals(actuals, index)
        precision, ap, ndcg, valid = (
            np.asarray(r)
            for r in ranking_metrics_batch(pred, enc, counts, k=self.K)
        )
        inv = {j: s for s, j in index.items()}
        for qi in range(pred.shape[0]):
            raw_pred = [inv[j] for j in pred[qi] if j >= 0]
            p_ref = precision_at_k(raw_pred, actuals[qi], self.K)
            ap_ref = average_precision_at_k(raw_pred, actuals[qi], self.K)
            ndcg_ref = ndcg_at_k(raw_pred, actuals[qi], self.K)
            if p_ref is None:  # empty actual set: kernel flags invalid
                assert not valid[qi]
                continue
            assert valid[qi]
            assert precision[qi] == pytest.approx(p_ref, abs=1e-6)
            assert ap[qi] == pytest.approx(ap_ref, abs=1e-6)
            assert ndcg[qi] == pytest.approx(ndcg_ref, abs=1e-6)

    def test_smaller_k_is_exact_prefix(self):
        """Slicing the [Q, k_max] matrix to a smaller k must equal
        scoring at that k directly — the fast path computes one top-k at
        k_max and serves every metric's k from slices."""
        from predictionio_tpu.ops.topk import ranking_metrics_batch

        pred, actuals, index = _random_eval_points(1, 64, 30, self.K)
        enc, counts = encode_actuals(actuals, index)
        small = 3
        direct = ranking_metrics_batch(
            pred[:, :small].copy(), enc, counts, k=small
        )
        sliced = ranking_metrics_batch(pred[:, :small], enc, counts, k=small)
        for a, b in zip(direct, sliced):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)

    def test_encode_actuals_layout(self):
        enc, counts = encode_actuals(
            [["i2", "i0"], [], ["i1", "ghost", "phantom"]], {"i0": 0, "i1": 1, "i2": 2}
        )
        assert counts.tolist() == [2, 0, 3]
        assert enc[0].tolist()[:2] == [0, 2]  # sorted ascending
        assert enc[1, 0] == ACTUAL_PAD  # empty row is all padding
        row2 = enc[2].tolist()
        # out-of-vocab actuals get distinct codes <= -2: they count
        # toward |actual| but can never match a predicted id (>= 0)
        assert sorted(x for x in row2 if x < 0) == [-3, -2]
        assert 1 in row2


# -- end-to-end: eval_device vs the per-query path over a real engine -------


@pytest.fixture(scope="module")
def _unshard_ring_cache():
    """ShardedCatalog instances cache per-model; nothing to reset, but
    keep a hook here so mesh-shape assumptions are in one place."""
    import jax

    assert jax.device_count() >= 8  # conftest's virtual CPU mesh
    yield


@dataclass
class _SynthDSParams(Params):
    seed: int = 0
    n_users: int = 40
    n_items: int = 25
    n_queries: int = 120


class _SynthDS(DataSource):
    """In-memory eval sets exercising every parity edge: unknown users
    (empty prediction rows), empty actual sets (Option-skip),
    out-of-vocab actual ids, and per-query num caps below/above k."""

    params_class = _SynthDSParams

    def _training(self, rng):
        p = self.params
        n = p.n_users * 15
        return TrainingData(
            user_ids=[f"u{j}" for j in range(p.n_users)],
            item_ids=[f"i{j}" for j in range(p.n_items)],
            rows=rng.integers(0, p.n_users, n).astype(np.int32),
            cols=rng.integers(0, p.n_items, n).astype(np.int32),
            ratings=rng.integers(1, 6, n).astype(np.float32),
        )

    def read_training(self, ctx):
        return self._training(np.random.default_rng(self.params.seed))

    def read_eval(self, ctx):
        p = self.params
        folds = []
        for fold in range(2):
            rng = np.random.default_rng(p.seed * 1000 + fold)
            td = self._training(rng)
            qa = []
            for qi in range(p.n_queries):
                user = (
                    f"ghost{qi}"  # unknown user -> empty prediction
                    if qi % 11 == 5
                    else f"u{rng.integers(0, p.n_users)}"
                )
                q = Query(user=user, num=int(rng.integers(1, 9)))
                if qi % 7 == 3:
                    qa.append((q, []))  # empty actual set
                    continue
                ids = [
                    f"i{j}"
                    for j in rng.choice(p.n_items, size=rng.integers(1, 5),
                                        replace=False)
                ]
                if qi % 5 == 0:
                    ids.append(f"oov{qi}")
                qa.append((q, ids))
            folds.append((td, {"fold": fold}, qa))
        return folds


def _make_engine(algo_cls=ALSAlgorithm, serving_cls=FirstServing):
    return Engine(
        datasource_classes=_SynthDS,
        preparator_classes=RecommendationPreparator,
        algorithm_classes={"als": algo_cls},
        serving_classes=serving_cls,
    )


def _candidates(n=4, **extra):
    out = []
    for ci in range(n):
        algo = ALSAlgorithmParams(
            rank=8, num_iterations=3, lambda_=0.01 * (ci + 1), seed=5, **extra
        )
        out.append(
            EngineParams(
                datasource=("", _SynthDSParams()),
                algorithms=[("als", algo)],
            )
        )
    return out


def _scores_of(result):
    return [
        [ms.score, *ms.other_scores] for _ep, ms in result.engine_params_scores
    ]


K = 5
METRIC_KW = dict(other_metrics=[MAPAtK(k=K), NDCGAtK(k=K)])


class TestEvalDeviceParity:
    def test_device_matches_per_query_single_chip(self):
        candidates = _candidates(4)
        fast = MetricEvaluator(PrecisionAtK(k=K), **METRIC_KW).evaluate(
            CTX, _make_engine(), candidates
        )
        serial = MetricEvaluator(
            PrecisionAtK(k=K), use_device_path=False, **METRIC_KW
        ).evaluate(CTX, _make_engine(), candidates)
        assert fast.fast_path_candidates == 4
        assert serial.fast_path_candidates == 0
        np.testing.assert_allclose(
            _scores_of(fast), _scores_of(serial), atol=1e-6
        )
        assert fast.best_idx == serial.best_idx
        # the report extras the CLI/dashboard surface
        assert set(fast.phase_seconds) >= {"train", "predict", "metric"}
        assert fast.cache_stats["misses"]["topk"] == 4
        assert "serial" in serial.phase_seconds

    def test_device_matches_per_query_sharded_mesh(self, _unshard_ring_cache):
        """sharded_serving ranks via the sharded catalog over the virtual
        8-device mesh; parity must hold across that path too."""
        candidates = _candidates(2, sharded_serving=True)
        fast = MetricEvaluator(PrecisionAtK(k=K), **METRIC_KW).evaluate(
            CTX, _make_engine(), candidates
        )
        serial = MetricEvaluator(
            PrecisionAtK(k=K), use_device_path=False, **METRIC_KW
        ).evaluate(CTX, _make_engine(), candidates)
        assert fast.fast_path_candidates == 2
        np.testing.assert_allclose(
            _scores_of(fast), _scores_of(serial), atol=1e-6
        )

    def test_empty_actuals_skip_preserved(self):
        """A split where EVERY actual set is empty scores nan on both
        paths (all queries Option-skipped), not 0.0."""

        class AllEmptyDS(_SynthDS):
            def read_eval(self, ctx):
                folds = super().read_eval(ctx)
                return [
                    (td, info, [(q, []) for q, _ in qa])
                    for td, info, qa in folds
                ]

        engine = Engine(
            datasource_classes=AllEmptyDS,
            preparator_classes=RecommendationPreparator,
            algorithm_classes={"als": ALSAlgorithm},
            serving_classes=FirstServing,
        )
        wf = FastEvalEngineWorkflow(engine, CTX)
        vals = wf.eval_device(_candidates(1)[0], [PrecisionAtK(k=K)])
        assert vals is not None and np.isnan(vals[0])


class TestFallbackGating:
    def test_metric_subclass_falls_back(self):
        """A PrecisionAtK subclass may override calculate_point, which
        the device kernel would ignore — exact-type gating sends it down
        the per-query path (same numbers here since nothing is
        overridden)."""

        class MyPrecision(PrecisionAtK):
            pass

        candidates = _candidates(2)
        sub = MetricEvaluator(MyPrecision(k=K)).evaluate(
            CTX, _make_engine(), candidates
        )
        stock = MetricEvaluator(PrecisionAtK(k=K)).evaluate(
            CTX, _make_engine(), candidates
        )
        assert MyPrecision(k=K).device_spec() is None
        assert sub.fast_path_candidates == 0
        assert stock.fast_path_candidates == 2
        np.testing.assert_allclose(
            _scores_of(sub), _scores_of(stock), atol=1e-6
        )

    def test_custom_serving_falls_back(self):
        class PassServing(Serving):
            def serve(self, query, predictions):
                return predictions[0]

        result = MetricEvaluator(PrecisionAtK(k=K)).evaluate(
            CTX, _make_engine(serving_cls=PassServing), _candidates(2)
        )
        assert result.fast_path_candidates == 0
        assert all(np.isfinite(s) for row in _scores_of(result) for s in row)

    def test_algorithm_without_eval_topk_falls_back(self):
        class NoTopK(ALSAlgorithm):
            eval_topk = Algorithm.eval_topk

        no_topk = MetricEvaluator(PrecisionAtK(k=K)).evaluate(
            CTX, _make_engine(algo_cls=NoTopK), _candidates(2)
        )
        stock = MetricEvaluator(PrecisionAtK(k=K)).evaluate(
            CTX, _make_engine(), _candidates(2)
        )
        assert no_topk.fast_path_candidates == 0
        np.testing.assert_allclose(
            _scores_of(no_topk), _scores_of(stock), atol=1e-6
        )

    def test_workflow_eval_device_gates_directly(self):
        """eval_device itself returns None (never wrong numbers) when a
        gate misses, leaving the caches untouched for the fallback."""
        engine = _make_engine()
        wf = FastEvalEngineWorkflow(engine, CTX)
        ep = _candidates(1)[0]

        class NotStock(PrecisionAtK):
            pass

        assert wf.eval_device(ep, [NotStock(k=K)]) is None
        assert wf.fast_path_candidates == 0
        vals = wf.eval_device(ep, [PrecisionAtK(k=K), MAPAtK(k=K)])
        assert vals is not None and len(vals) == 2
        assert wf.fast_path_candidates == 1
        # second call with the same candidate hits the top-k cache
        wf.eval_device(ep, [PrecisionAtK(k=K), MAPAtK(k=K)])
        assert wf.hits["topk"] == 1


@pytest.mark.slow
class TestHeavySweepParity:
    def test_eight_candidate_sweep_over_5k_queries(self):
        """The acceptance-scale sweep (8 candidates, >= 5k eval queries)
        at parity — timing lives in bench.py's eval section; this pins
        correctness at that scale in the suite."""
        ds = _SynthDSParams(n_users=400, n_items=200, n_queries=2500)
        candidates = []
        for ci in range(8):
            candidates.append(
                EngineParams(
                    datasource=("", ds),
                    algorithms=[("als", ALSAlgorithmParams(
                        rank=8, num_iterations=3,
                        lambda_=0.01 * (ci + 1), seed=5,
                    ))],
                )
            )
        fast = MetricEvaluator(PrecisionAtK(k=K), **METRIC_KW).evaluate(
            CTX, _make_engine(), candidates
        )
        serial = MetricEvaluator(
            PrecisionAtK(k=K), use_device_path=False, **METRIC_KW
        ).evaluate(CTX, _make_engine(), candidates)
        assert fast.fast_path_candidates == 8
        np.testing.assert_allclose(
            _scores_of(fast), _scores_of(serial), atol=1e-6
        )
