"""End-to-end recommendation template test: events -> train -> persist ->
deploy -> predict (the QuickStartTest lifecycle of the reference,
tests/pio_tests/scenarios/quickstart_test.py:50-105, minus HTTP)."""

from __future__ import annotations

import numpy as np
import pytest

from predictionio_tpu.core import EngineParams, WorkflowContext
from predictionio_tpu.core.workflow import prepare_deploy, run_train
from predictionio_tpu.data.event import Event
from predictionio_tpu.data.storage import App
from predictionio_tpu.models import recommendation as rec

CTX = WorkflowContext(mode="Test")


@pytest.fixture()
def seeded_app(storage):
    apps = storage.get_metadata_apps()
    app_id = apps.insert(App(0, "RecApp"))
    events = storage.get_events()
    events.init(app_id)
    rng = np.random.default_rng(0)
    # 30 users x 20 items; user u likes items with same parity
    for u in range(30):
        for _ in range(10):
            i = int(rng.integers(0, 10)) * 2 + (u % 2)
            rating = 5.0 if (i % 2) == (u % 2) else 1.0
            events.insert(
                Event(
                    event="rate",
                    entity_type="user",
                    entity_id=f"u{u}",
                    target_entity_type="item",
                    target_entity_id=f"i{i}",
                    properties={"rating": rating},
                ),
                app_id,
            )
    # a few buy events (implicit 4.0)
    for u in range(5):
        events.insert(
            Event(
                event="buy",
                entity_type="user",
                entity_id=f"u{u}",
                target_entity_type="item",
                target_entity_id=f"i{u % 2}",
            ),
            app_id,
        )
    return storage


def make_ep(**algo_kw):
    defaults = dict(rank=8, num_iterations=8, lambda_=0.05)
    defaults.update(algo_kw)
    return EngineParams(
        datasource=("", rec.DataSourceParams(app_name="RecApp")),
        algorithms=[("als", rec.ALSAlgorithmParams(**defaults))],
    )


class TestDataSource:
    def test_reads_rate_and_buy(self, seeded_app):
        ds = rec.RecommendationDataSource(rec.DataSourceParams(app_name="RecApp"))
        td = ds.read_training(CTX)
        assert len(td.ratings) == 305
        assert 4.0 in td.ratings  # buy mapped to 4.0
        td.sanity_check()

    def test_sanity_check_empty(self, storage):
        storage.get_metadata_apps().insert(App(0, "EmptyApp"))
        ds = rec.RecommendationDataSource(rec.DataSourceParams(app_name="EmptyApp"))
        td = ds.read_training(CTX)
        with pytest.raises(ValueError):
            td.sanity_check()


class TestTrainPredict:
    def test_full_lifecycle(self, seeded_app):
        engine = rec.engine()
        instance_id = run_train(
            engine,
            make_ep(),
            engine_id="rec",
            engine_factory="predictionio_tpu.models.recommendation.engine",
            storage=seeded_app,
        )
        inst = seeded_app.get_metadata_engine_instances().get_latest_completed(
            "rec", "0", "default"
        )
        assert inst.id == instance_id

        _, algos, models, serving = prepare_deploy(engine, inst, storage=seeded_app)
        [algo], [model] = algos, models
        assert isinstance(model, rec.ALSModel)

        q = rec.Query(user="u0", num=4)
        result = serving.serve(q, [algo.predict(model, q)])
        assert len(result.itemScores) == 4
        # preference structure recovered: even user ranks even items on top
        top = result.itemScores[0]
        assert int(top.item[1:]) % 2 == 0
        # scores sorted descending
        scores = [s.score for s in result.itemScores]
        assert scores == sorted(scores, reverse=True)

    def test_int8_lifecycle_roundtrip(self, seeded_app):
        """storage_dtype="int8" through the full framework path: the
        persisted MODELDATA blob carries (int8 values, per-row f32
        scales), deserializes intact, and serves the same preference
        structure as f32."""
        engine = rec.engine()
        instance_id = run_train(
            engine,
            make_ep(storage_dtype="int8"),
            engine_id="rec-i8",
            storage=seeded_app,
        )
        inst = seeded_app.get_metadata_engine_instances().get_latest_completed(
            "rec-i8", "0", "default"
        )
        assert inst.id == instance_id
        _, algos, models, serving = prepare_deploy(
            engine, inst, storage=seeded_app
        )
        [algo], [model] = algos, models
        assert model.user_factors.dtype == np.int8
        assert model.item_factors.dtype == np.int8
        assert model.user_scales is not None and model.user_scales.dtype == np.float32
        assert model.item_scales is not None
        assert model.user_scales.shape == (model.user_factors.shape[0],)
        q = rec.Query(user="u0", num=4)
        result = serving.serve(q, [algo.predict(model, q)])
        assert len(result.itemScores) == 4
        # preference structure recovered through quantized storage
        assert int(result.itemScores[0].item[1:]) % 2 == 0
        scores = [s.score for s in result.itemScores]
        assert scores == sorted(scores, reverse=True)
        # batch path scores the same items
        [(_, batch_res)] = algo.batch_predict(model, [(0, q)])
        assert [s.item for s in batch_res.itemScores] == [
            s.item for s in result.itemScores
        ]

    def test_int8_model_blob_shrinks_4x(self, seeded_app):
        """The point of quantized serving blobs: int8 factor payload is
        ~4x smaller than f32 (less one f32 scale per row)."""
        engine = rec.engine()
        run_train(engine, make_ep(), engine_id="rec-f32", storage=seeded_app)
        run_train(
            engine, make_ep(storage_dtype="int8"), engine_id="rec-i8b",
            storage=seeded_app,
        )
        instances = seeded_app.get_metadata_engine_instances()

        def model_of(engine_id):
            inst = instances.get_latest_completed(engine_id, "0", "default")
            _, _, [model], _ = prepare_deploy(engine, inst, storage=seeded_app)
            return model

        m32, m8 = model_of("rec-f32"), model_of("rec-i8b")

        def factor_bytes(m):
            arrs = [m.user_factors, m.item_factors]
            if m.user_scales is not None:
                arrs += [m.user_scales, m.item_scales]
            return sum(a.nbytes for a in arrs)

        # values shrink 4x; per-row scales add one f32 per row back
        assert factor_bytes(m8) < factor_bytes(m32) / 2

    def test_sharded_train_via_run_train_matches_single_chip(self, seeded_app):
        """`pio train` with shardedTrain trains over the mesh through the
        full framework path (run_train -> Engine -> ALSAlgorithm) and
        produces the same factors as single-chip (VERDICT r1 item 2)."""
        from predictionio_tpu.core.engine import WorkflowParams

        engine = rec.engine()
        single_id = run_train(
            engine, make_ep(), engine_id="rec-single", storage=seeded_app
        )
        sharded_id = run_train(
            engine,
            make_ep(sharded_train=True),
            engine_id="rec-sharded",
            workflow_params=WorkflowParams(mesh_axes=[("data", 8)]),
            storage=seeded_app,
        )
        instances = seeded_app.get_metadata_engine_instances()

        def factors(iid, engine_id):
            inst = instances.get_latest_completed(engine_id, "0", "default")
            assert inst.id == iid
            _, algos, ms, _ = prepare_deploy(engine, inst, storage=seeded_app)
            return ms[0].user_factors, ms[0].item_factors

        U1, V1 = factors(single_id, "rec-single")
        U8, V8 = factors(sharded_id, "rec-sharded")
        np.testing.assert_allclose(U1, U8, rtol=5e-4, atol=5e-5)
        np.testing.assert_allclose(V1, V8, rtol=5e-4, atol=5e-5)

    def test_unseen_user_empty_result(self, seeded_app):
        engine = rec.engine()
        algo = rec.ALSAlgorithm(rec.ALSAlgorithmParams(rank=4, num_iterations=2))
        td = rec.RecommendationDataSource(
            rec.DataSourceParams(app_name="RecApp")
        ).read_training(CTX)
        model = algo.train(CTX, td)
        assert algo.predict(model, rec.Query(user="stranger")).itemScores == []

    def test_sharded_serving_matches_dense(self, seeded_app):
        """Sharded serving (item rows stationary on the mesh) returns the
        same recommendations as the single-device dense path."""
        td = rec.RecommendationDataSource(
            rec.DataSourceParams(app_name="RecApp")
        ).read_training(CTX)
        dense = rec.ALSAlgorithm(rec.ALSAlgorithmParams(rank=4, num_iterations=3))
        model = dense.train(CTX, td)
        ring = rec.ALSAlgorithm(
            rec.ALSAlgorithmParams(rank=4, num_iterations=3, sharded_serving=True)
        )
        q = rec.Query(user="u3", num=5)
        assert [s.item for s in ring.predict(model, q).itemScores] == [
            s.item for s in dense.predict(model, q).itemScores
        ]
        queries = [(0, rec.Query("u0", 3)), (1, rec.Query("u4", 4))]
        rb, db = dict(ring.batch_predict(model, queries)), dict(
            dense.batch_predict(model, queries)
        )
        for ix in (0, 1):
            assert [s.item for s in rb[ix].itemScores] == [
                s.item for s in db[ix].itemScores
            ]

    def test_batch_predict_matches_single(self, seeded_app):
        algo = rec.ALSAlgorithm(rec.ALSAlgorithmParams(rank=4, num_iterations=3))
        td = rec.RecommendationDataSource(
            rec.DataSourceParams(app_name="RecApp")
        ).read_training(CTX)
        model = algo.train(CTX, td)
        queries = [(0, rec.Query("u1", 3)), (1, rec.Query("nope", 2)), (2, rec.Query("u2", 3))]
        batch = dict(algo.batch_predict(model, queries))
        assert batch[1].itemScores == []
        for ix, q in [(0, queries[0][1]), (2, queries[2][1])]:
            single = algo.predict(model, q)
            assert [s.item for s in batch[ix].itemScores] == [
                s.item for s in single.itemScores
            ]

    def test_eval_folds(self, seeded_app):
        engine = rec.engine()
        results = engine.eval(CTX, make_ep(num_iterations=2, rank=4))
        assert len(results) == 3
        total = sum(len(served) for _, served in results)
        assert total == 305  # every rating lands in exactly one fold

    def test_model_pickles_and_predicts_after_restore(self, seeded_app):
        import pickle

        algo = rec.ALSAlgorithm(rec.ALSAlgorithmParams(rank=4, num_iterations=2))
        td = rec.RecommendationDataSource(
            rec.DataSourceParams(app_name="RecApp")
        ).read_training(CTX)
        model = algo.train(CTX, td)
        _ = model.device_factors()  # materialize device cache, must not pickle
        restored = pickle.loads(pickle.dumps(model))
        r1 = algo.predict(model, rec.Query("u3", 3))
        r2 = algo.predict(restored, rec.Query("u3", 3))
        assert [s.item for s in r1.itemScores] == [s.item for s in r2.itemScores]


class TestReviewRegressions:
    def test_buy_rating_forced_over_property(self, seeded_app):
        """buy events train at buy_rating even with a rating property
        (reference DataSource.scala:55 ignores properties for buy)."""
        from predictionio_tpu.data.event import Event
        from predictionio_tpu.data.storage import set_storage
        from predictionio_tpu.models.recommendation import (
            DataSourceParams,
            RecommendationDataSource,
        )

        storage = seeded_app
        app_id = storage.get_metadata_apps().get_by_name("RecApp").id
        storage.get_events().insert(
            Event(event="buy", entity_type="user", entity_id="uX",
                  target_entity_type="item", target_entity_id="i0",
                  properties={"rating": 1.0}), app_id)
        set_storage(storage)
        try:
            td = RecommendationDataSource(
                DataSourceParams(app_name="RecApp")
            ).read_training(None)
        finally:
            set_storage(None)
        ux = td.user_ids.index("uX")
        vals = [float(v) for r, v in zip(td.rows, td.ratings) if r == ux]
        assert vals == [4.0]

    def test_eval_folds_exclude_test_only_users(self, seeded_app):
        """A user whose only ratings fell in the test fold must be absent
        from that fold's training id space (unseen-user semantics)."""
        from predictionio_tpu.data.storage import set_storage
        from predictionio_tpu.models.recommendation import (
            DataSourceParams,
            RecommendationDataSource,
        )

        set_storage(seeded_app)
        try:
            folds = RecommendationDataSource(
                DataSourceParams(app_name="RecApp")
            ).read_eval(None)
        finally:
            set_storage(None)
        for train, _info, qa in folds:
            n_users = len(train.user_ids)
            n_items = len(train.item_ids)
            # every indexed entity appears in at least one training rating
            assert set(train.rows.tolist()) == set(range(n_users))
            assert set(train.cols.tolist()) == set(range(n_items))
