"""Batched-vs-unbatched serving parity.

The device-batched predict path must be invisible to clients: a query's
answer on ``/queries.json`` holds the same items in the same order, with
scores equal to the last bits of f32, whether it is served alone or
coalesced into an [N, K] device batch — across every factor storage
dtype, with mixed query shapes sharing one batch — and business-rule
filters (blackList, seen items) apply per query INSIDE a batch. Byte for
byte is not a property of the dot on either backend (its summation order
can move with the batch size: 0.6818156838 alone, 0.6818156242 in a
batch; PERF.md section 6, PR 26), so scores are held to 2e-6 as
tests/test_ecommerce_rules.py holds the storefront's. A batchmate whose
batch dispatch fails is retried individually — a batch of one again, and
that IS byte-identical — without poisoning its neighbors.
"""

from __future__ import annotations

import json
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from predictionio_tpu.cli import commands
from predictionio_tpu.core import EngineParams, WorkflowContext
from predictionio_tpu.core.workflow import run_train
from predictionio_tpu.data.event import Event

CTX = WorkflowContext(mode="BatchParityTest")

# mixed shapes on purpose: different num values (different headroom-k
# buckets), an unknown user (host-side empty result inside a batch)
QUERIES = [
    {"user": "u0", "num": 1},
    {"user": "u1", "num": 3},
    {"user": "u2", "num": 5},
    {"user": "u3", "num": 3},
    {"user": "zz", "num": 3},
    {"user": "u4", "num": 2},
    {"user": "u5", "num": 3},
    {"user": "u6", "num": 4},
]


def _post_raw(url: str, body: dict) -> tuple[int, bytes]:
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(), method="POST"
    )
    try:
        with urllib.request.urlopen(req, timeout=15) as resp:
            return resp.status, resp.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def _train_rec(storage, storage_dtype="float32"):
    from predictionio_tpu.models import recommendation as rec

    info = commands.app_new("ParityApp", storage=storage)
    events = storage.get_events()
    rng = np.random.default_rng(0)
    for u in range(12):
        for _ in range(6):
            i = int(rng.integers(0, 8))
            events.insert(
                Event(
                    event="rate", entity_type="user", entity_id=f"u{u}",
                    target_entity_type="item", target_entity_id=f"i{i}",
                    properties={"rating": float(rng.integers(1, 6))},
                ),
                info["id"],
            )
    engine = rec.engine()
    ep = EngineParams(
        datasource=("", rec.DataSourceParams(app_name="ParityApp")),
        algorithms=[(
            "als",
            rec.ALSAlgorithmParams(
                rank=4, num_iterations=3, storage_dtype=storage_dtype
            ),
        )],
    )
    run_train(engine, ep, engine_id="parity", storage=storage)
    inst = storage.get_metadata_engine_instances().get_latest_completed(
        "parity", "0", "default"
    )
    return engine, inst


def _expected_bytes(engine, inst, storage) -> dict[str, tuple[int, bytes]]:
    """Serve QUERIES one at a time through a server with no batcher."""
    from predictionio_tpu.server.engine_server import EngineServer

    server = EngineServer(
        engine, inst, storage=storage, host="127.0.0.1", port=0
    )
    port = server.start()
    try:
        assert server.batcher is None
        return {
            json.dumps(q): _post_raw(
                f"http://127.0.0.1:{port}/queries.json", q
            )
            for q in QUERIES
        }
    finally:
        server.stop()


def _assert_same_answer(got: bytes, want: bytes, what) -> None:
    """Two response bodies: the same JSON but for the scores' last bits."""
    got, want = json.loads(got), json.loads(want)
    assert set(got) == set(want), what
    assert [s["item"] for s in got["itemScores"]] == [
        s["item"] for s in want["itemScores"]
    ], what
    np.testing.assert_allclose(
        [s["score"] for s in got["itemScores"]],
        [s["score"] for s in want["itemScores"]],
        rtol=2e-6, atol=2e-6, err_msg=str(what),
    )


def _batched_server(engine, inst, storage):
    from predictionio_tpu.server.engine_server import EngineServer

    # dispatch_cost_s pins window-wait mode so concurrent queries
    # reliably coalesce regardless of the probe on this machine
    server = EngineServer(
        engine, inst, storage=storage, host="127.0.0.1", port=0,
        batch_window_ms=25.0, dispatch_cost_s=10.0,
    )
    return server, server.start()


def _concurrent_post(port, queries) -> dict[str, tuple[int, bytes]]:
    results: dict[str, tuple[int, bytes]] = {}
    barrier = threading.Barrier(len(queries))

    def one(q):
        barrier.wait(timeout=10)
        results[json.dumps(q)] = _post_raw(
            f"http://127.0.0.1:{port}/queries.json", q
        )

    threads = [threading.Thread(target=one, args=(q,)) for q in queries]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    return results


@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int8"])
def test_batched_answers_match_unbatched(storage, dtype):
    """The same answer batched and unbatched (items and order equal,
    scores to 2e-6), per storage dtype, with mixed query shapes
    coalesced into one device batch."""
    engine, inst = _train_rec(storage, storage_dtype=dtype)
    expected = _expected_bytes(engine, inst, storage)

    server, port = _batched_server(engine, inst, storage)
    algo = server.algorithms[0]
    real_bp = type(algo).batch_predict
    batches: list[list[int]] = []

    def counting_bp(self_, model, queries):
        batches.append([int(q.num) for _, q in queries])
        return real_bp(self_, model, queries)

    type(algo).batch_predict = counting_bp
    try:
        results = _concurrent_post(port, QUERIES)
        for q in QUERIES:
            key = json.dumps(q)
            status, body = results[key]
            assert status == 200, (q, body)
            _assert_same_answer(
                body, expected[key][1], f"batched answer diverges for {q}"
            )
        coalesced = [b for b in batches if len(b) > 1]
        assert coalesced, f"no coalesced batch formed: {batches}"
        # mixed shapes really shared a dispatch
        assert any(len(set(b)) > 1 for b in coalesced), batches
    finally:
        type(algo).batch_predict = real_bp
        server.stop()


def test_failing_batchmate_retried_individually(storage):
    """A batch-level dispatch failure falls back to per-query scoring:
    every batchmate still gets its exact unbatched response."""
    engine, inst = _train_rec(storage)
    expected = _expected_bytes(engine, inst, storage)

    server, port = _batched_server(engine, inst, storage)
    algo = server.algorithms[0]
    real_bp = type(algo).batch_predict
    failed = []

    def flaky_bp(self_, model, queries):
        if len(queries) > 1:  # batch dispatch blows up; retries are B=1
            failed.append(len(queries))
            raise RuntimeError("device OOM on batched dispatch")
        return real_bp(self_, model, queries)

    type(algo).batch_predict = flaky_bp
    try:
        results = _concurrent_post(port, QUERIES)
        assert failed, "no multi-query batch was ever dispatched"
        for q in QUERIES:
            key = json.dumps(q)
            status, body = results[key]
            assert status == 200, (q, body)
            assert body == expected[key][1], q
    finally:
        type(algo).batch_predict = real_bp
        server.stop()


def _set(entity_type, entity_id, props):
    return Event(
        event="$set", entity_type=entity_type, entity_id=entity_id,
        properties=props,
    )


def _interaction(name, user, item):
    return Event(
        event=name, entity_type="user", entity_id=user,
        target_entity_type="item", target_entity_id=item,
    )


class TestPerQueryFiltersInBatch:
    """Business rules are per-query even when queries share a device
    dispatch: blackList hits and seen items vanish from exactly the
    queries that asked, and a filtered query matches its own unbatched
    result (items and order; scores to 2e-6)."""

    def _similar_model(self, storage):
        from predictionio_tpu.data.storage import App
        from predictionio_tpu.models import similarproduct as sim

        app_id = storage.get_metadata_apps().insert(App(0, "SimBatchApp"))
        events = storage.get_events()
        rng = np.random.default_rng(1)
        for i in range(12):
            events.insert(
                _set("item", f"i{i}",
                     {"categories": ["even" if i % 2 == 0 else "odd"]}),
                app_id,
            )
        for u in range(30):
            events.insert(_set("user", f"u{u}", {}), app_id)
            for _ in range(8):
                i = int(rng.integers(0, 6)) * 2 + (u % 2)
                events.insert(_interaction("view", f"u{u}", f"i{i}"), app_id)
        algo = sim.ALSAlgorithm(
            sim.ALSAlgorithmParams(rank=4, num_iterations=4)
        )
        td = sim.SimilarProductDataSource(
            sim.DataSourceParams(app_name="SimBatchApp")
        ).read_training(CTX)
        return sim, algo, algo.train(CTX, td)

    def test_blacklist_applies_per_query(self, storage):
        sim, algo, model = self._similar_model(storage)
        q_black = sim.Query(items=["i0"], num=5, blackList=["i2", "i4"])
        q_plain = sim.Query(items=["i0"], num=5)
        q_cat = sim.Query(items=["i0"], num=5, categories=["odd"])
        got = dict(
            algo.batch_predict(model, [(0, q_black), (1, q_plain), (2, q_cat)])
        )
        black_items = [s.item for s in got[0].itemScores]
        assert "i2" not in black_items and "i4" not in black_items
        assert all(int(s.item[1:]) % 2 == 1 for s in got[2].itemScores)
        # the un-filtered batchmate is untouched by its neighbors'
        # filters (its solo prediction's items in its order, the scores
        # to f32's last bits), and the filtered ones match THEIR solo
        # predictions too
        for row, q in ((1, q_plain), (0, q_black), (2, q_cat)):
            solo = algo.predict(model, q)
            assert [s.item for s in got[row].itemScores] == [
                s.item for s in solo.itemScores
            ], q
            np.testing.assert_allclose(
                [s.score for s in got[row].itemScores],
                [s.score for s in solo.itemScores], rtol=2e-6, atol=2e-6,
            )

    def test_seen_items_filtered_per_user_in_batch(self, storage):
        from predictionio_tpu.data.storage import App
        from predictionio_tpu.models import ecommerce as ecom

        app_id = storage.get_metadata_apps().insert(App(0, "EcomBatchApp"))
        events = storage.get_events()
        rng = np.random.default_rng(2)
        for i in range(10):
            events.insert(
                _set("item", f"i{i}",
                     {"categories": ["cat-a" if i < 5 else "cat-b"]}),
                app_id,
            )
        for u in range(20):
            events.insert(_set("user", f"u{u}", {}), app_id)
            for _ in range(6):
                i = int(rng.integers(0, 5)) + (0 if u % 2 == 0 else 5)
                events.insert(_interaction("view", f"u{u}", f"i{i}"), app_id)
        algo = ecom.ECommAlgorithm(
            ecom.ECommAlgorithmParams(
                app_name="EcomBatchApp", rank=4, num_iterations=4,
                unseen_only=True,
            )
        )
        td = ecom.ECommerceDataSource(
            ecom.DataSourceParams(app_name="EcomBatchApp")
        ).read_training(CTX)
        model = algo.train(CTX, td)
        seen = {}
        for u in ("u0", "u1"):
            seen[u] = {i for uu, i in td.view_events.iter_pairs() if uu == u}
        got = dict(
            algo.batch_predict(
                model,
                [(0, ecom.Query(user="u0", num=10)),
                 (1, ecom.Query(user="u1", num=10))],
            )
        )
        # each query filtered by ITS OWN user's seen set
        assert seen["u0"].isdisjoint({s.item for s in got[0].itemScores})
        assert seen["u1"].isdisjoint({s.item for s in got[1].itemScores})
        # u1 (odd) views cat-b items, so its unseen recs exist and are
        # not just u0's filter applied twice
        assert got[0].itemScores and got[1].itemScores
        assert {s.item for s in got[0].itemScores} != {
            s.item for s in got[1].itemScores
        }
