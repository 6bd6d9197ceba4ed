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
import time
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


# -- the dispatch slot: a lone query is scored on its own request thread -------


@pytest.fixture(scope="module")
def trained():
    """One tiny trained Recommendation engine for the slot's cases (they
    stub ``predict`` / ``batch_predict`` or ask for one answer)."""
    from predictionio_tpu.data.storage import set_storage, test_storage

    s = test_storage()
    set_storage(s)
    try:
        engine, inst = _train_rec(s)
    finally:
        set_storage(None)
    return s, engine, inst


class _Slot:
    """A live server whose batcher does not window-wait (a lone item is
    dispatched at once), its algorithm's ``predict`` / ``batch_predict``
    wrapped: ``calls`` gets (kind, rows, thread) a call, and a call waits
    for ``gate`` where the test cleared it."""

    def __init__(self, trained, **kwargs):
        from predictionio_tpu.obs import metrics
        from predictionio_tpu.server.engine_server import EngineServer

        storage, engine, inst = trained
        kwargs.setdefault("batch_window_ms", 5.0)
        kwargs.setdefault("dispatch_cost_s", 0.0)
        self.server = EngineServer(
            engine, inst, storage=storage, host="127.0.0.1", port=0, **kwargs
        )
        self.port = self.server.start()
        self.batcher = self.server.batcher
        self.calls: list[tuple[str, int, threading.Thread]] = []
        self.entered = threading.Semaphore(0)
        self.gate = threading.Event()
        self.gate.set()
        self.fail: Exception | None = None
        self._cls = type(self.server.algorithms[0])
        self._real = (self._cls.predict, self._cls.batch_predict)
        real_predict, real_bp = self._real
        me = self

        def predict(self_, model, query):
            me._called("predict", 1)
            return real_predict(self_, model, query)

        def batch_predict(self_, model, queries):
            # ``predict`` delegates here: only the batcher's own call counts
            if len(queries) > 1:
                me._called("batch_predict", len(queries))
            return real_bp(self_, model, queries)

        self._cls.predict, self._cls.batch_predict = predict, batch_predict
        self._metrics = metrics

    def _called(self, kind, n):
        self.calls.append((kind, n, threading.current_thread()))
        self.entered.release()
        assert self.gate.wait(timeout=20)
        if self.fail is not None:
            raise self.fail

    def close(self):
        self.gate.set()
        self._cls.predict, self._cls.batch_predict = self._real
        self.server.stop()

    def path(self, which):
        return self._metrics.counter(
            "pio_batch_dispatch_path_total", path=which).value()

    def enqueued(self, reason):
        return self._metrics.counter(
            "pio_batch_enqueued_total", reason=reason).value()

    def ask(self, user="u1", variant=None):
        """One query on a thread of its own, as a request thread makes
        it: the thread, and a box that gets the answer or the exception."""
        box: dict = {}

        def run():
            try:
                box["answer"] = json.loads(self.server.serve_query_bytes(
                    {"user": user, "num": 3}, variant))
            except Exception as e:  # noqa: BLE001 - the test reads it
                box["error"] = e

        t = threading.Thread(target=run, name=f"request-{user}")
        t.start()
        return t, box

    def waiting(self, n):
        """Until ``n`` queries are enqueued and their turn has not begun."""
        deadline = time.monotonic() + 10
        while self.batcher._waiting != n:
            assert time.monotonic() < deadline, self.batcher._waiting
            time.sleep(0.002)


@pytest.fixture()
def slot(trained):
    made = []

    def make(**kwargs):
        made.append(_Slot(trained, **kwargs))
        return made[-1]

    yield make
    for s in made:
        s.close()


def _join(*threads):
    for t in threads:
        t.join(timeout=20)
        assert not t.is_alive()


class TestDispatchSlot:
    def test_lone_query_runs_on_its_request_thread(self, slot):
        s = slot()
        inline0, worker0 = s.path("inline"), s.path("worker")
        for user in ("u1", "u2", "u3"):
            t, box = s.ask(user)
            _join(t)
            assert len(box["answer"]["itemScores"]) == 3
            assert s.calls[-1] == ("predict", 1, t)
        assert s.path("inline") == inline0 + 3 and s.path("worker") == worker0
        assert not s.batcher._slot.locked() and s.batcher._waiting == 0
        # over HTTP the thread is one of the front end's pool, not the worker's
        status, _ = _post_raw(
            f"http://127.0.0.1:{s.port}/queries.json", {"user": "u4", "num": 3})
        assert status == 200
        assert s.calls[-1][2] is not s.batcher._thread
        assert s.path("inline") == inline0 + 4
        with urllib.request.urlopen(
                f"http://127.0.0.1:{s.port}/stats.json", timeout=15) as resp:
            batch = json.loads(resp.read())["batch"]
        assert batch["enabled"] and not batch["window_wait"]
        assert set(batch["enqueued"]) == {"deadline", "window", "queued", "slot_busy"}
        block = batch["dispatch_path"]
        assert block["inline"] == s.path("inline")
        assert block["inline_share"] == pytest.approx(
            block["inline"] / (block["inline"] + block["worker"]), abs=1e-4)

    @pytest.mark.parametrize("holder", ["inline", "worker"])
    def test_what_queues_behind_a_dispatch_leaves_as_one_batch(self, slot, holder):
        """N requests that arrive while a dispatch holds the slot — a
        request thread's or the worker's — are ONE batch of N on the
        worker, as they were when every dispatch was the worker's."""
        s = slot()
        worker = s.batcher._thread
        busy0 = s.enqueued("slot_busy")
        threads = []
        if holder == "worker":
            # a first query finds the slot taken and queues; the worker
            # dispatches it once the slot is free, and holds it there
            assert s.batcher._slot.acquire(blocking=False)
            t, _ = s.ask("u0")
            threads.append(t)
            s.waiting(1)
            s.gate.clear()
            s.batcher._slot.release()
        else:
            s.gate.clear()
            t, _ = s.ask("u0")
            threads.append(t)
        assert s.entered.acquire(timeout=10)
        assert (s.calls[-1][2] is worker) == (holder == "worker")
        boxes = []
        for i in range(1, 6):
            t, box = s.ask(f"u{i}")
            threads.append(t)
            boxes.append(box)
            s.waiting(i)  # in order, each behind the one before
        s.gate.set()
        _join(*threads)
        assert [c[:2] for c in s.calls[1:]] == [("batch_predict", 8)]
        assert s.calls[-1][2] is worker
        assert all(len(b["answer"]["itemScores"]) == 3 for b in boxes)
        assert s.enqueued("slot_busy") - busy0 >= 1
        assert not s.batcher._slot.locked() and s.batcher._waiting == 0

    def test_no_request_overtakes_a_queued_one(self, slot):
        """The worker has taken the one queued item and waits for the
        slot: the queue reads empty, and the slot may come free any
        moment — a newcomer still queues behind it."""
        s = slot()
        assert s.batcher._slot.acquire(blocking=False)  # a dispatch in flight
        ta, _ = s.ask("u1")
        s.waiting(1)
        deadline = time.monotonic() + 10
        while not s.batcher._q.empty():  # the worker holds the item now
            assert time.monotonic() < deadline
            time.sleep(0.002)
        queued0, inline0 = s.enqueued("queued"), s.path("inline")
        tb, _ = s.ask("u2")
        s.waiting(2)
        assert s.enqueued("queued") == queued0 + 1
        s.batcher._slot.release()
        _join(ta, tb)
        assert s.path("inline") == inline0
        assert [c[:2] for c in s.calls] == [("batch_predict", 2)]
        assert s.calls[0][2] is s.batcher._thread

    @pytest.mark.parametrize("why, kwargs", [
        ("window", {"dispatch_cost_s": 10.0}),
        ("deadline", {"query_deadline_ms": 2000.0}),
    ])
    def test_a_window_or_a_deadline_keeps_every_query_on_the_worker(
            self, slot, why, kwargs):
        s = slot(**kwargs)
        inline0, worker0, why0 = s.path("inline"), s.path("worker"), s.enqueued(why)
        for user in ("u1", "u2"):
            status, _ = _post_raw(
                f"http://127.0.0.1:{s.port}/queries.json", {"user": user, "num": 3})
            assert status == 200
        assert s.path("inline") == inline0
        assert s.path("worker") == worker0 + 2
        assert s.enqueued(why) == why0 + 2
        assert all(c[2] is s.batcher._thread for c in s.calls)

    def test_the_deadlines_503_arrives_at_the_deadline(self, slot):
        """... while the device call is still in flight: what needs the
        scoring on another thread, and keeps such a server off the
        inline path."""
        s = slot(query_deadline_ms=150.0)
        s.gate.clear()
        t0 = time.perf_counter()
        status, body = _post_raw(
            f"http://127.0.0.1:{s.port}/queries.json", {"user": "u1", "num": 3})
        took = time.perf_counter() - t0
        assert status == 503, body
        assert 0.14 <= took < 5.0 and not s.gate.is_set()  # predict still waits
        assert s.calls[0][2] is s.batcher._thread

    @pytest.mark.parametrize("path", ["inline", "worker"])
    @pytest.mark.parametrize("exc, status", [
        (ValueError("no such field"), 400), (KeyError("user"), 400),
        (RuntimeError("device lost"), 500),
    ])
    def test_a_failing_predict_maps_to_the_same_status(self, slot, path, exc, status):
        s = slot(**({"dispatch_cost_s": 10.0} if path == "worker" else {}))
        n0 = s.path(path)
        s.fail = exc
        got, body = _post_raw(
            f"http://127.0.0.1:{s.port}/queries.json", {"user": "u1", "num": 3})
        assert got == status, body
        assert s.path(path) == n0 + 1
        # the slot came back: the next query is served, on the same path
        s.fail = None
        got, _ = _post_raw(
            f"http://127.0.0.1:{s.port}/queries.json", {"user": "u1", "num": 3})
        assert got == 200 and s.path(path) == n0 + 2
        assert not s.batcher._slot.locked()

    def test_stop_waits_for_an_inline_dispatch(self, slot):
        s = slot()
        s.gate.clear()
        t, box = s.ask("u1")
        assert s.entered.acquire(timeout=10)
        stopper = threading.Thread(target=s.batcher.stop)
        stopper.start()
        stopper.join(timeout=0.3)
        assert stopper.is_alive()  # the query in flight is being served
        s.gate.set()
        _join(t, stopper)
        assert len(box["answer"]["itemScores"]) == 3
        assert not s.batcher.active
        with pytest.raises(RuntimeError, match="server stopping"):
            s.batcher.submit({"user": "u2", "num": 3})

    def test_two_variants_share_the_slot(self, slot, trained):
        from predictionio_tpu.models import recommendation as rec

        _, _, inst = trained
        s = slot(extra_variants=[("b", rec.engine(), inst)])
        b = s.server.variants["b"]
        inline0 = s.path("inline")
        ta, box_a = s.ask("u1")
        _join(ta)
        tb, box_b = s.ask("u1", b)
        _join(tb)
        assert box_a["answer"] == box_b["answer"]
        assert s.path("inline") == inline0 + 2
        assert [c[2] for c in s.calls] == [ta, tb]
        assert b.request_count == 1
        # one mount's dispatch in flight: the other's query queues
        s.gate.clear()
        ta, _ = s.ask("u2")
        assert s.entered.acquire(timeout=10)
        tb, box_b = s.ask("u3", b)
        s.waiting(1)
        s.gate.set()
        _join(ta, tb)
        assert s.calls[-1] == ("predict", 1, s.batcher._thread)
        assert len(box_b["answer"]["itemScores"]) == 3
