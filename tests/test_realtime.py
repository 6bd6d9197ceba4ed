"""Realtime speed layer tests: tailer cursor durability, fold-in parity
vs from-scratch retrain, /reload epoch fencing, and the end-to-end
deploy -> ingest -> fold -> personalized-serving -> retrain-supersedes
demo (ISSUE acceptance criteria)."""

from __future__ import annotations

import json

import numpy as np
import pytest

from predictionio_tpu.cli import commands
from predictionio_tpu.core import EngineParams
from predictionio_tpu.core.workflow import prepare_deploy, run_train
from predictionio_tpu.data.event import Event
from predictionio_tpu.models import recommendation as rec
from predictionio_tpu.ops import als as als_ops
from predictionio_tpu.realtime import (
    ALSFoldIn,
    EventTailer,
    FoldInConfig,
    SpeedLayer,
)

from tests.test_servers import http  # real-socket helper


def _rate(uid, iid, rating, event="rate"):
    return Event(
        event=event,
        entity_type="user",
        entity_id=uid,
        target_entity_type="item",
        target_entity_id=iid,
        properties={"rating": float(rating)},
    )


# ---------------------------------------------------------------------------
# tailer cursor durability
# ---------------------------------------------------------------------------


def _jsonl_events(tmp_path):
    from predictionio_tpu.data.storage.jsonl import (
        JSONLEvents,
        JSONLStorageClient,
    )

    return JSONLEvents(JSONLStorageClient({"path": str(tmp_path / "ev")}))


def _sqlite_events(tmp_path):
    from predictionio_tpu.data.storage.sqlite import (
        SQLiteEvents,
        SQLiteStorageClient,
    )

    return SQLiteEvents(
        SQLiteStorageClient({"path": str(tmp_path / "ev.db")})
    )


def _memory_events(tmp_path):
    from predictionio_tpu.data.storage.memory import (
        MemoryEvents,
        MemoryStorageClient,
    )

    return MemoryEvents(MemoryStorageClient({}))


def _partitioned_events(tmp_path):
    from predictionio_tpu.data.storage.partitioned import (
        PartitionedEvents,
        PartitionedStorageClient,
    )

    return PartitionedEvents(
        PartitionedStorageClient(
            {"path": str(tmp_path / "pev"), "partitions": 2}
        )
    )


BACKENDS = {
    "jsonl": _jsonl_events,
    "partitioned": _partitioned_events,
    "sqlite": _sqlite_events,
    "memory": _memory_events,
}


class TestTailerDurability:
    APP = 7

    @pytest.fixture(params=sorted(BACKENDS))
    def events(self, request, tmp_path):
        return BACKENDS[request.param](tmp_path)

    def test_attaches_at_end(self, events, tmp_path):
        # pre-deploy history belongs to the batch layer, not the tailer
        events.insert(_rate("old", "i0", 1), self.APP)
        t = EventTailer(
            events, self.APP, cursor_path=tmp_path / "cursor.json"
        )
        assert t.poll() == []
        events.insert(_rate("u1", "i1", 5), self.APP)
        assert [e.entity_id for e in t.poll()] == ["u1"]
        assert t.poll() == []

    def test_restart_mid_log_resumes_exactly(self, events, tmp_path):
        cursor = tmp_path / "cursor.json"
        t = EventTailer(events, self.APP, cursor_path=cursor)
        for k in range(10):
            events.insert(_rate(f"u{k}", "i1", 5), self.APP)
        first = t.poll(limit=4)
        assert len(first) == 4
        # process restart: a NEW tailer from the persisted cursor must
        # deliver the remaining 6 — no double-counting, no skipping
        t2 = EventTailer(events, self.APP, cursor_path=cursor)
        rest = t2.poll()
        assert len(rest) == 6
        got = {e.entity_id for e in first} | {e.entity_id for e in rest}
        assert got == {f"u{k}" for k in range(10)}
        assert t2.poll() == []
        assert t2.events_behind() in (0, None)

    def test_batches_respect_limit(self, events, tmp_path):
        t = EventTailer(events, self.APP, batch_limit=3)
        for k in range(8):
            events.insert(_rate(f"u{k}", "i1", 5), self.APP)
        sizes = []
        total = []
        while True:
            got = t.poll()
            if not got:
                break
            sizes.append(len(got))
            total.extend(got)
        assert all(s <= 3 for s in sizes)
        assert {e.entity_id for e in total} == {f"u{k}" for k in range(8)}

    def test_duplicate_ids_not_redelivered(self, events, tmp_path):
        t = EventTailer(events, self.APP)
        eid = events.insert(_rate("u1", "i1", 5), self.APP)
        assert len(t.poll()) == 1
        # replace the same event id (INSERT OR REPLACE / rewrite): the
        # tailer has already delivered it — dedupe by event id
        events.insert(
            Event(
                event="rate",
                entity_type="user",
                entity_id="u1",
                target_entity_type="item",
                target_entity_id="i1",
                properties={"rating": 2.0},
                event_id=eid,
            ),
            self.APP,
        )
        assert t.poll() == []


class TestTailerFileLineage:
    """File-backend specifics: rotation and torn trailing lines."""

    APP = 7

    def test_compaction_rotation_resumes_clean(self, tmp_path):
        events = _jsonl_events(tmp_path)
        cursor = tmp_path / "cursor.json"
        events.insert(_rate("old", "i0", 1), self.APP)
        t = EventTailer(events, self.APP, cursor_path=cursor)
        events.insert(_rate("u1", "i1", 5), self.APP)
        assert len(t.poll()) == 1
        # compact() rewrites the log into a NEW inode (rotation): the
        # re-read must not re-deliver u1 or resurrect pre-attach history
        events.compact(self.APP)
        assert t.poll() == []
        events.insert(_rate("u2", "i2", 5), self.APP)
        assert [e.entity_id for e in t.poll()] == ["u2"]

    def test_torn_trailing_line(self, tmp_path):
        events = _jsonl_events(tmp_path)
        cursor = tmp_path / "cursor.json"
        t = EventTailer(events, self.APP, cursor_path=cursor)
        path = events._file(self.APP, None)
        rec_line = json.dumps(
            _rate("torn", "i5", 2)
            .with_event_id("torn-1")
            .to_dict(for_api=False)
        )
        path.parent.mkdir(parents=True, exist_ok=True)
        with open(path, "ab") as f:
            f.write(rec_line[:25].encode())  # writer died mid-append
        assert t.poll() == []  # half a line is not an event
        with open(path, "ab") as f:
            f.write((rec_line[25:] + "\n").encode())
        assert [e.entity_id for e in t.poll()] == ["torn"]  # exactly once
        assert t.poll() == []
        # restart across the healed line: still not re-delivered
        t2 = EventTailer(events, self.APP, cursor_path=cursor)
        assert t2.poll() == []

    def test_attach_on_torn_line_delivers_once_completed(self, tmp_path):
        events = _jsonl_events(tmp_path)
        events.insert(_rate("old", "i0", 1), self.APP)
        path = events._file(self.APP, None)
        rec_line = json.dumps(
            _rate("torn", "i5", 2)
            .with_event_id("torn-2")
            .to_dict(for_api=False)
        )
        with open(path, "ab") as f:
            f.write(rec_line[:25].encode())
        # attach while the tail is torn: the end-offset scan must stop at
        # the last NEWLINE, not the torn bytes
        t = EventTailer(events, self.APP)
        assert t.poll() == []
        with open(path, "ab") as f:
            f.write((rec_line[25:] + "\n").encode())
        got = t.poll()
        assert [e.entity_id for e in got] == ["torn"]

    def test_partitioned_tails_across_partitions(self, tmp_path):
        events = _partitioned_events(tmp_path)
        t = EventTailer(events, self.APP)
        assert t.mode == "files"
        for k in range(16):  # ids hash across both partitions
            events.insert(_rate(f"u{k}", "i1", 5), self.APP)
        got = t.poll()
        assert {e.entity_id for e in got} == {f"u{k}" for k in range(16)}
        assert t.poll() == []
        assert t.events_behind() == 0


class TestSeqBackendTails:
    """tail_events/tail_end contract on the seq-ordered backends."""

    APP = 3

    def test_sqlite_rowid_tail(self, tmp_path):
        events = _sqlite_events(tmp_path)
        assert events.tail_end(self.APP) == 0  # missing table
        events.insert(_rate("u1", "i1", 5), self.APP)
        events.insert(_rate("u2", "i2", 4), self.APP)
        end = events.tail_end(self.APP)
        assert end == 2
        got, cur = events.tail_events(self.APP, after=0, limit=1)
        assert [e.entity_id for e in got] == ["u1"] and cur == 1
        got, cur = events.tail_events(self.APP, after=cur)
        assert [e.entity_id for e in got] == ["u2"] and cur == end

    def test_memory_seq_tail(self, tmp_path):
        events = _memory_events(tmp_path)
        events.insert(_rate("u1", "i1", 5), self.APP)
        end = events.tail_end(self.APP)
        got, cur = events.tail_events(self.APP, after=0)
        assert [e.entity_id for e in got] == ["u1"] and cur == end
        assert events.tail_events(self.APP, after=cur) == ([], cur)

    def test_postgres_creationtime_tail(self, tmp_path):
        from predictionio_tpu.data.storage.postgres import (
            PostgresEvents,
            PostgresStorageClient,
        )

        from tests.test_postgres import FakePgConnection

        events = PostgresEvents(
            PostgresStorageClient(connection=FakePgConnection())
        )
        assert events.tail_end(self.APP) == (0.0, "")
        events.insert(_rate("u1", "i1", 5), self.APP)
        end = events.tail_end(self.APP)
        assert end[0] > 0.0
        got, cur = events.tail_events(self.APP, after=None)
        assert [e.entity_id for e in got] == ["u1"]
        assert cur == end
        # keyset cursor is strictly-after: the boundary row is not
        # re-delivered, and same-timestamp bursts resume at the id
        got2, cur2 = events.tail_events(self.APP, after=cur)
        assert got2 == [] and cur2 == cur
        t = EventTailer(events, self.APP)
        events.insert(_rate("u2", "i2", 4), self.APP)
        assert [e.entity_id for e in t.poll()] == ["u2"]
        assert t.poll() == []


# ---------------------------------------------------------------------------
# fold-in parity vs from-scratch retrain
# ---------------------------------------------------------------------------

# Tolerances (documented): the fold-in solves the new user's row in
# closed form against FIXED item factors, while a retrain also moves the
# item factors — on this block-structured dataset the two agree to:
RMSE_TOL = {"float32": 0.35, "bfloat16": 0.4, "int8": 0.5}


def _train_model(storage, app_name, storage_dtype, sharded, engine_id):
    engine = rec.engine()
    ep = EngineParams(
        datasource=("", rec.DataSourceParams(app_name=app_name)),
        algorithms=[
            (
                "als",
                rec.ALSAlgorithmParams(
                    rank=4,
                    num_iterations=8,
                    storage_dtype=storage_dtype,
                    sharded_train=sharded,
                ),
            )
        ],
    )
    run_train(engine, ep, engine_id=engine_id, storage=storage)
    instance = storage.get_metadata_engine_instances().get_latest_completed(
        engine_id, "0", "default"
    )
    _, _, models, _ = prepare_deploy(engine, instance, storage=storage)
    return models[0], instance


def _scores(model, uid):
    row = model.user_rows([model.user_index[uid]])[0]
    V = np.asarray(als_ops.dense_factors(model.item_table()))
    return {
        iid: float(row @ V[ix]) for iid, ix in model.item_index.items()
    }


@pytest.mark.parametrize(
    "storage_dtype,sharded",
    [
        ("float32", False),
        ("bfloat16", False),
        ("int8", False),
        ("int8", True),  # virtual 8-device mesh train (conftest)
    ],
)
def test_foldin_parity_vs_retrain(storage, storage_dtype, sharded):
    """A folded-in user must rank like a from-scratch retrain that saw
    the same events: same preferred block, overlapping top items, and
    RMSE on the user's own ratings within the documented tolerance."""
    info = commands.app_new("FoldApp", storage=storage)
    app_id = info["id"]
    events = storage.get_events()
    # block structure: group A loves i0-3 / hates i4-7, group B inverse
    for u in range(6):
        for i in range(8):
            events.insert(_rate(f"a{u}", f"i{i}", 5 if i < 4 else 1), app_id)
            events.insert(_rate(f"b{u}", f"i{i}", 1 if i < 4 else 5), app_id)
    base_model, _ = _train_model(
        storage, "FoldApp", storage_dtype, sharded, "fold"
    )
    assert "newu" not in base_model.user_index

    # the new user arrives AFTER training: a clear group-A profile
    new_ratings = {"i0": 5, "i1": 5, "i4": 1, "i5": 1}
    new_events = [_rate("newu", iid, v) for iid, v in new_ratings.items()]
    for e in new_events:
        events.insert(e, app_id)

    foldin = ALSFoldIn(events, app_id, config=FoldInConfig())
    patched, stats = foldin.fold(base_model, new_events)
    assert patched is not None
    assert stats.users_added == 1
    assert patched.user_factors.shape[0] == base_model.user_factors.shape[0] + 1
    # served model untouched
    assert "newu" not in base_model.user_index

    retrained, _ = _train_model(
        storage, "FoldApp", storage_dtype, sharded, "fold2"
    )
    s_fold = _scores(patched, "newu")
    s_full = _scores(retrained, "newu")

    # ranking: the unrated group-A items must beat the unrated group-B
    # items under BOTH models
    for s in (s_fold, s_full):
        assert min(s["i2"], s["i3"]) > max(s["i6"], s["i7"]), s
    top3 = lambda s: {i for i, _ in sorted(s.items(), key=lambda kv: -kv[1])[:3]}
    assert len(top3(s_fold) & top3(s_full)) >= 2

    # reconstruction RMSE on the user's own ratings
    def rmse(s):
        err = [s[iid] - v for iid, v in new_ratings.items()]
        return float(np.sqrt(np.mean(np.square(err))))

    assert rmse(s_fold) <= rmse(s_full) + RMSE_TOL[storage_dtype], (
        rmse(s_fold),
        rmse(s_full),
    )


def test_foldin_updates_existing_user_and_requantizes(storage):
    """Folding new events for a KNOWN user rewrites that row in place
    (int8: with a fresh per-row scale) and leaves every other row
    byte-identical."""
    info = commands.app_new("Fold8App", storage=storage)
    app_id = info["id"]
    events = storage.get_events()
    for u in range(6):
        for i in range(8):
            events.insert(_rate(f"a{u}", f"i{i}", 5 if i < 4 else 1), app_id)
            events.insert(_rate(f"b{u}", f"i{i}", 1 if i < 4 else 5), app_id)
    model, _ = _train_model(storage, "Fold8App", "int8", False, "f8")
    # a0 flips preference entirely
    flips = [_rate("a0", f"i{i}", 1 if i < 4 else 5) for i in range(8)]
    for e in flips:
        events.insert(e, app_id)
    foldin = ALSFoldIn(events, app_id, config=FoldInConfig())
    patched, stats = foldin.fold(model, flips)
    assert patched is not None and stats.users_added == 0
    ix = model.user_index["a0"]
    assert patched.user_factors.dtype == np.int8
    assert patched.user_scales is not None
    assert not np.array_equal(patched.user_factors[ix], model.user_factors[ix])
    other = [i for i in range(len(model.user_index)) if i != ix]
    assert np.array_equal(
        patched.user_factors[other], model.user_factors[other]
    )
    s = _scores(patched, "a0")
    assert min(s["i4"], s["i5"]) > max(s["i0"], s["i1"]), s


def test_foldin_accumulates_cold_item_stats(storage):
    info = commands.app_new("ColdApp", storage=storage)
    app_id = info["id"]
    events = storage.get_events()
    for u in range(4):
        for i in range(4):
            events.insert(_rate(f"u{u}", f"i{i}", 4), app_id)
    model, _ = _train_model(storage, "ColdApp", "float32", False, "cold")
    batch = [
        _rate("u0", "BRAND_NEW", 5),
        _rate("u1", "BRAND_NEW", 3),
        _rate("u0", "i0", 2),
    ]
    for e in batch:
        events.insert(e, app_id)
    foldin = ALSFoldIn(events, app_id, config=FoldInConfig())
    patched, stats = foldin.fold(model, batch)
    assert patched is not None  # u0/u1 still solvable on known items
    assert stats.cold_item_events == 2
    assert foldin.cold_start_stats()["BRAND_NEW"] == {
        "events": 2,
        "mean_rating": 4.0,
    }
    assert "BRAND_NEW" not in patched.item_index  # items stay fixed


# ---------------------------------------------------------------------------
# epoch fencing: /reload vs apply_patch races
# ---------------------------------------------------------------------------


@pytest.fixture()
def deployed(storage):
    """Recommendation engine trained + deployed on a local port (same
    shape as test_servers.deployed_engine, with a second app for the
    speed layer tests to ingest into)."""
    from predictionio_tpu.server.engine_server import EngineServer

    info = commands.app_new("RtApp", storage=storage)
    events = storage.get_events()
    rng = np.random.default_rng(0)
    for u in range(12):
        for _ in range(6):
            i = int(rng.integers(0, 8))
            events.insert(
                _rate(f"u{u}", f"i{i}", float(rng.integers(1, 6))),
                info["id"],
            )
    engine = rec.engine()
    ep = EngineParams(
        datasource=("", rec.DataSourceParams(app_name="RtApp")),
        algorithms=[("als", rec.ALSAlgorithmParams(rank=4, num_iterations=3))],
    )
    run_train(engine, ep, engine_id="rt", storage=storage)
    instance = storage.get_metadata_engine_instances().get_latest_completed(
        "rt", "0", "default"
    )
    server = EngineServer(
        engine,
        instance,
        storage=storage,
        host="127.0.0.1",
        port=0,
        server_key="secret",
    )
    port = server.start()
    yield {
        "base": f"http://127.0.0.1:{port}",
        "server": server,
        "storage": storage,
        "engine": engine,
        "ep": ep,
        "app_id": info["id"],
        "access_key": info["access_key"],
    }
    server.stop()


class TestEpochFence:
    def test_stale_patch_rejected_after_reload(self, deployed):
        """The regression the satellite asks for: a fold-in that
        snapshotted before a /reload must NOT be able to resurrect
        pre-retrain factors."""
        server = deployed["server"]
        _, models, epoch = server.model_snapshot()
        # retrain + reload lands while the fold-in is "computing"
        run_train(
            deployed["engine"],
            deployed["ep"],
            engine_id="rt",
            storage=deployed["storage"],
        )
        status, _ = http("POST", deployed["base"] + "/reload?accessKey=secret")
        assert status == 200
        reloaded_models = server.models
        assert server.apply_patch(list(models), epoch) is False
        assert server.models is reloaded_models  # untouched

    def test_patch_applies_and_reload_supersedes(self, deployed):
        server = deployed["server"]
        _, models, epoch = server.model_snapshot()
        assert server.apply_patch(list(models), epoch) is True
        assert server._foldin_epoch == 1
        # a stale second apply with the consumed epoch is fenced out
        assert server.apply_patch(list(models), epoch) is False
        # reload resets the fold-in epoch: retrain wins
        run_train(
            deployed["engine"],
            deployed["ep"],
            engine_id="rt",
            storage=deployed["storage"],
        )
        assert server.reload() is True
        assert server._foldin_epoch == 0

    def test_stats_route_without_speed_layer(self, deployed):
        status, body = http("GET", deployed["base"] + "/stats.json")
        assert status == 200
        assert body["realtime"] == {"enabled": False}
        assert body["status"] == "alive"


# ---------------------------------------------------------------------------
# end-to-end: deploy -> ingest -> fold -> personalized -> retrain wins
# ---------------------------------------------------------------------------


class TestSpeedLayerEndToEnd:
    def test_demo_flow(self, deployed, tmp_path):
        """The ISSUE acceptance demo, with step() driven directly (no
        polling sleeps): a new user becomes personally servable without
        a retrain, then a retrain + /reload supersedes the patch."""
        from predictionio_tpu.server.event_server import EventServer

        server = deployed["server"]
        base = deployed["base"]
        es = EventServer(
            storage=deployed["storage"], host="127.0.0.1", port=0, stats=True
        )
        es_port = es.start()
        es_base = f"http://127.0.0.1:{es_port}"
        key = deployed["access_key"]

        layer = SpeedLayer(
            server,
            interval=3600,  # never fires on its own in this test
            cursor_path=tmp_path / "cursor.json",
        )
        assert server.speed_layer is layer
        assert layer.step() == "idle"

        # before ingest: the new user is a cold start
        status, body = http("POST", f"{base}/queries.json", {"user": "zz9"})
        assert status == 200 and body["itemScores"] == []

        # ingest the new user's ratings through the EVENT SERVER
        for iid, v in (("i0", 5.0), ("i1", 5.0), ("i2", 4.0)):
            status, _ = http(
                "POST",
                f"{es_base}/events.json?accessKey={key}",
                {
                    "event": "rate",
                    "entityType": "user",
                    "entityId": "zz9",
                    "targetEntityType": "item",
                    "targetEntityId": iid,
                    "properties": {"rating": v},
                },
            )
            assert status == 201

        assert layer.step() == "patched"

        # personalized results WITHOUT a retrain
        status, body = http(
            "POST", f"{base}/queries.json", {"user": "zz9", "num": 3}
        )
        assert status == 200 and len(body["itemScores"]) == 3

        status, stats_body = http("GET", f"{base}/stats.json")
        assert stats_body["realtime"]["enabled"] is True
        assert stats_body["realtime"]["foldin_epoch"] == 1
        assert stats_body["realtime"]["users_added"] == 1
        assert stats_body["realtime"]["events_behind"] == 0
        assert stats_body["realtime"]["seconds_behind"] == 0.0

        # full retrain (sees zz9's events) + /reload: retrain wins and
        # the tailer cursor advances to the new train watermark
        run_train(
            deployed["engine"],
            deployed["ep"],
            engine_id="rt",
            storage=deployed["storage"],
        )
        status, _ = http("POST", f"{base}/reload?accessKey=secret")
        assert status == 200
        assert layer.step() == "superseded"
        assert layer.tailer.poll() == []  # cursor at the new watermark
        status, stats_body = http("GET", f"{base}/stats.json")
        assert stats_body["realtime"]["foldin_epoch"] == 0
        # the retrained model serves zz9 natively now
        status, body = http(
            "POST", f"{base}/queries.json", {"user": "zz9", "num": 3}
        )
        assert status == 200 and len(body["itemScores"]) == 3

        es.stop()

    def test_reload_mid_fold_drops_batch(self, deployed, tmp_path):
        """A retrain landing between snapshot and patch: the fold loses
        the fence, sees the new instance, and drops the batch (the new
        instance's training read covered those events)."""
        server = deployed["server"]
        layer = SpeedLayer(server, interval=3600)
        events = deployed["storage"].get_events()
        events.insert(_rate("zz8", "i0", 5), deployed["app_id"])

        real_apply = server.apply_patch
        fired = []

        def racing_apply(models, epoch):
            if not fired:
                fired.append(True)
                run_train(
                    deployed["engine"],
                    deployed["ep"],
                    engine_id="rt",
                    storage=deployed["storage"],
                )
                server.reload()  # swaps instance + bumps the epoch
            return real_apply(models, epoch)

        server.apply_patch = racing_apply
        try:
            assert layer.step() == "superseded"
        finally:
            server.apply_patch = real_apply
        # the batch was dropped, not retried against the new instance
        assert layer.step() == "idle"

    def test_gauges_report_backlog(self, deployed, tmp_path):
        server = deployed["server"]
        layer = SpeedLayer(server, interval=3600)
        g = layer.gauges()
        assert g["enabled"] is True and g["mode"] == "seq"
        events = deployed["storage"].get_events()
        for k in range(5):
            events.insert(_rate("zz7", f"i{k}", 4), deployed["app_id"])
        assert layer.gauges()["events_behind"] == 5
        assert layer.step() == "patched"
        assert layer.gauges()["events_behind"] == 0


# ---------------------------------------------------------------------------
# event server /stats.json seq + ingest timestamp (satellite)
# ---------------------------------------------------------------------------


def test_event_server_stats_expose_seq_and_ingest_time(storage):
    from predictionio_tpu.server.event_server import EventServer

    info = commands.app_new("SeqApp", storage=storage)
    es = EventServer(storage=storage, host="127.0.0.1", port=0, stats=True)
    port = es.start()
    base = f"http://127.0.0.1:{port}"
    key = info["access_key"]
    try:
        status, body = http("GET", f"{base}/stats.json?accessKey={key}")
        assert status == 200
        assert body["lastEventSeq"] == 0
        assert body["lastIngestTime"] is None
        import time as _time

        t0 = _time.time()
        for k in range(3):
            status, _ = http(
                "POST",
                f"{base}/events.json?accessKey={key}",
                {
                    "event": "rate",
                    "entityType": "user",
                    "entityId": f"u{k}",
                    "targetEntityType": "item",
                    "targetEntityId": "i1",
                    "properties": {"rating": 3.0},
                },
            )
            assert status == 201
        status, body = http("GET", f"{base}/stats.json?accessKey={key}")
        assert body["lastEventSeq"] == 3
        assert body["lastIngestTime"] >= t0
        # rejected writes don't advance the accepted-write seq
        status, _ = http("POST", f"{base}/events.json?accessKey={key}", {})
        assert status == 400
        status, body = http("GET", f"{base}/stats.json?accessKey={key}")
        assert body["lastEventSeq"] == 3
    finally:
        es.stop()


# ---------------------------------------------------------------------------
# PR 4: the query cache under the epoch fence — swap races must never
# serve a pre-swap cached result
# ---------------------------------------------------------------------------


@pytest.fixture()
def cached_deployed(deployed):
    """A second server over the trained instance with the query cache
    enabled (the `deployed` server stays untouched for other tests)."""
    from predictionio_tpu.server.engine_server import EngineServer

    server = EngineServer(
        deployed["engine"], deployed["server"].instance,
        storage=deployed["storage"], host="127.0.0.1", port=0,
        server_key="secret", query_cache_mb=4,
    )
    port = server.start()
    yield {**deployed, "base": f"http://127.0.0.1:{port}", "server": server}
    server.stop()


class TestQueryCacheEpochFence:
    def _block_predict(self, server):
        """Gate the algorithm's predict on an event so a query can
        be held in flight while the model swaps under it."""
        import threading

        algo = server.algorithms[0]
        orig = algo.predict
        started, release = threading.Event(), threading.Event()

        def blocking(*a, **k):
            started.set()
            assert release.wait(timeout=30), "test never released the gate"
            return orig(*a, **k)

        algo.predict = blocking
        return started, release, orig

    def test_foldin_racing_inflight_query_never_caches_stale(
        self, cached_deployed
    ):
        """THE race the epoch fence exists for: a query snapshots the
        model, a fold-in patch swaps it mid-compute, the query finishes
        with pre-swap factors. Its result lands under the PRE-swap epoch
        key — unreachable — so the next identical query recomputes
        against the patched model and serves different bytes."""
        import dataclasses
        import threading

        from predictionio_tpu.server import jsonx
        from tests.test_servers import _raw_post

        server = cached_deployed["server"]
        url = cached_deployed["base"] + "/queries.json"
        q = {"user": "u1", "num": 3}
        started, release, orig = self._block_predict(server)

        result = {}
        t = threading.Thread(
            target=lambda: result.update(b=_raw_post(url, q))
        )
        t.start()
        assert started.wait(timeout=30)
        # the fold-in lands while the query is mid-compute: negated user
        # factors flip every score, so pre- and post-swap bytes differ
        _, models, epoch = server.model_snapshot()
        flipped = [
            dataclasses.replace(m, user_factors=-m.user_factors)
            for m in models
        ]
        assert server.apply_patch(flipped, epoch) is True
        release.set()
        t.join(timeout=30)
        assert not t.is_alive()
        stale = result["b"]

        server.algorithms[0].predict = orig
        fresh = _raw_post(url, q)
        assert fresh != stale  # post-swap model answers, not the cache
        assert fresh == jsonx.dumps_bytes(server.handle_query(q))
        # and the fresh bytes ARE now cached under the post-swap epoch
        hits_before = server.query_cache.gauges()["cache_hits"]
        assert _raw_post(url, q) == fresh
        assert server.query_cache.gauges()["cache_hits"] == hits_before + 1

    def test_reload_racing_inflight_query_never_caches_stale(
        self, cached_deployed
    ):
        """Same race via /reload: the in-flight result is stranded under
        the pre-reload epoch, the follow-up query recomputes on the
        reloaded instance's algorithm (a retrain on identical data is
        bit-identical, so the proof is the recompute, not the bytes)."""
        import threading

        from predictionio_tpu.server.query_cache import canonical_query_bytes
        from tests.test_servers import _raw_post

        server = cached_deployed["server"]
        url = cached_deployed["base"] + "/queries.json"
        q = {"user": "u1", "num": 3}
        started, release, _ = self._block_predict(server)

        t = threading.Thread(target=lambda: _raw_post(url, q))
        t.start()
        assert started.wait(timeout=30)
        run_train(
            cached_deployed["engine"], cached_deployed["ep"], engine_id="rt",
            storage=cached_deployed["storage"],
        )
        status, _ = http(
            "POST", cached_deployed["base"] + "/reload?accessKey=secret"
        )
        assert status == 200
        release.set()
        t.join(timeout=30)
        assert not t.is_alive()

        # the stale result is NOT reachable under the served epoch
        with server._lock:
            epoch = server._epoch
            variant = server.instance.engine_variant
        key = (variant, canonical_query_bytes(q), epoch)
        assert server.query_cache.get(key) is None
        # the follow-up query recomputes on the post-reload algorithm
        calls = []
        algo = server.algorithms[0]
        orig2 = algo.predict
        algo.predict = lambda *a, **k: (
            calls.append(1),  # noqa: B023 - count then delegate
            orig2(*a, **k),
        )[1]
        _raw_post(url, q)
        assert len(calls) == 1

    def test_speed_layer_counts_cache_invalidations(self, cached_deployed):
        """A patched step() on a cache-enabled server bumps the
        query_cache_invalidations gauge on /stats.json."""
        from predictionio_tpu.realtime.speed_layer import SpeedLayer

        server = cached_deployed["server"]
        layer = SpeedLayer(server, interval=60.0)
        # ingest a foldable rating into the deployed app, then step
        storage = cached_deployed["storage"]
        events = storage.get_events()
        events.insert(_rate("u1", "i2", 5.0), cached_deployed["app_id"])
        assert layer.step() == "patched"
        assert layer.gauges()["query_cache_invalidations"] == 1
        status, body = http("GET", cached_deployed["base"] + "/stats.json")
        assert status == 200
        assert body["realtime"]["query_cache_invalidations"] == 1


# ---------------------------------------------------------------------------
# robustness PR: corrupt-cursor recovery + fold-in circuit breaker
# ---------------------------------------------------------------------------


class TestCursorCorruptionRecovery:
    """Satellite: a truncated/corrupt cursor JSON must fall back to a
    watermark re-attach (reset) instead of crashing the speed layer,
    and count the recovery."""

    APP = 7

    def _recovered_counter(self):
        from predictionio_tpu.obs import metrics as obs_metrics

        return obs_metrics.counter(
            "pio_tailer_cursor_recovered",
            "Tailer restarts that discarded a corrupt cursor file",
        )

    def _tailer_with_cursor(self, tmp_path):
        events = _jsonl_events(tmp_path)
        cursor = tmp_path / "cursor.json"
        t = EventTailer(events, self.APP, cursor_path=cursor)
        events.insert(_rate("u1", "i1", 4), self.APP)
        assert len(t.poll()) == 1  # persists a real cursor
        return events, cursor

    @pytest.mark.parametrize(
        "corruption",
        [
            "torn-json",
            "not-a-dict",
            "watermark-wrong-type",
            "files-missing-fields",
            "seen-not-a-list",
        ],
    )
    def test_corrupt_cursor_falls_back_to_reattach(
        self, tmp_path, corruption
    ):
        events, cursor = self._tailer_with_cursor(tmp_path)
        good = json.loads(cursor.read_text())
        if corruption == "torn-json":
            cursor.write_text(cursor.read_text()[: len(cursor.read_text()) // 2])
        elif corruption == "not-a-dict":
            cursor.write_text("[1, 2, 3]")
        elif corruption == "watermark-wrong-type":
            good["watermark"] = ["not", "a", "number"]
            cursor.write_text(json.dumps(good))
        elif corruption == "files-missing-fields":
            good["files"] = {p: {"offset": 0} for p in good.get("files", {})}
            cursor.write_text(json.dumps(good))
        elif corruption == "seen-not-a-list":
            good["seen"] = 42
            cursor.write_text(json.dumps(good))
        before = self._recovered_counter().value()
        # events already in the log predate the re-attach watermark
        events.insert(_rate("u2", "i2", 3), self.APP)
        t2 = EventTailer(events, self.APP, cursor_path=cursor)
        if corruption != "seen-not-a-list":
            # set(42) raises; set of a list is fine — either way no crash
            assert self._recovered_counter().value() >= before
        assert t2.poll() == []  # re-attached at the end, not at zero
        events.insert(_rate("u3", "i3", 5), self.APP)
        got = t2.poll()
        assert [e.entity_id for e in got] == ["u3"]
        # the recovered tailer persists a fresh, valid cursor
        assert json.loads(cursor.read_text())["version"] == 1

    def test_structurally_corrupt_cursor_counts_recovery(self, tmp_path):
        events, cursor = self._tailer_with_cursor(tmp_path)
        good = json.loads(cursor.read_text())
        good["files"] = {p: {"offset": 0} for p in good.get("files", {})}
        cursor.write_text(json.dumps(good))
        before = self._recovered_counter().value()
        EventTailer(events, self.APP, cursor_path=cursor)
        assert self._recovered_counter().value() == before + 1


class TestFoldInCircuitBreaker:
    """Tentpole: repeated fold-in failures trip the breaker; the engine
    keeps serving the last good epoch-fenced model; the breaker
    half-opens after backoff and closes on a successful fold."""

    def _speed_layer(self, deployed, tmp_path, clock):
        from predictionio_tpu.common.breaker import CircuitBreaker

        breaker = CircuitBreaker(
            "foldin", failure_threshold=3, base_backoff_s=2.0,
            max_backoff_s=60.0, jitter=0.0, clock=clock,
        )
        return SpeedLayer(
            deployed["server"],
            cursor_path=tmp_path / "cursor.json",
            breaker=breaker,
        )

    def test_breaker_trips_half_opens_and_recovers(self, deployed, tmp_path):
        from predictionio_tpu import faults

        clock = {"t": 1000.0}
        sl = self._speed_layer(deployed, tmp_path, lambda: clock["t"])
        app_id = deployed["app_id"]
        events = deployed["storage"].get_events()
        _, models_before, _ = deployed["server"].model_snapshot()

        with faults.injected("foldin.fold:always"):
            for i in range(3):
                events.insert(_rate("u1", f"i{i % 3}", 5), app_id)
                assert sl.step() == "fold_failed"
            assert sl.breaker.state == "open"
            # while open: no poll, no fold, model untouched
            events.insert(_rate("u1", "i1", 5), app_id)
            assert sl.step() == "breaker_open"
        _, models_now, _ = deployed["server"].model_snapshot()
        # last good model still served (same objects, no patch applied)
        assert all(a is b for a, b in zip(models_now, models_before))

        snap = sl.gauges()["breaker"]
        assert snap["state"] == "open" and snap["trips_total"] == 1
        assert snap["failures_total"] == 3 and snap["retry_in_s"] > 0

        # backoff elapses -> half-open trial -> successful fold closes it
        clock["t"] += 2.5
        assert sl.step() == "patched"
        assert sl.breaker.state == "closed"
        _, models_after, _ = deployed["server"].model_snapshot()
        assert any(a is not b for a, b in zip(models_after, models_before))

    def test_open_breaker_does_not_consume_events(self, deployed, tmp_path):
        """The poll is gated on allow(): events arriving while the
        breaker is open must survive to be folded after recovery (a
        poll would persist the cursor and silently drop them)."""
        from predictionio_tpu import faults

        clock = {"t": 0.0}
        sl = self._speed_layer(deployed, tmp_path, lambda: clock["t"])
        app_id = deployed["app_id"]
        events = deployed["storage"].get_events()
        with faults.injected("foldin.fold:always"):
            for i in range(3):
                events.insert(_rate("u2", f"i{i % 3}", 4), app_id)
                assert sl.step() == "fold_failed"
            events.insert(_rate("u3", "i1", 5), app_id)  # lands while open
            assert sl.step() == "breaker_open"
        clock["t"] += 2.5
        before = sl.events_folded
        assert sl.step() == "patched"  # the held-back event folds now
        assert sl.events_folded == before + 1

    def test_breaker_state_rides_stats_json(self, deployed, tmp_path):
        clock = {"t": 0.0}
        self._speed_layer(deployed, tmp_path, lambda: clock["t"])
        status, body = http("GET", deployed["base"] + "/stats.json")
        assert status == 200
        assert body["realtime"]["breaker"]["state"] == "closed"
        assert body["realtime"]["breaker"]["trips_total"] == 0


# ---------------------------------------------------------------------------
# columnar tail path: span->array decode from log to fold-in (tentpole)
# ---------------------------------------------------------------------------

FILE_BACKENDS = {"jsonl": _jsonl_events, "partitioned": _partitioned_events}


def _columnar_configs():
    """Matching FoldInConfig/DecodeConfig exercising every rating
    resolution rule: property extraction, per-event defaults, and
    overrides."""
    from predictionio_tpu.data.storage import colspans

    cfg = FoldInConfig(
        event_names=("rate", "buy", "like"),
        default_ratings={"like": 5.0},
        override_ratings={"buy": 4.0},
    )
    dcfg = colspans.DecodeConfig(
        event_names=cfg.event_names,
        rating_key=cfg.rating_key,
        default_ratings=cfg.default_ratings,
        override_ratings=cfg.override_ratings,
        entity_type=cfg.entity_type,
        target_entity_type=cfg.target_entity_type,
    )
    return cfg, dcfg


def _batch_entity_ids(batch):
    """Delivered entity ids across a TailedBatch's mixed segments, in
    delivery order."""
    out = []
    for seg in batch.segments:
        if isinstance(seg, list):
            out.extend(e.entity_id for e in seg)
        else:
            out.extend(seg.user_ids[i] for i in seg.user_idx)
    return out


def _columnar_rows(batch):
    return sum(
        seg.n_rows for seg in batch.segments if not isinstance(seg, list)
    )


def _mixed_stream(events, app):
    """One of every classifier route: plain rates, a default-rated
    event, an override-rated event, a properties-rich $set, a
    rate-shaped line with no resolvable rating, a brand-new user, and a
    cold item."""
    evs = [
        _rate("u1", "i1", 5),
        _rate("u2", "i2", 3),
        Event(
            event="like", entity_type="user", entity_id="u1",
            target_entity_type="item", target_entity_id="i3",
        ),  # no rating property: default_ratings resolves 5.0
        Event(
            event="buy", entity_type="user", entity_id="u2",
            target_entity_type="item", target_entity_id="i1",
            properties={"rating": 1.0},
        ),  # override_ratings forces 4.0 over the property
        Event(
            event="$set", entity_type="user", entity_id="u1",
            properties={"plan": "pro"},
        ),  # properties-rich: must route to the object path
        _rate("u3", "i2", 4),
        Event(
            event="rate", entity_type="user", entity_id="u3",
            target_entity_type="item", target_entity_id="i4",
        ),  # rate-shaped but unresolvable: object path, not dropped
        _rate("nu1", "i0", 5),  # user unknown to the model
        _rate("u0", "COLD_ITEM", 4),  # item unknown to the model
    ]
    for e in evs:
        events.insert(e, app)
    return evs


def _synthetic_model(storage_dtype="float32", n_users=4, n_items=6, rank=4):
    from predictionio_tpu.data.bimap import BiMap

    rng = np.random.default_rng(11)
    U = rng.normal(size=(n_users, rank)).astype(np.float32)
    V = rng.normal(size=(n_items, rank)).astype(np.float32)
    user_scales = item_scales = None
    if storage_dtype == "int8":
        q, s = als_ops.quantize_rows(U)
        U, user_scales = np.asarray(q), np.asarray(s)
        q, s = als_ops.quantize_rows(V)
        V, item_scales = np.asarray(q), np.asarray(s)
    elif storage_dtype != "float32":
        U = np.asarray(als_ops.to_storage(U, storage_dtype))
        V = np.asarray(als_ops.to_storage(V, storage_dtype))
    return rec.ALSModel(
        user_index=BiMap.from_dense([f"u{i}" for i in range(n_users)]),
        item_index=BiMap.from_dense([f"i{i}" for i in range(n_items)]),
        user_factors=U,
        item_factors=V,
        user_scales=user_scales,
        item_scales=item_scales,
    )


class TestColumnarTail:
    """poll_columnar/fold_in_columnar must be observably identical to
    poll/fold — same deliveries, same cursor durability, bit-identical
    patches — while actually taking the span->array path for the
    rate-shaped lines."""

    APP = 7

    def _attach_pair(self, make, tmp_path):
        _, dcfg = _columnar_configs()
        events = make(tmp_path)
        # seed every partition so the logs exist BEFORE attach: a file
        # born after attach re-reads as fresh, which by design routes
        # to the object path
        for k in range(4):
            events.insert(_rate(f"pre{k}", "i0", 1), self.APP)
        t_obj = EventTailer(events, self.APP)
        t_col = EventTailer(events, self.APP, columnar_config=dcfg)
        return events, t_obj, t_col

    @pytest.mark.parametrize("storage_dtype", ["float32", "bfloat16", "int8"])
    @pytest.mark.parametrize("backend", sorted(FILE_BACKENDS))
    def test_mixed_stream_bit_parity(self, tmp_path, backend, storage_dtype):
        cfg, _ = _columnar_configs()
        events, t_obj, t_col = self._attach_pair(
            FILE_BACKENDS[backend], tmp_path
        )
        inserted = _mixed_stream(events, self.APP)
        obj_events = t_obj.poll()
        batch = t_col.poll_columnar()
        assert batch.n_events == len(obj_events) == len(inserted)
        assert _columnar_rows(batch) > 0  # the array path actually ran
        assert sorted(_batch_entity_ids(batch)) == sorted(
            e.entity_id for e in obj_events
        )

        model = _synthetic_model(storage_dtype)
        foldin_o = ALSFoldIn(events, self.APP, config=cfg)
        patched_o, stats_o = foldin_o.fold(model, obj_events)
        foldin_c = ALSFoldIn(events, self.APP, config=cfg)
        patched_c, stats_c = foldin_c.fold_in_columnar(model, batch)
        assert patched_o is not None and patched_c is not None
        assert stats_c == stats_o
        assert stats_c.users_added == 1  # nu1
        assert stats_c.cold_item_events == 1  # COLD_ITEM
        assert list(patched_c.user_index) == list(patched_o.user_index)
        assert patched_c.user_factors.dtype == patched_o.user_factors.dtype
        assert np.array_equal(patched_c.user_factors, patched_o.user_factors)
        if storage_dtype == "int8":
            assert np.array_equal(
                patched_c.user_scales, patched_o.user_scales
            )
        assert foldin_c.cold_start_stats() == foldin_o.cold_start_stats()

    def test_rotation_mid_stream_no_duplicates(self, tmp_path):
        _, dcfg = _columnar_configs()
        events = _jsonl_events(tmp_path)
        events.insert(_rate("old", "i0", 1), self.APP)
        t = EventTailer(events, self.APP, columnar_config=dcfg)
        events.insert(_rate("u1", "i1", 5), self.APP)
        assert _batch_entity_ids(t.poll_columnar()) == ["u1"]
        # compact() rewrites the log into a NEW inode: the re-read goes
        # through the object path (fresh lineage) and the seen-id set
        # must swallow u1 instead of re-delivering it
        events.compact(self.APP)
        assert t.poll_columnar().n_events == 0
        events.insert(_rate("u2", "i2", 5), self.APP)
        batch = t.poll_columnar()
        assert _batch_entity_ids(batch) == ["u2"]
        assert _columnar_rows(batch) == 1  # back on the array path

    def test_torn_trailing_line_columnar(self, tmp_path):
        events = _jsonl_events(tmp_path)
        events.insert(_rate("pre", "i0", 1), self.APP)
        _, dcfg = _columnar_configs()
        cursor = tmp_path / "cursor.json"
        t = EventTailer(
            events, self.APP, cursor_path=cursor, columnar_config=dcfg
        )
        path = events._file(self.APP, None)
        rec_line = json.dumps(
            _rate("torn", "i5", 2)
            .with_event_id("torn-col")
            .to_dict(for_api=False)
        )
        with open(path, "ab") as f:
            f.write(rec_line[:25].encode())  # writer died mid-append
        assert t.poll_columnar().n_events == 0
        with open(path, "ab") as f:
            f.write((rec_line[25:] + "\n").encode())
        batch = t.poll_columnar()
        assert _batch_entity_ids(batch) == ["torn"]  # exactly once
        assert _columnar_rows(batch) == 1
        assert t.poll_columnar().n_events == 0
        # restart across the healed line: still not re-delivered
        t2 = EventTailer(
            events, self.APP, cursor_path=cursor, columnar_config=dcfg
        )
        assert t2.poll_columnar().n_events == 0

    def test_read_cap_resumes_without_rereading(self, tmp_path, monkeypatch):
        """A capped read hands the decoder a clean newline prefix and
        parks the remainder behind an offset-only cursor: every line is
        delivered exactly once, in order, with no re-read."""
        from predictionio_tpu.realtime import tailer as tailer_mod

        _, dcfg = _columnar_configs()
        events = _jsonl_events(tmp_path)
        events.insert(_rate("pre", "i0", 1), self.APP)
        t = EventTailer(events, self.APP, columnar_config=dcfg)
        for k in range(40):
            events.insert(_rate(f"u{k}", "i1", 5), self.APP)
        monkeypatch.setattr(tailer_mod, "_READ_CAP", 1024)
        batch = t.poll_columnar()
        assert 0 < batch.n_events < 40
        cur = t._files[str(events._file(self.APP, None))]
        # the cap leaves an offset-only cursor (lineage unverifiable
        # until the remainder is consumed)
        assert cur.mtime_ns == -1 and cur.size == -1
        delivered = _batch_entity_ids(batch)
        polls = 1
        while True:
            got = t.poll_columnar()
            if not got.n_events:
                break
            delivered.extend(_batch_entity_ids(got))
            polls += 1
        assert polls > 1
        assert delivered == [f"u{k}" for k in range(40)]

    def test_decode_fault_falls_back_to_object_path(self, tmp_path):
        from predictionio_tpu import faults
        from predictionio_tpu.realtime import tailer as tailer_mod

        _, dcfg = _columnar_configs()
        events = _jsonl_events(tmp_path)
        events.insert(_rate("pre", "i0", 1), self.APP)
        t = EventTailer(events, self.APP, columnar_config=dcfg)
        for k in range(3):
            events.insert(_rate(f"u{k}", "i1", 4), self.APP)
        fb_before = tailer_mod._m_col_fallback.value()
        with faults.injected("tail.decode:always") as plan:
            batch = t.poll_columnar()
        assert plan.fire_count("tail.decode") == 1
        # identical delivery, just via the object parser
        assert _batch_entity_ids(batch) == ["u0", "u1", "u2"]
        assert _columnar_rows(batch) == 0
        assert tailer_mod._m_col_fallback.value() == fb_before + 3
        # and nothing is re-delivered once the fault clears
        assert t.poll_columnar().n_events == 0

    def test_counters_split_columnar_vs_fallback(self, tmp_path):
        from predictionio_tpu.realtime import tailer as tailer_mod

        events, _, t_col = self._attach_pair(_jsonl_events, tmp_path)
        col0 = tailer_mod._m_col_lines.value()
        fb0 = tailer_mod._m_col_fallback.value()
        _mixed_stream(events, self.APP)
        batch = t_col.poll_columnar()
        col_rows = _columnar_rows(batch)
        assert col_rows == 7  # 9 lines minus $set minus the bare rate
        assert tailer_mod._m_col_lines.value() == col0 + col_rows
        assert (
            tailer_mod._m_col_fallback.value()
            == fb0 + batch.n_events - col_rows
        )

    def test_decode_records_trace_span(self, tmp_path):
        from predictionio_tpu.obs import trace as obs_trace

        events, _, t_col = self._attach_pair(_jsonl_events, tmp_path)
        events.insert(_rate("u1", "i1", 5), self.APP)
        tr = obs_trace.Trace("poll")
        obs_trace.set_current_trace(tr)
        try:
            assert t_col.poll_columnar().n_events == 1
        finally:
            obs_trace.set_current_trace(None)
        assert any(name == "tail.decode" for name, *_ in tr.spans)

    def test_seq_backend_wraps_object_poll(self, tmp_path):
        """Backends without tail_files() keep working: poll_columnar
        degrades to the object poll, one Event segment."""
        _, dcfg = _columnar_configs()
        events = _memory_events(tmp_path)
        t = EventTailer(events, self.APP, columnar_config=dcfg)
        events.insert(_rate("u1", "i1", 5), self.APP)
        batch = t.poll_columnar()
        assert batch.n_events == 1 and _columnar_rows(batch) == 0
        assert _batch_entity_ids(batch) == ["u1"]


def test_columnar_foldin_vs_retrain(storage, tmp_path):
    """The retrain leg of the parity matrix: a columnar fold of a new
    user's ratings must rank like a from-scratch retrain that saw the
    same events (test_foldin_parity_vs_retrain pins the object path;
    the bit-parity tests above pin columnar == object; this closes the
    triangle directly)."""
    info = commands.app_new("ColFoldApp", storage=storage)
    app_id = info["id"]
    mem_events = storage.get_events()
    log_events = _jsonl_events(tmp_path)
    APP = 7

    def both(mk):
        mem_events.insert(mk(), app_id)
        log_events.insert(mk(), APP)

    for u in range(6):
        for i in range(8):
            both(lambda: _rate(f"a{u}", f"i{i}", 5 if i < 4 else 1))
            both(lambda: _rate(f"b{u}", f"i{i}", 1 if i < 4 else 5))
    base_model, _ = _train_model(
        storage, "ColFoldApp", "float32", False, "colfold"
    )
    assert "newu" not in base_model.user_index

    from predictionio_tpu.data.storage import colspans

    t = EventTailer(
        log_events, APP, columnar_config=colspans.DecodeConfig()
    )
    new_ratings = {"i0": 5, "i1": 5, "i4": 1, "i5": 1}
    for iid, v in new_ratings.items():
        both(lambda: _rate("newu", iid, v))
    batch = t.poll_columnar()
    assert batch.n_events == len(new_ratings)
    assert _columnar_rows(batch) == len(new_ratings)

    foldin = ALSFoldIn(log_events, APP, config=FoldInConfig())
    patched, stats = foldin.fold_in_columnar(base_model, batch)
    assert patched is not None and stats.users_added == 1

    retrained, _ = _train_model(
        storage, "ColFoldApp", "float32", False, "colfold2"
    )
    s_fold = _scores(patched, "newu")
    s_full = _scores(retrained, "newu")
    for s in (s_fold, s_full):
        assert min(s["i2"], s["i3"]) > max(s["i6"], s["i7"]), s
    top3 = lambda s: {  # noqa: E731
        i for i, _ in sorted(s.items(), key=lambda kv: -kv[1])[:3]
    }
    assert len(top3(s_fold) & top3(s_full)) >= 2

    def rmse(s):
        err = [s[iid] - v for iid, v in new_ratings.items()]
        return float(np.sqrt(np.mean(np.square(err))))

    assert rmse(s_fold) <= rmse(s_full) + RMSE_TOL["float32"], (
        rmse(s_fold),
        rmse(s_full),
    )
