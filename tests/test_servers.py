"""Event/engine/admin server HTTP tests (mirrors reference EventServiceSpec,
SegmentIOAuthSpec, AdminAPISpec — real sockets on localhost)."""

from __future__ import annotations

import json
import threading
import urllib.error
import urllib.request

import pytest

from predictionio_tpu.cli import commands
from predictionio_tpu.data.storage import AccessKey


def http(method, url, body=None, headers=None):
    data = None
    if body is not None:
        data = body if isinstance(body, bytes) else json.dumps(body).encode()
    req = urllib.request.Request(url, data=data, method=method)
    for k, v in (headers or {}).items():
        req.add_header(k, v)
    try:
        with urllib.request.urlopen(req, timeout=10) as resp:
            return resp.status, json.loads(resp.read() or b"{}")
    except urllib.error.HTTPError as e:
        payload = e.read()
        try:
            return e.code, json.loads(payload or b"{}")
        except json.JSONDecodeError:
            return e.code, {"raw": payload.decode()}


@pytest.fixture()
def event_server(storage):
    from predictionio_tpu.server.event_server import EventServer

    info = commands.app_new("EventApp", storage=storage)
    server = EventServer(storage=storage, host="127.0.0.1", port=0, stats=True)
    port = server.start()
    yield {
        "base": f"http://127.0.0.1:{port}",
        "key": info["access_key"],
        "app_id": info["id"],
        "storage": storage,
        "server": server,
    }
    server.stop()


PAST = 1 << 30  # rows of a catalog whose stored scores are past retrieval._UNCUT
EVENT = {
    "event": "rate",
    "entityType": "user",
    "entityId": "u1",
    "targetEntityType": "item",
    "targetEntityId": "i1",
    "properties": {"rating": 4.5},
}


class TestEventServer:
    def test_welcome(self, event_server):
        status, body = http("GET", event_server["base"] + "/")
        assert status == 200 and body["status"] == "alive"

    def test_create_and_get_event(self, event_server):
        base, key = event_server["base"], event_server["key"]
        status, body = http("POST", f"{base}/events.json?accessKey={key}", EVENT)
        assert status == 201 and "eventId" in body
        eid = body["eventId"]
        status, body = http("GET", f"{base}/events/{eid}.json?accessKey={key}")
        assert status == 200
        assert body["entityId"] == "u1"
        assert body["properties"]["rating"] == 4.5
        # query listing
        status, body = http("GET", f"{base}/events.json?accessKey={key}")
        assert status == 200 and len(body) == 1
        # delete
        status, _ = http("DELETE", f"{base}/events/{eid}.json?accessKey={key}")
        assert status == 200
        status, _ = http("GET", f"{base}/events/{eid}.json?accessKey={key}")
        assert status == 404

    def test_auth_required(self, event_server):
        base = event_server["base"]
        status, _ = http("POST", f"{base}/events.json", EVENT)
        assert status == 401
        status, _ = http("POST", f"{base}/events.json?accessKey=wrong", EVENT)
        assert status == 401

    def test_basic_auth_key(self, event_server):
        import base64

        base, key = event_server["base"], event_server["key"]
        cred = base64.b64encode(f"{key}:".encode()).decode()
        status, _ = http(
            "POST",
            f"{base}/events.json",
            EVENT,
            headers={"Authorization": f"Basic {cred}"},
        )
        assert status == 201

    def test_invalid_event_rejected(self, event_server):
        base, key = event_server["base"], event_server["key"]
        bad = dict(EVENT, event="$unset", properties={})
        bad.pop("targetEntityType")
        bad.pop("targetEntityId")
        status, body = http("POST", f"{base}/events.json?accessKey={key}", bad)
        assert status == 400

    def test_event_name_allowlist(self, event_server):
        storage = event_server["storage"]
        restricted = storage.get_metadata_access_keys().insert(
            AccessKey("", appid=event_server["app_id"], events=["view"])
        )
        base = event_server["base"]
        status, _ = http("POST", f"{base}/events.json?accessKey={restricted}", EVENT)
        assert status == 403
        view = dict(EVENT, event="view")
        status, _ = http("POST", f"{base}/events.json?accessKey={restricted}", view)
        assert status == 201

    def test_batch_limit_50(self, event_server):
        base, key = event_server["base"], event_server["key"]
        batch = [EVENT] * 51
        status, body = http("POST", f"{base}/batch/events.json?accessKey={key}", batch)
        assert status == 413
        assert body["error"] == "BatchTooLarge"
        assert "PIO_BATCH_MAX_EVENTS" in body["message"]
        batch = [EVENT, dict(EVENT, event="")]  # second invalid
        status, body = http("POST", f"{base}/batch/events.json?accessKey={key}", batch)
        assert status == 200
        assert body[0]["status"] == 201
        assert body[1]["status"] == 400

    def test_batch_limit_knob(self, storage, monkeypatch):
        from predictionio_tpu.server.event_server import EventServer

        monkeypatch.setenv("PIO_BATCH_MAX_EVENTS", "3")
        info = commands.app_new("KnobApp", storage=storage)
        server = EventServer(storage=storage, host="127.0.0.1", port=0)
        port = server.start()
        try:
            base, key = f"http://127.0.0.1:{port}", info["access_key"]
            status, _ = http(
                "POST", f"{base}/batch/events.json?accessKey={key}", [EVENT] * 3
            )
            assert status == 200
            status, body = http(
                "POST", f"{base}/batch/events.json?accessKey={key}", [EVENT] * 4
            )
            assert status == 413
            assert body["error"] == "BatchTooLarge"
        finally:
            server.stop()

    def test_channel_auth(self, event_server):
        base, key = event_server["base"], event_server["key"]
        status, _ = http(
            "POST", f"{base}/events.json?accessKey={key}&channel=nope", EVENT
        )
        assert status == 401
        commands.channel_new("EventApp", "live", storage=event_server["storage"])
        status, _ = http(
            "POST", f"{base}/events.json?accessKey={key}&channel=live", EVENT
        )
        assert status == 201
        # channel isolation: default channel has no events
        status, body = http("GET", f"{base}/events.json?accessKey={key}")
        assert status == 404

    def test_stats(self, event_server):
        base, key = event_server["base"], event_server["key"]
        http("POST", f"{base}/events.json?accessKey={key}", EVENT)
        status, body = http("GET", f"{base}/stats.json?accessKey={key}")
        assert status == 200
        assert body["eventCount"]["rate"] == 1

    def test_segmentio_webhook(self, event_server):
        base, key = event_server["base"], event_server["key"]
        payload = {
            "version": "2",
            "type": "track",
            "userId": "sio-user",
            "event": "Signed Up",
            "properties": {"plan": "Pro"},
            "timestamp": "2020-01-02T03:04:05.000Z",
        }
        status, body = http(
            "POST", f"{base}/webhooks/segmentio.json?accessKey={key}", payload
        )
        assert status == 201
        status, events = http(
            "GET", f"{base}/events.json?accessKey={key}&entityId=sio-user"
        )
        assert status == 200
        assert events[0]["event"] == "track"
        assert events[0]["properties"]["event"] == "Signed Up"

    def test_mailchimp_webhook_form(self, event_server):
        from urllib.parse import urlencode

        base, key = event_server["base"], event_server["key"]
        form = urlencode(
            {
                "type": "subscribe",
                "fired_at": "2009-03-26 21:35:57",
                "data[id]": "8a25ff1d98",
                "data[list_id]": "a6b5da1054",
                "data[email]": "api@mailchimp.com",
            }
        ).encode()
        status, body = http(
            "POST", f"{base}/webhooks/mailchimp.form?accessKey={key}", form
        )
        assert status == 201
        status, events = http(
            "GET", f"{base}/events.json?accessKey={key}&entityId=8a25ff1d98"
        )
        assert events[0]["event"] == "subscribe"
        assert events[0]["targetEntityId"] == "a6b5da1054"

    def test_unknown_webhook(self, event_server):
        base, key = event_server["base"], event_server["key"]
        status, _ = http("POST", f"{base}/webhooks/unknown.json?accessKey={key}", {})
        assert status == 404

    def test_plugins_json_inventory(self, event_server):
        """GET /plugins.json groups loaded plugins by interception type
        (reference EventServer.scala:156-177)."""
        base = event_server["base"]
        server = event_server["server"]
        from predictionio_tpu.server import plugins as plugin_mod

        class Sniffy(plugin_mod.EventServerPlugin):
            plugin_name = "sniffy"
            plugin_description = "records things"
            plugin_type = plugin_mod.INPUT_SNIFFER

        server.plugins.append(Sniffy())
        status, body = http("GET", f"{base}/plugins.json")
        assert status == 200
        entry = body["plugins"]["inputsniffers"]["sniffy"]
        assert entry["description"] == "records things"
        assert entry["class"].endswith("Sniffy")
        assert body["plugins"]["inputblockers"] == {}

    def test_plugin_rest_dispatch(self, event_server):
        """/plugins/<type>/<name>/<args...> authenticates, then hands the
        sub-path + app context to the plugin's handle_rest (reference
        EventServer.scala:178-196)."""
        base, key = event_server["base"], event_server["key"]
        server = event_server["server"]
        from predictionio_tpu.server import plugins as plugin_mod

        class Echo(plugin_mod.EventServerPlugin):
            plugin_name = "echo"
            plugin_type = plugin_mod.INPUT_SNIFFER

            def handle_rest(self, path, params):
                return {"path": path, "appId": params.get("appId"),
                        "q": params.get("q")}

        server.plugins.append(Echo())
        # auth required
        status, _ = http("GET", f"{base}/plugins/inputsniffer/echo/a/b")
        assert status == 401
        status, body = http(
            "GET",
            f"{base}/plugins/inputsniffer/echo/a/b?accessKey={key}&q=7",
        )
        assert status == 200
        assert body == {
            "path": "a/b",
            "appId": str(event_server["app_id"]),
            "q": "7",
        }
        # POST dispatches too, with or without trailing args
        status, body = http(
            "POST", f"{base}/plugins/inputsniffer/echo?accessKey={key}", {}
        )
        assert status == 200 and body["path"] == ""
        # wrong type or unknown name -> 404
        status, _ = http(
            "GET", f"{base}/plugins/inputblocker/echo?accessKey={key}"
        )
        assert status == 404
        status, _ = http(
            "GET", f"{base}/plugins/bogus/echo?accessKey={key}"
        )
        assert status == 404

    def test_plugin_rest_error_does_not_kill_server(self, event_server):
        base, key = event_server["base"], event_server["key"]
        server = event_server["server"]
        from predictionio_tpu.server import plugins as plugin_mod

        class Boom(plugin_mod.EventServerPlugin):
            plugin_name = "boom"
            plugin_type = plugin_mod.INPUT_BLOCKER

            def handle_rest(self, path, params):
                raise RuntimeError("kapow")

        server.plugins.append(Boom())
        status, body = http(
            "GET", f"{base}/plugins/inputblocker/boom?accessKey={key}"
        )
        assert status == 500 and "kapow" in body["message"]
        status, _ = http("GET", f"{base}/")
        assert status == 200


@pytest.fixture()
def deployed_engine(storage):
    """Train the recommendation engine and deploy it on a local port."""
    import numpy as np

    from predictionio_tpu.core import EngineParams
    from predictionio_tpu.core.workflow import run_train
    from predictionio_tpu.data.event import Event
    from predictionio_tpu.models import recommendation as rec
    from predictionio_tpu.server.engine_server import EngineServer

    info = commands.app_new("ServeApp", storage=storage)
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
        datasource=("", rec.DataSourceParams(app_name="ServeApp")),
        algorithms=[("als", rec.ALSAlgorithmParams(rank=4, num_iterations=3))],
    )
    run_train(engine, ep, engine_id="serve", storage=storage)
    instance = storage.get_metadata_engine_instances().get_latest_completed(
        "serve", "0", "default"
    )
    server = EngineServer(
        engine, instance, storage=storage, host="127.0.0.1", port=0,
        server_key="secret",
    )
    port = server.start()
    yield {
        "base": f"http://127.0.0.1:{port}",
        "server": server,
        "storage": storage,
        "engine": engine,
        "ep": ep,
    }
    server.stop()


class TestEngineServer:
    def test_status_page(self, deployed_engine):
        status, body = http("GET", deployed_engine["base"] + "/")
        assert status == 200
        assert body["status"] == "alive"
        assert body["requestCount"] == 0

    def test_query(self, deployed_engine):
        base = deployed_engine["base"]
        status, body = http("POST", f"{base}/queries.json", {"user": "u1", "num": 3})
        assert status == 200
        assert len(body["itemScores"]) == 3
        status, page = http("GET", base + "/")
        assert page["requestCount"] == 1
        assert page["lastServingSec"] > 0

    def test_query_unknown_user(self, deployed_engine):
        status, body = http(
            "POST", deployed_engine["base"] + "/queries.json", {"user": "zz"}
        )
        assert status == 200 and body["itemScores"] == []

    def test_bad_query(self, deployed_engine):
        status, body = http(
            "POST", deployed_engine["base"] + "/queries.json", [1, 2]
        )
        assert status == 400

    def test_reload_hot_swaps_latest(self, deployed_engine):
        from predictionio_tpu.core.workflow import run_train

        base = deployed_engine["base"]
        old_id = deployed_engine["server"].instance.id
        # unauthorized without key
        status, _ = http("POST", f"{base}/reload")
        assert status == 401
        # train a new instance, then reload with key
        run_train(
            deployed_engine["engine"], deployed_engine["ep"], engine_id="serve",
            storage=deployed_engine["storage"],
        )
        status, _ = http("POST", f"{base}/reload?accessKey=secret")
        assert status == 200
        assert deployed_engine["server"].instance.id != old_id

    def test_reload_onto_int8_instance_serves(self, deployed_engine):
        """An int8-trained instance round-trips through persistence and
        /reload: the hot-swapped model carries quantized factors + scales
        and answers queries."""
        import numpy as np

        from predictionio_tpu.core import EngineParams
        from predictionio_tpu.core.workflow import run_train
        from predictionio_tpu.models import recommendation as rec

        base = deployed_engine["base"]
        old_id = deployed_engine["server"].instance.id
        ep_i8 = EngineParams(
            datasource=("", rec.DataSourceParams(app_name="ServeApp")),
            algorithms=[(
                "als",
                rec.ALSAlgorithmParams(
                    rank=4, num_iterations=3, storage_dtype="int8"
                ),
            )],
        )
        run_train(
            deployed_engine["engine"], ep_i8, engine_id="serve",
            storage=deployed_engine["storage"],
        )
        status, _ = http("POST", f"{base}/reload?accessKey=secret")
        assert status == 200
        server = deployed_engine["server"]
        assert server.instance.id != old_id
        [model] = server.models
        assert model.user_factors.dtype == np.int8
        assert model.user_scales is not None
        status, body = http("POST", f"{base}/queries.json", {"user": "u1", "num": 3})
        assert status == 200
        assert len(body["itemScores"]) == 3

    def test_plugins_endpoint(self, deployed_engine):
        status, body = http("GET", deployed_engine["base"] + "/plugins.json")
        assert status == 200 and "plugins" in body

    def test_status_page_html_for_browsers(self, deployed_engine):
        """Accept: text/html gets the reference's HTML status render
        (CreateServer.scala:443-467); API clients keep JSON."""
        import urllib.request

        req = urllib.request.Request(
            deployed_engine["base"] + "/",
            headers={"Accept": "text/html,application/xhtml+xml"},
        )
        with urllib.request.urlopen(req, timeout=10) as resp:
            assert resp.status == 200
            assert resp.headers["Content-Type"].startswith("text/html")
            page = resp.read().decode()
        assert "Engine:" in page and "Algorithms" in page
        assert "ALSAlgorithm" in page or "als" in page

    def test_serving_error_posts_remote_log(self, storage, deployed_engine):
        """A failing query POSTs logPrefix + {engineInstance, message} to
        log_url (CreateServer.scala:422-433, :596-618)."""
        import threading

        from predictionio_tpu.server.http import HTTPApp, Response, Router

        received: list[bytes] = []
        got_one = threading.Event()
        catcher_router = Router()

        @catcher_router.route("POST", "/log")
        def catch(request):
            received.append(request.body)
            got_one.set()
            return Response.json({})

        catcher = HTTPApp(catcher_router, host="127.0.0.1", port=0)
        log_port = catcher.start()
        server = deployed_engine["server"]
        server.log_url = f"http://127.0.0.1:{log_port}/log"
        server.log_prefix = "PIO: "
        try:
            status, _ = http(
                "POST",
                deployed_engine["base"] + "/queries.json",
                {"user": "u1", "num": "not-a-number"},
            )
            assert status in (400, 500)
            assert got_one.wait(timeout=10), "remote log never arrived"
            body = received[0].decode()
            assert body.startswith("PIO: ")
            payload = json.loads(body[len("PIO: "):])
            assert payload["engineInstance"]["id"] == server.instance.id
            assert "Query" in payload["message"]
        finally:
            server.log_url = None
            catcher.stop()


class TestMicroBatchedServing:
    def test_batched_results_match_per_request(self, storage, deployed_engine):
        """Concurrent queries through a batch-window server must return
        exactly what per-request serving returns, while actually
        coalescing device calls (batch_predict invocations < queries)."""
        import threading as _threading

        from predictionio_tpu.server.engine_server import EngineServer

        base_server = deployed_engine["server"]
        engine = deployed_engine["engine"]
        inst = base_server.instance
        batched = EngineServer(
            engine, inst, storage=deployed_engine["storage"],
            host="127.0.0.1", port=0, batch_window_ms=25.0,
            dispatch_cost_s=10.0,  # pin window-wait mode (probe-independent)
        )
        port = batched.start()
        algo = batched.algorithms[0]
        calls = []
        real_bp = type(algo).batch_predict
        # expected responses BEFORE patching the class: the base server
        # shares the algorithm class, and single-query predict now
        # delegates to batch_predict (for batched/unbatched parity), so
        # patching first would count the base server's calls too
        users = [f"u{i}" for i in range(8)]
        expected = {
            u: http(
                "POST",
                deployed_engine["base"] + "/queries.json",
                {"user": u, "num": 3},
            )[1]
            for u in users
        }

        def counting_bp(self_, model, queries):
            calls.append(len(queries))
            return real_bp(self_, model, queries)

        type(algo).batch_predict = counting_bp
        try:
            results: dict = {}

            def one(u):
                status, body = http(
                    "POST", f"http://127.0.0.1:{port}/queries.json",
                    {"user": u, "num": 3},
                )
                results[u] = (status, body)

            threads = [_threading.Thread(target=one, args=(u,)) for u in users]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=30)
            for u in users:
                status, body = results[u]
                assert status == 200
                want = expected[u]
                # identical rankings; scores equal up to batched-matmul
                # accumulation-order roundoff
                assert [s["item"] for s in body["itemScores"]] == [
                    s["item"] for s in want["itemScores"]
                ], u
                for got_s, want_s in zip(
                    body["itemScores"], want["itemScores"]
                ):
                    assert abs(got_s["score"] - want_s["score"]) < 1e-4
            assert sum(calls) >= len(users)
            assert len(calls) < len(users), (
                f"no batching happened: {len(calls)} calls for {len(users)}"
            )
            # bookkeeping counted every query
            assert batched.status()["requestCount"] == len(users)
        finally:
            type(algo).batch_predict = real_bp
            batched.stop()

    def test_batching_amortizes_per_call_dispatch(self, storage, deployed_engine):
        """The design claim: when each DEVICE CALL carries a fixed,
        device-serialized cost (remote-TPU dispatch ~130ms), batching N
        concurrent queries into one call multiplies throughput.
        Simulated with an 80ms per-call tax behind a lock (device calls
        serialize on the device queue, unlike a parallel sleep)."""
        import threading as _threading
        import time as _time

        from predictionio_tpu.server.engine_server import EngineServer

        engine = deployed_engine["engine"]
        inst = deployed_engine["server"].instance
        device_lock = _threading.Lock()

        def run(batch_window_ms):
            server = EngineServer(
                engine, inst, storage=deployed_engine["storage"],
                host="127.0.0.1", port=0, batch_window_ms=batch_window_ms,
                dispatch_cost_s=10.0,  # pin window-wait mode
            )
            algo = server.algorithms[0]
            real_p, real_bp = type(algo).predict, type(algo).batch_predict

            def taxed_predict(self_, model, q):
                with device_lock:
                    _time.sleep(0.08)
                return real_p(self_, model, q)

            def taxed_batch(self_, model, queries):
                with device_lock:  # per CALL, like serialized dispatch
                    _time.sleep(0.08)
                return real_bp(self_, model, queries)

            type(algo).predict = taxed_predict
            type(algo).batch_predict = taxed_batch
            port = server.start()
            try:
                users = [f"u{i}" for i in range(8)]

                def round_trip():
                    threads = [
                        _threading.Thread(
                            target=http,
                            args=("POST",
                                  f"http://127.0.0.1:{port}/queries.json",
                                  {"user": u, "num": 3}),
                        )
                        for u in users
                    ]
                    for t in threads:
                        t.start()
                    for t in threads:
                        t.join(timeout=60)

                round_trip()  # warm: jit compiles outside the timing
                t0 = _time.perf_counter()
                round_trip()
                return _time.perf_counter() - t0
            finally:
                type(algo).predict = real_p
                type(algo).batch_predict = real_bp
                server.stop()

        unbatched = run(0.0)
        batched = run(40.0)
        # 8 concurrent x 80ms serialized per-call tax: unbatched pays
        # ~8 calls (~0.64s); batched ~1-2 calls + the 40ms window
        assert batched < unbatched / 2, (unbatched, batched)

    def test_bypass_mode_lone_query_skips_window(self, storage, deployed_engine):
        """Load-aware policy: the batcher stays engaged on fast-dispatch
        attachments (that's where BENCH_r04's regression came from — the
        old dispatch-cost floor disengaged it), but a lone query takes
        the single-item fast path and must NOT pay the configured
        window (the round-4 foot-gun: enabling batching on a
        fast-dispatch attachment made serving worse)."""
        import time as _time

        from predictionio_tpu.server.engine_server import EngineServer

        server = EngineServer(
            deployed_engine["engine"], deployed_engine["server"].instance,
            storage=deployed_engine["storage"], host="127.0.0.1", port=0,
            batch_window_ms=500.0, dispatch_cost_s=0.0,  # fast dispatch
        )
        # always engaged now; lone-query latency is protected by the
        # single-item fast path, not by disengaging
        assert server.batcher is not None and server.batcher.engaged
        port = server.start()
        try:
            http("POST", f"http://127.0.0.1:{port}/queries.json",
                 {"user": "u1", "num": 3})  # warm
            t0 = _time.perf_counter()
            status, _body = http(
                "POST", f"http://127.0.0.1:{port}/queries.json",
                {"user": "u1", "num": 3},
            )
            took = _time.perf_counter() - t0
            assert status == 200
            assert took < 0.25, (
                f"lone query took {took:.3f}s with a 0.5s window: the "
                "bypass did not kick in"
            )
        finally:
            server.stop()

    def test_bypass_mode_still_batches_under_serialized_dispatch(
        self, storage, deployed_engine
    ):
        """With the window bypassed, batches must still form naturally:
        requests that queue behind an in-flight (serialized) device call
        coalesce into the next call — the ~N x win survives without any
        configured wait."""
        import threading as _threading
        import time as _time

        from predictionio_tpu.server.engine_server import EngineServer

        engine = deployed_engine["engine"]
        inst = deployed_engine["server"].instance
        device_lock = _threading.Lock()
        server = EngineServer(
            engine, inst, storage=deployed_engine["storage"],
            host="127.0.0.1", port=0,
            # 5 ms dispatch: over the 1 ms engage floor, under the
            # 10 ms window -> drain-only natural batching
            batch_window_ms=10.0, dispatch_cost_s=0.005,
        )
        assert server.batcher.engaged and not server.batcher._window_wait
        algo = server.algorithms[0]
        real_bp = type(algo).batch_predict
        calls = []

        def taxed_batch(self_, model, queries):
            with device_lock:  # per CALL, like serialized dispatch
                _time.sleep(0.08)
            calls.append(len(queries))
            return real_bp(self_, model, queries)

        type(algo).batch_predict = taxed_batch
        port = server.start()
        try:
            users = [f"u{i}" for i in range(8)]

            def round_trip():
                threads = [
                    _threading.Thread(
                        target=http,
                        args=("POST", f"http://127.0.0.1:{port}/queries.json",
                              {"user": u, "num": 3}),
                    )
                    for u in users
                ]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join(timeout=60)

            round_trip()  # warm: jit compiles outside the measurement
            calls.clear()
            round_trip()
            # 8 concurrent queries behind 80ms serialized calls: natural
            # batching must coalesce them into far fewer calls
            # (sum(calls) exceeds 8: batches pad to power-of-two sizes)
            assert len(calls) <= 4, (
                f"no natural batching: {len(calls)} calls for {len(users)}"
            )
        finally:
            type(algo).batch_predict = real_bp
            server.stop()

    def test_bad_query_does_not_poison_batchmates(self, storage, deployed_engine):
        import threading as _threading

        from predictionio_tpu.server.engine_server import EngineServer

        batched = EngineServer(
            deployed_engine["engine"], deployed_engine["server"].instance,
            storage=deployed_engine["storage"], host="127.0.0.1", port=0,
            batch_window_ms=25.0, dispatch_cost_s=10.0,
        )
        port = batched.start()
        try:
            results: dict = {}

            def one(name, payload):
                results[name] = http(
                    "POST", f"http://127.0.0.1:{port}/queries.json", payload
                )

            threads = [
                _threading.Thread(target=one, args=("good", {"user": "u1", "num": 3})),
                _threading.Thread(target=one, args=("bad", {"user": "u2", "num": "x"})),
                _threading.Thread(target=one, args=("good2", {"user": "u3", "num": 2})),
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=30)
            assert results["good"][0] == 200
            assert len(results["good"][1]["itemScores"]) == 3
            assert results["good2"][0] == 200
            assert results["bad"][0] in (400, 500)
        finally:
            batched.stop()


class TestDashboardCors:
    def test_allow_origin_and_preflight(self, storage):
        """Dashboard responses carry Access-Control-Allow-Origin: * and
        OPTIONS preflights are answered (reference CorsSupport.scala)."""
        import urllib.request

        from predictionio_tpu.server.dashboard import Dashboard

        dash = Dashboard(storage=storage, host="127.0.0.1", port=0)
        port = dash.start()
        base = f"http://127.0.0.1:{port}"
        try:
            with urllib.request.urlopen(base + "/", timeout=10) as resp:
                assert resp.headers["Access-Control-Allow-Origin"] == "*"
            req = urllib.request.Request(base + "/", method="OPTIONS")
            with urllib.request.urlopen(req, timeout=10) as resp:
                assert resp.status == 200
                assert "GET" in resp.headers["Access-Control-Allow-Methods"]
                assert resp.headers["Access-Control-Allow-Origin"] == "*"
        finally:
            dash.stop()


class TestAdminServer:
    def test_app_crud_over_http(self, storage):
        from predictionio_tpu.server.admin_server import AdminServer

        server = AdminServer(storage=storage, host="127.0.0.1", port=0)
        port = server.start()
        base = f"http://127.0.0.1:{port}"
        try:
            status, body = http("GET", base + "/")
            assert body["status"] == "alive"
            status, body = http("POST", f"{base}/cmd/app", {"name": "AdminApp"})
            assert status == 200 and body["status"] == 1 and body["accessKey"]
            status, body = http("GET", f"{base}/cmd/app")
            assert [a["name"] for a in body["apps"]] == ["AdminApp"]
            status, body = http("POST", f"{base}/cmd/app", {"name": "AdminApp"})
            assert status == 400
            status, body = http("DELETE", f"{base}/cmd/app/AdminApp/data")
            assert body["status"] == 1
            status, body = http("DELETE", f"{base}/cmd/app/AdminApp")
            assert body["status"] == 1
            status, body = http("GET", f"{base}/cmd/app")
            assert body["apps"] == []
        finally:
            server.stop()


class TestFeedbackLoop:
    def test_predict_event_posted_back(self, storage):
        """Deploy with feedback: a query must produce a pio_pr predict
        event in the event store (reference CreateServer.scala:514-577)."""
        import time

        from predictionio_tpu.server.event_server import EventServer

        # reuse deployed_engine wiring manually to control feedback flags
        import numpy as np

        from predictionio_tpu.core import EngineParams
        from predictionio_tpu.core.workflow import run_train
        from predictionio_tpu.data.event import Event
        from predictionio_tpu.models import recommendation as rec
        from predictionio_tpu.server.engine_server import EngineServer

        info = commands.app_new("FbApp", storage=storage)
        for u in range(6):
            for i in range(4):
                storage.get_events().insert(
                    Event(
                        event="rate", entity_type="user", entity_id=f"u{u}",
                        target_entity_type="item", target_entity_id=f"i{i}",
                        properties={"rating": float((u + i) % 5 + 1)},
                    ),
                    info["id"],
                )
        es = EventServer(storage=storage, host="127.0.0.1", port=0)
        es_port = es.start()
        engine = rec.engine()
        ep = EngineParams(
            datasource=("", rec.DataSourceParams(app_name="FbApp")),
            algorithms=[("als", rec.ALSAlgorithmParams(rank=2, num_iterations=2))],
        )
        run_train(engine, ep, engine_id="fb", storage=storage)
        instance = storage.get_metadata_engine_instances().get_latest_completed(
            "fb", "0", "default"
        )
        server = EngineServer(
            engine, instance, storage=storage, host="127.0.0.1", port=0,
            feedback=True,
            event_server_url=f"http://127.0.0.1:{es_port}",
            access_key=info["access_key"],
        )
        port = server.start()
        try:
            status, body = http(
                "POST", f"http://127.0.0.1:{port}/queries.json", {"user": "u1"}
            )
            assert status == 200 and body["prId"]
            deadline = time.time() + 5
            feedback_events = []
            while time.time() < deadline and not feedback_events:
                feedback_events = storage.get_events().find(
                    info["id"], entity_type="pio_pr"
                )
                time.sleep(0.05)
            assert feedback_events, "no feedback event arrived"
            fe = feedback_events[0]
            assert fe.event == "predict"
            assert fe.pr_id == body["prId"]
            assert fe.properties["query"]["user"] == "u1"
        finally:
            server.stop()
            es.stop()


class TestReloadUnderLoad:
    def test_queries_survive_concurrent_reloads(self, deployed_engine):
        """Hot-swap must never surface a torn model to in-flight queries:
        hammer /queries.json from worker threads while /reload swaps
        instances; every response must be a well-formed 200."""
        import concurrent.futures
        from predictionio_tpu.core.workflow import run_train

        base = deployed_engine["base"]
        # a second completed instance so reload has something to swap to
        run_train(
            deployed_engine["engine"], deployed_engine["ep"], engine_id="serve",
            storage=deployed_engine["storage"],
        )
        stop = threading.Event()
        errors: list = []

        def hammer():
            while not stop.is_set():
                try:
                    status, body = http(
                        "POST", f"{base}/queries.json", {"user": "u1", "num": 2}
                    )
                    if status != 200 or "itemScores" not in body:
                        errors.append((status, body))
                except Exception as e:  # noqa: BLE001 - collect, then fail
                    errors.append(repr(e))

        with concurrent.futures.ThreadPoolExecutor(4) as pool:
            futures = [pool.submit(hammer) for _ in range(3)]
            try:
                for _ in range(10):
                    status, _ = http(
                        "POST", f"{base}/reload?accessKey=secret"
                    )
                    assert status == 200
            finally:
                stop.set()  # or a failed assert deadlocks pool shutdown
            for f in futures:
                f.result(timeout=30)
        assert not errors, errors[:3]


class TestHTTPParserFraming:
    """The hand-rolled HTTP/1.1 parser must never desync a keep-alive
    stream: unsupported framings are rejected with Connection: close."""

    def _app(self):
        from predictionio_tpu.server.http import HTTPApp, Response, Router

        router = Router()

        @router.route("POST", "/echo")
        def echo(request):
            return Response.json({"n": len(request.body)})

        return HTTPApp(router, host="127.0.0.1", port=0)

    def test_chunked_request_rejected(self):
        import socket

        app = self._app()
        port = app.start(background=True)
        try:
            s = socket.create_connection(("127.0.0.1", port))
            s.sendall(
                b"POST /echo HTTP/1.1\r\nHost: x\r\n"
                b"Transfer-Encoding: chunked\r\n\r\n5\r\nhello\r\n0\r\n\r\n"
            )
            assert s.recv(65536).decode().startswith("HTTP/1.1 501")
        finally:
            app.stop()

    def test_negative_content_length_rejected(self):
        import socket

        app = self._app()
        port = app.start(background=True)
        try:
            s = socket.create_connection(("127.0.0.1", port))
            s.sendall(
                b"POST /echo HTTP/1.1\r\nHost: x\r\n"
                b"Content-Length: -5\r\n\r\nhello"
            )
            assert s.recv(65536).decode().startswith("HTTP/1.1 400")
        finally:
            app.stop()

    def test_endless_header_lines_capped(self):
        import socket

        app = self._app()
        port = app.start(background=True)
        try:
            s = socket.create_connection(("127.0.0.1", port))
            s.sendall(b"POST /echo HTTP/1.1\r\n" + b"x: y\r\n" * 300)
            assert s.recv(65536).decode().startswith("HTTP/1.1 431")
        finally:
            app.stop()

    def test_conflicting_duplicate_content_length_rejected(self):
        import socket

        app = self._app()
        port = app.start(background=True)
        try:
            s = socket.create_connection(("127.0.0.1", port))
            s.sendall(
                b"POST /echo HTTP/1.1\r\nHost: x\r\n"
                b"Content-Length: 5\r\nContent-Length: 11\r\n\r\nhello"
            )
            assert s.recv(65536).decode().startswith("HTTP/1.1 400")
        finally:
            app.stop()

    def test_identical_duplicate_content_length_accepted(self):
        import json
        import socket

        app = self._app()
        port = app.start(background=True)
        try:
            s = socket.create_connection(("127.0.0.1", port))
            s.sendall(
                b"POST /echo HTTP/1.1\r\nHost: x\r\n"
                b"Content-Length: 5\r\nContent-Length: 5\r\n\r\nhello"
            )
            raw = s.recv(65536).decode()
            assert raw.startswith("HTTP/1.1 200")
            assert json.loads(raw.split("\r\n\r\n", 1)[1]) == {"n": 5}
        finally:
            app.stop()

    def test_pipelined_request_after_reject_not_parsed(self):
        """A smuggled second request riding behind a rejected framing
        must never be dispatched: the 400 closes the connection and the
        trailing bytes die with it."""
        import socket

        app = self._app()
        port = app.start(background=True)
        try:
            s = socket.create_connection(("127.0.0.1", port))
            s.sendall(
                b"POST /echo HTTP/1.1\r\nHost: x\r\n"
                b"Content-Length: 5\r\nContent-Length: 11\r\n\r\n"
                b"hello"
                b"POST /echo HTTP/1.1\r\nHost: x\r\nContent-Length: 0\r\n\r\n"
            )
            raw = s.recv(65536).decode()
            assert raw.startswith("HTTP/1.1 400")
            assert "Connection: close" in raw
            # only the 400 ever comes back; the pipelined request is dead
            assert raw.count("HTTP/1.1") == 1
            s.settimeout(5)
            assert s.recv(65536) == b""  # server closed
        finally:
            app.stop()

    def test_slow_client_read_timeout_frees_connection(self):
        """A client that stalls mid-request is cut loose after
        read_timeout instead of pinning a worker thread forever."""
        import socket
        import time

        from predictionio_tpu.server.http import HTTPApp, Response, Router

        router = Router()

        @router.route("POST", "/echo")
        def echo(request):
            return Response.json({"n": len(request.body)})

        app = HTTPApp(router, host="127.0.0.1", port=0, read_timeout=0.5)
        port = app.start(background=True)
        try:
            s = socket.create_connection(("127.0.0.1", port))
            # headers promise a body that never arrives
            s.sendall(b"POST /echo HTTP/1.1\r\nHost: x\r\nContent-Length: 5\r\n\r\n")
            s.settimeout(10)
            start = time.monotonic()
            assert s.recv(65536) == b""  # server dropped us, no response
            assert time.monotonic() - start < 8
            # server is still healthy for well-behaved clients
            s2 = socket.create_connection(("127.0.0.1", port))
            s2.sendall(
                b"POST /echo HTTP/1.1\r\nHost: x\r\nContent-Length: 2\r\n\r\nhi"
            )
            assert s2.recv(65536).decode().startswith("HTTP/1.1 200")
        finally:
            app.stop()


class TestWorkerProcesses:
    def test_eventserver_workers_share_port_without_loss(self, tmp_path):
        """`pio eventserver --workers N`: N processes bind the same port
        via SO_REUSEPORT; ingest across them must lose nothing and
        duplicate nothing (storage appends are cross-process flocked).
        This box is single-core so throughput cannot scale here — the
        test is about correctness of the shared-port worker set."""
        import os
        import subprocess
        import sys
        import time
        import urllib.request

        env = dict(
            os.environ,
            PIO_STORAGE_SOURCES_DB_TYPE="sqlite",
            PIO_STORAGE_SOURCES_DB_PATH=str(tmp_path / "pio.db"),
            PIO_STORAGE_SOURCES_LOG_TYPE="jsonl",
            PIO_STORAGE_SOURCES_LOG_PATH=str(tmp_path / "ev"),
            PIO_STORAGE_REPOSITORIES_METADATA_SOURCE="DB",
            PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE="LOG",
            PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE="DB",
        )
        from predictionio_tpu.data.storage import Storage

        storage = Storage(env=env)
        from predictionio_tpu.cli import commands

        info = commands.app_new("WorkerApp", storage=storage)
        import socket

        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        sup = subprocess.Popen(
            [sys.executable, "-m", "predictionio_tpu.cli.main",
             "eventserver", "--ip", "127.0.0.1", "--port", str(port),
             "--workers", "2"],
            env=env,
        )
        try:
            for _ in range(60):
                try:
                    urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/", timeout=2
                    )
                    break
                except Exception:
                    time.sleep(0.5)
            else:
                raise AssertionError("workers never came up")
            key = info["access_key"]
            for i in range(60):
                status, _ = http(
                    "POST",
                    f"http://127.0.0.1:{port}/events.json?accessKey={key}",
                    dict(EVENT, entityId=f"u{i}"),
                )
                assert status == 201
        finally:
            sup.terminate()
            sup.wait(timeout=15)
        events = storage.get_events().find(info["id"], limit=None)
        assert len(events) == 60
        assert len({e.event_id for e in events}) == 60


# ---------------------------------------------------------------------------
# PR 4: serving fast path — jsonx parity, query cache, HTTP floor pieces
# ---------------------------------------------------------------------------


def _raw_post(url: str, payload: dict) -> bytes:
    """POST and return the raw response BYTES (the cache stores and
    serves preserialized bytes; byte equality is the contract)."""
    req = urllib.request.Request(
        url,
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=15) as resp:
        return resp.read()


class TestJsonxByteParity:
    """jsonx must be wire-compatible across backends: the stdlib
    fallback is pinned to orjson's format (compact separators, raw
    utf-8), so cached bytes and parsed payloads are byte-identical no
    matter which backend the box has."""

    CASES = [
        {"itemScores": [{"item": "i1", "score": 1.5},
                        {"item": "ü", "score": -0.25}]},
        {"a": [1, 2.5, None, True, False, "snow☃"],
         "b": {"nested": {"k": []}}},
        [],
        {},
        {"unicode": "héllo wörld 中文"},
        {"big": 2**53 - 1, "neg": -0.0001},
    ]

    def test_dumps_matches_compact_stdlib(self):
        from predictionio_tpu.server import jsonx

        for obj in self.CASES:
            expected = json.dumps(
                obj, separators=(",", ":"), ensure_ascii=False
            ).encode("utf-8")
            assert jsonx.dumps_bytes(obj) == expected, obj

    def test_loads_round_trip(self):
        from predictionio_tpu.server import jsonx

        for obj in self.CASES:
            assert jsonx.loads(jsonx.dumps_bytes(obj)) == obj

    def test_loads_raises_stdlib_decode_error(self):
        """Dispatch's `except json.JSONDecodeError` must keep catching
        parse failures whichever backend is active."""
        from predictionio_tpu.server import jsonx

        with pytest.raises(json.JSONDecodeError):
            jsonx.loads(b"{not json")


class TestQueryCacheUnit:
    def _cache(self, capacity=64 * 1024, shards=1):
        from predictionio_tpu.server.query_cache import QueryCache

        return QueryCache(capacity, shards=shards)

    def _key(self, i, epoch=0):
        from predictionio_tpu.server.query_cache import canonical_query_bytes

        return ("default", canonical_query_bytes({"user": f"u{i}"}), epoch)

    def test_canonical_bytes_key_order_insensitive(self):
        from predictionio_tpu.server.query_cache import canonical_query_bytes

        a = canonical_query_bytes({"user": "u1", "num": 3})
        b = canonical_query_bytes({"num": 3, "user": "u1"})
        assert a == b

    def test_put_get_counters(self):
        cache = self._cache()
        k = self._key(1)
        assert cache.get(k) is None
        cache.put(k, b'{"ok":1}')
        assert cache.get(k) == b'{"ok":1}'
        g = cache.gauges()
        assert g["cache_hits"] == 1 and g["cache_misses"] == 1
        assert g["cache_entries"] == 1
        assert g["cache_hit_rate"] == 0.5
        assert g["cache_bytes"] > len(b'{"ok":1}')  # payload + key + overhead

    def test_eviction_under_pressure(self):
        """Byte cap enforced per shard: filling far past capacity evicts
        LRU entries, keeps bytes under the cap, and counts evictions."""
        cache = self._cache(capacity=8 * 1024, shards=1)
        payload = b"x" * 512
        for i in range(50):
            cache.put(self._key(i), payload)
        g = cache.gauges()
        assert g["cache_bytes"] <= 8 * 1024
        assert 0 < g["cache_entries"] < 50
        assert g["cache_evictions"] == 50 - g["cache_entries"]
        assert cache.get(self._key(0)) is None  # oldest evicted
        assert cache.get(self._key(49)) == payload  # newest retained

    def test_get_refreshes_lru_order(self):
        cache = self._cache(capacity=8 * 1024, shards=1)
        payload = b"x" * 512
        cache.put(self._key(0), payload)
        for i in range(1, 11):
            cache.put(self._key(i), payload)
            cache.get(self._key(0))  # keep key 0 hot
        assert cache.get(self._key(0)) == payload

    def test_oversized_payload_skipped(self):
        cache = self._cache(capacity=4 * 1024, shards=1)
        cache.put(self._key(1), b"y" * 8 * 1024)  # larger than the shard
        assert cache.gauges()["cache_entries"] == 0

    def test_sweep_drops_stale_epochs(self):
        cache = self._cache()
        for i, epoch in enumerate((0, 0, 1, 2)):
            cache.put(self._key(i, epoch=epoch), b"z")
        dropped = cache.sweep(2)
        assert dropped == 3
        g = cache.gauges()
        assert g["cache_entries"] == 1
        assert cache.get(self._key(3, epoch=2)) == b"z"


@pytest.fixture()
def cached_engine(deployed_engine):
    """A second EngineServer over the already-trained instance with the
    query-result cache enabled (no retrain; construction is cheap)."""
    from predictionio_tpu.server.engine_server import EngineServer

    d = deployed_engine
    server = EngineServer(
        d["engine"], d["server"].instance, storage=d["storage"],
        host="127.0.0.1", port=0, server_key="secret", query_cache_mb=4,
    )
    port = server.start()
    yield {
        "base": f"http://127.0.0.1:{port}",
        "server": server,
        "storage": d["storage"],
        "engine": d["engine"],
        "ep": d["ep"],
    }
    server.stop()


def _scraped_uploads(scraped) -> float:
    """The serving chain's uploads in a ``/metrics`` scrape: the copies —
    and as many timed observations — of the transfer family's ``h2d``
    sites ``serve.dispatch`` (every host array a stage converted) and
    ``serve.rules`` (each per-query part of ``device_rules``)."""
    total = 0.0
    for op in ("serve.dispatch", "serve.rules"):
        site = f'{{direction="h2d",op="{op}"}}'
        n = scraped.get("pio_device_transfers_total" + site, 0.0)
        assert scraped.get("pio_device_transfer_seconds_count" + site, 0.0) == n
        total += n
    return total


class TestQueryCacheServing:
    def _count_predict(self, server):
        """Wrap the deployed algorithm's predict with a call
        counter (the device-dispatch skip is the point of a hit)."""
        algo = server.algorithms[0]
        calls = []
        orig = algo.predict

        def counting(*a, **k):
            calls.append(1)
            return orig(*a, **k)

        algo.predict = counting
        return calls

    def test_hit_serves_identical_bytes_without_recompute(self, cached_engine):
        server = cached_engine["server"]
        url = cached_engine["base"] + "/queries.json"
        calls = self._count_predict(server)
        b1 = _raw_post(url, {"user": "u1", "num": 3})
        b2 = _raw_post(url, {"user": "u1", "num": 3})
        assert b1 == b2
        assert len(calls) == 1  # second request never touched the model
        g = server.query_cache.gauges()
        assert g["cache_hits"] == 1 and g["cache_entries"] == 1
        # the canonical key ignores body key order: still a hit
        b3 = _raw_post(url, {"num": 3, "user": "u1"})
        assert b3 == b1 and len(calls) == 1

    def test_hits_count_in_request_count(self, cached_engine):
        url = cached_engine["base"] + "/queries.json"
        _raw_post(url, {"user": "u1", "num": 3})
        _raw_post(url, {"user": "u1", "num": 3})
        status, page = http("GET", cached_engine["base"] + "/")
        assert status == 200 and page["requestCount"] == 2

    def test_stats_route_exposes_cache_gauges(self, cached_engine):
        url = cached_engine["base"] + "/queries.json"
        _raw_post(url, {"user": "u1", "num": 3})
        _raw_post(url, {"user": "u1", "num": 3})
        status, body = http("GET", cached_engine["base"] + "/stats.json")
        assert status == 200
        cache = body["cache"]
        assert cache["enabled"] is True
        assert cache["cache_hits"] == 1 and cache["cache_misses"] == 1
        assert cache["cache_hit_rate"] == 0.5
        assert cache["cache_entries"] == 1 and cache["cache_bytes"] > 0

    def test_stats_route_reports_disabled_without_cache(self, deployed_engine):
        status, body = http("GET", deployed_engine["base"] + "/stats.json")
        assert status == 200
        assert body["cache"] == {"enabled": False}

    def test_stats_route_carries_the_retrieval_block_and_no_choice_of_body(
            self, deployed_engine):
        """The block is ``retrieval.stats_block()``'s; a scan has one
        step body, so nothing counts a choice (``tile_select`` is gone)."""
        from predictionio_tpu.ops import retrieval

        status, body = http("GET", deployed_engine["base"] + "/stats.json")
        assert status == 200
        assert set(body["retrieval"]) == set(retrieval.stats_block())
        assert "tile_select" not in body["retrieval"]
        with urllib.request.urlopen(
                deployed_engine["base"] + "/metrics", timeout=10) as r:
            assert b"tile_select" not in r.read()

    def test_stats_route_carries_the_id_lookup_block(self, deployed_engine):
        """``model_ids`` beside ``retrieval``: the look-ups of the model
        files' id dictionaries by what answered them, as ``/metrics`` has
        them under ``pio_model_id_*``; a served query moves ``hashed``
        (the deployed model is loaded from its file) and decodes nothing."""
        from predictionio_tpu.models import modelfile

        base = deployed_engine["base"]
        status, before = http("GET", base + "/stats.json")
        assert status == 200
        assert set(before["model_ids"]) == set(modelfile.id_stats_block())
        _raw_post(base + "/queries.json", {"user": "u1", "num": 3})
        _, after = http("GET", base + "/stats.json")
        a, b = after["model_ids"], before["model_ids"]
        assert a["decodes"] == b["decodes"]
        assert a["lookups"]["decoded"] == b["lookups"]["decoded"]
        assert a["lookups"]["hashed"] > b["lookups"]["hashed"]
        with urllib.request.urlopen(base + "/metrics", timeout=10) as r:
            text = r.read()
        assert b"pio_model_id_decodes_total" in text
        assert b'pio_model_id_lookups_total{path="hashed"}' in text

    def test_stats_route_carries_the_upload_counter(self, deployed_engine):
        """``retrieval.uploads`` beside ``retrieval.host_reads``: the
        host-to-device transfers of the serving chain, as ``/metrics``
        has them in the transfer family (``_scraped_uploads``)."""
        import numpy as np

        from predictionio_tpu.obs import metrics as obs_metrics
        from predictionio_tpu.ops import retrieval

        base = deployed_engine["base"]

        def read():
            status, body = http("GET", base + "/stats.json")
            assert status == 200
            with urllib.request.urlopen(base + "/metrics", timeout=10) as r:
                scraped = obs_metrics.parse_prometheus(r.read().decode())
            block = body["retrieval"]
            assert _scraped_uploads(scraped) == block["uploads"]
            assert scraped["pio_retrieval_host_reads_total"] == block["host_reads"]
            return block["uploads"]

        rng = np.random.default_rng(8)
        cat = retrieval.CoarseCatalog(
            rng.normal(size=(300, 8)).astype(np.float32), tile=128
        )
        before = read()
        cat.shortlist(rng.normal(size=(3, 8)).astype(np.float32), 16)
        assert read() == before + 1

    @pytest.mark.parametrize("batch,form", [
        (1, "dot"), (2, "rows"), (4, "rows"),
    ])
    def test_a_shortlist_call_moves_its_counters_by_one_on_both_routes(
        self, deployed_engine, batch, form
    ):
        """``/stats.json`` and ``/metrics`` of the engine server read the
        counters that the shortlist call of its process counts: its
        score form, ONE upload and ONE blocking read — also where the
        batch is scanned in chunks (rank 8 in bf16: two queries a pass,
        so four are two chunks of one program)."""
        import numpy as np

        from predictionio_tpu.obs import metrics as obs_metrics
        from predictionio_tpu.ops import retrieval

        base = deployed_engine["base"]

        def read():
            status, body = http("GET", base + "/stats.json")
            assert status == 200
            with urllib.request.urlopen(base + "/metrics", timeout=10) as r:
                scraped = obs_metrics.parse_prometheus(r.read().decode())
            block = body["retrieval"]
            for f, n in block["score_form"].items():
                assert scraped[
                    f'pio_retrieval_score_form_total{{form="{f}"}}'
                ] == n
            assert _scraped_uploads(scraped) == block["uploads"]
            assert scraped["pio_retrieval_host_reads_total"] == block["host_reads"]
            return {**block["score_form"], "uploads": block["uploads"],
                    "host_reads": block["host_reads"]}

        rng = np.random.default_rng(7)
        cat = retrieval.CoarseCatalog(
            rng.normal(size=(2 * 8192 + 3, 8)).astype(np.float32), tile=8192
        )
        assert batch // retrieval.scan_chunk(batch, 8, "bf16", PAST) == (2 if batch == 4 else 1)
        before = read()
        cat.shortlist(rng.normal(size=(batch, 8)).astype(np.float32), 16)
        after = read()
        for p in after:
            assert after[p] == before[p] + (p in (form, "uploads", "host_reads"))

    def test_stats_route_carries_the_score_form_counter(self, deployed_engine):
        from predictionio_tpu.ops import retrieval

        status, body = http("GET", deployed_engine["base"] + "/stats.json")
        assert status == 200
        block = body["retrieval"]["score_form"]
        assert set(block) == {"dot", "rows"}
        assert block == retrieval.stats_block()["score_form"]

    def test_reload_invalidates(self, cached_engine):
        from predictionio_tpu.core.workflow import run_train

        server = cached_engine["server"]
        url = cached_engine["base"] + "/queries.json"
        calls = self._count_predict(server)
        _raw_post(url, {"user": "u1", "num": 3})
        assert len(calls) == 1
        run_train(
            cached_engine["engine"], cached_engine["ep"], engine_id="serve",
            storage=cached_engine["storage"],
        )
        status, _ = http(
            "POST", cached_engine["base"] + "/reload?accessKey=secret"
        )
        assert status == 200
        # the reload re-wraps algorithms; recount on the fresh object
        calls2 = self._count_predict(server)
        _raw_post(url, {"user": "u1", "num": 3})
        assert len(calls2) == 1  # recomputed: pre-reload entry swept
        assert server.query_cache.gauges()["cache_entries"] == 1

    def test_cacheable_false_bypasses_cache(self, cached_engine):
        server = cached_engine["server"]
        url = cached_engine["base"] + "/queries.json"
        server.algorithms[0].cacheable_query = lambda q: False
        calls = self._count_predict(server)
        b1 = _raw_post(url, {"user": "u1", "num": 3})
        b2 = _raw_post(url, {"user": "u1", "num": 3})
        assert b1 == b2
        assert len(calls) == 2  # both recomputed
        assert server.query_cache.gauges()["cache_entries"] == 0

    def test_ecommerce_algorithm_opts_out(self):
        """The live-filter engine (per-query event-store reads the epoch
        fence can't see) must refuse caching by contract."""
        from predictionio_tpu.models import ecommerce

        algo = ecommerce.ECommAlgorithm(
            ecommerce.ECommAlgorithmParams(app_name="x")
        )
        assert algo.cacheable_query(ecommerce.Query(user="u1")) is False

    def test_recommendation_algorithm_default_cacheable(self):
        from predictionio_tpu.models import recommendation as rec

        algo = rec.ALSAlgorithm(rec.ALSAlgorithmParams())
        assert algo.cacheable_query(rec.Query(user="u1")) is True

    def test_warmup_compiles_per_algorithm(self, deployed_engine):
        assert deployed_engine["server"].warmup() == 1


class TestHTTPFastPathPieces:
    def test_preencoded_bytes_sent_verbatim(self):
        """Response.json_bytes: the body bytes go out untouched — the
        no-re-encode contract the cache hit path relies on."""
        from predictionio_tpu.server import jsonx
        from predictionio_tpu.server.http import HTTPApp, Response, Router

        payload = jsonx.dumps_bytes({"x": [1, 2, 3], "s": "é"})
        router = Router()
        router.add("GET", "/pre", lambda req: Response.json_bytes(payload))
        app = HTTPApp(router, host="127.0.0.1", port=0)
        port = app.start(background=True)
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/pre", timeout=10
            ) as resp:
                assert resp.status == 200
                assert resp.headers["Content-Type"].startswith(
                    "application/json"
                )
                assert resp.read() == payload
        finally:
            app.stop()

    def test_rfile_fallback_serves_keep_alive(self):
        """recv_buffer=False pins the stdlib rfile reader (the bench's
        http-floor 'before'); framing and keep-alive must be identical."""
        import http.client

        from predictionio_tpu.server.http import HTTPApp, Response, Router

        router = Router()
        router.add(
            "POST", "/echo",
            lambda req: Response.json({"n": len(req.body)}),
        )
        app = HTTPApp(router, host="127.0.0.1", port=0, recv_buffer=False)
        port = app.start(background=True)
        try:
            c = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
            for i in range(3):  # same connection: keep-alive holds
                c.request(
                    "POST", "/echo", body=b"x" * (i + 1),
                    headers={"Content-Type": "application/json"},
                )
                r = c.getresponse()
                assert r.status == 200
                assert json.loads(r.read()) == {"n": i + 1}
            c.close()
        finally:
            app.stop()

    def test_conn_reader_matches_rfile_semantics(self):
        """_ConnReader.readline(limit)/read(n) must mirror the buffered
        rfile exactly — it IS the drop-in for the request parser."""
        import socket

        from predictionio_tpu.server.http import _ConnReader

        a, b = socket.socketpair()
        try:
            reader = _ConnReader(a)
            b.sendall(b"hello\nworld")
            assert reader.readline(100) == b"hello\n"
            assert reader.read(5) == b"world"
            # a line longer than limit comes back as exactly limit bytes
            b.sendall(b"abcdefgh")
            b.close()
            assert reader.readline(4) == b"abcd"
            assert reader.readline(100) == b"efgh"  # EOF: remainder
            assert reader.readline(100) == b""
            assert reader.read(3) == b""
        finally:
            a.close()


# ---------------------------------------------------------------------------
# graceful degradation under failure (robustness PR): 503 + Retry-After
# during model swaps and deadline overruns, micro-batcher fallback
# ---------------------------------------------------------------------------


def http_full(method, url, body=None, headers=None):
    """Like http() but also returns response headers (Retry-After)."""
    data = None
    if body is not None:
        data = body if isinstance(body, bytes) else json.dumps(body).encode()
    req = urllib.request.Request(url, data=data, method=method)
    for k, v in (headers or {}).items():
        req.add_header(k, v)
    try:
        with urllib.request.urlopen(req, timeout=10) as resp:
            return resp.status, json.loads(resp.read() or b"{}"), dict(resp.headers)
    except urllib.error.HTTPError as e:
        payload = e.read()
        try:
            parsed = json.loads(payload or b"{}")
        except json.JSONDecodeError:
            parsed = {"raw": payload.decode()}
        return e.code, parsed, dict(e.headers)


class TestGracefulDegradation:
    def test_reload_in_flight_keeps_serving_old_model(
        self, deployed_engine
    ):
        """The satellite regression: hold a /reload open and prove the
        OLD model keeps answering 200 for the whole swap window —
        prepare_deploy runs off the server lock and the swap itself is
        atomic, so a reload never degrades availability. (Deploy warmup
        is the path that fences with 503 + Retry-After; see
        test_warmup_blocks_queries_while_running.)"""
        server = deployed_engine["server"]
        base = deployed_engine["base"]
        entered = threading.Event()
        release = threading.Event()
        orig_load = server._load

        def slow_load(instance):
            entered.set()
            assert release.wait(10)
            return orig_load(instance)

        server._load = slow_load
        try:
            t = threading.Thread(
                target=http,
                args=("POST", base + "/reload?accessKey=secret"),
            )
            t.start()
            assert entered.wait(10)
            status, body, _ = http_full(
                "POST", base + "/queries.json", {"user": "u1", "num": 3}
            )
            assert status == 200 and body["itemScores"]
        finally:
            release.set()
            server._load = orig_load
        t.join(timeout=30)
        status, body, _ = http_full(
            "POST", base + "/queries.json", {"user": "u1", "num": 3}
        )
        assert status == 200 and body["itemScores"]

    def test_query_deadline_times_out_to_503(self, deployed_engine):
        from predictionio_tpu import faults
        from predictionio_tpu.server.engine_server import EngineServer

        server = EngineServer(
            deployed_engine["engine"],
            deployed_engine["server"].instance,
            storage=deployed_engine["storage"],
            host="127.0.0.1", port=0, query_deadline_ms=150.0,
        )
        port = server.start()
        try:
            base = f"http://127.0.0.1:{port}"
            # fast query under the deadline serves normally
            status, body, _ = http_full(
                "POST", base + "/queries.json", {"user": "u1", "num": 3}
            )
            assert status == 200
            with faults.injected("serve.query:sleep=600"):
                status, body, headers = http_full(
                    "POST", base + "/queries.json", {"user": "u1", "num": 3}
                )
            assert status == 503
            assert headers.get("Retry-After") == "1"
            assert "deadline" in json.dumps(body)
            # deadline overruns must not poison later queries
            status, body, _ = http_full(
                "POST", base + "/queries.json", {"user": "u1", "num": 3}
            )
            assert status == 200 and body["itemScores"]
        finally:
            server.stop()

    def test_batcher_failure_falls_back_to_unbatched(self, deployed_engine):
        from predictionio_tpu.obs import metrics as obs_metrics
        from predictionio_tpu.server.engine_server import EngineServer

        server = EngineServer(
            deployed_engine["engine"],
            deployed_engine["server"].instance,
            storage=deployed_engine["storage"],
            host="127.0.0.1", port=0, batch_window_ms=25.0,
            dispatch_cost_s=10.0,  # pin engaged mode
        )
        port = server.start()
        fallback_counter = obs_metrics.counter(
            "pio_batcher_fallback_total",
            "Queries served unbatched after a micro-batcher failure",
        )
        before = fallback_counter.value()
        try:

            def broken_submit(body):
                raise RuntimeError("batch worker failed")

            server.batcher.submit = broken_submit
            status, body, _ = http_full(
                "POST",
                f"http://127.0.0.1:{port}/queries.json",
                {"user": "u1", "num": 3},
            )
            assert status == 200 and body["itemScores"]
            assert fallback_counter.value() == before + 1
        finally:
            server.stop()

    def test_batcher_query_errors_still_propagate(self, deployed_engine):
        """Only infrastructure failures fall back; a bad query through
        the batcher stays a 400, not a silent unbatched retry."""
        from predictionio_tpu.server.engine_server import EngineServer

        server = EngineServer(
            deployed_engine["engine"],
            deployed_engine["server"].instance,
            storage=deployed_engine["storage"],
            host="127.0.0.1", port=0, batch_window_ms=25.0,
            dispatch_cost_s=10.0,
        )
        port = server.start()
        try:
            status, _, _ = http_full(
                "POST", f"http://127.0.0.1:{port}/queries.json", [1, 2]
            )
            assert status == 400
        finally:
            server.stop()

    def test_warmup_blocks_queries_while_running(self, deployed_engine):
        server = deployed_engine["server"]
        base = deployed_engine["base"]
        server._swapping.set()  # what warm_up() holds while compiling
        try:
            status, _, headers = http_full(
                "POST", base + "/queries.json", {"user": "u1"}
            )
            assert status == 503 and headers.get("Retry-After") == "1"
        finally:
            server._swapping.clear()
        status, _, _ = http_full(
            "POST", base + "/queries.json", {"user": "u1"}
        )
        assert status == 200
