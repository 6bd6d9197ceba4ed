"""Device-observability tests: compile tracking per distinct shape, the
device-memory gauges and /traces.json query params over a live socket,
progress-file atomicity under a concurrent reader, the `pio profile`
smoke, and the 503-path trace-span regression."""

from __future__ import annotations

import json
import os
import threading
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.cli import commands
from predictionio_tpu.obs import device as obs_device
from predictionio_tpu.obs import metrics, progress, trace
from predictionio_tpu.obs.metrics import parse_prometheus
from predictionio_tpu.server.http import HTTPApp, Router, add_obs_routes


def _get(url: str):
    with urllib.request.urlopen(url, timeout=10) as resp:
        return resp.status, resp.read()


class TestCompileTracker:
    def test_one_compile_per_distinct_shape(self):
        """The cache-size delta counts exactly one compile per new
        (shape, static-args) specialization and a cache hit on repeats
        — the shape-churn detector the micro-batcher needs."""
        f = obs_device.track_jit("test.shape_churn")(
            jax.jit(lambda x: (x * 2.0).sum())
        )
        before = obs_device.compile_snapshot().get(
            "test.shape_churn", {"calls": 0, "compiles": 0, "cache_hits": 0}
        )
        f(jnp.ones((4,)))
        f(jnp.ones((4,)))  # cache hit
        f(jnp.ones((8,)))  # new shape -> compile
        f(jnp.ones((8,)))  # cache hit
        after = obs_device.compile_snapshot()["test.shape_churn"]
        assert after["calls"] - before["calls"] == 4
        assert after["compiles"] - before["compiles"] == 2
        assert after["cache_hits"] - before["cache_hits"] == 2

    def test_counters_and_call_time_exported(self):
        f = obs_device.track_jit("test.exported")(jax.jit(lambda x: x + 1))
        f(jnp.zeros((3,)))
        f(jnp.zeros((3,)))
        rendered = metrics.render_prometheus().decode()
        assert 'pio_jit_compiles_total{fn="test.exported"}' in rendered
        assert 'pio_jit_cache_hits_total{fn="test.exported"}' in rendered
        # the launch is timed on the call that hit the cache, not on the
        # one that compiled
        assert 'pio_jit_call_seconds_count{fn="test.exported"} 1' in rendered

    def test_disabled_is_a_passthrough(self):
        f = obs_device.track_jit("test.disabled")(jax.jit(lambda x: x - 1))
        metrics.set_enabled(False)
        try:
            f(jnp.zeros((5,)))
            snap = obs_device.compile_snapshot()
            assert "test.disabled" not in snap or snap["test.disabled"][
                "calls"
            ] == 0
        finally:
            metrics.set_enabled(True)

    def test_wrapped_function_still_correct(self):
        f = obs_device.track_jit("test.correct")(jax.jit(lambda x: x * 3.0))
        np.testing.assert_allclose(
            np.asarray(f(jnp.asarray([1.0, 2.0]))), [3.0, 6.0]
        )


@pytest.fixture()
def obs_app():
    """A bare server mounting only the obs routes — the surface every
    framework server shares."""
    router = Router()
    add_obs_routes(router)
    app = HTTPApp(router, host="127.0.0.1", port=0, name="obstest")
    port = app.start(background=True)
    yield f"http://127.0.0.1:{port}"
    app.stop()


class TestDeviceEndpoints:
    def test_memory_gauges_on_live_metrics(self, obs_app):
        """Per-device memory gauges are present and non-negative on
        /metrics over a real socket (CPU backend: stats unsupported ->
        zeros plus a supported=0 flag, never missing)."""
        # jax is imported (this module) and a tracked call has run, so
        # the scrape registers the device gauges
        obs_device.track_jit("test.scrape")(jax.jit(lambda x: x))(
            jnp.zeros(())
        )
        status, body = _get(f"{obs_app}/metrics")
        assert status == 200
        parsed = parse_prometheus(body)
        mem = {k: v for k, v in parsed.items()
               if k.startswith("pio_device_memory_bytes")}
        assert mem, sorted(parsed)
        assert all(v >= 0 for v in mem.values()), mem
        assert any(
            k.startswith("pio_device_memory_stats_supported") for k in parsed
        )
        assert any(k.startswith("pio_device_count") for k in parsed)
        assert any(k.startswith("pio_jit_compiles_total") for k in parsed)

    def test_traces_json_limit_and_since_ms(self, obs_app):
        trace.TRACES.clear()
        for i, dur in enumerate((0.5, 0.3, 0.1)):
            tr = trace.Trace(f"fabricated.{i}")
            tr.finish(200)
            tr.duration_s = dur
            trace.TRACES.offer(tr)
        status, body = _get(f"{obs_app}/traces.json")
        assert status == 200
        assert len(json.loads(body)["traces"]) == 3

        status, body = _get(f"{obs_app}/traces.json?limit=2")
        traces = json.loads(body)["traces"]
        # slowest-first ordering survives the cap
        assert [t["name"] for t in traces] == ["fabricated.0", "fabricated.1"]

        # all fabricated traces started just now: a future cutoff drops
        # them all, a past cutoff keeps them all
        far_future_ms = (trace.Trace("x").wall_start + 3600.0) * 1000.0
        status, body = _get(
            f"{obs_app}/traces.json?since_ms={far_future_ms}"
        )
        assert json.loads(body)["traces"] == []
        status, body = _get(f"{obs_app}/traces.json?since_ms=0&limit=1")
        assert len(json.loads(body)["traces"]) == 1

        with pytest.raises(urllib.error.HTTPError) as err:
            _get(f"{obs_app}/traces.json?limit=nope")
        assert err.value.code == 400


class TestProgressFile:
    def test_atomic_under_concurrent_reader(self, tmp_path):
        """A reader polling the progress file while a writer republishes
        continuously never sees a torn/partial document."""
        path = str(tmp_path / "progress.json")
        pub = progress.ProgressPublisher(100, path=path, mesh="single")
        pub.publish(1)
        stop = threading.Event()
        errors: list[Exception] = []

        def writer():
            i = 2
            while not stop.is_set():
                pub.publish(i, rmse=1.0 / i, events_per_s=1e6,
                            segment_wall_s=0.5, checkpoint_epoch=i)
                i += 1

        def reader():
            while not stop.is_set():
                try:
                    doc = progress.read_progress(path)
                    # read_progress returns None only for missing or
                    # corrupt files; the file exists from the start
                    assert doc is not None
                    assert doc["total_iterations"] == 100
                    assert doc["state"] == "running"
                except Exception as e:  # pragma: no cover
                    errors.append(e)
                    return

        threads = [threading.Thread(target=writer),
                   threading.Thread(target=reader),
                   threading.Thread(target=reader)]
        for t in threads:
            t.start()
        import time as _time

        _time.sleep(0.4)
        stop.set()
        for t in threads:
            t.join()
        assert not errors, errors[0]
        # no stray tmp files leak from the atomic replace loop
        leftovers = [p for p in os.listdir(tmp_path) if p.endswith(".tmp")]
        assert leftovers == []

    def test_liveness(self, tmp_path):
        path = str(tmp_path / "p.json")
        pub = progress.ProgressPublisher(10, path=path)
        pub.publish(3)
        doc = progress.read_progress(path)
        assert progress.is_live(doc)  # our own pid, fresh
        assert doc["iteration"] == 3 and doc["eta_s"] is not None
        pub.done()
        assert not progress.is_live(progress.read_progress(path))
        # dead writer -> not live even in "running" state
        pub2 = progress.ProgressPublisher(10, path=path)
        pub2.publish(1)
        doc = progress.read_progress(path)
        doc["pid"] = 2 ** 30  # no such process
        assert not progress.is_live(doc)

    def test_corrupt_file_reads_as_none(self, tmp_path):
        path = tmp_path / "p.json"
        path.write_text("{not json")
        assert progress.read_progress(str(path)) is None
        assert progress.read_progress(str(tmp_path / "absent.json")) is None

    def test_tol_run_reports_bounds_not_predictions(self, tmp_path):
        """Under --tol the configured count is an upper bound: a live
        doc flags eta_is_bound, and a plateau stop pins
        total_iterations to the count actually run."""
        path = str(tmp_path / "p.json")
        pub = progress.ProgressPublisher(100, path=path, tol=1e-3,
                                         mesh="single")
        pub.publish(10)
        doc = progress.read_progress(path)
        assert doc["configured_iterations"] == 100
        assert doc["tol"] == 1e-3
        assert doc["eta_is_bound"] is True
        assert doc["early_stopped"] is False
        pub.done(12, early_stopped=True)
        doc = progress.read_progress(path)
        assert doc["state"] == "done"
        assert doc["early_stopped"] is True
        assert doc["total_iterations"] == 12
        assert doc["configured_iterations"] == 100
        assert doc["eta_is_bound"] is False
        # without --tol the ETA is a prediction, never flagged a bound
        pub2 = progress.ProgressPublisher(100, path=path, mesh="single")
        pub2.publish(10)
        doc = progress.read_progress(path)
        assert doc["eta_is_bound"] is False and doc["tol"] is None



class TestProfileSmoke:
    def test_cli_profile_produces_trace_dir(self, tmp_path, capsys):
        from predictionio_tpu.cli.main import main

        out = str(tmp_path / "trace")
        rc = main(["profile", "--seconds", "0.2", "--out", out])
        assert rc == 0
        summary = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert summary["trace_dir"] == out
        assert summary["files"] > 0 and summary["bytes"] > 0
        # the profiler actually wrote under the dir
        found = [
            os.path.join(r, f)
            for r, _d, files in os.walk(out)
            for f in files
        ]
        assert found

    def test_concurrent_capture_refused(self, tmp_path):
        import time as _time

        first_started = threading.Event()
        results: list = []

        def long_capture():
            first_started.set()
            results.append(
                obs_device.profile_capture(
                    0.6, out_dir=str(tmp_path / "a"), burn=False
                )
            )

        t = threading.Thread(target=long_capture)
        t.start()
        first_started.wait()
        _time.sleep(0.1)  # let it take the lock
        with pytest.raises(RuntimeError):
            obs_device.profile_capture(0.1, out_dir=str(tmp_path / "b"))
        t.join()
        assert results and results[0]["trace_dir"].endswith("a")


class Test503TraceRegression:
    def test_swap_503_records_unavailable_span(self, storage):
        """Queries rejected during a warmup-overlap swap must leave a
        trace (serve.unavailable span, status 503) in /traces.json —
        PR 8 only counted them."""
        from predictionio_tpu.core import EngineParams
        from predictionio_tpu.core.workflow import run_train
        from predictionio_tpu.data.event import Event
        from predictionio_tpu.models import recommendation as rec
        from predictionio_tpu.server.engine_server import EngineServer

        info = commands.app_new("Obs503App", storage=storage)
        events = storage.get_events()
        rng = np.random.default_rng(0)
        for u in range(8):
            for _ in range(4):
                events.insert(
                    Event(
                        event="rate", entity_type="user",
                        entity_id=f"u{u}",
                        target_entity_type="item",
                        target_entity_id=f"i{int(rng.integers(0, 5))}",
                        properties={"rating": float(rng.integers(1, 6))},
                    ),
                    info["id"],
                )
        engine = rec.engine()
        ep = EngineParams(
            datasource=("", rec.DataSourceParams(app_name="Obs503App")),
            algorithms=[
                ("als", rec.ALSAlgorithmParams(rank=4, num_iterations=2))
            ],
        )
        run_train(engine, ep, engine_id="obs-503", storage=storage)
        instance = storage.get_metadata_engine_instances() \
            .get_latest_completed("obs-503", "0", "default")
        server = EngineServer(
            engine, instance, storage=storage, host="127.0.0.1", port=0
        )
        port = server.start()
        try:
            trace.TRACES.clear()
            server._swapping.set()
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/queries.json",
                data=json.dumps({"user": "u1", "num": 3}).encode(),
                method="POST",
                headers={"Content-Type": "application/json"},
            )
            with pytest.raises(urllib.error.HTTPError) as err:
                urllib.request.urlopen(req, timeout=10)
            assert err.value.code == 503
            server._swapping.clear()

            status, body = _get(f"http://127.0.0.1:{port}/traces.json")
            assert status == 200
            traces = json.loads(body)["traces"]
            rejected = [
                t for t in traces
                if any(s["name"] == "serve.unavailable"
                       for s in t.get("spans", []))
            ]
            assert rejected, traces
            assert rejected[0]["status"] == 503
        finally:
            server.stop()
