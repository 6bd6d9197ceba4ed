"""The runtime instruments (``obs.runtime``, PR 34): the batch worker's
time by state on a live ``_MicroBatcher``, ``region(cpu_hist=)`` on the
thread's CPU clock, the collector hook, the ``obs-beat`` thread's stop
record (a held interpreter against a stopped process), inertness under
``PIO_OBS=0``, and the twelve per-layer readers that read them.

Jax-free and quick: the batcher runs against a stub server (the real
``EngineServer`` methods on an object that holds only what they touch).
"""

from __future__ import annotations

import gc
import json
import logging
import os
import signal
import subprocess
import sys
import threading
import time
from concurrent.futures import Future

import pytest

from predictionio_tpu.obs import metrics, runtime
from predictionio_tpu.obs import trace as obs_trace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

import manifest  # noqa: E402
import readers  # noqa: E402
import run as bench_run  # noqa: E402

METRICS_DIR = os.path.join(ROOT, "benchmark", "metrics")
with open(os.path.join(ROOT, "BENCHMARK.json")) as _fh:
    MANIFEST = json.load(_fh)

STEADY = "retrieval-yambda.serve-steady"
ITEMPAGE = "similarproduct-taobao.serve-itempage"
SATURATED = "retrieval-yambda.serve-saturated"
INT8 = "recommendation-amazon23-int8.serve-onechip-steady"  # PR 41 joined two lists
LIVE = "recommendation-amazon23-int8-live.serve-foldin-steady"  # PR 45 joined two lists


@pytest.fixture()
def armed():
    """The hook and the beat, taken out again whatever the test did."""
    runtime.reset_for_tests()
    was = metrics.enabled()
    metrics.set_enabled(True)
    runtime.arm()
    yield runtime
    runtime.reset_for_tests()
    metrics.set_enabled(was)


def _state_seconds() -> dict[str, float]:
    return {
        s: metrics.counter("pio_batch_worker_seconds_total", state=s).value()
        for s in runtime.STATES
    }


def _path_count(path: str) -> int:
    return metrics.counter("pio_batch_dispatch_path_total", path=path).value()


class _Ann:
    """In ``TraceAnnotation``'s place: what was entered and left, on which
    thread, appended to ``seen``."""

    def __init__(self, name, seen):
        self.name, self.seen = name, seen

    def __enter__(self):
        self.seen.append(("in", self.name, threading.current_thread().name))

    def __exit__(self, *exc):
        self.seen.append(("out", self.name, threading.current_thread().name))


# -- 1. the worker's time by state --------------------------------------------


class _Algo:
    """What ``_score_batch_group`` asks of an algorithm, no device."""

    query_class = None  # a query is its JSON body

    def __init__(self, sleep_s: float = 0.0):
        self.sleep_s = sleep_s

    def predict(self, model, sup):
        time.sleep(self.sleep_s)
        return ("p", sup)

    def batch_predict(self, model, indexed):
        time.sleep(self.sleep_s)
        return [(i, ("p", sup)) for i, sup in indexed]


class _Serving:
    def supplement(self, query):
        return query


class _Variant:
    def __init__(self, algo):
        self.algorithms, self.models, self.serving = [algo], [None], _Serving()


@pytest.fixture()
def worker():
    """A live ``_MicroBatcher`` whose server is ``EngineServer``'s own batch
    methods over a stub algorithm: ``submit(variant, n)`` enqueues n items
    and returns their futures."""
    from predictionio_tpu.server.engine_server import EngineServer, _MicroBatcher

    was = metrics.enabled()
    metrics.set_enabled(True)
    srv = EngineServer.__new__(EngineServer)
    srv._lock = threading.Lock()
    srv._m_batch_size = metrics.histogram("pio_batch_size", bounds=(1, 2, 4, 8, 16, 32, 64, 128))
    srv._m_dispatch = metrics.histogram("pio_batch_dispatch_seconds")
    srv._m_dispatch_cpu = metrics.histogram("pio_batch_dispatch_cpu_seconds")
    srv._m_dispatch_self = metrics.histogram("pio_batch_dispatch_self_seconds")
    srv._m_rows_real = metrics.counter("pio_batch_rows_total", kind="real")
    srv._m_rows_padded = metrics.counter("pio_batch_rows_total", kind="padded")
    # and what ``_serve_batched`` touches around a dispatch
    srv._m_submit = metrics.histogram("pio_serving_submit_seconds")
    srv._m_wake = metrics.histogram("pio_serving_wake_seconds")
    srv._m_tail = metrics.histogram("pio_serving_tail_seconds")
    srv.query_deadline_s = None
    srv._default_variant = _Variant(_Algo())
    srv._finish_query = lambda body, query, predictions, *a, **kw: predictions
    srv.batcher = _MicroBatcher(srv, window_ms=1.0, dispatch_cost_s=0.0)

    def submit(variant, n=1):
        futs = [Future() for _ in range(n)]
        for f in futs:
            with srv.batcher._lock:  # as ``submit`` enqueues
                srv.batcher._waiting += 1
                srv.batcher._q.put((f, time.perf_counter(), None, "q", variant))
        return futs

    yield srv.batcher, submit
    srv.batcher.stop()
    metrics.set_enabled(was)


def _settle(batcher, state="idle"):
    deadline = time.monotonic() + 5
    while batcher.clock.state != state or not batcher._q.empty():
        assert time.monotonic() < deadline, batcher.clock.state
        time.sleep(0.002)
    time.sleep(0.06)  # one idle time-out of the worker: idle so far is counted


class TestWorkerClock:
    def test_idle_is_all_of_it_without_traffic(self, worker):
        batcher, _ = worker
        _settle(batcher)
        s0, t0 = _state_seconds(), time.perf_counter()
        time.sleep(0.5)
        _settle(batcher)
        s1, wall = _state_seconds(), time.perf_counter() - t0
        d = {k: s1[k] - s0[k] for k in s0}
        assert d["collect"] == d["dispatch"] == d["resolve"] == 0.0
        assert d["idle"] == pytest.approx(wall, abs=0.06)  # one time-out of the get
        assert batcher.clock.state == "idle" and runtime.block()["worker"] == "idle"

    def test_states_sum_to_wall_and_dispatch_grows_with_a_slow_stub(self, worker):
        batcher, submit = worker
        fast, slow = _Variant(_Algo(0.0)), _Variant(_Algo(0.02))
        _settle(batcher)
        s0, t0 = _state_seconds(), time.perf_counter()
        for _ in range(5):
            for f in submit(fast):
                assert f.result(timeout=5) == [("p", "q")]
        _settle(batcher)
        s1, t1 = _state_seconds(), time.perf_counter()
        for _ in range(5):
            for f in submit(slow):
                assert f.result(timeout=5) == [("p", "q")]
        for f in submit(slow, n=3):  # one padded batch of 3 -> 4 rows
            assert f.result(timeout=5) == [("p", "q")]
        _settle(batcher)
        s2, t2 = _state_seconds(), time.perf_counter()
        a = {k: s1[k] - s0[k] for k in s0}
        b = {k: s2[k] - s1[k] for k in s0}
        # the identity: the four states are the worker's wall time
        assert sum(a.values()) == pytest.approx(t1 - t0, rel=0.01, abs=0.06)
        assert sum(b.values()) == pytest.approx(t2 - t1, rel=0.01, abs=0.06)
        assert a["dispatch"] < 0.02 and b["dispatch"] >= 6 * 0.02
        assert all(v > 0.0 for v in b.values())
        # the dispatch state is the batch.dispatch region: its histogram's sum
        assert b["dispatch"] == pytest.approx(0.12, abs=0.05)

    def test_a_failing_dispatch_leaves_the_worker_in_resolve_then_idle(self, worker):
        batcher, submit = worker

        class Boom(_Algo):
            def predict(self, model, sup):
                raise ValueError("no")

        (f,) = submit(_Variant(Boom()))
        with pytest.raises(ValueError):
            f.result(timeout=5)
        _settle(batcher)
        assert batcher.clock.state == "idle"

    def test_inline_queries_alone_fill_every_series_and_leave_the_worker_idle(self, worker):
        """ISSUE 47: a lone query is dispatched on its request thread. The
        histograms the worker's path observes count it all the same, its
        trace has the chain without a hole, and none of it is the worker's
        time."""
        batcher, _ = worker
        srv = batcher._server
        series = [
            "pio_batch_queue_wait_seconds", "pio_serving_wake_seconds",
            "pio_batch_size", "pio_batch_dispatch_seconds",
            "pio_batch_dispatch_self_seconds", "pio_serving_submit_seconds",
            "pio_serving_tail_seconds",
        ]
        count = lambda n: metrics.histogram(n).merged()[2]  # noqa: E731
        _settle(batcher)
        n0 = {n: count(n) for n in series}
        rows0 = metrics.counter("pio_batch_rows_total", kind="real").value()
        p0 = (_path_count("inline"), _path_count("worker"))
        wake0 = metrics.histogram("pio_serving_wake_seconds").merged()[1]
        s0, t0 = _state_seconds(), time.perf_counter()
        tr = obs_trace.Trace("inline")
        with obs_trace.use_trace(tr), obs_trace.region("serve"):
            for i in range(7):
                assert srv._serve_batched({"q": i}) == b'[["p",{"q":%d}]]' % i
        _settle(batcher)
        s1, wall = _state_seconds(), time.perf_counter() - t0
        for n in series:
            assert count(n) == n0[n] + 7, n
        assert metrics.counter("pio_batch_rows_total", kind="real").value() == rows0 + 7
        assert (_path_count("inline"), _path_count("worker")) == (p0[0] + 7, p0[1])
        assert batcher.stats_block()["dispatch_path"]["inline"] == p0[0] + 7
        # two clock readings apart, not a thread switch
        assert metrics.histogram("pio_serving_wake_seconds").merged()[1] - wake0 < 0.05
        spans = [(s[0], s[3]) for s in tr.spans]
        assert spans[:5] == [
            ("serve.submit", "serve"), ("batch.queue_wait", "serve"),
            ("batch.dispatch[1]", "serve"), ("serve.wake", "serve"),
            ("serve.tail", "serve"),
        ]
        assert len(spans) == 7 * 5 + 1
        # the worker's clock is the worker's: idle all along, and whole
        d = {k: s1[k] - s0[k] for k in s0}
        assert d["collect"] == d["dispatch"] == d["resolve"] == 0.0
        assert d["idle"] == pytest.approx(wall, abs=0.06)

    def test_a_query_behind_an_inline_dispatch_is_the_workers(self, worker):
        """... and its wait for the slot is the worker's ``collect``: the
        four states still sum to the worker's wall time."""
        batcher, _ = worker
        srv = batcher._server
        inside, gate = threading.Event(), threading.Event()

        class Held(_Algo):
            def predict(self, model, sup):
                if not inside.is_set():  # the first, inline: holds the slot
                    inside.set()
                    assert gate.wait(timeout=5)
                return ("p", sup)

        srv._default_variant = _Variant(Held())
        _settle(batcher)
        s0, t0 = _state_seconds(), time.perf_counter()
        out = []
        threads = [
            threading.Thread(target=lambda i=i: out.append(srv._serve_batched({"q": i})))
            for i in range(2)
        ]
        threads[0].start()
        assert inside.wait(timeout=5)
        threads[1].start()
        deadline = time.monotonic() + 5
        while batcher.clock.state != "collect":  # the worker has the item, not the slot
            assert time.monotonic() < deadline
            time.sleep(0.001)
        time.sleep(0.05)
        gate.set()
        for t in threads:
            t.join(timeout=5)
            assert not t.is_alive()
        _settle(batcher)
        s1, wall = _state_seconds(), time.perf_counter() - t0
        assert sorted(out) == [b'[["p",{"q":0}]]', b'[["p",{"q":1}]]']
        d = {k: s1[k] - s0[k] for k in s0}
        assert sum(d.values()) == pytest.approx(wall, rel=0.01, abs=0.06)
        assert d["collect"] >= 0.045  # behind the first, then its own dispatch
        assert 0.0 < d["dispatch"] < d["collect"]

    def test_many_request_threads_share_one_slot(self, worker):
        """More threads than cores, the interpreter switching every 10 us:
        no two dispatches overlap, every query gets ITS answer, every row
        is dispatched once, and the slot and the count of waiting items
        come back to rest (a lost update of ``_waiting`` would keep every
        later query off the inline path)."""
        batcher, _ = worker
        srv = batcher._server
        inside, overlaps = [0], []

        class Exclusive(_Algo):
            def _enter(self):
                inside[0] += 1
                if inside[0] != 1:
                    overlaps.append(inside[0])
                time.sleep(0.0002)
                inside[0] -= 1

            def predict(self, model, sup):
                self._enter()
                return ("p", sup)

            def batch_predict(self, model, indexed):
                self._enter()
                return [(i, ("p", sup)) for i, sup in indexed]

        srv._default_variant = _Variant(Exclusive())
        real = metrics.counter("pio_batch_rows_total", kind="real")
        rows0, p0 = real.value(), (_path_count("inline"), _path_count("worker"))
        n_threads, each = 4 * (os.cpu_count() or 4), 40
        wrong = []

        def client(k):
            for i in range(each):
                body = {"q": k * each + i}
                got = json.loads(srv._serve_batched(body))
                if got != [["p", body]]:
                    wrong.append((body, got))

        was = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            threads = [threading.Thread(target=client, args=(k,)) for k in range(n_threads)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
                assert not t.is_alive()
        finally:
            sys.setswitchinterval(was)
        _settle(batcher)
        assert not wrong and not overlaps
        assert real.value() == rows0 + n_threads * each
        inline, by_worker = _path_count("inline") - p0[0], _path_count("worker") - p0[1]
        assert inline >= 1 and by_worker >= 1  # both paths ran
        assert batcher._waiting == 0 and not batcher._slot.locked()

    def test_resolve_is_an_annotation_while_a_profile_runs(self, worker, monkeypatch):
        batcher, submit = worker
        seen = []
        _settle(batcher)
        monkeypatch.setattr(obs_trace, "_annotation", lambda name: _Ann(name, seen))
        obs_trace.set_annotating(True)
        try:
            for f in submit(_Variant(_Algo())):
                f.result(timeout=5)
            _settle(batcher)
        finally:
            obs_trace.set_annotating(False)
        names = [(io, n) for io, n, _ in seen]
        i = names.index(("in", "batch.resolve"))
        assert names.index(("out", "batch.dispatch[1]")) < i < names.index(("out", "batch.resolve"))
        assert len({th for _, _, th in seen}) == 1  # all on the worker's line


# -- 2. on-CPU against wall ----------------------------------------------------


class TestRegionCpu:
    def test_sleep_reads_no_cpu_and_a_busy_loop_reads_its_wall(self):
        h = metrics.Histogram("t_cpu_seconds", "")
        with obs_trace.region("sleeps", cpu_hist=h) as r:
            time.sleep(0.05)
        _, cpu, n = h.merged()
        assert n == 1 and cpu < 0.01 and r.seconds >= 0.05
        h2 = metrics.Histogram("t_cpu2_seconds", "")
        with obs_trace.region("spins", cpu_hist=h2) as r2:
            end = time.thread_time() + 0.05  # 50 ms ON the CPU, however long it takes
            while time.thread_time() < end:
                pass
        _, cpu2, _ = h2.merged()
        # all of a busy loop's wall time but what the machine took from it
        assert 0.05 <= cpu2 <= r2.seconds + 1e-3 and cpu2 < 0.06

    def test_no_thread_time_call_without_cpu_hist(self, monkeypatch):
        calls = []
        real = time.thread_time
        monkeypatch.setattr(time, "thread_time", lambda: calls.append(1) or real())
        h = metrics.Histogram("t_wall_seconds", "")
        with obs_trace.region("plain", hist=h):
            with obs_trace.region("plain.inner"):
                pass
        assert calls == []
        hc = metrics.Histogram("t_c_seconds", "")
        for _ in range(2 * obs_trace.CPU_EVERY):
            with obs_trace.region("timed", cpu_hist=hc, hist=h):
                pass
        # one call in CPU_EVERY reads the clock (twice), the first among them
        assert len(calls) == 4 and hc.merged()[2] == 2
        assert h.merged()[2] == 1 + 2 * obs_trace.CPU_EVERY

    def test_the_three_enqueue_regions_carry_a_cpu_histogram(self):
        """The stages that only enqueue: the dispatch and (module level, so a
        jax import is needed only here) the shortlist and the rescore."""
        from predictionio_tpu.ops import retrieval

        assert retrieval._shortlist_stage.keywords["cpu_hist"].name == \
            "pio_retrieval_shortlist_cpu_seconds"
        assert retrieval._rescore_stage.keywords["cpu_hist"].name == \
            "pio_retrieval_rescore_cpu_seconds"
        assert retrieval._shortlist_stage.args == ("dispatch.shortlist",)


# -- 3. collector pauses -------------------------------------------------------


class TestCollector:
    def test_a_full_collection_in_a_region_is_a_child_span(self, armed, monkeypatch):
        h2 = metrics.histogram("pio_gc_pause_seconds", generation="2")
        n0 = h2.merged()[2]
        monkeypatch.setattr(runtime, "_GC_SPAN_S", 0.0)  # a small heap collects in < 1 ms
        tr = obs_trace.Trace("t")
        with obs_trace.use_trace(tr):
            with obs_trace.region("outer") as r:
                gc.collect()
        assert h2.merged()[2] == n0 + 1
        spans = {s[0]: s for s in tr.spans}
        name, off, dur, parent = spans["gc.pause[2]"]
        assert parent == "outer" and dur > 0.0
        assert spans["outer"][1] <= off and off + dur <= spans["outer"][1] + spans["outer"][2]
        # it entered the region's children: self time is less by the pause
        assert r.self_seconds == pytest.approx(r.seconds - dur, abs=1e-9)
        blk = armed.block()
        assert blk["armed"] and blk["gc"]["full"] >= 1 and blk["gc"]["pause_s"] >= dur - 1e-6
        assert len(blk["gc"]["stats"]) == 3 and len(blk["gc"]["threshold"]) == 3
        assert isinstance(blk["gc"]["frozen"], int)

    def test_a_short_pause_is_counted_and_leaves_no_span(self, armed):
        h0 = metrics.histogram("pio_gc_pause_seconds", generation="0")
        n0 = h0.merged()[2]
        tr = obs_trace.Trace("t")
        with obs_trace.use_trace(tr):
            with obs_trace.region("outer") as r:
                gc.collect(0)
        assert h0.merged()[2] == n0 + 1
        assert [s[0] for s in tr.spans] == ["outer"] and r.self_seconds == r.seconds

    def test_generation_two_is_an_annotation_while_a_profile_runs(self, armed, monkeypatch):
        seen = []
        monkeypatch.setattr(obs_trace, "_annotation", lambda name: _Ann(name, seen))
        obs_trace.set_annotating(True)
        try:
            gc.collect(1)
            assert seen == []
            gc.collect()
        finally:
            obs_trace.set_annotating(False)
        assert [e[:2] for e in seen] == [("in", "gc.pause[2]"), ("out", "gc.pause[2]")]

    def test_the_hook_is_cheap(self, armed):
        """Two calls a collection; held loosely here (a shared CPU), the
        measured cost is in PERF.md."""
        info = {"generation": 0, "collected": 0, "uncollectable": 0}
        n = 20000
        t0 = time.perf_counter()
        for _ in range(n):
            runtime._on_gc("start", info)
            runtime._on_gc("stop", info)
        assert (time.perf_counter() - t0) / n < 20e-6


# -- 4. process stops ----------------------------------------------------------


def _hold_the_interpreter(seconds: float) -> float:
    """Spin without giving the interpreter up: with the switch interval
    out of reach no other thread is handed it at a bytecode boundary, as
    none is inside one long C call. -> how long it held."""
    was = sys.getswitchinterval()
    sys.setswitchinterval(30.0)
    try:
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            pass
        return time.perf_counter() - t0
    finally:
        sys.setswitchinterval(was)


class TestBeat:
    def test_a_held_interpreter_is_one_record_with_cpu(self, armed, caplog):
        time.sleep(0.1)  # the beat is running
        n0 = len(armed.block()["stalls"])
        cpu0 = metrics.counter("pio_process_stall_cpu_seconds_total").value()
        with caplog.at_level(logging.WARNING, logger="predictionio_tpu.obs.runtime"):
            held = _hold_the_interpreter(0.2)
            time.sleep(0.1)
        stalls = armed.block()["stalls"][n0:]
        assert held >= 0.2
        # one record for the one stop (a loaded machine may add short ones)
        long = [s for s in stalls if s["late_ms"] >= 150.0 - 20.0]
        assert len(long) == 1, stalls
        rec = long[0]
        assert rec["late_ms"] <= 1e3 * held + 100.0
        # a thread of ours burned the CPU for as long as the beat was late
        assert rec["cpu_ms"] >= 0.5 * rec["late_ms"]
        assert rec["worker"] is None or rec["worker"] in runtime.STATES
        lines = [r for r in caplog.records if "process stall" in r.getMessage()]
        assert len(lines) == len(stalls) and lines[0].levelno == logging.WARNING
        assert metrics.counter("pio_process_stall_cpu_seconds_total").value() - cpu0 \
            == pytest.approx(sum(s["cpu_ms"] for s in stalls) / 1e3, abs=1e-3)
        late = metrics.histogram("pio_process_stall_seconds")
        assert late.merged()[2] > 0 and late.percentile(1.0) >= 0.1

    def test_a_stopped_process_is_one_record_without_cpu(self, tmp_path):
        child = (
            "import json, sys, time\n"
            "from predictionio_tpu.obs import runtime\n"
            "runtime.arm()\n"
            "time.sleep(0.2)\n"
            "print('ready', flush=True)\n"
            "sys.stdin.readline()\n"
            "time.sleep(0.1)\n"
            "print(json.dumps(runtime.block()['stalls']), flush=True)\n"
        )
        env = {**os.environ, "PYTHONPATH": ROOT, "PIO_OBS": "1"}
        proc = subprocess.Popen(
            [sys.executable, "-c", child], stdin=subprocess.PIPE,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=env,
        )
        try:
            assert proc.stdout.readline().strip() == "ready"
            os.kill(proc.pid, signal.SIGSTOP)
            time.sleep(0.3)
            os.kill(proc.pid, signal.SIGCONT)
            proc.stdin.write("\n")
            proc.stdin.flush()
            out, err = proc.communicate(timeout=20)
        finally:
            if proc.poll() is None:
                proc.kill()
        stalls = json.loads(out.strip().splitlines()[-1])
        long = [s for s in stalls if s["late_ms"] >= 250.0]
        assert len(long) == 1, (stalls, err)
        assert long[0]["cpu_ms"] <= 30.0 and long[0]["gc_full"] == 0
        assert err.count("process stall") == len(stalls)  # the log line, on stderr

    def test_the_record_names_the_collector(self, armed):
        """A stop that is a full collection says so: collector seconds and
        full collections inside it come with the record."""
        beat = runtime._beat
        n0 = len(armed.block()["stalls"])
        beat._record(0.2, 0.19, 0.18, 1)
        rec = armed.block()["stalls"][n0]
        assert (rec["late_ms"], rec["cpu_ms"], rec["gc_ms"], rec["gc_full"]) == \
            (200.0, 190.0, 180.0, 1)
        for _ in range(40):
            beat._record(0.06, 0.0, 0.0, 0)
        assert len(armed.block()["stalls"]) == 16  # a ring


# -- inert under PIO_OBS=0 -----------------------------------------------------


def test_everything_is_inert_under_pio_obs_0():
    child = (
        "import gc, json, threading, time\n"
        "from predictionio_tpu.obs import metrics, runtime, trace\n"
        "from predictionio_tpu.server.http import Router, add_obs_routes\n"
        "add_obs_routes(Router())\n"
        "clock = runtime.WorkerClock()\n"
        "clock.to('dispatch'); clock.to('idle')\n"
        "h = metrics.Histogram('x_seconds', '')\n"
        "with trace.region('r', cpu_hist=h):\n"
        "    gc.collect()\n"
        "print(json.dumps({\n"
        "  'callbacks': len(gc.callbacks),\n"
        "  'threads': sorted(t.name for t in threading.enumerate()),\n"
        "  'armed': runtime.block()['armed'], 'stalls': runtime.block()['stalls'],\n"
        "  'cpu_obs': h.merged()[2],\n"
        "  'worker': [metrics.counter('pio_batch_worker_seconds_total', state=s).value()\n"
        "             for s in runtime.STATES],\n"
        "  'text': metrics.render_prometheus().decode(),\n"
        "}))\n"
    )
    env = {**os.environ, "PYTHONPATH": ROOT, "PIO_OBS": "0"}
    out = subprocess.run(
        [sys.executable, "-c", child], capture_output=True, text=True,
        env=env, timeout=60,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["callbacks"] == 0 and got["armed"] is False and got["stalls"] == []
    assert "obs-beat" not in got["threads"]
    assert got["cpu_obs"] == 0 and got["worker"] == [0, 0, 0, 0]
    assert "pio_gc_pause_seconds" not in got["text"]
    assert "pio_process_stall_seconds" not in got["text"]


def test_arming_is_once_and_mounting_the_routes_arms(armed):
    from predictionio_tpu.server.http import Router, add_obs_routes

    add_obs_routes(Router())
    runtime.arm()
    assert gc.callbacks.count(runtime._on_gc) == 1
    assert [t.name for t in threading.enumerate()].count("obs-beat") == 1
    text = metrics.render_prometheus().decode()
    for g in "012":
        assert f'pio_gc_pause_seconds_count{{generation="{g}"}}' in text
    assert "pio_process_stall_seconds_count" in text
    assert "pio_process_stall_cpu_seconds_total" in text


# -- the per-layer readers -----------------------------------------------------


def _bucket(le: str) -> str:
    return 'pio_process_stall_seconds_bucket{le="%s"}' % le


DELTA = {
    'pio_batch_worker_seconds_total{state="idle"}': 15.0,
    'pio_batch_worker_seconds_total{state="collect"}': 0.25,
    'pio_batch_worker_seconds_total{state="dispatch"}': 4.5,
    'pio_batch_worker_seconds_total{state="resolve"}': 0.25,
    "pio_batch_dispatch_seconds_sum": 4.5, "pio_batch_dispatch_seconds_count": 1000.0,
    "pio_batch_dispatch_cpu_seconds_sum": 0.17875, "pio_batch_dispatch_cpu_seconds_count": 143.0,
    "pio_retrieval_shortlist_seconds_sum": 0.75, "pio_retrieval_shortlist_seconds_count": 1000.0,
    "pio_retrieval_rescore_seconds_sum": 0.75, "pio_retrieval_rescore_seconds_count": 1000.0,
    # the CPU clock is read on one call in seven: fewer observations, the same means
    "pio_retrieval_shortlist_cpu_seconds_sum": 0.0715, "pio_retrieval_shortlist_cpu_seconds_count": 143.0,
    "pio_retrieval_rescore_cpu_seconds_sum": 0.03575, "pio_retrieval_rescore_cpu_seconds_count": 143.0,
    'pio_gc_pause_seconds_sum{generation="0"}': 0.004,
    'pio_gc_pause_seconds_sum{generation="1"}': 0.001,
    'pio_gc_pause_seconds_sum{generation="2"}': 0.120,
    'pio_gc_pause_seconds_count{generation="2"}': 1.0,
    _bucket("0.00032"): 900.0, _bucket("0.00064"): 990.0, _bucket("0.65536"): 990.0,
    _bucket("1.31072"): 991.0, _bucket("2.62144"): 991.0, _bucket("+Inf"): 991.0,
    "pio_process_stall_seconds_count": 991.0,
}
EXPECT = {
    "worker_busy_share": 25.0, "worker_turnaround_ms": 0.5, "enqueue_offcpu_ms": 0.75,
    "dispatch_cpu_ms": 1.25, "gc_pause_ms_sum": 125.0, "proc_stall_ms_max": 1311.0,
}
LAYER = {
    "worker_busy_share": "HTTP and batcher", "worker_turnaround_ms": "HTTP and batcher",
    "enqueue_offcpu_ms": "score", "dispatch_cpu_ms": "dispatch",
    "gc_pause_ms_sum": "dispatch", "proc_stall_ms_max": "HTTP and batcher",
}
CELLS = {
    "worker_busy_share": [STEADY, ITEMPAGE, INT8, LIVE], "worker_turnaround_ms": [STEADY],
    "enqueue_offcpu_ms": [STEADY, ITEMPAGE], "dispatch_cpu_ms": [STEADY, INT8],
    "gc_pause_ms_sum": [STEADY, ITEMPAGE], "proc_stall_ms_max": [STEADY, ITEMPAGE, LIVE],
}
NAMES = [n + sfx for sfx in ("", ".saturated") for n in EXPECT]


@pytest.mark.parametrize("name", NAMES)
def test_reader_reads_the_counters_or_nothing(name):
    base = name.removesuffix(".saturated")
    (entry,) = [m for m in MANIFEST["per_layer"] if m["name"] == name]
    twin = name != base
    assert entry["moves"] == ("serve_qps" if twin else "query_p50_ms")
    assert entry["workloads"] == ([SATURATED] if twin else CELLS[base])
    assert (entry["source"], entry["layer"]) == ("program_counter", LAYER[base])
    read = readers.load_metric(METRICS_DIR, name)
    got = read({"counters_delta": DELTA}, {})
    assert got == pytest.approx(EXPECT[base])
    if base != "dispatch_cpu_ms":  # four significant digits keep the line short
        assert len(repr(float(got))) <= 7
    # the parent commit has none of these series: nothing, and no raise
    old = {"pio_batch_dispatch_seconds_sum": 1.0, "pio_batch_dispatch_seconds_count": 9.0,
           "pio_retrieval_shortlist_seconds_sum": 0.7, "pio_retrieval_rescore_seconds_sum": 0.2}
    assert read({"counters_delta": old}, {}) is None
    assert read({"counters_delta": {}}, {}) is None
    assert read({}, {}) is None


def test_the_readers_round_and_read_the_edges():
    busy = readers.load_metric(METRICS_DIR, "worker_busy_share")
    d = dict(DELTA)
    d['pio_batch_worker_seconds_total{state="idle"}'] = 14.0 / 3.0
    assert busy({"counters_delta": d}, {}) == 51.72  # 51.7241...
    stall = readers.load_metric(METRICS_DIR, "proc_stall_ms_max")
    quiet = {k: (990.0 if "bucket" in k and v > 990.0 else v) for k, v in DELTA.items()}
    assert stall({"counters_delta": quiet}, {}) == 0.64
    over = {**DELTA, _bucket("+Inf"): 992.0}
    assert stall({"counters_delta": over}, {}) == 5243.0  # twice the last edge, 5242.88
    none = {k: 0.0 for k in DELTA if "stall" in k}
    assert stall({"counters_delta": none}, {}) is None
    gcs = readers.load_metric(METRICS_DIR, "gc_pause_ms_sum")
    zero = {k: 0.0 for k in DELTA if "gc_pause" in k}
    assert gcs({"counters_delta": zero}, {}) == 0.0


def test_the_manifest_is_clean_and_each_cell_lists_its_own():
    assert manifest.validate(MANIFEST, ROOT) == []
    for cell in (w["name"] for w in MANIFEST["workloads"]):
        traced = {d["name"] for d in bench_run.metrics_for(MANIFEST, cell, True)}
        want = {n for n in NAMES
                if cell in next(m for m in MANIFEST["per_layer"] if m["name"] == n)["workloads"]}
        assert traced & set(NAMES) == want, cell
        assert not set(NAMES) & {d["name"] for d in bench_run.metrics_for(MANIFEST, cell, False)}
    assert len([n for n in NAMES if n.endswith(".saturated")]) == 6


def test_a_collection_inside_a_read_of_its_own_histogram_does_not_deadlock():
    """``Histogram.merged`` allocates while it holds a stripe's lock; a
    collection that starts there runs the ``gc.callbacks`` hook on the same
    thread, which observes into a histogram — with a plain lock and the
    histogram being read, that thread waited for itself (the flicker of
    ``test_bench_smoke.py``). The stripe's lock is re-entrant."""
    h = metrics.Histogram("t_gc_reentrant_seconds", "test")
    h.observe(0.001)  # this thread's stripe
    stripe = h._stripes[metrics._tls.stripe]
    with stripe.lock:  # as merged() holds it
        h.observe(0.002)  # as _on_gc would, on the same thread
    assert h.merged()[2] == 2
