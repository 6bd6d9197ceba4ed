"""The one way to record a stage (``obs.trace.region``) and the serving
span chain it closes: socket to socket, every dispatch counted, spans on
the profiler's clock while a capture runs.

Runs on XLA:CPU; the live-server tests train a small ALS model with the
two-stage retrieval threshold forced under the fixture catalog, so the
score layer's own spans are in the chain.
"""

from __future__ import annotations

import glob
import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

from predictionio_tpu.obs import metrics
from predictionio_tpu.obs import trace as obs_trace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

STAGES = (
    "http.handoff", "http.read_parse", "dispatch", "serve", "serve.submit",
    "batch.queue_wait", "batch.dispatch[1]", "dispatch.shortlist",
    "dispatch.rescore", "dispatch.fetch", "serve.wake", "serve.tail",
    "http.write",
)
# below the stages, the crossings of a ``UserRows`` dispatch (PR 50): the
# vectors up and the scan launched, the indices up behind it and the
# rescore launched, the wait and the copy back — once a crossing
CROSSINGS = (
    ("xfer.h2d[serve.dispatch]", "dispatch.shortlist"),
    ("launch[retrieval.coarse_topk]", "dispatch.shortlist"),
    ("xfer.h2d[serve.dispatch]", "dispatch.rescore"),
    ("launch[retrieval.rescore_gather]", "dispatch.rescore"),
    ("fetch.wait", "dispatch.fetch"),
    ("xfer.d2h[serve.answers]", "dispatch.fetch"),
)
CHAIN = STAGES + tuple(name for name, _ in CROSSINGS)
PARENTS = {
    "http.handoff": None, "http.read_parse": None, "dispatch": None,
    "http.write": None, "serve": "dispatch", "serve.submit": "serve",
    "batch.queue_wait": "serve", "batch.dispatch[1]": "serve",
    "dispatch.shortlist": "batch.dispatch[1]",
    "dispatch.rescore": "batch.dispatch[1]",
    "dispatch.fetch": "batch.dispatch[1]",
    "serve.wake": "serve", "serve.tail": "serve",
}
NEW_HISTOGRAMS = (
    ("pio_http_handoff_seconds", {"server": "engine"}),
    ("pio_http_write_seconds", {"server": "engine"}),
    ("pio_serving_submit_seconds", {}),
    ("pio_serving_wake_seconds", {}),
    ("pio_serving_tail_seconds", {}),
    ("pio_batch_dispatch_self_seconds", {}),
)


# -- the helper ---------------------------------------------------------------


class TestRegion:
    def test_records_parent_and_nests(self):
        tr = obs_trace.Trace("t")
        h = metrics.histogram("test_region_seconds")
        n0 = h.merged()[2]
        with obs_trace.use_trace(tr):
            with obs_trace.region("outer") as outer:
                with obs_trace.region("inner", hist=h) as inner:
                    time.sleep(0.002)
                with obs_trace.region("inner2"):
                    pass
        by_name = {s["name"]: s for s in tr.to_dict()["spans"]}
        assert by_name["outer"]["parent"] is None
        assert by_name["inner"]["parent"] == "outer"
        assert by_name["inner2"]["parent"] == "outer"
        assert h.merged()[2] == n0 + 1
        assert inner.seconds >= 0.002
        # self time = duration - children, computable from the dicts too
        assert outer.self_seconds == pytest.approx(
            outer.seconds - inner.seconds
            - tr.spans[1][2]  # inner2's duration
        )
        assert obs_trace.current_trace() is None

    def test_backdated_start_and_explicit_trace(self):
        tr = obs_trace.Trace("t")
        t_early = time.perf_counter()
        time.sleep(0.001)
        with obs_trace.region("late", trace=tr, start=t_early) as r:
            pass
        assert r.start == t_early and r.seconds >= 0.001
        assert tr.spans[0][0] == "late"

    def test_fanout_lands_on_every_batchmate(self):
        a, b = obs_trace.Trace("a"), obs_trace.Trace("b")
        with obs_trace.use_trace(obs_trace.Fanout([a, None, b]), parent="serve"):
            with obs_trace.region("batch.dispatch[2]"):
                with obs_trace.region("dispatch.shortlist"):
                    pass
        for tr in (a, b):
            assert [(s[0], s[3]) for s in tr.spans] == [
                ("dispatch.shortlist", "batch.dispatch[2]"),
                ("batch.dispatch[2]", "serve"),
            ]

    def test_disabled_is_a_noop(self):
        tr = obs_trace.Trace("t")
        h = metrics.histogram("test_region_disabled_seconds")
        metrics.set_enabled(False)
        try:
            with obs_trace.use_trace(tr):
                with obs_trace.region("x", hist=h):
                    with obs_trace.annotate("y"):
                        pass
        finally:
            metrics.set_enabled(True)
        assert tr.spans == [] and h.merged()[2] == 0

    def test_no_profile_no_jax(self):
        """obs.trace imports without jax, and using regions with no
        profile running never imports jax.profiler."""
        code = (
            "import sys\n"
            "from predictionio_tpu.obs import trace\n"
            "tr = trace.Trace('t')\n"
            "with trace.use_trace(tr):\n"
            "    with trace.region('a'):\n"
            "        with trace.annotate('b'):\n"
            "            pass\n"
            "assert [s[0] for s in tr.spans] == ['a']\n"
            "bad = [m for m in sys.modules if m == 'jax' or m.startswith('jax.')]\n"
            "assert not bad, bad\n"
        )
        env = {**os.environ, "PYTHONPATH": ROOT}
        r = subprocess.run(
            [sys.executable, "-c", code], env=env, capture_output=True,
            text=True, timeout=60,
        )
        assert r.returncode == 0, r.stderr

    def test_annotation_reaches_the_xplane(self, tmp_path):
        """With a profile running the helper's names are in the
        ``.xplane.pb``, on the profiler's clock; the Python tracer is off
        by default (no per-call frames in the host plane)."""
        import jax
        import jax.numpy as jnp
        from jax.profiler import ProfileData

        from predictionio_tpu.obs import device as obs_device

        out = str(tmp_path / "prof")
        box = {}
        cap = threading.Thread(
            target=lambda: box.update(
                obs_device.profile_capture(0.5, out_dir=out)
            )
        )
        f = jax.jit(lambda x: (x @ x.T).sum())
        x = jnp.ones((64, 64), jnp.float32)
        f(x).block_until_ready()
        cap.start()
        deadline = time.monotonic() + 30
        while cap.is_alive() and time.monotonic() < deadline:
            with obs_trace.region("unit.region"):
                with obs_trace.annotate("unit.annotation"):
                    f(x).block_until_ready()
                    time.sleep(0.002)
        cap.join(timeout=30)
        assert not cap.is_alive() and box["files"] >= 1
        assert not obs_device.profile_active()
        path = max(
            glob.glob(os.path.join(out, "**", "*.xplane.pb"), recursive=True),
            key=os.path.getmtime,
        )
        names = set()
        for plane in ProfileData.from_file(path).planes:
            for line in plane.lines:
                names.update(e.name for e in line.events)
        assert "unit.region" in names and "unit.annotation" in names
        # a Python-tracer event would be named after this very function
        assert not any("test_annotation_reaches_the_xplane" in n for n in names)
        # and once the capture is over the helper stops annotating
        assert obs_trace.annotate("after") is obs_trace.annotate("after2")


# -- the live server ----------------------------------------------------------


def _hist_count(name, labels):
    return metrics.histogram(name, **labels).merged()[2]


def _counter(name, **labels):
    return metrics.counter(name, **labels).value()


# which thread dispatches a lone query (ISSUE 47): the batch worker's
# where the batcher window-waits, the request's own where it does not
PATHS = pytest.mark.parametrize("served", ["worker", "inline"], indirect=True)


@pytest.fixture()
def served(storage, monkeypatch, request):
    """A live EngineServer over a 48-item ALS model with two-stage
    retrieval forced on and the batcher window-waiting (40 ms) — or,
    asked for ``inline``, not: a lone query keeps its request thread."""
    from predictionio_tpu.cli import commands
    from predictionio_tpu.core import EngineParams
    from predictionio_tpu.core.workflow import run_train
    from predictionio_tpu.data.event import Event
    from predictionio_tpu.models import recommendation as rec
    from predictionio_tpu.server.engine_server import EngineServer

    monkeypatch.setenv("PIO_RETRIEVAL_THRESHOLD", "16")
    monkeypatch.setenv("PIO_RETRIEVAL_TILE", "16")
    monkeypatch.setenv("PIO_RETRIEVAL_PROBE_EVERY", "0")
    # the read is told apart on one dispatch in CPU_EVERY: here on every one
    monkeypatch.setattr(obs_trace, "CPU_EVERY", 1)
    info = commands.app_new("TraceChainApp", storage=storage)
    events = storage.get_events()
    rng = np.random.default_rng(0)
    for u in range(12):
        for i in rng.choice(48, size=12, replace=False):
            events.insert(
                Event(
                    event="rate", entity_type="user", entity_id=f"u{u}",
                    target_entity_type="item", target_entity_id=f"i{int(i)}",
                    properties={"rating": float(rng.integers(1, 6))},
                ),
                info["id"],
            )
    engine = rec.engine()
    ep = EngineParams(
        datasource=("", rec.DataSourceParams(app_name="TraceChainApp")),
        algorithms=[("als", rec.ALSAlgorithmParams(rank=4, num_iterations=2))],
    )
    run_train(engine, ep, engine_id="trace-chain", storage=storage)
    instance = storage.get_metadata_engine_instances().get_latest_completed(
        "trace-chain", "0", "default"
    )
    lone_path = getattr(request, "param", "worker")
    server = EngineServer(
        engine, instance, storage=storage, host="127.0.0.1", port=0,
        batch_window_ms=40.0,
        dispatch_cost_s=1.0 if lone_path == "worker" else 0.0,  # window-wait: batches form
    )
    server.lone_path = lone_path
    port = server.start()
    try:
        yield server, port
    finally:
        server.stop()


def _query(port, user, trace_id=None):
    headers = {"Content-Type": "application/json"}
    if trace_id:
        headers["X-PIO-Trace"] = trace_id
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/queries.json",
        data=json.dumps({"user": user, "num": 3}).encode(),
        method="POST", headers=headers,
    )
    with urllib.request.urlopen(req, timeout=30) as resp:
        assert resp.status == 200
        return json.loads(resp.read())


def _retained(trace_id, want_write=True):
    """The ring's entry for ``trace_id`` (http.write is appended after the
    response left, so wait for it)."""
    deadline = time.monotonic() + 5
    while True:
        for t in obs_trace.TRACES.snapshot():
            if t["traceId"] == trace_id:
                names = [s["name"] for s in t["spans"]]
                if not want_write or "http.write" in names:
                    return t
        assert time.monotonic() < deadline, f"trace {trace_id} not retained"
        time.sleep(0.01)


def _burst(port, users, prefix):
    threads = [
        threading.Thread(target=_query, args=(port, u, f"{prefix}{i:08x}"))
        for i, u in enumerate(users)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
        assert not t.is_alive()


class TestServingChain:
    @PATHS
    def test_one_request_yields_every_span_once(self, served):
        """On either path: the same chain, the same parents, one
        observation a histogram — no hole where no thread was switched."""
        server, port = served
        paths0 = {p: _counter("pio_batch_dispatch_path_total", path=p)
                  for p in ("inline", "worker")}
        # compiles; its trace is not the one read, but its http.write is
        # recorded after the response left: wait for it, or it counts below
        _query(port, "u0", "feedc0de00000000")
        _retained("feedc0de00000000")
        obs_trace.TRACES.clear()
        before = {n: _hist_count(n, lab) for n, lab in NEW_HISTOGRAMS}
        rows = (_counter("pio_batch_rows_total", kind="real"),
                _counter("pio_batch_rows_total", kind="padded"))
        got = _query(port, "u1", "feedc0de00000001")
        assert len(got["itemScores"]) == 3
        t = _retained("feedc0de00000001")
        spans = t["spans"]
        names = [s["name"] for s in spans]
        assert sorted(names) == sorted(CHAIN), names
        by_name = {s["name"]: s for s in spans}
        eps = 2e-3  # offsets and durations are rounded to 1 us each
        assert sorted(
            (s["name"], s["parent"]) for s in spans if s["name"] not in PARENTS
        ) == sorted(CROSSINGS)
        for stage in ("dispatch.shortlist", "dispatch.rescore", "dispatch.fetch"):
            inside = sum(s["durationMs"] for s in spans if s["parent"] == stage)
            assert inside <= by_name[stage]["durationMs"] + 4 * eps, stage
        for s in spans:
            if s["name"] in PARENTS:
                assert s["parent"] == PARENTS[s["name"]], s
            assert s["offsetMs"] >= -eps
            if s["parent"] is not None:
                p = by_name[s["parent"]]
                assert s["offsetMs"] >= p["offsetMs"] - eps, (s, p)
                assert (s["offsetMs"] + s["durationMs"]
                        <= p["offsetMs"] + p["durationMs"] + eps), (s, p)
        # request order along the chain
        order = [
            "http.handoff", "http.read_parse", "serve.submit",
            "batch.queue_wait", "batch.dispatch[1]", "serve.wake",
            "serve.tail", "http.write",
        ]
        starts = [by_name[n]["offsetMs"] for n in order]
        assert starts == sorted(starts), list(zip(order, starts))
        # the stages account for the request, socket to socket (what is
        # left is router matching and bookkeeping between the stages;
        # the allowance is for a thread switch landing there)
        covered = sum(by_name[n]["durationMs"] for n in order)
        whole = by_name["http.write"]["offsetMs"] + by_name["http.write"]["durationMs"]
        assert covered <= whole + 8 * eps
        assert whole - covered < max(5.0, 0.2 * whole), (whole, covered)
        for n, lab in NEW_HISTOGRAMS:
            assert _hist_count(n, lab) == before[n] + 1, n
        assert _counter("pio_batch_rows_total", kind="real") == rows[0] + 1
        assert _counter("pio_batch_rows_total", kind="padded") == rows[1] + 1
        # both queries went the fixture's way, and only that way
        for p, n0 in paths0.items():
            assert _counter("pio_batch_dispatch_path_total", path=p) \
                == n0 + 2 * (p == server.lone_path), p
        if server.lone_path == "inline":
            # no thread was switched: the two hops are clock readings apart
            assert by_name["batch.queue_wait"]["durationMs"] < 1.0
            assert by_name["serve.wake"]["durationMs"] < 1.0

    def test_every_dispatch_is_counted(self, served):
        """Singles and multi-item batches alike: the dispatch histogram
        and the batch-size histogram count the same events, and the row
        counters add up to the queries served."""
        _, port = served
        _query(port, "u0")
        size0 = _hist_count("pio_batch_size", {})
        disp0 = _hist_count("pio_batch_dispatch_seconds", {})
        self0 = _hist_count("pio_batch_dispatch_self_seconds", {})
        real0 = _counter("pio_batch_rows_total", kind="real")
        pad0 = _counter("pio_batch_rows_total", kind="padded")
        for u in ("u1", "u2", "u3"):
            _query(port, u)
        _burst(port, ["u4", "u5", "u6", "u7", "u8"], "b0b0b0b0")
        _query(port, "u9")
        _burst(port, ["u1", "u2", "u3"], "b1b1b1b1")
        served_n = 3 + 5 + 1 + 3
        d_size = _hist_count("pio_batch_size", {}) - size0
        d_disp = _hist_count("pio_batch_dispatch_seconds", {}) - disp0
        assert d_disp == d_size
        assert _hist_count("pio_batch_dispatch_self_seconds", {}) - self0 == d_disp
        assert 4 <= d_disp < served_n  # the singles, and at least one batch
        real = _counter("pio_batch_rows_total", kind="real") - real0
        padded = _counter("pio_batch_rows_total", kind="padded") - pad0
        assert real == served_n
        assert real <= padded

    def test_singles_do_not_leak_into_the_next_batch(self, served):
        """The old side channel added every single's stage seconds to
        the next batch's spans. Now a batch's dispatch.shortlist lies
        inside its own batch.dispatch."""
        _, port = served
        _query(port, "u0")
        _burst(port, ["u1", "u2", "u3", "u4"], "aaaaaaaa")  # compile the batch shapes
        for u in ("u1", "u2", "u3", "u4", "u5", "u6"):
            _query(port, u)  # k singles
        obs_trace.TRACES.clear()
        _burst(port, ["u7", "u8", "u9", "u10"], "cafe0000")
        batched = 0
        for i in range(4):
            t = _retained(f"cafe0000{i:08x}", want_write=False)
            by_name = {s["name"]: s for s in t["spans"]}
            disp = [n for n in by_name if n.startswith("batch.dispatch[")]
            assert len(disp) == 1, by_name.keys()
            d, sl = by_name[disp[0]], by_name["dispatch.shortlist"]
            batched += disp[0] != "batch.dispatch[1]"
            assert sl["parent"] == disp[0]
            assert sl["durationMs"] <= d["durationMs"]
            assert sl["offsetMs"] >= d["offsetMs"] - 2e-3
            assert (sum(by_name[n]["durationMs"] for n in (
                "dispatch.shortlist", "dispatch.rescore", "dispatch.fetch"
            )) <= d["durationMs"] + 6e-3)
        assert batched >= 2  # the burst did coalesce

    @PATHS
    def test_pio_obs_off_records_none(self, served):
        _, port = served
        _query(port, "u0")
        obs_trace.TRACES.clear()
        before = {n: _hist_count(n, lab) for n, lab in NEW_HISTOGRAMS}
        before["pio_batch_dispatch_seconds"] = _hist_count(
            "pio_batch_dispatch_seconds", {})
        real0 = _counter("pio_batch_rows_total", kind="real")
        metrics.set_enabled(False)
        try:
            got = _query(port, "u1", "0ff0000000000001")
        finally:
            metrics.set_enabled(True)
        assert len(got["itemScores"]) == 3
        time.sleep(0.05)
        assert obs_trace.TRACES.snapshot() == []
        for n, lab in NEW_HISTOGRAMS:
            assert _hist_count(n, lab) == before[n], n
        assert _hist_count("pio_batch_dispatch_seconds", {}) \
            == before["pio_batch_dispatch_seconds"]
        assert _counter("pio_batch_rows_total", kind="real") == real0

    def test_unbatched_dispatch_is_a_span_too(self, served):
        """The unbatched handle_query path (batcher off or failed over)
        records batch.dispatch[1] with the score stages under it."""
        server, _ = served
        tr = obs_trace.Trace("direct")
        disp0 = _hist_count("pio_batch_dispatch_seconds", {})
        size0 = _hist_count("pio_batch_size", {})
        with obs_trace.use_trace(tr):
            out = server.handle_query({"user": "u2", "num": 3})
        assert len(out["itemScores"]) == 3
        got = {s[0]: s[3] for s in tr.spans if not s[0].startswith("gc.pause")}
        # the dispatch that first scores also stages the model (PR 41)
        staged = {got.pop(n, "batch.dispatch[1]")
                  for n in ("model.stage_table", "model.coarse_build")}
        assert staged == {"batch.dispatch[1]"}
        # staging launches and copies of its own, under the staging spans
        staging = ("model.stage_table", "model.coarse_build", "batch.dispatch[1]")
        crossings = sorted(
            (s[0], s[3]) for s in tr.spans
            if s[0].startswith(("xfer.", "launch[", "fetch.")) and s[3] not in staging
        )
        assert crossings == sorted(CROSSINGS)
        assert {n: p for n, p in got.items() if n.startswith(("dispatch.", "batch."))} == {
            "dispatch.shortlist": "batch.dispatch[1]",
            "dispatch.rescore": "batch.dispatch[1]",
            "dispatch.fetch": "batch.dispatch[1]",
            "batch.dispatch[1]": None,
        }
        assert _hist_count("pio_batch_dispatch_seconds", {}) == disp0 + 1
        assert _hist_count("pio_batch_size", {}) == size0 + 1


# -- names on the device --------------------------------------------------------


def test_serving_programs_carry_their_scopes():
    """jax.named_scope names reach the compiled programs' op metadata,
    which is what a trace viewer shows."""
    import re

    import jax.numpy as jnp

    from predictionio_tpu.ops import retrieval

    tiles = jnp.ones((3, 16, 8), jnp.bfloat16)
    ids = jnp.arange(48, dtype=jnp.int32).reshape(3, 16)
    hlo = retrieval._coarse_topk.lower(
        jnp.ones((2, 8)), tiles, None, ids, k=4, mode="bf16"
    ).compile().as_text()
    # a catalog too small to split into groups: the step scores and
    # keeps, one ``top_k`` of the row follows the loop
    assert set(re.findall(r"retrieval\.shortlist\.\w+", hlo)) == {
        "retrieval.shortlist.score", "retrieval.shortlist.select",
    }
    hlo = retrieval._coarse_topk.lower(
        jnp.ones((2, 8)), jnp.ones((3, 4096, 8), jnp.bfloat16), None,
        jnp.arange(3 * 4096, dtype=jnp.int32).reshape(3, 32, 128),
        k=16, mode="bf16",
    ).compile().as_text()
    assert retrieval.select_group(4096, 16, 3) == 128
    assert set(re.findall(r"retrieval\.shortlist\.\w+", hlo)) == {
        "retrieval.shortlist.score", "retrieval.shortlist.group_max",
        "retrieval.shortlist.select",
    }
    hlo = retrieval._rescore_gather.lower(
        jnp.zeros((2,), jnp.int32), jnp.ones((4, 8)), jnp.ones((48, 8)),
        jnp.zeros((2, 6), jnp.int32), k=3,
    ).compile().as_text()
    assert set(re.findall(r"retrieval\.rescore\.\w+", hlo)) == {
        "retrieval.rescore.gather", "retrieval.rescore.score",
        "retrieval.rescore.topk",
    }
