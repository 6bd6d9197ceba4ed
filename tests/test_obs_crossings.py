"""The crossings of a dispatch — upload, launch, read — as the program
times them at their seams (ISSUE 50): one region a crossing on the
request's trace, inside its stage; one family for every host<->device
copy; a launch's time for every tracked call that did not compile; a
profiler capture that books its own three phases; and ``pio layers``,
which reads the chain back from two scrapes. XLA:CPU, toy sizes: what
is held here is who records what and where, never a time."""

from __future__ import annotations

import glob
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from predictionio_tpu.cli import layers
from predictionio_tpu.obs import device as obs_device
from predictionio_tpu.obs import metrics
from predictionio_tpu.obs import trace as obs_trace
from predictionio_tpu.ops import retrieval
from test_ecommerce_rules import World as ShopWorld
from test_shard_rules import ShardedWorld
from test_similar_rules import World as PageWorld

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STAGES = ("dispatch.shortlist", "dispatch.rescore", "dispatch.fetch")
READ = [("fetch.wait", "dispatch.fetch"),
        ("xfer.d2h[serve.answers]", "dispatch.fetch")]
UP = "xfer.h2d[serve.dispatch]"


def _is_crossing(name: str) -> bool:
    return name.startswith(("xfer.", "launch[", "fetch.wait"))


@pytest.fixture()
def two_stage(monkeypatch):
    monkeypatch.setenv("PIO_RETRIEVAL_THRESHOLD", "16")
    monkeypatch.setenv("PIO_RETRIEVAL_TILE", "256")
    monkeypatch.setenv("PIO_RETRIEVAL_PROBE_EVERY", "0")
    # the read is told apart on one dispatch in CPU_EVERY: here on every one
    monkeypatch.setattr(obs_trace, "CPU_EVERY", 1)


@pytest.fixture()
def server(storage, two_stage):
    """A live EngineServer over a 48-item Recommendation model
    (``UserRows``); ``mount`` puts another template's algorithm and
    model on its one variant, so that the same server answers the other
    forms."""
    from predictionio_tpu.cli import commands
    from predictionio_tpu.core import EngineParams
    from predictionio_tpu.core.workflow import run_train
    from predictionio_tpu.data.event import Event
    from predictionio_tpu.models import recommendation as rec
    from predictionio_tpu.server.engine_server import EngineServer

    info = commands.app_new("CrossingsApp", storage=storage)
    events = storage.get_events()
    rng = np.random.default_rng(0)
    for u in range(12):
        for i in rng.choice(48, size=12, replace=False):
            events.insert(Event(
                event="rate", entity_type="user", entity_id=f"u{u}",
                target_entity_type="item", target_entity_id=f"i{int(i)}",
                properties={"rating": float(rng.integers(1, 6))},
            ), info["id"])
    engine = rec.engine()
    run_train(engine, EngineParams(
        datasource=("", rec.DataSourceParams(app_name="CrossingsApp")),
        algorithms=[("als", rec.ALSAlgorithmParams(rank=4, num_iterations=2))],
    ), engine_id="crossings", storage=storage)
    instance = storage.get_metadata_engine_instances().get_latest_completed(
        "crossings", "0", "default"
    )
    srv = EngineServer(engine, instance, storage=storage, host="127.0.0.1",
                       port=0, batch_window_ms=2.0)
    srv.start()

    def mount(algo, model):
        v = srv._default_variant
        with srv._lock:
            v.algorithms, v.models = [algo], [model]

    srv.mount = mount
    try:
        yield srv
    finally:
        srv.stop()


def _body(q) -> dict:
    import dataclasses

    return {k: v for k, v in dataclasses.asdict(q).items() if v is not None}


# form -> (what to mount: (server, storage) -> [bodies, the last one traced],
#          the crossings of a dispatch of it: (span, parent))
def _user_rows(server, storage):
    return [{"user": "u1", "num": 3}, {"user": "u2", "num": 3}]


def _vectors_rules(server, storage):
    w = ShopWorld(storage, "float32")
    server.mount(w.algo, w.model)
    return [_body(w.query("home", 0)), _body(w.query("category", 1))]


def _sum_rows(server, storage):
    w = PageWorld("float32", 1)
    server.mount(w.algo, w.model)
    return [_body(w.query("similar", 0)), _body(w.query("session", 3))]


def _sharded(kind):
    def mount(server, storage):
        w = ShardedWorld(storage, "float32")
        server.mount(w.algo, w.model)
        # two queries of one shape: the second finds the zero blocks there
        return [_body(w.query(kind, 0)), _body(w.query(kind, 2))]
    return mount


FORMS = {
    "UserRows": (_user_rows, [
        (UP, "dispatch.shortlist"),  # the vectors
        ("launch[retrieval.coarse_topk]", "dispatch.shortlist"),
        (UP, "dispatch.rescore"),  # the indices, behind the running scan
        ("launch[retrieval.rescore_gather]", "dispatch.rescore"),
    ]),
    "Vectors+Rules": (_vectors_rules, [
        (UP, "dispatch.shortlist"),  # pack's one buffer
        ("launch[retrieval.coarse_topk_masked]", "dispatch.shortlist"),
        ("launch[retrieval.rescore_vectors_masked]", "dispatch.rescore"),
    ]),
    "SumRows": (_sum_rows, [
        (UP, "dispatch.shortlist"),
        ("launch[retrieval.coarse_topk_masked]", "dispatch.shortlist"),
        ("launch[retrieval.rescore_sum_rows_masked]", "dispatch.rescore"),
    ]),
    "sharded": (_sharded("home"), [
        (UP, "dispatch.shortlist"),  # ONE copy, to the mesh's first device
        ("launch[retrieval.sharded_topk_masked]", "dispatch.shortlist"),
    ]),
    "sharded-whiteList": (_sharded("whiteList"), [
        (UP, "dispatch.rescore"),  # pack's buffer
        (UP, "dispatch.rescore"),  # the listed candidates' ids
        ("launch[retrieval.sharded_topk_masked]", "dispatch.rescore"),
    ]),
}


@pytest.mark.parametrize("form", list(FORMS))
def test_a_query_of_each_form_holds_every_crossing_once(
        server, storage, monkeypatch, form):
    """The live server's trace of one query: ``launch[...]``,
    ``xfer.h2d[...]``, ``fetch.wait``, ``xfer.d2h[...]`` once a crossing,
    each inside its stage, and a stage's crossings no longer than it."""
    if form.startswith("sharded"):
        monkeypatch.setenv("PIO_MESH", "data=4")
        monkeypatch.setenv("PIO_RETRIEVAL_THRESHOLD", "500")
    mount, crossings = FORMS[form]
    *warm, body = mount(server, storage)
    for b in warm:  # stages the model, compiles its programs
        server.handle_query(b)
    tr = obs_trace.Trace(form)
    with obs_trace.use_trace(tr):
        out = server.handle_query(body)
    assert out["itemScores"]
    spans = [s for s in tr.spans if not s[0].startswith("gc.pause")]
    got = sorted((name, parent) for name, _, _, parent in spans
                 if _is_crossing(name))
    assert got == sorted(crossings + READ)
    by_stage = {name: dur for name, _, dur, _ in spans if name in STAGES}
    for stage, dur in by_stage.items():
        inside = sum(d for _, _, d, parent in spans if parent == stage)
        assert 0 < inside <= dur + 1e-9, stage
    # the stages are what they were: children of the dispatch, in order
    assert [n for n, *_ in spans if n in STAGES] == [
        s for s in STAGES if s in by_stage]
    assert {p for n, _, _, p in spans if n in STAGES} == {"batch.dispatch[1]"}


def _copies():
    return {
        op: obs_device.transfer_count("h2d", op)
        for op in ("serve.dispatch", "serve.rules", "serve.zero_blocks")
    }


def _moved(before):
    return {op: n - before[op] for op, n in _copies().items()}


# the counts that ``pio_retrieval_uploads_total`` and
# ``pio_retrieval_shard_h2d_copies_total`` were pinned to, read from the
# family: form -> (copies of a dispatch by site, probe every)
COPIES = {
    "UserRows": ({"serve.dispatch": 2}, 0),
    "Vectors+Rules": ({"serve.dispatch": 1}, 0),
    "Vectors+Rules, probed": ({"serve.dispatch": 1, "serve.rules": 3}, 1),
    "SumRows": ({"serve.dispatch": 1}, 0),
    "SumRows, probed": ({"serve.dispatch": 1, "serve.rules": 3}, 1),
    "sharded": ({"serve.dispatch": 1}, 0),
    "sharded-whiteList": ({"serve.dispatch": 2}, 0),
}


@pytest.mark.parametrize("case", list(COPIES))
def test_copies_a_dispatch_are_observations_of_the_family(
        server, storage, monkeypatch, case):
    """A copy is counted where it is made, once: by site, with as many
    timed observations as copies; ``/stats.json``'s ``uploads`` and
    ``shard_h2d_copies`` are sums over the family."""
    want, probe_every = COPIES[case]
    form = case.split(",")[0]
    if form.startswith("sharded"):
        monkeypatch.setenv("PIO_MESH", "data=4")
        monkeypatch.setenv("PIO_RETRIEVAL_THRESHOLD", "500")
    *warm, body = FORMS[form][0](server, storage)
    for b in warm:  # a sharded catalog's zero blocks go up once a shape
        server.handle_query(b)
    monkeypatch.setenv("PIO_RETRIEVAL_PROBE_EVERY", str(probe_every))
    stats0, copies0 = retrieval.stats_block(), _copies()
    timed0 = {
        op: metrics.histogram(
            "pio_device_transfer_seconds", direction="h2d", op=op
        ).merged()[2] for op in copies0
    }
    server.handle_query(body)
    moved = _moved(copies0)
    assert {op: n for op, n in moved.items() if n} == want
    for op, n in moved.items():
        assert metrics.histogram(
            "pio_device_transfer_seconds", direction="h2d", op=op
        ).merged()[2] == timed0[op] + n
    stats1 = retrieval.stats_block()
    assert stats1["uploads"] - stats0["uploads"] == sum(want.values())
    if form.startswith("sharded"):  # the shape's zero blocks went up warm
        assert stats1["shard_h2d_copies"] - stats0["shard_h2d_copies"] \
            == want["serve.dispatch"]
    # the wait in front of the read is no second read (a one-chip probe
    # reads its exact answer outside the chain's ``_fetch``)
    assert stats1["host_reads"] - stats0["host_reads"] == 1


def test_the_zero_blocks_are_copies_of_their_own_site(monkeypatch):
    """The first dispatch of a shape writes shards - 1 resident blocks
    (``serve.zero_blocks``) inside its one ``serve.dispatch`` copy."""
    from predictionio_tpu.parallel.mesh import make_mesh
    from predictionio_tpu.parallel.shard_topk import ShardedCatalog

    rng = np.random.default_rng(3)
    cat = ShardedCatalog(
        rng.standard_normal((3001, 16)).astype(np.float32),
        make_mesh([("data", 4)]),
    )
    q = rng.standard_normal((3, 16)).astype(np.float32)
    tr = obs_trace.Trace("zeros")
    before = _copies()
    with obs_trace.use_trace(tr), obs_trace.region("dispatch.shortlist"):
        cat.put_queries(q)
    assert _moved(before) == {
        "serve.dispatch": 1, "serve.rules": 0, "serve.zero_blocks": 3}
    assert [(n, p) for n, _, _, p in tr.spans] == [
        ("xfer.h2d[serve.zero_blocks]", UP)] * 3 + [
        (UP, "dispatch.shortlist"), ("dispatch.shortlist", None)]
    before = _copies()
    cat.put_queries(q)
    assert _moved(before) == {
        "serve.dispatch": 1, "serve.rules": 0, "serve.zero_blocks": 0}


def test_the_read_is_told_apart_on_one_dispatch_in_seven(server, monkeypatch):
    """``fetch.wait`` + ``xfer.d2h[serve.answers]`` cost a dispatch a
    second runtime call and the overlap of the read with the device's
    last work: taken by the stride that is there, every read counted."""
    monkeypatch.setattr(obs_trace, "CPU_EVERY", 7)
    server.handle_query({"user": "u1", "num": 3})
    wait = metrics.histogram("pio_retrieval_fetch_wait_seconds")
    fetch = metrics.histogram("pio_retrieval_fetch_seconds")
    before = (wait.merged()[2], obs_device.transfer_count("d2h", "serve.answers"),
              fetch.merged()[2], retrieval.stats_block()["host_reads"])
    split = 0
    for n in range(14):
        tr = obs_trace.Trace("stride")
        with obs_trace.use_trace(tr):
            server.handle_query({"user": f"u{n % 12}", "num": 3})
        inside = sorted(n_ for n_, _, _, p in tr.spans if p == "dispatch.fetch")
        assert inside in ([], ["fetch.wait", "xfer.d2h[serve.answers]"])
        split += bool(inside)
    assert split == 2
    after = (wait.merged()[2], obs_device.transfer_count("d2h", "serve.answers"),
             fetch.merged()[2], retrieval.stats_block()["host_reads"])
    assert [a - b for a, b in zip(after, before)] == [2, 2, 14, 14]


def test_a_launch_is_timed_unless_it_compiled(server):
    """Sum of ``pio_jit_call_seconds_count`` = tracked calls - compiles,
    function by function, over the serving programs of a few queries."""
    def counts():
        snap = obs_device.compile_snapshot()
        return {
            fn: (s["calls"], s["compiles"], metrics.histogram(
                "pio_jit_call_seconds", fn=fn).merged()[2])
            for fn, s in snap.items()
        }

    before = counts()
    for u in ("u1", "u2", "u3", "u1"):
        server.handle_query({"user": u, "num": 3})
    after = counts()
    moved = {
        fn: tuple(a - b for a, b in zip(after[fn], before.get(fn, (0, 0, 0))))
        for fn in after if after[fn] != before.get(fn)
    }
    assert {"retrieval.coarse_topk", "retrieval.rescore_gather"} <= set(moved)
    for fn, (calls, compiles, timed) in moved.items():
        assert timed == calls - compiles, fn
    assert sum(c for c, _, _ in moved.values()) >= 8


def test_nothing_is_recorded_with_observability_off(server):
    server.handle_query({"user": "u1", "num": 3})  # staged and compiled
    fetch_wait = metrics.histogram("pio_retrieval_fetch_wait_seconds")
    calls = metrics.histogram("pio_jit_call_seconds", fn="retrieval.coarse_topk")
    before = (_copies(), obs_device.transfer_count("d2h", "serve.answers"),
              fetch_wait.merged()[2], calls.merged()[2])
    metrics.set_enabled(False)
    try:
        tr = obs_trace.Trace("off")
        with obs_trace.use_trace(tr):
            out = server.handle_query({"user": "u2", "num": 3})
    finally:
        metrics.set_enabled(True)
    assert len(out["itemScores"]) == 3
    assert tr.spans == []
    assert before == (_copies(), obs_device.transfer_count("d2h", "serve.answers"),
                      fetch_wait.merged()[2], calls.merged()[2])


# -- the capture ----------------------------------------------------------------


def _phases():
    return {p: metrics.counter("pio_profile_seconds_total", phase=p).value()
            for p in ("start", "capture", "stop")}


def test_a_capture_books_three_phases_and_names_the_crossings(server, tmp_path):
    """``profile_capture``'s reply and ``pio_profile_seconds_total`` hold
    start, capture and stop, which sum to its wall time; the four
    crossing names are in the ``.xplane.pb`` it wrote."""
    import threading

    from jax.profiler import ProfileData

    server.handle_query({"user": "u1", "num": 3})
    stop = threading.Event()

    def traffic():
        while not stop.is_set():
            server.handle_query({"user": "u2", "num": 3})
            time.sleep(0.01)

    t = threading.Thread(target=traffic)
    t.start()
    before = _phases()
    t0 = time.perf_counter()
    try:
        reply = obs_device.profile_capture(0.4, out_dir=str(tmp_path))
    finally:
        wall = time.perf_counter() - t0
        stop.set()
        t.join(timeout=30)
    assert not t.is_alive()
    booked = {p: v - before[p] for p, v in _phases().items()}
    assert booked["capture"] >= 0.4 and min(booked.values()) > 0
    assert reply["seconds"] == 0.4
    assert reply["start_s"] == pytest.approx(booked["start"], abs=1e-3)
    assert reply["stop_s"] == pytest.approx(booked["stop"], abs=1e-3)
    # the three phases are the capture: what is left is the lock, two
    # imports and the walk of the written files
    assert sum(booked.values()) <= wall
    assert wall - sum(booked.values()) < 0.5
    path = max(glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    names = set()
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            names.update(e.name for e in line.events)
    # ... and the batch worker's wait, which began long before the capture
    # did (every query here is scored off the worker): entered again
    for name in ("launch[retrieval.coarse_topk]", UP, "fetch.wait",
                 "xfer.d2h[serve.answers]", "profile.capture", "batch.collect"):
        assert name in names, name


def test_a_second_capture_is_refused_and_books_nothing(tmp_path):
    import threading

    got = {}
    t = threading.Thread(target=lambda: got.update(
        obs_device.profile_capture(0.5, out_dir=str(tmp_path / "a"))))
    t.start()
    deadline = time.monotonic() + 10
    while not obs_device.profile_active():
        assert time.monotonic() < deadline
        time.sleep(0.005)
    with pytest.raises(RuntimeError):
        obs_device.profile_capture(0.05, out_dir=str(tmp_path / "b"))
    t.join(timeout=60)
    assert not t.is_alive() and got["seconds"] == 0.5


# -- pio layers -------------------------------------------------------------------


def _scrape(requests, dispatches, profile_stop=None):
    """A synthetic /metrics scrape: every stage 1 ms a call but the ones
    given here."""
    def hist(name, n, total, labels=""):
        return [f"{name}_sum{labels} {total}", f"{name}_count{labels} {n}"]

    e = '{server="engine"}'
    lines = []
    for name, ms in (("pio_http_handoff_seconds", 0.2), ("pio_http_read_parse_seconds", 0.05),
                     ("pio_http_write_seconds", 0.1)):
        lines += hist(name, requests, requests * ms * 1e-3, e)
    for name, ms in (("pio_serving_submit_seconds", 0.05), ("pio_batch_queue_wait_seconds", 0.2),
                     ("pio_serving_wake_seconds", 0.01), ("pio_serving_tail_seconds", 0.09),
                     ("pio_serving_seconds", 4.3)):
        lines += hist(name, requests, requests * ms * 1e-3)
    lines += hist("pio_serving_seconds", requests, 1.0, '{variant="default"}')
    for name, ms in (("pio_batch_dispatch_seconds", 4.0), ("pio_batch_dispatch_self_seconds", 0.3),
                     ("pio_retrieval_shortlist_seconds", 0.8), ("pio_retrieval_rescore_seconds", 0.9),
                     ("pio_retrieval_fetch_seconds", 2.0)):
        lines += hist(name, dispatches, dispatches * ms * 1e-3)
    sampled = dispatches // 5  # the read is told apart on a stride
    lines += hist("pio_retrieval_fetch_wait_seconds", sampled, sampled * 1.5e-3)
    lines += hist("pio_batch_size", dispatches, requests)
    for fn, ms in (("retrieval.coarse_topk", 0.4), ("retrieval.rescore_gather", 0.35)):
        lines += hist("pio_jit_call_seconds", dispatches, dispatches * ms * 1e-3, f'{{fn="{fn}"}}')
    lines += hist("pio_device_transfer_seconds", 2 * dispatches, 2 * dispatches * 0.25e-3,
                  '{direction="h2d",op="serve.dispatch"}')
    lines += hist("pio_device_transfer_seconds", sampled, sampled * 0.45e-3,
                  '{direction="d2h",op="serve.answers"}')
    lines += [f'pio_batch_dispatch_path_total{{path="inline"}} {0.9 * dispatches}',
              f'pio_batch_dispatch_path_total{{path="worker"}} {0.1 * dispatches}']
    for state, s in (("idle", 19.0), ("collect", 0.01), ("dispatch", 0.9), ("resolve", 0.09)):
        lines.append(f'pio_batch_worker_seconds_total{{state="{state}"}} {s * requests / 220}')
    if profile_stop is not None:
        for phase, s in (("start", 0.2), ("capture", 5.0), ("stop", profile_stop)):
            lines.append(f'pio_profile_seconds_total{{phase="{phase}"}} {s}')
    return metrics.parse_prometheus("\n".join(lines))


def test_layers_reads_the_chain_from_two_synthetic_scrapes():
    doc = layers.table(_scrape(100, 100, 0.0), _scrape(320, 320, 0.0), 20.0)
    assert (doc["requests"], doc["dispatches"]) == (220, 220)
    by = {r["span"]: r for r in doc["request"]}
    assert by["serve.submit"]["ms_each"] == pytest.approx(0.05)
    # what a request saw of its dispatch: serving less wait, wake and tail
    assert by["batch.dispatch (as a request saw it)"]["ms_each"] == pytest.approx(4.0)
    assert doc["request_sum_ms"] == pytest.approx(0.2 + 0.05 + 0.05 + 4.3 + 0.1)
    rows = {(r["span"], r["depth"]): r for r in doc["dispatch"]}
    assert rows[("launch[retrieval.coarse_topk]", 2)]["ms_each"] == pytest.approx(0.4)
    assert rows[(UP, 2)]["n"] == 440 and rows[(UP, 2)]["ms_each"] == pytest.approx(0.5)
    assert rows[("self (convert, pad, pack)", 2)]["ms_each"] == pytest.approx(1.7 - 0.75 - 0.5)
    assert rows[("fetch.wait", 2)]["ms_each"] == pytest.approx(1.5)
    assert rows[("self", 2)]["ms_each"] == pytest.approx(0.05)
    enqueue, fetch = doc["identities"].values()
    # the read was told apart on a fifth of the dispatches: no verdict
    assert enqueue["holds"] and fetch["holds"] is None
    assert fetch["told_apart"] == [44, 220]
    assert fetch["fetch_wait_ms"] + fetch["fetch_read_ms"] == pytest.approx(1.95)
    assert enqueue["upload_ms"] + enqueue["launch_ms"] == pytest.approx(1.25)
    assert doc["dispatch_path"]["inline_share"] == pytest.approx(0.9)
    assert doc["worker"]["busy_share"] == pytest.approx(0.05)
    assert doc["capture"] == {"start_s": 0.0, "capture_s": 0.0, "stop_s": 0.0, "ran": False}
    text = layers.render(doc, "synthetic")
    for want in ("http.handoff", "serve.submit", "batch.queue_wait", "dispatch.shortlist",
                 "launch[retrieval.rescore_gather]", UP, "fetch.wait",
                 "xfer.d2h[serve.answers]", "serve.wake", "serve.tail", "http.write",
                 "upload_ms + launch_ms <= shortlist_ms + rescore_ms",
                 "fetch_wait_ms + fetch_read_ms <= fetch_ms", ": holds",
                 "the read was told apart on 44 of 220 dispatches",
                 "inline share 90.0 %", "profiler capture: none in this interval"):
        assert want in text, want
    assert "DOES NOT HOLD" not in text


def test_layers_says_when_a_capture_ran_and_when_it_cannot_tell():
    ran = layers.table(_scrape(100, 100, 0.0), _scrape(320, 320, 9.0), 20.0)
    assert ran["capture"]["ran"] and ran["capture"]["stop_s"] == 9.0
    assert "RAN in this interval" in layers.render(ran)
    parent = layers.table(_scrape(100, 100), _scrape(320, 320), 20.0)
    assert parent["capture"] is None
    assert "does not say" in layers.render(parent)


def test_layers_over_the_windows_of_a_rehearsed_benchmark_run(tmp_path):
    """``benchmark/run.py --dry-run-cpu --save-logs`` keeps both scrapes
    of the window; ``pio layers --windows`` prints the chain and the two
    identities from them, text and ``--json``."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    logs = tmp_path / "logs"
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "retrieval-yambda.serve-steady", "--seed", str(2**31 + 50),
         "--seconds", "3", "--trace", "0", "--dry-run-cpu", "--save-logs", str(logs)],
        capture_output=True, text=True, timeout=600, env=env, cwd=str(tmp_path),
    )
    assert proc.returncode == 3, proc.stdout[-2000:] + proc.stderr[-2000:]
    windows = str(logs / "gen.windows.json")
    cli = [sys.executable, "-m", "predictionio_tpu.cli.main", "layers", "--windows", windows]
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(cli, capture_output=True, text=True, timeout=120, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    for want in ("window:", "http.handoff", "batch.queue_wait", "dispatch.shortlist",
                 "launch[retrieval.coarse_topk]", "launch[retrieval.rescore_gather]", UP,
                 "dispatch.fetch", "fetch.wait", "xfer.d2h[serve.answers]", "http.write",
                 "profiler capture: none in this interval", "generator, sent -> done"):
        assert want in out.stdout, want
    # the uploads and launches fit their stages; the read is told apart on
    # one dispatch in seven, so its identity is shown and not judged
    assert out.stdout.count(": holds") == 1 and "DOES NOT HOLD" not in out.stdout
    assert "the read was told apart on" in out.stdout
    js = subprocess.run(cli + ["--json"], capture_output=True, text=True, timeout=120, env=env)
    doc = json.loads(js.stdout)["window"]
    assert doc["requests"] > 10 and doc["dispatches"] > 10
    enqueue, fetch = doc["identities"].values()
    assert enqueue["holds"] and fetch["holds"] is None
    assert 0 < fetch["told_apart"][0] < fetch["told_apart"][1]
    # the chain is the request: what the generator saw, less the wire
    assert 0.5 < doc["request_sum_ms"] / doc["generator"]["mean_ms"] <= 1.05
    assert "jax" not in subprocess.run(
        [sys.executable, "-c", "import sys; from predictionio_tpu.cli import layers; "
         "print(sorted(m for m in sys.modules if m == 'jax'))"],
        capture_output=True, text=True, timeout=60, env=env).stdout


def test_layers_wants_one_source(capsys):
    from predictionio_tpu.cli import main as cli_main

    assert cli_main.main(["layers"]) == 2
    assert "--url BASE or --windows FILE" in capsys.readouterr().err
