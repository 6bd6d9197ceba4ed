"""Engine Server: deployed-engine query serving (default port 8000).

Capability parity with the reference CreateServer
(core/.../workflow/CreateServer.scala:105-663):

- ``POST /queries.json`` — deserialize query via the algorithm's query
  class, ``serving.supplement``, score every algorithm, ``serving.serve``,
  JSON response (:470-500). Per-request bookkeeping: requestCount,
  avgServingSec, lastServingSec (:399-403).
- ``GET /`` — status page with engine info and serving stats; browsers
  (Accept: text/html) get the HTML render (:443-467), API clients JSON.
- Serving errors POST ``logPrefix + {engineInstance, message}`` to
  ``--log-url`` when configured (:422-433, :596-618).
- ``POST /reload`` — hot-swap to the newest COMPLETED engine instance
  (:316-342); key-authenticated.
- ``POST /stop`` — key-authenticated shutdown (:260-285).
- ``GET /plugins.json`` + output blocker/sniffer plugins (:578-581).
- Feedback loop (:514-577): when enabled, asynchronously POSTs a
  ``predict`` event (entityType ``pio_pr``) with query+prediction back to
  the Event Server, generating/propagating ``prId``.

The reference scores algorithms sequentially per request with a
"TODO: Parallelize" note (:494-496); here multi-algorithm scoring still
iterates host-side but each algorithm's scoring is one fused device call.
"""

from __future__ import annotations

import dataclasses
import json
import logging
import os
import threading
import time
import urllib.request
import uuid
from concurrent.futures import Future, InvalidStateError
from concurrent.futures import TimeoutError as FuturesTimeout
from typing import Any, NamedTuple

from predictionio_tpu import faults
from predictionio_tpu.core.engine import Engine
from predictionio_tpu.core.workflow import prepare_deploy
from predictionio_tpu.data.storage import EngineInstance, Storage, get_storage
from predictionio_tpu.obs import device as obs_device
from predictionio_tpu.obs import freshness as obs_freshness
from predictionio_tpu.obs import metrics as obs_metrics
from predictionio_tpu.obs import runtime as obs_runtime
from predictionio_tpu.obs import slo as obs_slo
from predictionio_tpu.obs import trace as obs_trace
from predictionio_tpu.server import jsonx
from predictionio_tpu.server import plugins as plugin_mod
from predictionio_tpu.server.http import (
    HTTPApp,
    Request,
    Response,
    Router,
    add_obs_routes,
)
from predictionio_tpu.server.query_cache import (
    QueryCache,
    canonical_query_bytes,
)

logger = logging.getLogger(__name__)


class QueryDeadlineExceeded(Exception):
    """A query overran the configured per-query deadline
    (PIO_QUERY_DEADLINE_MS); the route maps this to 503 + Retry-After
    instead of letting the client hang."""


def _to_jsonable(obj: Any) -> Any:
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        return dataclasses.asdict(obj)
    return obj


def _model_bytes(obj: Any, _depth: int = 0) -> int:
    """Total ``nbytes`` reachable from a deployed model list — the byte
    size of a model put/patch for the device transfer accounting. Walks
    containers and object attributes a few levels deep (an ALS model is
    an object holding factor arrays or int8 (values, scales) pairs);
    anything unrecognized counts as 0 rather than guessing."""
    if _depth > 3:
        return 0
    nbytes = getattr(obj, "nbytes", None)
    if isinstance(nbytes, int):
        return nbytes
    if isinstance(obj, (list, tuple)):
        return sum(_model_bytes(o, _depth + 1) for o in obj)
    if isinstance(obj, dict):
        return sum(_model_bytes(o, _depth + 1) for o in obj.values())
    if hasattr(obj, "__dict__"):
        return sum(_model_bytes(v, _depth + 1) for v in vars(obj).values())
    return 0


def _patch_cost(old: Any, new: Any) -> tuple[int, list[str]]:
    """(bytes a patch of ``old`` into ``new`` sends to the device, the
    item-side parts it will stage or build AGAIN). A patched model that
    holds the served model's resident parts by reference
    (``resident_parts``) sent the rows it says it sent
    (``patch_h2d_bytes``); any other model is new to the device and goes
    up whole the next time a query asks for it."""
    if new is old:
        return 0, []
    parts = getattr(old, "resident_parts", None)
    if parts is None or not hasattr(new, "resident_parts"):
        return _model_bytes(new), []
    after = new.resident_parts()
    lost = [
        part for part, held in parts().items()
        if held is not None and after.get(part) is not held
    ]
    sent = getattr(new, "patch_h2d_bytes", None)
    if lost or sent is None:
        return _model_bytes(new), lost
    return int(sent), []


def _query_from_json(query_class: type | None, data: dict[str, Any]) -> Any:
    """JSON -> query object (reference JsonExtractor.extract on
    algo.queryClass, CreateServer.scala:479-485)."""
    if query_class is None:
        return data
    if dataclasses.is_dataclass(query_class):
        names = {f.name for f in dataclasses.fields(query_class)}
        return query_class(**{k: v for k, v in data.items() if k in names})
    return query_class(**data)


class _MicroBatcher:
    """Collects concurrent ``/queries.json`` requests and scores them
    with ONE ``batch_predict`` call per algorithm — amortizing the fixed
    per-device-call dispatch cost across requests. Where dispatch
    dominates, N concurrent requests cost ~1 dispatch instead of N;
    batch_predict's batched matmul also fills the MXU where single
    queries underuse it.

    LOAD-AWARE: the batcher is ALWAYS engaged — the engage decision
    moved from deploy time (the retired ``MIN_DISPATCH_S`` floor, which
    disengaged every local attachment and was exactly why an early
    driver bench measured batching LOSING) to per-request time, where
    the queue and the dispatch in flight are known.

    The right to dispatch is a SLOT (``_slot``, a lock) that one thread
    holds at a time: the worker around its drain-and-dispatch turn, or a
    request thread for one query of its own.

    - nothing queued, nothing in flight (idle server): ``submit`` takes
      the slot and the request thread scores its OWN query — straight to
      ``predict``, no padding, no queue item, no ``Future``, no thread
      hop either way (``EngineServer._score_inline``). Only where a lone
      item would be dispatched at once anyway: not on a window-waiting
      batcher, and not under a per-query deadline (whose early 503 needs
      the scoring on another thread).
    - a dispatch in flight, or anything queued: the request enqueues — it
      never overtakes a queued one. The worker takes its first item,
      then the slot, then whatever else queued meanwhile: depth > 1
      (amortization wins by construction) is ONE padded ``batch_predict``
      per algorithm; depth 1 is the same single-item fast path, on the
      worker, at the cost of the two hops (request -> worker -> request).
      Depth is created by load itself: requests queue behind the
      in-flight device call, inline or the worker's, and coalesce into
      the next one.
    - ``dispatch > window`` (a slow attachment): the worker
      additionally waits up to the window to grow the batch — added
      latency bounded by the window, itself below one dispatch.

    ``pio_batch_dispatch_path_total{path}`` counts dispatches by the
    thread that made them, ``pio_batch_enqueued_total{reason}`` why a
    request was not taken inline.

    Batches pad to power-of-two sizes (1,2,4,...,``max_batch``) so the
    jitted scoring programs specialize on at most log2(max_batch)+1
    shapes — ``pio_jit_compiles_total`` stays flat under load.

    Semantics are identical to per-request serving: every Algorithm has
    ``batch_predict`` (the default loops ``predict``), and
    serving/plugins/feedback still run per query. Queries are parsed on
    their REQUEST thread (a malformed body 400s without occupying a
    place in a batch), and the serving/feedback/plugin tail also runs on
    the request thread — the worker only collects and dispatches, so the
    JSON/serving work of batchmates overlaps. A failing batch retries
    its items individually so one bad query can't poison its
    batchmates."""

    def __init__(self, server: "EngineServer", window_ms: float,
                 max_batch: int = 64, dispatch_cost_s: float | None = None):
        import queue

        self._server = server
        self._window = window_ms / 1e3
        self._max = max_batch
        self._q: "queue.Queue" = queue.Queue()
        self._stopped = False
        self._lock = threading.Lock()
        # the right to dispatch; _waiting counts the items enqueued whose
        # turn has not begun (under _lock): the queue alone reads empty
        # between the worker's first get and its taking the slot
        self._slot = threading.Lock()
        self._waiting = 0
        self.dispatch_cost_s = (
            self._measure_dispatch() if dispatch_cost_s is None
            else dispatch_cost_s
        )
        # kept for dashboards/tests: the batcher no longer disengages —
        # single-item batches bypass the machinery instead
        self.engaged = True
        self._window_wait = self.dispatch_cost_s > self._window
        if not self._window_wait:
            logger.info(
                "micro-batch: measured dispatch %.2f ms <= window %.1f ms "
                "on this attachment; window bypassed (batches form only "
                "from naturally queued requests)",
                self.dispatch_cost_s * 1e3,
                window_ms,
            )
        else:
            logger.info(
                "micro-batch: measured dispatch %.2f ms > window %.1f ms "
                "on this attachment; window-waiting to grow batches",
                self.dispatch_cost_s * 1e3,
                window_ms,
            )
        # where requests wait; how big batches get and what a dispatch
        # costs are counted per dispatch by the server (_dispatch)
        self._m_queue_wait = obs_metrics.histogram(
            "pio_batch_queue_wait_seconds",
            "Per-query wait from submit to the start of its dispatch turn",
        )
        self._m_path = {
            path: obs_metrics.counter(
                "pio_batch_dispatch_path_total",
                "Device dispatches by the thread that made them (inline: "
                "the request's own; worker: the batch worker)",
                path=path,
            )
            for path in ("inline", "worker")
        }
        self._m_enqueued = {
            reason: obs_metrics.counter(
                "pio_batch_enqueued_total",
                "Queries handed to the batch worker, by what kept them "
                "off their own thread",
                reason=reason,
            )
            for reason in ("deadline", "window", "queued", "slot_busy")
        }
        # the worker's time by state (idle / collect / dispatch /
        # resolve); only the worker thread moves it — an inline dispatch
        # is not the worker's time
        self.clock = obs_runtime.WorkerClock()
        obs_metrics.gauge(
            "pio_batch_engaged",
            "1 when the micro-batcher serves queries, 0 when disengaged",
        ).set(1.0)
        obs_metrics.gauge(
            "pio_batch_dispatch_cost_seconds",
            "Measured per-device-call dispatch cost at deploy",
        ).set(self.dispatch_cost_s)
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    @staticmethod
    def _measure_dispatch() -> float:
        """Per-device-call dispatch cost (seconds): a cached no-op jit
        round trip — the fixed cost micro-batching amortizes (~0.1 ms
        on XLA:CPU; on a TPU: not measured)."""
        try:
            import jax
            import jax.numpy as jnp

            f = jax.jit(lambda x: x + 1)
            x = jnp.zeros((), jnp.float32)
            f(x).block_until_ready()  # compile outside the timing
            t0 = time.perf_counter()
            for _ in range(3):
                f(x).block_until_ready()
            return (time.perf_counter() - t0) / 3
        except Exception:  # pragma: no cover - probe must never kill boot
            logger.exception("dispatch probe failed; assuming fast")
            return 0.0

    @property
    def active(self) -> bool:
        return not self._stopped

    def submit(self, body: dict, variant=None) -> "_Submitted":
        """Parse on the request thread, then either take the dispatch
        slot for it (``fut`` None: the caller scores the query itself and
        MUST ``release()``) or enqueue for the worker (``fut`` pending,
        resolving to the per-algorithm predictions). Either way the
        parsed context the request thread needs to finish the query
        comes back. Parse errors raise here — a malformed body 400s
        without ever occupying a place in a batch."""
        server = self._server
        v = variant if variant is not None else server._default_variant
        with server._lock:
            algorithms, serving = v.algorithms, v.serving
        query, sup = server._parse_query(body, algorithms, serving)
        t0 = time.perf_counter()
        # what a lone item would NOT be dispatched at once for
        if server.query_deadline_s is not None:
            reason = "deadline"
        elif self._window_wait:
            reason = "window"
        else:
            reason = None
        # the stopped check and the put share stop()'s lock: stop() can
        # never drain between them and strand this future in a dead
        # queue. The slot is judged under it too: of two requests that
        # find the server idle one takes the slot and the other queues
        with self._lock:
            if self._stopped:
                raise RuntimeError("server stopping")
            if reason is None:
                if self._waiting:
                    reason = "queued"  # never overtake a queued request
                elif not self._slot.acquire(blocking=False):
                    reason = "slot_busy"
            if reason is not None:
                f: Future = Future()
                self._waiting += 1
                # the request thread's trace rides the queue item — the
                # worker thread can't see this thread's thread-local
                self._q.put((f, t0, obs_trace.current_trace(), sup, v))
        if reason is None:
            return _Submitted(None, query, serving, t0, sup)
        self._m_enqueued[reason].inc()
        return _Submitted(f, query, serving, t0, sup)

    def release(self) -> None:
        """Give the slot back: an inline dispatch has ended."""
        self._slot.release()

    def stats_block(self) -> dict:
        """``batch`` in ``/stats.json``."""
        inline, worker = (
            self._m_path[p].value() for p in ("inline", "worker")
        )
        n = inline + worker
        return {
            "window_wait": self._window_wait,
            "dispatch_cost_ms": round(self.dispatch_cost_s * 1e3, 4),
            "dispatch_path": {
                "inline": inline,
                "worker": worker,
                "inline_share": round(inline / n, 4) if n else None,
            },
            "enqueued": {r: c.value() for r, c in self._m_enqueued.items()},
        }

    def stop(self) -> None:
        import queue

        with self._lock:
            if self._stopped:
                return
            self._stopped = True
        # no submit can enqueue or take the slot past this point (flag
        # is set under the lock); let the worker finish its in-flight
        # batch and a request thread its inline dispatch, then fail
        # whatever is still queued rather than leaving clients blocked
        # on the future timeout
        if self._thread is not None:
            self._thread.join(timeout=5)
        if self._slot.acquire(timeout=5):
            self._slot.release()
        while True:
            try:
                f, *_ = self._q.get_nowait()
            except queue.Empty:
                break
            if not f.done():
                f.set_exception(RuntimeError("server stopping"))

    def _collect(self) -> list | None:
        """Wait for the first item, then the slot, then the window: the
        next batch — with the slot HELD, ``_loop`` gives it back — or
        None once the batcher has stopped. One ``batch.collect``
        annotation covers the whole wait, so a profile can put an idle
        device down to "no request was queued" — the whole wait INSIDE a
        capture: an annotation is decided where it is entered, and since
        lone queries are scored on their request threads this thread can
        sit here for minutes, so the wait is entered again when a
        capture begins or ends."""
        import queue

        clock = self.clock
        while not self._stopped:
            with obs_trace.annotate("batch.collect"):
                capture = obs_trace.annotating()
                first = None
                while not self._stopped and capture == obs_trace.annotating():
                    try:
                        first = self._q.get(timeout=0.05)
                        break
                    except queue.Empty:
                        # idle so far is counted now: a scrape never finds
                        # more than this timeout of the worker's time missing
                        clock.to("idle")
                if first is None:
                    continue
                clock.to("collect")
                # the slot BEFORE the rest of the queue: the batch is
                # everything that queued while the slot's holder (an
                # inline dispatch; the previous batch was this thread's)
                # was in flight
                self._slot.acquire()
                batch = [first]
                deadline = time.perf_counter() + self._window
                while len(batch) < self._max:
                    try:
                        batch.append(self._q.get_nowait())
                        continue
                    except queue.Empty:
                        pass
                    # queue is empty: idle-wait for more only when a
                    # saved dispatch is worth more than the window
                    if not self._window_wait:
                        break
                    remaining = deadline - time.perf_counter()
                    if remaining <= 0:
                        break
                    try:
                        batch.append(self._q.get(timeout=remaining))
                    except queue.Empty:
                        break
                with self._lock:
                    self._waiting -= len(batch)
                return batch
        return None

    def _loop(self) -> None:
        while True:
            batch = self._collect()
            if batch is None:
                return
            try:
                self._server._handle_query_batch(batch)
            except Exception:  # pragma: no cover - worker must survive
                logger.exception("micro-batch worker failed")
                for f, *_ in batch:
                    if not f.done():
                        f.set_exception(RuntimeError("batch worker failed"))
            finally:
                self._slot.release()
            self.clock.to("idle")


class _Submitted(NamedTuple):
    """What ``_MicroBatcher.submit`` hands back to the request thread:
    the pending predictions future — or None: this thread holds the
    dispatch slot and scores ``sup`` itself — plus the context to finish
    the query (serving/feedback/plugins run on the request thread, not
    the batch worker)."""

    fut: Any
    query: Any
    serving: Any
    t0: float
    sup: Any


class _Variant:
    """One mounted tenant of an EngineServer: its own engine, instance,
    models, epoch fence, serving bookkeeping, and (optionally) speed
    layer — while the HTTP front end, micro-batcher worker, jit cache,
    and query-cache byte budget stay shared process-wide.

    Duck-types the surface ``realtime.SpeedLayer`` expects from a
    "server" (engine_params / storage / instance / model_snapshot /
    apply_patch / query_cache / _lock / _foldin_epoch / speed_layer), so
    a layer constructed with a mount folds into exactly that tenant.

    ``labeled`` is True on multi-tenant servers: per-tenant metric
    series ride a ``variant=<name>`` label and ``variant_name`` suffixes
    the mount's SLO names. Solo deploys stay unlabeled so their metric
    and SLO names are byte-identical to the pre-multi-tenant server."""

    def __init__(
        self,
        server: "EngineServer",
        name: str,
        engine: Engine,
        instance: EngineInstance,
        labeled: bool,
    ):
        self.server = server
        self.name = name
        self.engine = engine
        self._epoch = 0
        self._foldin_epoch = 0
        self.speed_layer = None  # attached by realtime.SpeedLayer
        self.request_count = 0
        self.serving_seconds = 0.0
        self.last_serving_sec = 0.0
        self.last_reload_ts = 0.0
        self.variant_name = name if labeled else None
        self._m_serving_v = (
            obs_metrics.histogram(
                "pio_serving_seconds",
                "Per-query scoring+serve time (parse through plugins)",
                variant=name,
            )
            if labeled
            else None
        )
        self._m_requests_v = (
            obs_metrics.counter(
                "pio_serving_requests_total",
                "Queries served, per tenant",
                variant=name,
            )
            if labeled
            else None
        )
        self._slos: list = []
        self._load(instance)

    # -- shared infrastructure (also the SpeedLayer "server" surface) ------
    @property
    def _lock(self):
        return self.server._lock

    @property
    def storage(self):
        return self.server.storage

    @property
    def query_cache(self):
        return self.server.query_cache

    # -- load / reload ------------------------------------------------------
    def _load(self, instance: EngineInstance) -> None:
        engine_params, algorithms, models, serving = prepare_deploy(
            self.engine, instance, storage=self.server.storage
        )
        obs_device.count_transfer(
            "h2d", "serve.model_put", _model_bytes(models)
        )
        with self._lock:
            self.instance = instance
            self.engine_params = engine_params
            self.algorithms = algorithms
            self.models = models
            self.serving = serving
            # retrain wins: a reload supersedes any applied fold-in
            # patches (the new instance was trained on the full log)
            self._epoch += 1
            self._foldin_epoch = 0
            epoch = self._epoch
        # entries under older epochs are unreachable by key the moment
        # the counter moves; the sweep reclaims their bytes — scoped to
        # THIS tenant's partition, so reloading one mount never flushes
        # a co-tenant's cached results
        if self.query_cache is not None:
            self.query_cache.sweep(epoch, variant=self.name)
        # freshness lineage, batch side: events ingested before this
        # instance's training began are servable NOW — one sample of
        # (commit - train_start) records the batch-layer staleness floor
        try:
            train_start = instance.start_time.timestamp()
        except (AttributeError, OSError, ValueError):
            train_start = None
        obs_freshness.observe_commit(
            [train_start] if train_start is not None else [],
            kind="reload",
            epoch=epoch,
        )
        self.last_reload_ts = time.time()
        logger.info(
            "engine instance %s loaded for serving (variant %s)",
            instance.id,
            self.name,
        )

    def reload(self) -> bool:
        """Swap this mount to its latest completed instance."""
        latest = self.storage.get_metadata_engine_instances().get_latest_completed(
            self.instance.engine_id,
            self.instance.engine_version,
            self.instance.engine_variant,
        )
        if latest is None:
            return False
        # prepare_deploy runs OFF the server lock; the swap is atomic —
        # the old model keeps serving 200s through the whole reload
        self._load(latest)
        return True

    # -- speed-layer hot patching -------------------------------------------
    def model_snapshot(self):
        """(instance_id, models, epoch) under the lock — the fenced read
        a fold-in starts from."""
        with self._lock:
            return self.instance.id, self.models, self._epoch

    def apply_patch(self, models, expected_epoch: int) -> bool:
        """Epoch-fenced swap of this mount's model list."""
        with self._lock:
            if expected_epoch != self._epoch:
                return False
            served, self.models = self.models, models
            self._epoch += 1
            self._foldin_epoch += 1
            epoch = self._epoch
        # a patched model that holds the served one's resident item side
        # sent its rows, and the fold booked that copy where it made it
        # (``xfer.h2d[serve.model_patch]``); one that does not goes up
        # whole at its next query: booked here, with no copy to time
        whole = 0
        for old, new in zip(served, models):
            nbytes, restaged = _patch_cost(old, new)
            if restaged or getattr(new, "patch_h2d_bytes", None) is None:
                whole += nbytes
            for part in restaged:
                obs_device.count_restage(part)
        obs_device.count_transfer("h2d", "serve.model_patch", whole)
        if self.query_cache is not None:
            self.query_cache.sweep(epoch, variant=self.name)
        return True

    # -- observability -------------------------------------------------------
    def stats(self) -> dict[str, Any]:
        """One row of the /stats.json ``variants`` block: qps inputs,
        p99, epoch, freshness, SLO states for this tenant."""
        with self._lock:
            avg = (
                self.serving_seconds / self.request_count
                if self.request_count
                else 0.0
            )
            d: dict[str, Any] = {
                "engineInstanceId": self.instance.id,
                "engineVariant": self.instance.engine_variant,
                "epoch": self._epoch,
                "foldinEpoch": self._foldin_epoch,
                "requestCount": self.request_count,
                "avgServingSec": round(avg, 6),
                "lastServingSec": round(self.last_serving_sec, 6),
            }
        hist = (
            self._m_serving_v
            if self._m_serving_v is not None
            else self.server._m_serving
        )
        try:
            d["p99Ms"] = round(hist.percentile(0.99) * 1e3, 3)
        except Exception:  # pragma: no cover - stats must never 500
            d["p99Ms"] = None
        d["modelAgeSec"] = (
            round(time.time() - self.last_reload_ts, 1)
            if self.last_reload_ts
            else None
        )
        layer = self.speed_layer
        d["secondsBehind"] = (
            layer.gauges().get("seconds_behind") if layer is not None else None
        )
        if self._slos:
            d["slo"] = {s.name: s.state for s in self._slos}
        return d


class EngineServer:
    def __init__(
        self,
        engine: Engine,
        instance: EngineInstance,
        storage: Storage | None = None,
        host: str = "0.0.0.0",
        port: int = 8000,
        server_key: str | None = None,
        feedback: bool = False,
        event_server_url: str | None = None,
        access_key: str | None = None,
        server_config=None,
        log_url: str | None = None,
        log_prefix: str | None = None,
        batch_window_ms: float = 0.0,
        dispatch_cost_s: float | None = None,
        reuse_port: bool = False,
        query_cache_mb: float = 0.0,
        query_deadline_ms: float | None = None,
        extra_variants: list[tuple[str, Engine, EngineInstance]] | None = None,
    ):
        self.storage = storage or get_storage()
        self.host = host
        # server.conf-style config supplies the control key and TLS
        # (reference common KeyAuthentication + SSLConfiguration)
        self.server_config = server_config
        self.server_key = server_key
        self.feedback = feedback
        self.event_server_url = event_server_url
        self.access_key = access_key
        # serving errors POST to this URL when set (reference
        # CreateServer.scala remoteLog, :422-433 + :596-618)
        self.log_url = log_url
        self.log_prefix = log_prefix or ""
        self._lock = threading.RLock()
        self.query_cache: QueryCache | None = None
        # set while deploy warmup overlaps live traffic (reuse_port
        # workers, late warmups): /queries.json answers 503 +
        # Retry-After instead of paying a compile it didn't order.
        # /reload does NOT set this — the old model serves through the
        # whole swap (prepare_deploy runs off-lock, the swap is atomic)
        self._swapping = threading.Event()
        # per-query deadline (PIO_QUERY_DEADLINE_MS or query_deadline_ms
        # arg): a query that overruns it gets 503 + Retry-After instead
        # of hanging its connection; None = unbounded (the default)
        if query_deadline_ms is None:
            try:
                query_deadline_ms = float(
                    os.environ.get("PIO_QUERY_DEADLINE_MS", "0").strip() or 0
                )
            except ValueError:
                logger.warning("ignoring non-numeric PIO_QUERY_DEADLINE_MS")
                query_deadline_ms = 0.0
        self.query_deadline_s = (
            query_deadline_ms / 1e3 if query_deadline_ms > 0 else None
        )
        # deadline expiry rides the HTTP front end's timer wheel
        # (HTTPApp.call_later) — a heap entry per in-flight deadline
        # query, not the 32-thread watcher pool this replaced. The
        # unbatched path still needs the scoring off the request thread
        # to answer 503 AT the deadline; a short-lived thread per query
        # does that, capped so an overload degrades to inline scoring
        # with post-hoc shedding instead of unbounded thread spawn.
        self._ddl_slots = threading.BoundedSemaphore(32)

        # tenant mounts: the primary (engine, instance) is the DEFAULT
        # variant — bare /queries.json serves it, and every legacy
        # attribute (engine/instance/models/_epoch/...) delegates to it,
        # so a solo deploy behaves byte-identically to the
        # single-tenant server. extra_variants adds co-tenants routed by
        # /<name>/queries.json or the X-PIO-Variant header; each keeps
        # its own epoch fence, /reload, speed layer, and query-cache
        # partition while sharing this process's HTTP front end,
        # micro-batcher, jit cache (pow2 buckets make compiled programs
        # tenant-independent), and query-cache byte budget.
        default_name = instance.engine_variant or "default"
        mounts = [(default_name, engine, instance)] + list(extra_variants or [])
        labeled = len(mounts) > 1
        self.variants: dict[str, _Variant] = {}
        for name, eng, inst in mounts:
            if not name or "/" in name:
                raise ValueError(f"invalid variant mount name {name!r}")
            if name in self.variants:
                raise ValueError(f"duplicate variant mount name {name!r}")
            self.variants[name] = _Variant(self, name, eng, inst, labeled)
        self.default_variant_name = default_name
        self._default_variant = self.variants[default_name]

        self.start_time = time.time()
        self._m_serving = obs_metrics.histogram(
            "pio_serving_seconds",
            "Per-query scoring+serve time (parse through plugins)",
        )
        self._m_cache_lookup = obs_metrics.histogram(
            "pio_cache_lookup_seconds",
            "Query-cache canonicalize+lookup time (hits and misses)",
        )
        # the request's stages on the request thread, in order
        # (docs/observability.md has the whole chain)
        self._m_submit = obs_metrics.histogram(
            "pio_serving_submit_seconds",
            "Handler entered -> query parsed, supplemented and enqueued",
        )
        self._m_wake = obs_metrics.histogram(
            "pio_serving_wake_seconds",
            "Batch worker resolved the future -> request thread runs again",
        )
        self._m_tail = obs_metrics.histogram(
            "pio_serving_tail_seconds",
            "Request thread resumed -> response bytes encoded "
            "(serve, feedback, plugins, JSON)",
        )
        # every device dispatch, whatever its size and whichever path
        # made it (micro-batch, its single-item fast path, unbatched)
        self._m_batch_size = obs_metrics.histogram(
            "pio_batch_size", "Queries coalesced per device dispatch",
            bounds=(1, 2, 4, 8, 16, 32, 64, 128),
        )
        self._m_dispatch = obs_metrics.histogram(
            "pio_batch_dispatch_seconds",
            "Device-dispatch time per dispatch (predict / batch_predict)",
        )
        self._m_dispatch_cpu = obs_metrics.histogram(
            "pio_batch_dispatch_cpu_seconds",
            "Dispatching thread's thread_time per dispatch: wall minus "
            "this is time it waited (the device, the interpreter)",
        )
        self._m_dispatch_self = obs_metrics.histogram(
            "pio_batch_dispatch_self_seconds",
            "Dispatch time outside its child stages (padding, index "
            "plumbing, host side of batch_predict)",
        )
        self._m_rows_real = obs_metrics.counter(
            "pio_batch_rows_total", "Query rows dispatched", kind="real"
        )
        self._m_rows_padded = obs_metrics.counter(
            "pio_batch_rows_total", "Query rows dispatched", kind="padded"
        )
        # default objectives: p99 latency, 5xx availability, the
        # warmup/deadline 503 budget, ingest-to-servable freshness
        obs_slo.install_engine_slos(self)
        # multi-tenant servers additionally get one latency objective
        # per mount (names suffixed [mount]) so one noisy tenant pages
        # as itself, not as the process aggregate
        if labeled:
            for v in self.variants.values():
                v._slos = obs_slo.install_variant_slos(v)

        self.plugins = plugin_mod.load_plugins(plugin_mod.EngineServerPlugin)
        self.plugin_context: dict[str, Any] = {"storage": self.storage}
        for p in self.plugins:
            p.start(self.plugin_context)

        # query-result cache: preserialized response bytes keyed by
        # (engine_variant, canonical_query_bytes, epoch) — the epoch
        # fence gives EXACT invalidation (every /reload and every
        # speed-layer patch bumps it), so a hit can never be stale with
        # respect to the served model. Two per-request effects make
        # caching incorrect, so they disable it outright:
        if query_cache_mb and query_cache_mb > 0:
            blockers = [
                p for p in self.plugins
                if p.plugin_type == plugin_mod.OUTPUT_BLOCKER
            ]
            if feedback:
                logger.warning(
                    "query cache disabled: --feedback generates a fresh "
                    "prId and POSTs a predict event per request"
                )
            elif blockers:
                logger.warning(
                    "query cache disabled: output-blocker plugin(s) %s "
                    "rewrite responses per request",
                    [p.plugin_name for p in blockers],
                )
            else:
                self.query_cache = QueryCache(int(query_cache_mb * 2**20))

        # micro-batched serving: amortize device dispatch across
        # concurrent requests (0 = per-request, the reference behavior;
        # dispatch_cost_s overrides the startup probe — tests pin it to
        # force window/bypass mode deterministically)
        self.batcher = (
            _MicroBatcher(self, batch_window_ms, dispatch_cost_s=dispatch_cost_s)
            if batch_window_ms > 0
            else None
        )

        self.app = HTTPApp(
            self._router(),
            host=host,
            port=port,
            ssl_context=(
                server_config.ssl_context() if server_config is not None else None
            ),
            reuse_port=reuse_port,
            name="engine",
            ready_check=self._ready_reason,
        )
        # drain-time flush: the speed layer persists its tailer cursor
        # and the batcher stops dispatching before the loop exits
        self.app.add_shutdown_hook(self._drain_flush)

    # -- default-variant delegation -----------------------------------------
    # Legacy surface: every pre-multi-tenant attribute reads/writes the
    # default mount. SpeedLayer(server), the supervisor, the CLI, and
    # tests keep working unchanged; per-tenant state lives on _Variant.

    def _delegate(attr):  # noqa: N805 - descriptor factory, not a method
        def _get(self):
            return getattr(self._default_variant, attr)

        def _set(self, value):
            setattr(self._default_variant, attr, value)

        return property(_get, _set)

    engine = _delegate("engine")
    instance = _delegate("instance")
    engine_params = _delegate("engine_params")
    algorithms = _delegate("algorithms")
    models = _delegate("models")
    serving = _delegate("serving")
    _epoch = _delegate("_epoch")
    _foldin_epoch = _delegate("_foldin_epoch")
    speed_layer = _delegate("speed_layer")
    request_count = _delegate("request_count")
    serving_seconds = _delegate("serving_seconds")
    last_serving_sec = _delegate("last_serving_sec")
    del _delegate

    def _load(self, instance: EngineInstance) -> None:
        self._default_variant._load(instance)

    # -- query path --------------------------------------------------------
    def serve_query_bytes(
        self, body: dict[str, Any], variant: "_Variant | None" = None,
        t_in: float | None = None,
    ) -> bytes:
        """THE /queries.json read path: preserialized response bytes,
        under the ``serve`` span (backdated to ``t_in``, the route
        handler's entry, when the caller has it)."""
        with obs_trace.region("serve", start=t_in) as r:
            return self._serve_query_bytes(
                body, variant if variant is not None else self._default_variant,
                r.start,
            )

    def _serve_query_bytes(
        self, body: dict[str, Any], v: "_Variant", t_in: float | None
    ) -> bytes:
        """Cache lookup, scoring, encode.

        Cache hit: one canonical-bytes build + one sharded dict lookup —
        no device dispatch, no serving join, no JSON encode, and the
        request never enters the micro-batch queue. Miss: the normal
        scoring path, then the encoded bytes are stored iff every
        Algorithm and the Serving say the query is cacheable.

        Epoch fencing: the epoch is snapshotted BEFORE scoring, so a
        model swap landing mid-flight strands the computed result under
        the pre-swap epoch — it can never be served after the swap. (The
        reverse order would race: old-model results could be filed under
        the new epoch.)"""
        cache = self.query_cache
        key = None
        if cache is not None:
            t_c0 = time.perf_counter()
            with self._lock:
                epoch = v._epoch
            try:
                # keyed by the MOUNT name (not instance.engine_variant):
                # unique even when several mounts share one instance, so
                # each tenant keeps its own cache partition
                key = (v.name, canonical_query_bytes(body), epoch)
            except (TypeError, ValueError):
                key = None  # non-canonicalizable body: uncacheable
            payload = cache.get(key) if key is not None else None
            t_c1 = time.perf_counter()
            self._m_cache_lookup.observe(t_c1 - t_c0)
            tr = obs_trace.current_trace()
            if tr is not None:
                tr.add_span(
                    "cache.hit" if payload is not None else "cache.miss",
                    t_c0, t_c1, "serve",
                )
            if payload is not None:
                # a hit is still a served request; it adds ~0 to
                # serving_seconds by construction
                with self._lock:
                    v.request_count += 1
                if v._m_requests_v is not None:
                    v._m_requests_v.inc()
                return payload
        if self.batcher is not None and self.batcher.active:
            try:
                payload = self._serve_batched(body, v, t_in)
            except RuntimeError as e:
                # batcher INFRASTRUCTURE failure (dead worker / stopping
                # server), not a query error: degrade to the unbatched
                # path so the request still serves
                if str(e) not in ("batch worker failed", "server stopping"):
                    raise
                obs_metrics.counter(
                    "pio_batcher_fallback_total",
                    "Queries served unbatched after a micro-batcher failure",
                ).inc()
                logger.warning(
                    "micro-batcher unavailable (%s); serving unbatched", e
                )
                payload = jsonx.dumps_bytes(self._query_with_deadline(body, v))
        else:
            payload = jsonx.dumps_bytes(self._query_with_deadline(body, v))
        if key is not None and self._query_cacheable(body, v):
            cache.put(key, payload)
        return payload

    def _query_cacheable(
        self, body: dict[str, Any], variant: "_Variant | None" = None
    ) -> bool:
        """Every Algorithm AND the Serving must consent (core/base.py
        ``cacheable_query``). Runs on the miss path only."""
        v = variant if variant is not None else self._default_variant
        with self._lock:
            algorithms, serving = v.algorithms, v.serving
        try:
            query, supplemented = self._parse_query(body, algorithms, serving)
        except Exception:
            return False
        if not serving.cacheable_query(query):
            return False
        return all(a.cacheable_query(supplemented) for a in algorithms)

    def _serve_batched(
        self, body: dict[str, Any], variant: "_Variant | None" = None,
        t_in: float | None = None,
    ) -> bytes:
        """Score through the micro-batcher; returns the encoded
        response. A query the batcher gave the dispatch slot is scored
        HERE (``_score_inline``); any other waits for the worker to
        resolve its future with the per-algorithm predictions. Either
        way serving/feedback/plugins (``_finish_query``) and the JSON
        encode run HERE on the request thread (``serve.tail``), so
        batchmates' response tails overlap instead of serializing on the
        worker. Deadline expiry is a timer-wheel entry that fails the
        future — the client gets its 503 AT the deadline even while the
        device call is still in flight."""
        with obs_trace.region("serve.submit", hist=self._m_submit, start=t_in):
            # legacy single-arg call for the default mount (submit
            # defaults to it): solo-deploy wrappers/stubs of submit keep
            # working
            if variant is None or variant is self._default_variant:
                sub = self.batcher.submit(body)
            else:
                sub = self.batcher.submit(body, variant)
        if sub.fut is None:
            predictions, t_resolved = self._score_inline(sub, variant)
        else:
            predictions, t_resolved = self._await_worker(sub.fut)
        t_resumed = time.perf_counter()
        # the hop back from the dispatching thread: a thread switch
        # behind the worker, two clock readings apart behind an inline
        # dispatch (no start where a stub resolved the future)
        if t_resolved is not None and obs_metrics.enabled():
            self._m_wake.observe(t_resumed - t_resolved)
            tr = obs_trace.current_trace()
            if tr is not None:
                tr.add_span("serve.wake", t_resolved, t_resumed, "serve")
        with obs_trace.region("serve.tail", hist=self._m_tail, start=t_resumed):
            return jsonx.dumps_bytes(self._finish_query(
                body, sub.query, predictions, sub.serving, sub.t0,
                variant=variant,
            ))

    def _await_worker(self, fut) -> tuple[Any, float | None]:
        """Block on a queued query's future: (predictions, when the
        worker resolved it)."""
        handle = None
        if self.query_deadline_s is not None:
            handle = self.app.call_later(
                self.query_deadline_s,
                lambda: self._expire_future(fut, "batched"),
            )
        # with a timer armed, result() only needs a generous backstop;
        # without one (loop not running, or no deadline) the result
        # timeout itself enforces the bound
        if self.query_deadline_s is None:
            timeout = 60.0
        elif handle is None:
            timeout = self.query_deadline_s
        else:
            timeout = self.query_deadline_s + 60.0
        try:
            with obs_trace.annotate("serve.wait"):
                predictions = fut.result(timeout=timeout)
        except FuturesTimeout:
            self._count_deadline("batched")
            raise QueryDeadlineExceeded(
                "query exceeded the per-query deadline"
            ) from None
        finally:
            if handle is not None:
                handle.cancel()
        # the worker stamped the future as it resolved it
        return predictions, getattr(fut, "t_resolved", None)

    def _score_inline(
        self, sub: "_Submitted", variant: "_Variant | None"
    ) -> tuple[Any, float]:
        """The single-item fast path on the REQUEST's thread, which holds
        the dispatch slot (``_MicroBatcher.submit``): the series and
        spans of a worker's single, under the request's own trace, with
        what the quantities are here — a queue wait and, in the caller,
        a wake of microseconds. An exception leaves as it would through
        the future. Returns (predictions, when the dispatch ended)."""
        try:
            algorithms, models = self._begin_turn(
                variant if variant is not None else self._default_variant,
                [(sub.t0, obs_trace.current_trace())],
            )
            predictions = self._predict_single(
                algorithms, models, sub.sup, "inline"
            )
            return predictions, time.perf_counter()
        finally:
            self.batcher.release()

    @staticmethod
    def _count_deadline(path: str) -> None:
        obs_metrics.counter(
            "pio_query_deadline_exceeded_total",
            "Queries 503'd for overrunning PIO_QUERY_DEADLINE_MS",
            path=path,
        ).inc()

    def _expire_future(self, fut, path: str) -> None:
        """Timer-wheel callback: fail a still-pending query future at
        its deadline. Counts only when this call actually expired it
        (the scoring path winning the race resolves the future first)."""
        if fut.done():
            return
        try:
            fut.set_exception(
                QueryDeadlineExceeded("query exceeded the per-query deadline")
            )
        except InvalidStateError:
            return
        self._count_deadline(path)

    def _query_with_deadline(
        self, body: dict[str, Any], variant: "_Variant | None" = None
    ) -> dict[str, Any]:
        """Unbatched scoring under the per-query deadline (a plain
        ``handle_query`` call when no deadline is configured — the
        zero-cost default path).

        With a deadline: scoring runs on a short-lived thread while a
        timer-wheel entry arms the 503 — the client is answered AT the
        deadline and an overrunning call finishes discarded (Python
        can't preempt it). The thread count is capped; past the cap —
        or before the HTTP loop starts — scoring runs inline and
        overruns are shed after the fact (same 503 + Retry-After, the
        response-freshness guarantee holds, only the early answer is
        lost)."""
        if self.query_deadline_s is None:
            return self.handle_query(body, variant)
        fut: Future = Future()
        handle = self.app.call_later(
            self.query_deadline_s, lambda: self._expire_future(fut, "unbatched")
        )
        if handle is None or not self._ddl_slots.acquire(blocking=False):
            if handle is not None:
                handle.cancel()
            t0 = time.monotonic()
            result = self.handle_query(body, variant)
            if time.monotonic() - t0 > self.query_deadline_s:
                self._count_deadline("unbatched")
                raise QueryDeadlineExceeded(
                    "query exceeded the per-query deadline"
                )
            return result

        def run() -> None:
            try:
                r = self.handle_query(body, variant)
            except BaseException as e:
                if not fut.done():
                    try:
                        fut.set_exception(e)
                    except InvalidStateError:
                        pass
            else:
                if not fut.done():
                    try:
                        fut.set_result(r)
                    except InvalidStateError:
                        pass
            finally:
                self._ddl_slots.release()

        threading.Thread(target=run, daemon=True, name="query-ddl").start()
        try:
            return fut.result(timeout=self.query_deadline_s + 60.0)
        except FuturesTimeout:
            self._count_deadline("unbatched")
            raise QueryDeadlineExceeded(
                "query exceeded the per-query deadline"
            ) from None
        finally:
            handle.cancel()

    def handle_query(
        self, body: dict[str, Any], variant: "_Variant | None" = None
    ) -> dict[str, Any]:
        faults.fault_point("serve.query")
        v = variant if variant is not None else self._default_variant
        t0 = time.perf_counter()
        with self._lock:
            algorithms, models, serving = v.algorithms, v.models, v.serving
        query, supplemented = self._parse_query(body, algorithms, serving)
        predictions = self._dispatch(
            1, 1, lambda: [
                a.predict(m, supplemented) for a, m in zip(algorithms, models)
            ],
        )
        return self._finish_query(
            body, query, predictions, serving, t0, variant=v
        )

    def _dispatch(self, n_real: int, n_padded: int, call, path=None):
        """One device dispatch of ``n_real`` queries in ``n_padded`` rows:
        the ``batch.dispatch[n]`` span on the current trace(s), its
        histograms and the row counters — EVERY dispatch, single or
        batched, so ``pio_batch_dispatch_seconds`` and ``pio_batch_size``
        count the same events. The score layer records its stages
        (``dispatch.shortlist`` / ``dispatch.rescore`` / ``dispatch.fetch``:
        two launches, then one read) as children. ``path`` says which
        thread of the micro-batcher dispatches (``inline`` / ``worker``;
        None: no batcher) and is counted; the worker's clock is in
        ``dispatch`` for the region, in ``resolve`` after."""
        self._m_batch_size.observe(float(n_real))
        self._m_rows_real.inc(n_real)
        self._m_rows_padded.inc(n_padded)
        clock = None
        if path is not None:
            self.batcher._m_path[path].inc()
            if path == "worker":
                clock = self.batcher.clock
                clock.to("dispatch")
        try:
            with obs_trace.region(
                f"batch.dispatch[{n_real}]", hist=self._m_dispatch,
                cpu_hist=self._m_dispatch_cpu,
            ) as r:
                out = call()
        finally:
            if clock is not None:
                clock.to("resolve")
        self._m_dispatch_self.observe(r.self_seconds)
        return out

    @staticmethod
    def _parse_query(body, algorithms, serving):
        query_class = algorithms[0].query_class
        query = _query_from_json(query_class, body)
        return query, serving.supplement(query)

    def _finish_query(
        self, body, query, predictions, serving, t0, variant=None
    ) -> dict[str, Any]:
        """Per-query tail shared by the per-request and micro-batched
        paths: serve, feedback, plugins, bookkeeping — on the request
        thread, whose current trace names the feedback hop."""
        v = variant if variant is not None else self._default_variant
        result = serving.serve(query, predictions)
        response = _to_jsonable(result)

        pr_id: str | None = None
        if self.feedback:
            pr_id = body.get("prId") or uuid.uuid4().hex[:16]
            self._send_feedback(
                body, response, pr_id, trace=obs_trace.current_trace()
            )
            if isinstance(response, dict):
                response = {**response, "prId": pr_id}

        for p in self.plugins:
            if p.plugin_type == plugin_mod.OUTPUT_BLOCKER:
                response = p.process(
                    v.instance.engine_variant, body, response, self.plugin_context
                )
            else:
                p.process(
                    v.instance.engine_variant, body, response, self.plugin_context
                )

        t_end = time.perf_counter()
        dt = t_end - t0
        self._m_serving.observe(dt)
        if v._m_serving_v is not None:
            v._m_serving_v.observe(dt)
        if v._m_requests_v is not None:
            v._m_requests_v.inc()
        with self._lock:
            v.request_count += 1
            v.serving_seconds += dt
            v.last_serving_sec = dt
        return response

    @staticmethod
    def _resolve(fut, predictions=None, exc=None) -> None:
        # the deadline timer may have expired the future already —
        # losing that race is normal, never an error
        if fut.done():
            return
        # serve.wake starts here and ends on the request thread
        fut.t_resolved = time.perf_counter()
        try:
            if exc is not None:
                fut.set_exception(exc)
            else:
                fut.set_result(predictions)
        except InvalidStateError:
            pass

    def _handle_query_batch(self, items) -> None:
        """Score one micro-batch, grouped by tenant mount: queries for
        different variants never share a ``batch_predict`` call (their
        models differ), but co-tenant queries still coalesce — and
        because every group pads to the same pow2 buckets, the jitted
        programs stay shared across tenants (compiles bounded by bucket
        count, not tenant count)."""
        groups: dict[int, list] = {}
        by_id: dict[int, Any] = {}
        for it in items:
            v = it[4]
            groups.setdefault(id(v), []).append(it)
            by_id[id(v)] = v
        for vid, group in groups.items():
            self._score_batch_group(by_id[vid], group)

    def _begin_turn(self, variant: "_Variant", waits) -> tuple[list, list]:
        """What a dispatch turn of the micro-batcher starts with, on
        whichever thread holds the slot: the mount's (algorithms, models)
        under the lock, and each query's wait since its submit —
        ``waits`` holds a (t0, trace) a query — observed and on its
        trace."""
        with self._lock:
            algorithms, models = variant.algorithms, variant.models
        queue_wait = self.batcher._m_queue_wait
        t_collect = time.perf_counter()
        for t0, tr in waits:
            queue_wait.observe(t_collect - t0)
            if tr is not None:
                tr.add_span("batch.queue_wait", t0, t_collect, "serve")
        return algorithms, models

    def _predict_single(self, algorithms, models, sup, path: str):
        """The single-item fast path: no padding, no index plumbing —
        straight to ``predict``."""
        return self._dispatch(1, 1, lambda: [
            a.predict(m, sup) for a, m in zip(algorithms, models)
        ], path=path)

    def _score_batch_group(self, variant: "_Variant", items) -> None:
        """Score one tenant's micro-batch: every algorithm runs ONE
        batch_predict over the whole batch; serving/feedback/plugins run
        per query on the REQUEST threads (the futures resolve to
        predictions, not responses). A single-item batch — a lone query
        that queued behind a dispatch in flight — skips the
        padding/coalesce machinery and goes straight to ``predict``, as
        ``_score_inline`` does for one that met none. A failing batch
        retries its queries individually so one bad request can't fail
        its batchmates."""
        algorithms, models = self._begin_turn(
            variant, [(t0, tr) for _, t0, tr, _, _ in items]
        )

        def predict_one(sup):
            return self._predict_single(algorithms, models, sup, "worker")

        # the worker has no trace of its own: for the dispatch it stands
        # in for every batchmate's, as a child of their ``serve`` spans
        fanout = obs_trace.Fanout(tr for _, _, tr, _, _ in items)
        if len(items) == 1:
            # FAST PATH: no padding, no index plumbing — lone-query
            # latency matches per-request serving
            fut, _, _, sup, _ = items[0]
            try:
                with obs_trace.use_trace(fanout, parent="serve"):
                    predictions = predict_one(sup)
            except Exception as e:
                self._resolve(fut, exc=e)
                return
            self._resolve(fut, predictions)
            return
        per_algo: list[dict] | None
        try:
            # pad to a power-of-two batch size with copies of the first
            # query (padding results are discarded): jitted batch
            # programs specialize on the batch shape, and
            # traffic-dependent sizes would recompile per distinct size
            # — the stall the window exists to avoid
            n_real = len(items)
            pad_to = 1 << max(0, n_real - 1).bit_length()

            def batch_call():
                indexed = [
                    (i, sup) for i, (_, _, _, sup, _) in enumerate(items)
                ]
                indexed += [
                    (n_real + j, indexed[0][1]) for j in range(pad_to - n_real)
                ]
                faults.fault_point("serve.batch_dispatch")
                return [
                    dict(a.batch_predict(m, indexed))
                    for a, m in zip(algorithms, models)
                ]

            with obs_trace.use_trace(fanout, parent="serve"):
                per_algo = self._dispatch(
                    n_real, pad_to, batch_call, path="worker"
                )
        except Exception:
            logger.exception("batched scoring failed; retrying per query")
            per_algo = None
        for i, (fut, t0, tr, sup, _) in enumerate(items):
            if per_algo is None:
                try:
                    with obs_trace.use_trace(tr, parent="serve"):
                        predictions = predict_one(sup)
                except Exception as e:
                    self._resolve(fut, exc=e)
                    continue
            else:
                predictions = [d[i] for d in per_algo]
            self._resolve(fut, predictions)

    @staticmethod
    def _post_async(
        url: str,
        payload: bytes,
        what: str,
        headers: dict[str, str] | None = None,
    ) -> None:
        """Fire-and-forget POST on a daemon thread — failures are logged,
        never raised (feedback + remote-log transport)."""

        def post():
            try:
                req = urllib.request.Request(
                    url, data=payload, headers=headers or {}
                )
                urllib.request.urlopen(req, timeout=10).read()
            except Exception:
                logger.exception("%s POST failed", what)

        threading.Thread(target=post, daemon=True).start()

    def _send_feedback(
        self, query: dict, prediction: Any, pr_id: str, trace=None
    ) -> None:
        """Async predict-event POST back to the event server
        (CreateServer.scala:514-577)."""
        if not (self.event_server_url and self.access_key):
            logger.warning("feedback enabled but event server/access key missing")
            return
        payload = json.dumps(
            {
                "event": "predict",
                "entityType": "pio_pr",
                "entityId": pr_id,
                "properties": {"query": query, "prediction": prediction},
                "prId": pr_id,
            }
        ).encode()
        url = (
            f"{self.event_server_url.rstrip('/')}/events.json"
            f"?accessKey={self.access_key}"
        )
        headers = {"Content-Type": "application/json"}
        if trace is not None:
            # the event server's ingest hop joins this query's timeline
            headers[obs_trace.TRACE_HEADER] = trace.trace_id
        self._post_async(url, payload, "feedback event", headers=headers)

    def _remote_log(self, message: str) -> None:
        """Best-effort POST of a serving error to ``log_url`` (reference
        CreateServer.scala:422-433 remoteLog; fired on query failures at
        :596-618). Body is ``log_prefix`` + JSON {engineInstance,
        message}, like the reference's logPrefix + write(...)."""
        if not self.log_url:
            return
        payload = (
            self.log_prefix
            + json.dumps(
                {
                    "engineInstance": {
                        "id": self.instance.id,
                        "engineFactory": self.instance.engine_factory,
                        "engineVariant": self.instance.engine_variant,
                    },
                    "message": message,
                }
            )
        ).encode()
        self._post_async(self.log_url, payload, "remote log")

    # -- control -----------------------------------------------------------
    def reload(self, variant: "_Variant | None" = None) -> bool:
        """Swap a mount to its latest completed instance (reference
        /reload). Defaults to the default mount; the per-variant routes
        pass their own. Other mounts' epochs and cache partitions are
        untouched."""
        v = variant if variant is not None else self._default_variant
        latest = self.storage.get_metadata_engine_instances().get_latest_completed(
            v.instance.engine_id,
            v.instance.engine_version,
            v.instance.engine_variant,
        )
        if latest is None:
            return False
        # prepare_deploy runs OFF the server lock; the swap is atomic —
        # the old model keeps serving 200s through the whole reload. The
        # default mount goes through the server-level _load hook (tests
        # and plugins may wrap it); co-tenants load directly.
        if v is self._default_variant:
            self._load(latest)
        else:
            v._load(latest)
        return True

    # -- speed-layer hot patching -------------------------------------------
    def model_snapshot(self):
        """(instance_id, models, epoch) of the DEFAULT mount under the
        lock — the fenced read a fold-in starts from (solo-deploy compat;
        multi-tenant speed layers hold their _Variant directly)."""
        return self._default_variant.model_snapshot()

    def apply_patch(self, models, expected_epoch: int) -> bool:
        """Epoch-fenced swap of the default mount's model list
        (speed-layer hot patch). Returns False without touching anything
        when the epoch moved since the snapshot — the caller re-reads
        and re-folds."""
        return self._default_variant.apply_patch(models, expected_epoch)

    def status(self) -> dict[str, Any]:
        with self._lock:
            avg = (
                self.serving_seconds / self.request_count
                if self.request_count
                else 0.0
            )
            return {
                "status": "alive",
                "engineInstanceId": self.instance.id,
                "engineFactory": self.instance.engine_factory,
                "engineVariant": self.instance.engine_variant,
                "startTime": self.start_time,
                "requestCount": self.request_count,
                "avgServingSec": round(avg, 6),
                "lastServingSec": round(self.last_serving_sec, 6),
                "plugins": [p.plugin_name for p in self.plugins],
            }

    def _status_html(self) -> str:
        """Minimal render of the reference's HTML status page
        (CreateServer.scala:443-467, templates html.index): engine info,
        component params, serving stats."""
        import html as html_mod

        s = self.status()
        with self._lock:
            algo_rows = "".join(
                f"<tr><td>{html_mod.escape(type(a).__name__)}</td>"
                f"<td><pre>{html_mod.escape(str(p))}</pre></td></tr>"
                for a, (_, p) in zip(
                    self.algorithms, self.engine_params.algorithms
                )
            )
            serving_name = type(self.serving).__name__
        rows = "".join(
            f"<tr><th>{html_mod.escape(str(k))}</th>"
            f"<td>{html_mod.escape(str(v))}</td></tr>"
            for k, v in s.items()
        )
        return (
            "<!DOCTYPE html><html><head>"
            "<title>Engine Server at "
            f"{html_mod.escape(self.host)}</title></head><body>"
            f"<h1>Engine: {html_mod.escape(s['engineFactory'])}</h1>"
            f"<table border='1'>{rows}</table>"
            f"<h2>Algorithms</h2><table border='1'>"
            f"<tr><th>Class</th><th>Params</th></tr>{algo_rows}</table>"
            f"<h2>Serving</h2><p>{html_mod.escape(serving_name)}</p>"
            "</body></html>"
        )

    # -- routes ------------------------------------------------------------
    def _router(self) -> Router:
        router = Router()
        server = self

        @router.route("GET", "/")
        def status(request: Request) -> Response:
            # browsers get the reference's HTML status page
            # (CreateServer.scala:443-467 renders html.index); API
            # clients keep the JSON body
            if "text/html" in request.headers.get("accept", ""):
                return Response.html(server._status_html())
            return Response.json(server.status())

        @router.route("GET", "/stats.json")
        def stats(request: Request) -> Response:
            body = server.status()
            layer = server.speed_layer
            body["realtime"] = (
                layer.gauges() if layer is not None else {"enabled": False}
            )
            cache = server.query_cache
            body["cache"] = (
                {"enabled": True, **cache.gauges()}
                if cache is not None
                else {"enabled": False}
            )
            # per-mount rows: solo deploys get a one-entry block keyed by
            # the default mount name, so dashboards render one code path
            body["variants"] = {
                name: v.stats() for name, v in server.variants.items()
            }
            # additive: existing consumers keep their fields untouched
            body["obs"] = obs_metrics.stats_block()
            body["device"] = obs_device.device_block()
            body["freshness"] = obs_freshness.block()
            body["runtime"] = obs_runtime.block()
            batcher = server.batcher
            body["batch"] = (
                {"enabled": True, **batcher.stats_block()}
                if batcher is not None
                else {"enabled": False}
            )
            try:
                from predictionio_tpu.models import modelfile as _modelfile
                from predictionio_tpu.ops import retrieval as _retrieval

                body["retrieval"] = _retrieval.stats_block()
                body["model_ids"] = _modelfile.id_stats_block()
            except Exception:  # pragma: no cover - stats must never 500
                pass
            return Response.json(body)

        def _resolve_header_variant(request: Request) -> "_Variant | None":
            """Mount for a BARE-path request: the ``X-PIO-Variant``
            header when present (None for an unknown name -> 404), else
            the default mount."""
            name = request.headers.get("x-pio-variant")
            if name is None:
                return server._default_variant
            return server.variants.get(name)

        def _handle_queries(request: Request, v: "_Variant") -> Response:
            t_in = time.perf_counter()  # serve / serve.submit start here
            if server._swapping.is_set():
                obs_metrics.counter(
                    "pio_query_unavailable_total",
                    "Queries 503'd while unavailable",
                    reason="swap",
                ).inc()
                # a 503 burst must be visible in /traces.json, not just
                # as a counter — mark the request's trace
                tr = obs_trace.current_trace()
                if tr is not None:
                    now = time.perf_counter()
                    tr.add_span("serve.unavailable", now, now)
                return Response(
                    status=503,
                    body={"message": "model swap in progress; retry shortly"},
                    headers={"Retry-After": "1"},
                )
            body = request.json()
            if not isinstance(body, dict):
                return Response.error("request body must be a JSON object", 400)
            try:
                return Response.json_bytes(
                    server.serve_query_bytes(body, v, t_in)
                )
            except QueryDeadlineExceeded as e:
                obs_metrics.counter(
                    "pio_query_unavailable_total",
                    "Queries 503'd while unavailable",
                    reason="deadline",
                ).inc()
                # like the swap branch: a deadline 503 burst must be
                # visible in /traces.json, not just as a counter
                tr = obs_trace.current_trace()
                if tr is not None:
                    now = time.perf_counter()
                    tr.add_span("serve.unavailable", now, now)
                return Response(
                    status=503,
                    body={"message": str(e)},
                    headers={"Retry-After": "1"},
                )
            except (TypeError, KeyError, ValueError) as e:
                # reference: MappingException -> 400 + remote log
                # (CreateServer.scala:596-604)
                server._remote_log(
                    f"Query:\n{request.body.decode(errors='replace')}\n\n"
                    f"Error:\n{e}\n\n"
                )
                return Response.error(f"Your query is not valid. {e}", 400)
            except Exception as e:
                # reference: Throwable -> 500 + remote log (:605-618)
                logger.exception("serving failed")
                server._remote_log(
                    f"Query:\n{request.body.decode(errors='replace')}\n\n"
                    f"Error:\n{e}\n\n"
                )
                return Response.error(f"serving failed: {e}", 500)

        @router.route("POST", "/queries.json")
        def queries(request: Request) -> Response:
            v = _resolve_header_variant(request)
            if v is None:
                return Response.error(
                    "unknown engine variant "
                    f"{request.headers.get('x-pio-variant')!r}", 404
                )
            return _handle_queries(request, v)

        def _handle_reload(request: Request, v: "_Variant") -> Response:
            if not server._auth_control(request):
                return Response.error("Invalid accessKey.", 401)
            ok = server.reload(v)
            if not ok:
                return Response.error("no completed engine instance found", 404)
            return Response.json({"message": "Reloading..."})

        @router.route("POST", "/reload")
        def reload(request: Request) -> Response:
            v = _resolve_header_variant(request)
            if v is None:
                return Response.error(
                    "unknown engine variant "
                    f"{request.headers.get('x-pio-variant')!r}", 404
                )
            return _handle_reload(request, v)

        @router.route("POST", "/stop")
        def stop(request: Request) -> Response:
            if not server._auth_control(request):
                return Response.error("Invalid accessKey.", 401)
            response = Response.json({"message": "Shutting down..."})
            response.after_send = server.stop  # runs after the bytes flush
            return response

        @router.route("GET", "/plugins.json")
        def plugins_route(request: Request) -> Response:
            return Response.json(
                {
                    "plugins": {
                        p.plugin_name: {
                            "outputblocker": p.plugin_type
                            == plugin_mod.OUTPUT_BLOCKER,
                            "description": p.plugin_description,
                        }
                        for p in server.plugins
                    }
                }
            )

        @router.route("GET", "/plugins/<name>.json")
        def plugin_rest(request: Request) -> Response:
            name = request.path_params["name"]
            for p in server.plugins:
                if p.plugin_name == name:
                    return Response.json(p.handle_rest(dict(request.query)))
            return Response.error("plugin not found", 404)

        # path-prefix tenant routing: /<variant>/queries.json is the
        # load-balancer-friendly form of the X-PIO-Variant header. The
        # exact routes above win first (registration order), so a mount
        # can never shadow /plugins.json or /stats.json.
        @router.route("POST", "/<variant>/queries.json")
        def variant_queries(request: Request) -> Response:
            v = server.variants.get(request.path_params["variant"])
            if v is None:
                return Response.error(
                    f"unknown engine variant "
                    f"{request.path_params['variant']!r}", 404
                )
            return _handle_queries(request, v)

        @router.route("POST", "/<variant>/reload")
        def variant_reload(request: Request) -> Response:
            v = server.variants.get(request.path_params["variant"])
            if v is None:
                return Response.error(
                    f"unknown engine variant "
                    f"{request.path_params['variant']!r}", 404
                )
            return _handle_reload(request, v)

        add_obs_routes(router)
        return router

    def _auth_control(self, request: Request) -> bool:
        """/reload and /stop are guarded by the server key when set
        (reference common KeyAuthentication). When a ServerConfig is
        present its enforcement flag decides — an enforced-but-empty key
        still requires a matching (empty-string) param rather than
        silently disabling auth."""
        if self.server_config is not None:
            from predictionio_tpu.common import KeyAuthentication

            allowed = KeyAuthentication(self.server_config).authorized(request.query)
            if not allowed:
                return False
            if self.server_key is None:
                return True
        if not self.server_key:
            return True
        return request.query.get("accessKey") == self.server_key

    # -- lifecycle ---------------------------------------------------------
    def warmup(self) -> int:
        """Deploy-time AOT warmup: one throwaway ``batch_predict`` per
        algorithm BEFORE the port binds, so the first real query pays a
        scoring-program cache hit instead of an XLA compile (seconds on
        CPU, tens of seconds on TPU attachments). Queries come from each
        algorithm's ``warmup_query`` hook; failures are logged and
        swallowed — warmup must never block a deploy. Returns how many
        algorithms were warmed. Multi-tenant mounts warm every variant
        — co-tenants of one instance share the scoring-program cache, so
        repeats hit compiled programs and cost one throwaway score."""
        warmed = 0
        # normally warmup runs before the port binds, but reuse_port
        # workers and late warmups can overlap live traffic — those
        # queries get 503 + Retry-After instead of queueing behind the
        # warm-up compile
        self._swapping.set()
        try:
            for v in self.variants.values():
                with self._lock:
                    algorithms, models = v.algorithms, v.models
                for a, m in zip(algorithms, models):
                    try:
                        q = a.warmup_query(m)
                        if q is None:
                            continue
                        t0 = time.perf_counter()
                        a.batch_predict(m, [(0, q)])
                        logger.info(
                            "warmup: %s compiled+scored in %.3fs",
                            type(a).__name__, time.perf_counter() - t0,
                        )
                        warmed += 1
                    except Exception:
                        logger.exception(
                            "warmup predict failed for %s (serving unaffected)",
                            type(a).__name__,
                        )
        finally:
            self._swapping.clear()
        return warmed

    def _ready_reason(self) -> str | None:
        """The engine half of ``/readyz`` (the HTTPApp adds the draining
        check): warmup/model-swap fencing and a loaded model on every
        mount."""
        if self._swapping.is_set():
            return "model swap/warmup in progress"
        for v in self.variants.values():
            if not v.models:
                return f"no model loaded ({v.name})"
        return None

    def _drain_flush(self) -> None:
        for v in self.variants.values():
            if v.speed_layer is not None:
                v.speed_layer.stop()
        if self.batcher is not None:
            self.batcher.stop()

    def start(self, background: bool = True) -> int:
        port = self.app.start(background=background)
        logger.info("Engine Server listening on %s:%d", self.host, port)
        return port

    def drain(self) -> None:
        """Graceful shutdown: finish in-flight queries, flush the speed
        layer's cursor, then stop."""
        self.app.drain()

    def stop(self) -> None:
        for v in self.variants.values():
            if v.speed_layer is not None:
                v.speed_layer.stop()
        if self.batcher is not None:
            self.batcher.stop()
        self.app.stop()
