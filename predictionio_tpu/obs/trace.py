"""Request tracing: per-request spans and a slowest-traces ring.

Every HTTP request handled by :mod:`predictionio_tpu.server.http` gets a
:class:`Trace` — its id honors an incoming ``X-PIO-Trace`` header (so a
client, a webhook source, or the feedback loop can stitch hops into one
timeline) and is propagated on outbound framework POSTs. Stage
boundaries record spans (name + offset + duration + parent tuples in a
flat list; ``parent`` names the span that caused this one, so self time
= duration - children is computable from ``/traces.json``), and on
completion the trace is offered to :data:`TRACES`, a fixed-capacity ring
that retains the N SLOWEST recent traces: the p99 outliers an operator
actually wants to dissect survive, uninteresting fast requests fall out
first. Served as ``GET /traces.json`` on every server and rendered as a
waterfall table on the dashboard.

There is ONE way to record a stage: :func:`region`, a context manager
that timestamps with ``perf_counter``, adds the span to the current
trace (or the one it is handed) under the enclosing region's name,
feeds the stage's always-on histogram, and — only while a profiler
capture runs (``obs.device.profile_capture``) — also enters a
``jax.profiler.TraceAnnotation`` so the span lands in the ``.xplane.pb``
on the device trace's clock. Retroactive ``Trace.add_span`` stays for
spans whose start and end are on different threads.

The current trace rides a thread-local so instrumented stages deep in a
handler need no plumbing; work that hops threads (the micro-batch
worker) carries the Trace objects through its queue items and installs
a :class:`Fanout` of them for the duration of a dispatch —
``add_span`` is safe from any thread. This module never imports jax
unless a profile is running.
"""

from __future__ import annotations

import itertools
import os
import threading
import time
import uuid
from heapq import heappush, heapreplace

from predictionio_tpu.obs import metrics as _metrics

__all__ = [
    "TRACE_HEADER",
    "Trace",
    "TraceRing",
    "TRACES",
    "Fanout",
    "current_trace",
    "set_current_trace",
    "use_trace",
    "region",
    "one_in_every",
    "annotate",
    "annotating",
    "set_annotating",
    "new_trace_id",
]

# canonical wire spelling; server/http.py lowercases header keys
TRACE_HEADER = "X-PIO-Trace"


# ids are minted on EVERY request, so uuid4-per-call (an os.urandom
# syscall) is too dear: a random per-process prefix + an atomic counter
# gives the same 16-hex wire shape at ~1/10 the cost
_ID_PREFIX = uuid.uuid4().hex[:8]
_id_counter = itertools.count(1)


def new_trace_id() -> str:
    return f"{_ID_PREFIX}{next(_id_counter) & 0xFFFFFFFF:08x}"


# maps a perf_counter reading to wall time without a time.time() call
# per trace; the mapping drifts only with NTP slew, irrelevant at the
# ring's 1 h retention scale
_EPOCH_OFFSET = time.time() - time.perf_counter()


class Trace:
    """One request's timeline. ``t0`` is a perf_counter anchor; spans are
    ``(name, offset_s, duration_s, parent)`` tuples relative to it.

    Construction is on every request's entry path, so everything
    deferrable is deferred: the trace id is minted only when first read
    (most requests carry no ``X-PIO-Trace`` and never get admitted to
    the ring), and the wall-clock start is derived from ``t0``."""

    __slots__ = ("_tid", "name", "t0", "spans", "status", "duration_s")

    def __init__(self, name: str, trace_id: str | None = None,
                 t0: float | None = None):
        self._tid = trace_id
        self.name = name
        self.t0 = time.perf_counter() if t0 is None else t0
        self.spans: list[tuple[str, float, float, str | None]] = []
        self.status: int | None = None
        self.duration_s: float = 0.0

    @property
    def trace_id(self) -> str:
        tid = self._tid
        if tid is None:
            tid = self._tid = new_trace_id()
        return tid

    @property
    def wall_start(self) -> float:
        return _EPOCH_OFFSET + self.t0

    def add_span(self, name: str, start: float, end: float,
                 parent: str | None = None) -> None:
        """Record a stage from perf_counter timestamps (thread-safe:
        list.append is atomic under the GIL). ``parent`` names the span
        that caused this one."""
        self.spans.append((name, start - self.t0, end - start, parent))

    def span(self, name: str) -> "region":
        return region(name, trace=self)

    def span_dict(self, name: str, start: float, end: float,
                  parent: str | None = None) -> dict:
        """One span in ``to_dict``'s wire shape."""
        return _span_dict(name, start - self.t0, end - start, parent)

    def finish(self, status: int | None = None) -> None:
        self.status = status
        self.duration_s = time.perf_counter() - self.t0

    def to_dict(self) -> dict:
        return {
            "traceId": self.trace_id,
            "name": self.name,
            "start": round(self.wall_start, 3),
            "durationMs": round(self.duration_s * 1e3, 3),
            "status": self.status,
            "spans": [_span_dict(*span) for span in self.spans],
        }


def _span_dict(name: str, off: float, dur: float, parent: str | None) -> dict:
    return {
        "name": name,
        "offsetMs": round(off * 1e3, 3),
        "durationMs": round(dur * 1e3, 3),
        "parent": parent,
    }


class Fanout:
    """Several requests' traces behind one ``add_span``: what the batch
    worker installs as its current trace for the duration of a dispatch,
    so a stage recorded once lands on every batchmate's timeline."""

    __slots__ = ("traces",)

    def __init__(self, traces):
        self.traces = [t for t in traces if t is not None]

    def add_span(self, name: str, start: float, end: float,
                 parent: str | None = None) -> None:
        for t in self.traces:
            t.add_span(name, start, end, parent)


# -- thread-local current trace ---------------------------------------------

_tls = threading.local()


def _state() -> list:
    """This thread's [current trace, innermost open region's name,
    seconds of the regions already closed inside it]: ONE thread-local
    read for a region's entry (a region is entered ~16 times a query)."""
    try:
        return _tls.st
    except AttributeError:
        st = _tls.st = [None, None, 0.0]
        return st


def current_trace() -> "Trace | Fanout | None":
    return _state()[0]


def set_current_trace(trace: "Trace | Fanout | None") -> None:
    _state()[0] = trace


class use_trace:
    """Install ``trace`` as this thread's current trace for a block;
    ``parent`` names the span (open on ANOTHER thread) that regions
    entered inside the block are children of."""

    __slots__ = ("_trace", "_parent", "_prev")

    def __init__(self, trace, parent: str | None = None):
        self._trace = trace
        self._parent = parent

    def __enter__(self):
        st = _state()
        self._prev = (st[0], st[1])
        st[0], st[1] = self._trace, self._parent
        return self._trace

    def __exit__(self, *exc):
        st = _state()
        st[0], st[1] = self._prev
        return False


# -- regions ------------------------------------------------------------------

# True only while obs.device.profile_capture has a jax.profiler trace
# running: the one flag a region reads to decide whether to annotate
_annotating = False


def set_annotating(flag: bool) -> None:
    global _annotating
    _annotating = bool(flag)


def annotating() -> bool:
    """Is a capture running? For a wait that outlasts captures: an
    annotation is decided where it is entered, so a thread that waits in
    one for seconds enters it again when this changes."""
    return _annotating


class _Null:
    """Shared do-nothing context manager (no profile running)."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _Null()


def _annotation(name: str):
    from jax.profiler import TraceAnnotation  # lazy: a profile is running

    return TraceAnnotation(name)


def annotate(name: str):
    """Annotation-only region: a named host state (``batch.collect``,
    ``http.poll``, ``serve.wait``) in the profiler's trace while a
    capture runs, so an idle device can be put down to it; nothing —
    one flag read — otherwise."""
    if _annotating and _metrics.enabled():
        return _annotation(name)
    return _NULL


# ``time.thread_time()`` is a system call: 0.25 us on a plain Linux host,
# but 6 us alone and ~35 us inside a serving process on a sandboxed one
# (the benchmark's: PERF.md section 6, PR 34). So a region given a
# ``cpu_hist`` reads it on one call in CPU_EVERY, counted per histogram:
# the histogram's mean stays a mean, to set beside the wall histogram's.
# Seven and not eight: a stride that shares a factor with a batcher's
# rhythm (a small batch, a large one, ...) would always meet the same kind.
CPU_EVERY = 7
_calls: dict[int, int] = {}


def one_in_every(key) -> bool:
    """True on one call in ``CPU_EVERY``, counted per ``key`` (an
    instrument): the stride for a reading that costs too much to take on
    every call — a second clock, a second runtime call. Never under
    ``PIO_OBS=0``."""
    if not _metrics._enabled:
        return False
    k = id(key)
    n = _calls.get(k, 0)
    _calls[k] = n + 1
    return n % CPU_EVERY == 0


class region:
    """Record one stage: ``with region("serve.tail", hist=h): ...``.

    On exit the span ``(name, start, end, parent)`` is added to ``trace``
    (default: this thread's current trace, if any), where ``parent`` is
    the region enclosing this one on this thread; ``hist`` (a
    ``metrics.Histogram``) observes the duration; while a profiler
    capture runs the block is also a ``jax.profiler.TraceAnnotation``.
    ``start`` backdates the region to a perf_counter reading taken
    earlier on this thread. ``cpu_hist`` observes the block's
    ``time.thread_time()`` — this thread's CPU, so wall minus CPU is time
    it waited (for the interpreter, for a runtime call that blocks) — on
    one call in ``CPU_EVERY``; without it no second clock is read. After
    exit ``seconds`` is the
    duration and ``self_seconds`` the duration minus the regions nested
    directly inside it. A no-op under ``PIO_OBS=0``."""

    __slots__ = (
        "name", "start", "end", "seconds", "self_seconds",
        "_hist", "_trace", "_st", "_parent", "_outer_children", "_ann", "_on",
        "_cpu_hist", "_cpu0",
    )

    def __init__(self, name: str, hist=None, trace=None,
                 start: float | None = None, cpu_hist=None):
        self.name = name
        self.start = start
        self.end = self.seconds = self.self_seconds = 0.0
        self._hist = hist
        self._trace = trace
        self._cpu_hist = cpu_hist

    def __enter__(self):
        on = self._on = _metrics._enabled
        if not on:
            return self
        try:
            st = self._st = _tls.st
        except AttributeError:
            st = self._st = _state()
        if self._trace is None:
            self._trace = st[0]
        self._parent = st[1]
        self._outer_children = st[2]
        st[1] = self.name
        st[2] = 0.0
        self._ann = None
        if _annotating:
            self._ann = _annotation(self.name)
            self._ann.__enter__()
        if self._cpu_hist is not None:
            if one_in_every(self._cpu_hist):
                self._cpu0 = time.thread_time()
            else:
                self._cpu_hist = None
        if self.start is None:
            self.start = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if not self._on:
            return False
        end = self.end = time.perf_counter()
        if self._cpu_hist is not None:
            self._cpu_hist.observe(time.thread_time() - self._cpu0)
        if self._ann is not None:
            self._ann.__exit__(*exc)
        dt = self.seconds = end - self.start
        st = self._st
        self.self_seconds = dt - st[2]
        st[2] = self._outer_children + dt
        st[1] = self._parent
        if self._hist is not None:
            self._hist.observe(dt)
        if self._trace is not None:
            self._trace.add_span(self.name, self.start, end, self._parent)
        return False


# -- retention ---------------------------------------------------------------


class TraceRing:
    """Fixed-capacity retention of the slowest recent traces.

    A min-heap keyed by duration: a finished trace is admitted while
    there is room, and past capacity only if it is slower than the
    current fastest retained trace (which it evicts). ``max_age_s``
    bounds "recent": expired entries are pruned on a ~1 s schedule and
    on snapshot so one ancient outlier cannot squat the ring forever.

    ``offer`` is on every request's exit path, so its steady-state cost
    is one lock + one float compare: serialization (``to_dict``) happens
    only when the trace is actually admitted, and the age prune (a
    rebuild+sort of the heap list) runs at most once a second.
    """

    def __init__(self, capacity: int = 64, max_age_s: float = 3600.0):
        self.capacity = int(capacity)
        self.max_age_s = float(max_age_s)
        self._lock = threading.Lock()
        self._seq = 0  # heap tiebreak: equal durations evict oldest-first
        self._next_prune = 0.0
        self._heap: list[tuple[float, int, dict]] = []

    def offer(self, trace: Trace) -> dict | None:
        """Admit ``trace`` if it ranks; returns the retained entry (so a
        span that ends after the offer — ``http.write`` — can still be
        appended to its ``spans``) or None."""
        if not _metrics.enabled():
            return None
        d = trace.duration_s
        heap = self._heap
        # unlocked peek (GIL-atomic list reads): once the ring is full,
        # the common case is a trace faster than the retained floor — a
        # stale read can only skip one borderline admission, which a
        # diagnostics ring tolerates
        if (
            len(heap) >= self.capacity
            and heap[0][0] >= d
            and time.time() < self._next_prune
        ):
            return None
        entry = None
        with self._lock:
            now = time.time()
            if now >= self._next_prune:
                self._prune_locked(now)
                self._next_prune = now + 1.0
            if len(self._heap) < self.capacity:
                entry = self._admit(trace, d)
                heappush(self._heap, (d, self._next_seq(), entry))
            elif self._heap and d > self._heap[0][0]:
                entry = self._admit(trace, d)
                heapreplace(self._heap, (d, self._next_seq(), entry))
        return entry

    @staticmethod
    def _admit(trace: Trace, duration_s: float) -> dict:
        """Serialize an admitted trace, tagging it with the SLOs it is
        evidence for (currently-violated objectives plus any latency
        objective this single request blew) so ``/traces.json``'s
        ``?slo=violated`` filter jumps straight to the bodies."""
        entry = trace.to_dict()
        try:
            from predictionio_tpu.obs import slo as _slo

            tags = _slo.trace_tags(duration_s)
        except Exception:
            tags = []
        if tags:
            entry["sloViolated"] = tags
        return entry

    def _next_seq(self) -> int:
        self._seq += 1
        return self._seq

    def _prune_locked(self, now: float | None = None) -> None:
        if self.max_age_s <= 0 or not self._heap:
            return
        horizon = (time.time() if now is None else now) - self.max_age_s
        if all(e[2]["start"] >= horizon for e in self._heap):
            return  # nothing expired: keep the heap as-is
        self._heap = [e for e in self._heap if e[2]["start"] >= horizon]
        self._heap.sort()  # restore heap order (sorted list is a heap)

    def snapshot(self) -> list[dict]:
        """Retained traces, slowest first."""
        with self._lock:
            self._prune_locked()
            entries = sorted(self._heap, reverse=True)
        return [e[2] for e in entries]

    def clear(self) -> None:
        with self._lock:
            self._heap.clear()


def _env_positive(name: str, default: float) -> float:
    raw = os.environ.get(name, "").strip()
    if raw:
        try:
            v = float(raw)
            if v > 0:
                return v
        except ValueError:
            pass
    return default


# process-global ring every server serves from (one process == one
# server role in this framework; the multi-tenant supervisor will hang
# per-tenant rings off this when it lands). Retention is env-tunable:
# a debugging session can hold thousands of traces for a day, a tight
# edge box can shrink to a handful of minutes.
TRACES = TraceRing(
    capacity=int(_env_positive("PIO_TRACE_RING_CAPACITY", 64)),
    max_age_s=_env_positive("PIO_TRACE_RING_MAX_AGE_S", 3600.0),
)
