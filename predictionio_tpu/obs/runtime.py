"""Host time in which the device could have been working, by cause.

Every span in :mod:`predictionio_tpu.obs.trace` is wall time: it cannot
tell a thread that computes from one that waits for the interpreter, a
collector pause from a slow launch, or a process that was not scheduled
from one that was busy. Four instruments, all jax-free and all feeding
the process registry, name that time:

- :class:`WorkerClock` cuts the batch worker's time into ``idle`` /
  ``collect`` / ``dispatch`` / ``resolve``
  (``pio_batch_worker_seconds_total{state}``; the states sum to the
  worker's wall time).
- ``trace.region(cpu_hist=...)`` observes a stage's ``thread_time``
  beside its wall time, on one call in ``trace.CPU_EVERY`` (the
  histograms are made by their stages; wall minus CPU of a stage that
  only enqueues is time its thread wanted to run and did not).
- one ``gc.callbacks`` hook: ``pio_gc_pause_seconds{generation}``, a
  ``gc.pause[g]`` child span under the enclosing region for pauses of
  1 ms and up, and for generation 2 a ``TraceAnnotation`` while a
  profile runs.
- one daemon thread, ``obs-beat``, due every 20 ms, records how late it
  woke (``pio_process_stall_seconds``); a beat late by 50 ms or more is
  a STOP: its process CPU goes to ``pio_process_stall_cpu_seconds_total``
  and one record of it to the log and to a ring of 16.

:func:`arm` installs the hook and starts the thread (``server/http.py``
``add_obs_routes``); under ``PIO_OBS=0`` it does nothing. :func:`block`
is the ``runtime`` object of ``/stats.json``.
"""

from __future__ import annotations

import gc
import logging
import threading
import time
import weakref
from collections import deque

from predictionio_tpu.obs import metrics as _metrics
from predictionio_tpu.obs import trace as _trace

__all__ = [
    "STATES",
    "WorkerClock",
    "arm",
    "block",
    "reset_for_tests",
    "BEAT_S",
    "STALL_S",
]

logger = logging.getLogger(__name__)

# -- 1. the batch worker's time by state -------------------------------------

STATES = ("idle", "collect", "dispatch", "resolve")

# the stop record names the state of the worker made last (a process
# serves through one); weak, so a stopped server's clock is not pinned
_worker: "weakref.ref[WorkerClock] | None" = None


class WorkerClock:
    """One thread's wall time cut into named states by ``perf_counter``:
    ``to(state)`` closes the running state and opens the next, so the
    states' counters sum to the time since construction (less the state
    still running). Only the clock's own thread calls ``to``; ``state``
    is a plain attribute any thread may read. While a profile runs
    ``resolve`` is also a ``batch.resolve`` annotation, so the worker's
    line has a name from one dispatch's end to the next collect."""

    __slots__ = ("state", "_t", "_secs", "_ann", "__weakref__")

    def __init__(self):
        global _worker
        self._secs = {
            s: _metrics.counter(
                "pio_batch_worker_seconds_total",
                "Batch worker wall time by state (idle: nothing queued; "
                "collect: first item -> batch formed; dispatch: the "
                "batch.dispatch region; resolve: futures, fallback)",
                state=s,
            )
            for s in STATES
        }
        self.state = "idle"
        self._t = time.perf_counter()
        self._ann = None
        _worker = weakref.ref(self)

    def to(self, state: str) -> None:
        if not _metrics.enabled():
            return
        now = time.perf_counter()
        prev = self.state
        self._secs[prev].inc(now - self._t)
        self._t = now
        if prev == state:
            return
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        if state == "resolve" and _trace._annotating:
            self._ann = _trace._annotation("batch.resolve")
            self._ann.__enter__()
        self.state = state


def _worker_state() -> str | None:
    w = _worker() if _worker is not None else None
    return w.state if w is not None else None


# -- 3. collector pauses ------------------------------------------------------

# spans and annotations only for pauses an operator would look for
_GC_SPAN_S = 1e-3

_arm_lock = threading.Lock()
_gc_hists: tuple = ()
_gc_t0 = 0.0
_gc_ann = None
# running totals the stop record differences (plain reads from the beat)
_gc_seconds = 0.0
_gc_full = 0


def _on_gc(phase: str, info: dict) -> None:
    """The one ``gc.callbacks`` hook. The interpreter runs no second
    collection between a ``start`` and its ``stop`` (``collecting`` is
    set around both), so module globals carry the start time."""
    global _gc_t0, _gc_ann, _gc_seconds, _gc_full
    if phase == "start":
        if _trace._annotating and info["generation"] == 2 and _metrics.enabled():
            _gc_ann = _trace._annotation("gc.pause[2]")
            _gc_ann.__enter__()
        _gc_t0 = time.perf_counter()
        return
    end = time.perf_counter()
    dt = end - _gc_t0
    gen = info["generation"]
    _gc_hists[gen].observe(dt)
    _gc_seconds += dt
    if gen == 2:
        _gc_full += 1
        ann, _gc_ann = _gc_ann, None
        if ann is not None:
            ann.__exit__(None, None, None)
    if dt >= _GC_SPAN_S and _metrics.enabled():
        # a child of the region this thread is in: it enters the
        # region's children, so the region's self time stays self time
        st = _trace._state()  # [trace, region, children's seconds]
        st[2] += dt
        if st[0] is not None:
            st[0].add_span(f"gc.pause[{gen}]", _gc_t0, end, st[1])


# -- 4. process stops ---------------------------------------------------------

BEAT_S = 0.020
STALL_S = 0.050

_stalls: deque = deque(maxlen=16)
_beat: "_Beat | None" = None


class _Beat(threading.Thread):
    """Due every ``BEAT_S``; how late it wakes is how long nothing of
    this process could run: a thread that held the interpreter (process
    CPU ~ lateness) or a process that was not scheduled (CPU ~ 0)."""

    def __init__(self):
        super().__init__(name="obs-beat", daemon=True)
        self._stop_evt = threading.Event()
        self._m_late = _metrics.histogram(
            "pio_process_stall_seconds",
            "How late the 20 ms obs-beat thread woke",
        )
        self._m_cpu = _metrics.counter(
            "pio_process_stall_cpu_seconds_total",
            "Process CPU between the beats around a stop (a beat late "
            "by 50 ms or more)",
        )

    def stop(self) -> None:
        self._stop_evt.set()

    def run(self) -> None:
        wait = self._stop_evt.wait
        cpu0, gc_s0, gc_n0 = time.process_time(), _gc_seconds, _gc_full
        due = time.perf_counter() + BEAT_S
        while not wait(max(0.0, due - time.perf_counter())):
            now = time.perf_counter()
            cpu, gc_s, gc_n = time.process_time(), _gc_seconds, _gc_full
            late = now - due
            self._m_late.observe(late)
            if late >= STALL_S and _metrics.enabled():
                self._record(late, cpu - cpu0, gc_s - gc_s0, gc_n - gc_n0)
            cpu0, gc_s0, gc_n0 = cpu, gc_s, gc_n
            due = now + BEAT_S  # anchored anew: one stop is one late beat

    def _record(self, late: float, cpu: float, gc_s: float, gc_n: int) -> None:
        self._m_cpu.inc(cpu)
        rec = {
            "at": round(time.time(), 3),
            "late_ms": round(late * 1e3, 3),
            "cpu_ms": round(cpu * 1e3, 3),
            "gc_ms": round(gc_s * 1e3, 3),
            "gc_full": gc_n,
            "worker": _worker_state(),
        }
        _stalls.append(rec)
        logger.warning(
            "process stall: obs-beat woke %.1f ms late; process CPU since "
            "the beat before %.1f ms, collector %.1f ms (%d full), batch "
            "worker %s",
            rec["late_ms"], rec["cpu_ms"], rec["gc_ms"], gc_n, rec["worker"],
        )


# -- arming and the /stats.json block ----------------------------------------


def arm() -> None:
    """Install the collector hook and start ``obs-beat``, once; nothing
    under ``PIO_OBS=0``."""
    global _gc_hists, _beat
    if _beat is not None or not _metrics.enabled():
        return
    with _arm_lock:
        if _beat is not None:
            return
        _gc_hists = tuple(
            _metrics.histogram(
                "pio_gc_pause_seconds",
                "Collector pause per collection (gc.callbacks start -> stop)",
                generation=str(g),
            )
            for g in range(3)
        )
        gc.callbacks.append(_on_gc)
        _beat = _Beat()
        _beat.start()


def block() -> dict:
    """``runtime`` in ``/stats.json``."""
    return {
        "armed": _beat is not None,
        "gc": {
            "stats": gc.get_stats(),
            "threshold": list(gc.get_threshold()),
            "frozen": gc.get_freeze_count(),
            "pause_s": round(_gc_seconds, 6),
            "full": _gc_full,
        },
        "stalls": list(_stalls),
        "worker": _worker_state(),
    }


def reset_for_tests() -> None:
    """Take the hook out, stop the beat, clear the ring and the totals."""
    global _beat, _gc_ann, _gc_seconds, _gc_full, _worker
    with _arm_lock:
        if _on_gc in gc.callbacks:
            gc.callbacks.remove(_on_gc)
        b, _beat = _beat, None
    if b is not None:
        b.stop()
        b.join(timeout=2)
    _stalls.clear()
    _gc_ann = None
    _gc_seconds, _gc_full = 0.0, 0
    _worker = None
