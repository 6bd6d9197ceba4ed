"""Device-level observability: XLA compile tracking, device memory and
transfer telemetry, and on-demand profiler capture.

PR 7's obs layer measures host wall clock; this module opens the device
black box — the telemetry ALX (arxiv 2112.02194) uses to attribute TPU
time between gather, solve, and collectives, and that arxiv 2501.10546
treats as first-class production signals:

- **Compile tracking and the launch** — :func:`track_jit` wraps a
  jitted entry point and detects recompiles by the
  executable-cache-size delta across each call (``fn._cache_size()``),
  exporting ``pio_jit_compiles_total{fn}`` /
  ``pio_jit_cache_hits_total{fn}``. A process-global ``jax.monitoring``
  listener feeds backend compile durations into
  ``pio_jit_compile_seconds``. Shape-churn recompiles (the
  micro-batcher's known failure mode) become a counter on ``/metrics``
  instead of mystery latency. The call itself is the ``launch[<fn>]``
  region: what the host pays to hand a program to the runtime
  (``pio_jit_call_seconds{fn}``; a call that compiled is left out).
- **Memory & transfer telemetry** — per-device gauges evaluated at
  scrape time from ``device.memory_stats()`` (None-tolerant: CPU
  backends report no stats and export zeros with a ``supported`` gauge
  saying so), plus ONE family for every host<->device copy,
  ``pio_device_transfer_{seconds,bytes_total}{direction,op}`` and
  ``pio_device_transfers_total{direction,op}``, fed by :class:`transfer`
  (the ``xfer.<direction>[<op>]`` region around a copy) at the copy
  sites: a dispatch's uploads and its read, the training bucket upload,
  the sharded pack upload, the checkpoint snapshot gather, a fold-in's
  patch.
- **On-demand profiling** — :func:`profile_capture` runs a bounded
  ``jax.profiler`` trace capture behind a process lock (one capture at
  a time), backing ``pio profile`` and the ``POST /profile`` endpoint,
  and books what each of its phases cost the process
  (``pio_profile_seconds_total{phase}``).

Everything is lazy about jax: importing this module never imports jax,
and scrape-time paths only look at devices when ``jax`` is already in
``sys.modules`` — ``/metrics`` on a jax-free server stays jax-free.
All instruments honor the global ``PIO_OBS=0`` kill switch.
"""

from __future__ import annotations

import logging
import os
import sys
import threading
import time

from predictionio_tpu.obs import metrics as _metrics
from predictionio_tpu.obs import trace as _trace

logger = logging.getLogger(__name__)

__all__ = [
    "track_jit",
    "transfer",
    "count_transfer",
    "transfer_count",
    "count_restage",
    "transfer_totals",
    "compile_snapshot",
    "ensure_device_gauges",
    "device_block",
    "where",
    "profile_capture",
    "profile_active",
]


# -- compile tracking ---------------------------------------------------------

_lock = threading.Lock()
_listener_installed = False

_m_compile_seconds = _metrics.histogram(
    "pio_jit_compile_seconds",
    "XLA backend compile time per compiled program",
)


class _JitStats:
    """Per-tracked-function call/compile/hit counters (host-side; the
    source of truth for the compile counters and /stats.json block)."""

    __slots__ = ("calls", "compiles", "cache_hits")

    def __init__(self) -> None:
        self.calls = 0
        self.compiles = 0
        self.cache_hits = 0


_jit_stats: dict[str, _JitStats] = {}


def _install_compile_listener() -> None:
    """Register the global jax.monitoring duration listener once per
    process. Called from the first tracked call (jax is importable by
    then — the wrapped function IS a jit). Failures are swallowed: the
    cache-size tracker still counts compiles without durations."""
    global _listener_installed
    if _listener_installed:
        return
    with _lock:
        if _listener_installed:
            return
        _listener_installed = True
        try:
            import jax

            def _on_duration(event: str, duration: float, **_kw) -> None:
                if event == "/jax/core/compile/backend_compile_duration":
                    _m_compile_seconds.observe(duration)

            jax.monitoring.register_event_duration_secs_listener(_on_duration)
        except Exception:  # pragma: no cover - telemetry must never break jit
            logger.debug("jax.monitoring listener unavailable", exc_info=True)


def track_jit(name: str):
    """Wrap a jitted callable so every call updates the compile tracker.

    The compile test is the executable-cache-size delta across the call
    (``fn._cache_size()``): a new (shape, static-args) specialization
    grew the cache -> one compile; an unchanged cache -> a hit. This is
    exact per USER-LEVEL program — the monitoring listener sees several
    backend_compile events per jit (sub-compiles), so durations come
    from the listener while counts come from here.

    The call is the ``launch[<name>]`` region (``obs.trace.region``: a
    span under the stage that launched, an annotation while a capture
    runs): what the host pays to hand the program to the runtime — the
    arguments checked, the executable looked up, the launch enqueued;
    nothing is waited for. ``pio_jit_call_seconds{fn}`` observes it,
    but for a call that compiled: that time is a compile's, and
    ``pio_jit_compile_seconds`` has it.

    Apply ABOVE the ``jax.jit`` decoration (outermost). Overhead when
    enabled is one region, two getattr+int reads and a counter inc per
    call; disabled cost is one flag check.
    """
    stats = _jit_stats.setdefault(name, _JitStats())
    span = f"launch[{name}]"
    m_call = _metrics.histogram(
        "pio_jit_call_seconds",
        "host time inside a tracked jit call that hit the executable "
        "cache: the launch, nothing waited for",
        fn=name,
    )
    m_compiles = _metrics.counter(
        "pio_jit_compiles_total",
        "XLA compiles triggered by tracked jit entry points",
        fn=name,
    )
    m_hits = _metrics.counter(
        "pio_jit_cache_hits_total",
        "Tracked jit calls served from the executable cache",
        fn=name,
    )

    def deco(fn):
        cache_size = getattr(fn, "_cache_size", None)

        def wrapper(*args, **kwargs):
            if not _metrics.enabled() or cache_size is None:
                return fn(*args, **kwargs)
            _install_compile_listener()
            try:
                before = cache_size()
            except Exception:
                before = -1
            with _trace.region(span) as launch:
                out = fn(*args, **kwargs)
            stats.calls += 1
            try:
                after = cache_size()
            except Exception:
                after = before
            if before >= 0 and after > before:
                stats.compiles += after - before
                m_compiles.inc(after - before)
            else:
                stats.cache_hits += 1
                m_hits.inc()
                m_call.observe(launch.seconds)
            return out

        wrapper.__name__ = getattr(fn, "__name__", name)
        wrapper.__doc__ = getattr(fn, "__doc__", None)
        wrapper.__wrapped__ = fn
        # keep the jit surface callers rely on (tests/tooling introspect
        # the executable cache and AOT-compile through the wrapper)
        for attr in ("_cache_size", "lower", "trace", "clear_cache"):
            val = getattr(fn, attr, None)
            if val is not None:
                setattr(wrapper, attr, val)
        return wrapper

    return deco


def compile_snapshot() -> dict[str, dict[str, int]]:
    """Per-tracked-function {calls, compiles, cache_hits} — the delta
    source for per-sweep compile accounting (core/fast_eval.py) and the
    /stats.json device block."""
    return {
        name: {
            "calls": s.calls,
            "compiles": s.compiles,
            "cache_hits": s.cache_hits,
        }
        for name, s in sorted(_jit_stats.items())
    }


# -- host <-> device copies: one family ---------------------------------------

_TRANSFER_BYTES = "pio_device_transfer_bytes_total"
_sites: dict[tuple[str, str], tuple] = {}


def _site(direction: str, op: str) -> tuple:
    """(span name, seconds histogram, bytes counter, copies counter) of
    one site, made once: a dispatch passes here at every crossing."""
    key = (direction, op)
    found = _sites.get(key)
    if found is None:
        found = _sites[key] = (
            f"xfer.{direction}[{op}]",
            _metrics.histogram(
                "pio_device_transfer_seconds",
                "Host time of one host<->device copy, to its return, by site",
                direction=direction, op=op,
            ),
            _metrics.counter(
                _TRANSFER_BYTES,
                "Bytes moved between host and device, by site",
                direction=direction, op=op,
            ),
            _metrics.counter(
                "pio_device_transfers_total",
                "Host<->device copies, by site",
                direction=direction, op=op,
            ),
        )
    return found


def count_transfer(direction: str, op: str, nbytes: int) -> None:
    """Book one host<->device copy that no :class:`transfer` region
    timed — a site that only learns afterwards what went up (a model
    that stages itself at its first query): the bytes and the copy,
    without a duration. ``direction`` is ``h2d``/``d2h``; ``op`` names
    the site, from a closed set — on the serving path ``serve.dispatch``
    (a dispatch's host arrays), ``serve.rules`` (the per-query rules put
    up one by one), ``serve.zero_blocks`` (a sharded catalog's resident
    blocks, once a shape), ``serve.answers`` (the read),
    ``serve.model_patch``; at load ``serve.model_put``,
    ``train.buckets``, ``train.packed_side``, ``checkpoint``."""
    if not _metrics.enabled() or nbytes <= 0:
        return
    _, _, m_bytes, m_copies = _site(direction, op)
    m_bytes.inc(int(nbytes))
    m_copies.inc()


class transfer(_trace.region):
    """The region around ONE host<->device copy:
    ``with transfer("h2d", "serve.dispatch", a.nbytes): jnp.asarray(a)``
    is the span ``xfer.h2d[serve.dispatch]`` under the stage that
    copies (an annotation while a capture runs) and, when the copy
    returns, one observation of the family: its seconds, its bytes, the
    copy (``op``: :func:`count_transfer`'s closed set). An upload is
    timed to its return — the runtime may still be moving the bytes — a
    read to the bytes on the host; a read may set ``nbytes`` inside the
    block, from what arrived."""

    __slots__ = ("nbytes", "_site")

    def __init__(self, direction: str, op: str, nbytes: int = 0):
        site = self._site = _site(direction, op)
        super().__init__(site[0], hist=site[1])
        self.nbytes = nbytes

    def __exit__(self, *exc):
        super().__exit__(*exc)
        if self._on:
            self._site[2].inc(int(self.nbytes))
            self._site[3].inc()
        return False


def transfer_count(direction: str, *ops: str) -> int:
    """Copies booked so far at the named sites of one direction."""
    return sum(_site(direction, op)[3].value() for op in ops)


def count_restage(part: str) -> None:
    """A resident part of a served model staged or built AGAIN because of
    a fold-in patch: "users" when the user table outgrew its capacity and
    went up at twice the size (realtime/foldin.py); an item-side part
    ("table", "coarse", "sharded") where a patched model does not hold
    the served model's (``engine_server`` ``apply_patch``) — never, in a
    sound run."""
    _metrics.counter(
        "pio_foldin_restage_total",
        "Resident parts staged or built again because of a patch",
        part=part,
    ).inc()


def transfer_totals() -> dict[str, int]:
    """Bytes by ``direction.op``, read from the registry's family."""
    totals = {}
    for m in _metrics.REGISTRY.family(_TRANSFER_BYTES):
        lab = dict(m.labels)
        if m.value():
            totals[f"{lab['direction']}.{lab['op']}"] = m.value()
    return dict(sorted(totals.items()))


# -- device memory gauges -----------------------------------------------------

_gauges_registered = False
# memory_stats() keys worth exporting, normalized to a short gauge kind
_MEM_KINDS = (
    ("bytes_in_use", "in_use"),
    ("bytes_limit", "limit"),
    ("peak_bytes_in_use", "peak"),
)


def _mem_stat(device, key: str) -> float:
    try:
        stats = device.memory_stats()
    except Exception:
        stats = None
    if not stats:
        return 0.0
    return float(stats.get(key, 0))


def ensure_device_gauges() -> bool:
    """Register per-device memory gauges (scrape-time callbacks), once.

    Deliberately a no-op until ``jax`` is already imported — the
    /metrics route calls this on every scrape, and a jax-free server
    (dashboard, event server before any training) must never pay a jax
    import for a scrape. Returns True when gauges are live."""
    global _gauges_registered
    if _gauges_registered:
        return True
    if "jax" not in sys.modules:
        return False
    with _lock:
        if _gauges_registered:
            return True
        try:
            import jax

            devices = jax.local_devices()
        except Exception:  # pragma: no cover - broken backend
            logger.debug("jax.local_devices unavailable", exc_info=True)
            return False
        platforms: dict[str, int] = {}
        for d in devices:
            label = f"{d.platform}:{d.id}"
            platforms[d.platform] = platforms.get(d.platform, 0) + 1
            supported = False
            try:
                supported = bool(d.memory_stats())
            except Exception:
                supported = False
            _metrics.gauge(
                "pio_device_memory_stats_supported",
                "1 when the backend reports allocator memory stats "
                "(CPU backends report none and export zeros)",
                device=label,
            ).set_function(lambda s=supported: 1.0 if s else 0.0)
            for key, kind in _MEM_KINDS:
                _metrics.gauge(
                    "pio_device_memory_bytes",
                    "Device allocator memory, read at scrape time "
                    "(0 when the backend reports no stats)",
                    device=label, kind=kind,
                ).set_function(lambda d=d, k=key: _mem_stat(d, k))
        for platform, n in platforms.items():
            _metrics.gauge(
                "pio_device_count", "Local devices visible to this process",
                platform=platform,
            ).set(float(n))
        _gauges_registered = True
        return True


def where() -> dict:
    """Which device this process computes on, as jax reports it — the
    fields a trainer publishes and ``pio train`` prints so that a run
    which quietly landed on the CPU says so. Initializes the backend."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
    }


def device_block() -> dict:
    """The additive ``device`` block for ``/stats.json``: per-device
    memory (None-tolerant), transfer byte totals, and the compile
    tracker summary. Safe on a jax-free process (empty device list)."""
    devices = []
    if "jax" in sys.modules:
        ensure_device_gauges()
        try:
            import jax

            for d in jax.local_devices():
                try:
                    stats = d.memory_stats()
                except Exception:
                    stats = None
                devices.append(
                    {
                        "device": f"{d.platform}:{d.id}",
                        "kind": getattr(d, "device_kind", ""),
                        "memory": (
                            {
                                kind: int(stats.get(key, 0))
                                for key, kind in _MEM_KINDS
                            }
                            if stats
                            else None
                        ),
                    }
                )
        except Exception:  # pragma: no cover - stats must never 500
            logger.debug("device stats read failed", exc_info=True)
    return {
        "devices": devices,
        "transfer_bytes": transfer_totals(),
        "jit": compile_snapshot(),
    }


# -- on-demand profiling ------------------------------------------------------

_profile_lock = threading.Lock()
_profile_running = False

MAX_PROFILE_SECONDS = 120.0


def profile_active() -> bool:
    return _profile_running


# the last stretch of a capture's window, in which nothing new is annotated
_CLOSING_S = 0.06


def _sleep_until(deadline: float) -> None:
    while time.perf_counter() < deadline:
        time.sleep(min(0.05, max(deadline - time.perf_counter(), 0)))


def _default_profile_dir() -> str:
    base = os.path.join(
        os.path.expanduser(os.environ.get("PIO_RUN_DIR", "~/.pio_tpu/run")),
        "profiles",
    )
    return os.path.join(base, time.strftime("%Y%m%d-%H%M%S"))


_PHASES = ("start", "capture", "stop")
_m_profile = {
    phase: _metrics.counter(
        "pio_profile_seconds_total",
        "seconds this process spent in profiler captures it was asked for, "
        "by phase: start = jax.profiler.start_trace; capture = the window "
        "asked for; stop = stop_trace, the trace collected and written",
        phase=phase,
    )
    for phase in _PHASES
}


def profile_capture(
    seconds: float, out_dir: str | None = None, burn: bool = False,
    python_tracer: bool = False,
) -> dict:
    """Capture a ``jax.profiler`` trace for ``seconds`` and return
    {trace_dir, seconds, start_s, stop_s, files, bytes}.

    The host plane holds the program's own regions
    (``obs.trace.region`` / ``annotate`` become ``TraceAnnotation`` s
    for the length of the capture) and the runtime's events; the
    profiler's Python tracer — one event per Python call, which slowed a
    saturated server by a sixth — is off unless ``python_tracer`` asks
    for frames. The runtime's own host tracer stays at the profiler's
    default level (2): at 1 the capture loses only the allocator's and
    the transposes' events and its ``stop_trace`` is no shorter (PERF.md
    section 6, PR 50).

    A capture records itself: three regions — ``profile.start``
    (``start_trace``), ``profile.capture`` (the window; ``seconds`` in
    the reply), ``profile.stop`` (``stop_trace``: the trace collected,
    converted and written, while the server goes on serving) — each
    added to ``pio_profile_seconds_total{phase}``, so that two scrapes
    around any interval say how much of it a capture took, and
    ``start_s`` / ``stop_s`` in the reply.

    One capture at a time (RuntimeError when one is already running —
    the /profile route maps it to 409); seconds is clamped to
    ``MAX_PROFILE_SECONDS`` so a fat-fingered request can't profile a
    production server for an hour. ``burn`` keeps a tiny jitted op
    looping during the window so an otherwise-idle process still
    produces a non-empty trace (the in-process ``pio profile`` path);
    servers capture whatever traffic is actually running."""
    global _profile_running
    seconds = min(max(float(seconds), 0.05), MAX_PROFILE_SECONDS)
    trace_dir = out_dir or _default_profile_dir()
    if not _profile_lock.acquire(blocking=False):
        raise RuntimeError("a profile capture is already running")
    phases = {name: _trace.region(f"profile.{name}") for name in _PHASES}
    try:
        _profile_running = True
        import jax
        import jax.profiler

        os.makedirs(trace_dir, exist_ok=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 1 if python_tracer else 0
        with phases["start"]:
            jax.profiler.start_trace(trace_dir, profiler_options=options)
        _trace.set_annotating(True)
        try:
            with phases["capture"]:
                deadline = time.perf_counter() + seconds
                closing = deadline - min(_CLOSING_S, seconds / 2)
                if burn:
                    import jax.numpy as jnp

                    f = jax.jit(lambda x: (x @ x.T).sum())
                    x = jnp.ones((256, 256), jnp.float32)
                    while time.perf_counter() < closing:
                        f(x).block_until_ready()
                else:
                    _sleep_until(closing)
                # the tracer keeps an annotation only if it ENDS inside
                # the capture: no new ones from here, and a wait that
                # polls ``obs.trace.annotating()`` (the batch worker's,
                # every 50 ms) leaves its own before the tracer stops
                _trace.set_annotating(False)
                _sleep_until(deadline)
        finally:
            _trace.set_annotating(False)
            with phases["stop"]:
                jax.profiler.stop_trace()
    finally:
        _profile_running = False
        _profile_lock.release()
        for name, r in phases.items():
            _m_profile[name].inc(r.seconds)
    n_files = 0
    n_bytes = 0
    for root, _dirs, files in os.walk(trace_dir):
        for f in files:
            n_files += 1
            try:
                n_bytes += os.path.getsize(os.path.join(root, f))
            except OSError:
                pass
    return {
        "trace_dir": trace_dir,
        "seconds": round(seconds, 3),
        "start_s": round(phases["start"].seconds, 3),
        "stop_s": round(phases["stop"].seconds, 3),
        "files": n_files,
        "bytes": n_bytes,
    }
