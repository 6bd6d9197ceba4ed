"""``pio layers`` — where a query's time goes on a server, from two
scrapes of its ``/metrics``.

Every stage of a request and every crossing of a dispatch feeds an
always-on histogram (``obs.trace.region``); the difference of two scrapes
is therefore the whole chain over the interval between them, with no
profiler attached: per REQUEST ``http.handoff`` -> ``http.read_parse`` ->
``serve.submit`` -> ``batch.queue_wait`` -> the dispatch as the request
saw it -> ``serve.wake`` -> ``serve.tail`` -> ``http.write``; per
DISPATCH ``batch.dispatch`` with the template's build, the two enqueue
stages with the uploads (``xfer.h2d[...]``) and launches (``launch[...]``)
inside them, ``dispatch.fetch`` with its wait and its copy back (told
apart on one dispatch in seven), and self time. Beside it: which thread dispatched, the batch worker's states,
and whether a profiler capture ran in the interval (it changes exactly
these stages).

The scrapes come from a live server (``--url`` and ``--seconds``) or from
the ``gen.windows.json`` a benchmark run kept with ``--save-logs``
(``metrics_open`` / ``metrics_close`` of each measured window). This
module reads series; it defines none, and imports no jax.
"""

from __future__ import annotations

import json
import os
import re
import time

from predictionio_tpu.obs.metrics import parse_prometheus

__all__ = ["table", "render", "from_windows", "from_url"]

_LABELS = re.compile(r'(\w+)="([^"]*)"')

# a request's chain, in request order: (span, histogram); all but the
# dispatch, which a request sees through pio_serving_seconds
_BEFORE = (
    ("http.handoff", "pio_http_handoff_seconds"),
    ("http.read_parse", "pio_http_read_parse_seconds"),
    ("serve.submit", "pio_serving_submit_seconds"),
    ("batch.queue_wait", "pio_batch_queue_wait_seconds"),
)
_AFTER = (
    ("serve.wake", "pio_serving_wake_seconds"),
    ("serve.tail", "pio_serving_tail_seconds"),
    ("http.write", "pio_http_write_seconds"),
)
_WORKER_STATES = ("idle", "collect", "dispatch", "resolve")
_PHASES = ("start", "capture", "stop")
# which enqueue stage a tracked program is launched from, by its name
_SCANS = ("retrieval.coarse_topk", "retrieval.sharded_")
_RESCORES = ("retrieval.rescore_",)


def _delta(before: dict, after: dict) -> dict:
    return {k: v - before.get(k, 0.0) for k, v in after.items()}


def _family(d: dict, name: str) -> dict[tuple, float]:
    """{labels as a sorted tuple of pairs: value} of one series name."""
    out = {}
    for key, v in d.items():
        if key == name:
            out[()] = v
        elif key.startswith(name + "{"):
            out[tuple(sorted(_LABELS.findall(key[len(name):])))] = v
    return out


def _hist(d: dict, name: str, **labels) -> tuple[float, float]:
    """(observations, seconds) of a histogram over the interval: the
    unlabelled series where it exists (a per-variant twin beside it
    would count twice), else every series that carries ``labels``."""
    want = set(labels.items())
    n = s = 0.0
    counts, sums = _family(d, name + "_count"), _family(d, name + "_sum")
    if not labels and () in counts:
        return counts[()], sums.get((), 0.0)
    for lab, v in counts.items():
        if want <= set(lab):
            n += v
            s += sums.get(lab, 0.0)
    return n, s


def _row(span: str, n: float, secs: float, per: float, depth: int = 0) -> dict:
    """One line of a table: ``n`` observations of ``secs`` seconds in
    all, and what that is for each of the ``per`` units (requests or
    dispatches) of the interval."""
    return {
        "span": span, "depth": depth, "n": int(n),
        "mean_ms": 1e3 * secs / n if n else None,
        "ms_each": 1e3 * secs / per if per else None,
    }


def table(before: dict, after: dict, interval_s: float | None = None) -> dict:
    """The chain over the interval between two parsed scrapes."""
    d = _delta(before, after)
    requests, serving_s = _hist(d, "pio_serving_seconds")
    dispatches, dispatch_s = _hist(d, "pio_batch_dispatch_seconds")

    # -- a request ----------------------------------------------------------
    request = [_row(s, *_hist(d, h), requests) for s, h in _BEFORE]
    tail = [_row(s, *_hist(d, h), requests) for s, h in _AFTER]
    # pio_serving_seconds runs from the enqueue to the end of the tail
    around = sum(r["ms_each"] or 0.0 for r in request + tail if r["span"] in
                 ("batch.queue_wait", "serve.wake", "serve.tail"))
    seen = 1e3 * serving_s / requests - around if requests else None
    request.append({
        "span": "batch.dispatch (as a request saw it)", "depth": 0,
        "n": int(requests), "mean_ms": seen, "ms_each": seen,
    })
    request += tail
    request_sum = sum(r["ms_each"] or 0.0 for r in request) if requests else None
    # the server's own clock around a whole request, request line to the
    # final write (http.write times that) — of EVERY route, so a scrape or
    # a /profile in the interval is in it
    whole = _row("http request, every route (read to the write)",
                 *_hist(d, "pio_http_request_seconds"), 0)

    # -- a dispatch ---------------------------------------------------------
    def stage(span, hist, depth=1, **labels):
        return _row(span, *_hist(d, hist, **labels), dispatches, depth)

    launches = {
        dict(lab)["fn"]: (n, _family(d, "pio_jit_call_seconds_sum").get(lab, 0.0))
        for lab, n in _family(d, "pio_jit_call_seconds_count").items() if n
    }

    def launched(prefixes, depth):
        return [
            _row(f"launch[{fn}]", n, s, dispatches, depth)
            for fn, (n, s) in sorted(launches.items())
            if fn.startswith(prefixes)
        ]

    def xfer(direction, op, depth):
        return stage(f"xfer.{direction}[{op}]", "pio_device_transfer_seconds",
                     depth, direction=direction, op=op)

    shortlist = stage("dispatch.shortlist", "pio_retrieval_shortlist_seconds")
    rescore = stage("dispatch.rescore", "pio_retrieval_rescore_seconds")
    fetch = stage("dispatch.fetch", "pio_retrieval_fetch_seconds")
    # the read is told apart on one dispatch in obs.trace.CPU_EVERY: a
    # dispatch has one read, so a sampled call's mean is a dispatch's
    wait = stage("fetch.wait", "pio_retrieval_fetch_wait_seconds", 2)
    read = xfer("d2h", "serve.answers", 2)
    for sampled in (wait, read):
        sampled["ms_each"] = sampled["mean_ms"]
    uploads = [xfer("h2d", "serve.dispatch", 2), xfer("h2d", "serve.rules", 2)]
    scans, rescores = launched(_SCANS, 2), launched(_RESCORES, 2)
    others = [
        _row(f"launch[{fn}]", n, s, dispatches, 1)
        for fn, (n, s) in sorted(launches.items())
        if not fn.startswith(_SCANS + _RESCORES)
    ]

    def ms(rows):
        return sum(r["ms_each"] or 0.0 for r in rows)

    def derived(span, depth, ms_each, there):
        """A line computed from its neighbours; kept where they are."""
        return {"span": span, "depth": depth, "n": 0, "mean_ms": None,
                "ms_each": ms_each, "derived": there}

    enqueue_ms = ms([shortlist, rescore])
    crossing_ms = ms(uploads[:1] + scans + rescores)
    self_s = _hist(d, "pio_batch_dispatch_self_seconds")
    dispatch = [
        _row("batch.dispatch", dispatches, dispatch_s, dispatches),
        stage("rules.build", "pio_ecomm_rules_seconds"),
        stage("rules.seen_read", "pio_ecomm_seen_read_seconds", 2),
        stage("similar.build", "pio_similar_build_seconds"),
        shortlist, *scans,
        rescore, *rescores,
        # the uploads' series is one for both enqueue stages
        derived("in the two enqueues:", 1, None, bool(uploads[0]["n"])),
        *uploads,
        derived("self (convert, pad, pack)", 2, enqueue_ms - crossing_ms,
                bool(launches)),
        fetch, wait, read,
        derived("self", 2, ms([fetch]) - ms([wait, read]), bool(wait["n"])),
        *others,
        _row("self", *self_s, dispatches, 1),
    ]
    # a series the server has not, or that did not move, gives no row
    dispatch = [r for r in dispatch if r["n"] or r.get("derived")]

    upload_ms, launch_ms = ms(uploads), ms(scans + rescores + others)
    identities = None
    if launches and wait["n"]:
        identities = {
            "upload_ms + launch_ms <= shortlist_ms + rescore_ms": {
                "upload_ms": upload_ms, "launch_ms": launch_ms,
                "stages_ms": enqueue_ms,
                "holds": upload_ms + launch_ms <= enqueue_ms * 1.001,
            },
            "fetch_wait_ms + fetch_read_ms <= fetch_ms": {
                "fetch_wait_ms": ms([wait]), "fetch_read_ms": ms([read]),
                "fetch_ms": ms([fetch]),
                # comparable only where every read was told apart: a split
                # read no longer overlaps the device's last work, so the
                # sampled dispatches' fetch is dearer than the mean of all
                "holds": ms([wait, read]) <= ms([fetch]) * 1.001
                if wait["n"] >= fetch["n"] else None,
                "told_apart": [wait["n"], fetch["n"]],
            },
        }

    # -- beside the chain ---------------------------------------------------
    path = {
        dict(lab).get("path", ""): v for lab, v in
        _family(d, "pio_batch_dispatch_path_total").items()
    }
    by_path = sum(path.values())
    states = {
        dict(lab).get("state", ""): v for lab, v in
        _family(d, "pio_batch_worker_seconds_total").items()
    }
    worker = None
    if sum(states.values()) > 0:
        worker = {s: states.get(s, 0.0) for s in _WORKER_STATES}
        worker["busy_share"] = 1.0 - worker["idle"] / sum(states.values())
    phases = {
        dict(lab).get("phase", ""): v for lab, v in
        _family(d, "pio_profile_seconds_total").items()
    }
    capture = None
    if phases:  # the server can tell: did a capture run in the interval
        capture = {f"{p}_s": phases.get(p, 0.0) for p in _PHASES}
        capture["ran"] = sum(phases.values()) > 0
    size_n, size_sum = _hist(d, "pio_batch_size")
    return {
        "interval_s": interval_s,
        "requests": int(requests), "dispatches": int(dispatches),
        "batch_mean": size_sum / size_n if size_n else None,
        "request": request, "request_sum_ms": request_sum,
        "http_request": whole if whole["n"] else None,
        "dispatch": dispatch, "identities": identities,
        "dispatch_path": {
            **{p: int(v) for p, v in sorted(path.items())},
            "inline_share": path.get("inline", 0.0) / by_path if by_path else None,
        },
        "worker": worker, "capture": capture,
    }


def _ms(v) -> str:
    return "       -" if v is None else f"{v:8.3f}"


def _lines(rows, each: str) -> list[str]:
    out = [f"  {'span':<44} {'n':>7} {'mean ms':>8} {each:>13}"]
    for r in rows:
        name = "  " * r["depth"] + r["span"]
        n = f"{r['n']:>7}" if r["n"] else " " * 7
        out.append(f"  {name:<44} {n} {_ms(r['mean_ms'])} {_ms(r['ms_each']):>13}")
    return out


def render(doc: dict, title: str = "") -> str:
    """The table as text."""
    out = []
    secs = doc.get("interval_s")
    head = f"{doc['requests']} requests, {doc['dispatches']} dispatches"
    if secs:
        head += (f" in {secs:.2f} s ({doc['requests'] / secs:.1f} requests/s, "
                 f"{doc['dispatches'] / secs:.1f} dispatches/s)")
    if doc.get("batch_mean") is not None:
        head += f", {doc['batch_mean']:.2f} queries a dispatch"
    out.append((title + ": " if title else "") + head)
    cap = doc.get("capture")
    if cap is None:
        out.append("profiler capture: this server does not say "
                   "(no pio_profile_seconds_total)")
    elif cap["ran"]:
        out.append(
            "profiler capture: RAN in this interval — start "
            f"{cap['start_s']:.2f} s, capture {cap['capture_s']:.2f} s, stop "
            f"{cap['stop_s']:.2f} s: the host stages below are a traced "
            "server's")
    else:
        out.append("profiler capture: none in this interval")
    out.append("")
    out.append("a request (ms a request):")
    out += _lines(doc["request"], "ms a request")
    if doc.get("request_sum_ms") is not None:
        out.append(f"  {'sum of the chain':<44} {'':>7} {'':>8} "
                   f"{_ms(doc['request_sum_ms']):>13}")
    whole = doc.get("http_request")
    if whole:
        out.append(f"  {whole['span']:<44} {whole['n']:>7} {_ms(whole['mean_ms'])}")
    gen = doc.get("generator")
    if gen:
        out.append(
            f"  {'generator, sent -> done (mean of ' + str(gen['requests']) + ')':<44} "
            f"{'':>7} {'':>8} {_ms(gen['mean_ms']):>13}"
            f"   chain / generator = {doc['request_sum_ms'] / gen['mean_ms']:.3f}")
    out.append("")
    out.append("a dispatch (ms a dispatch):")
    out += _lines(doc["dispatch"], "ms a dispatch")
    for name, terms in (doc.get("identities") or {}).items():
        *parts, total = [v for k, v in terms.items()
                         if k not in ("holds", "told_apart")]
        line = (f"  {name}: " + " + ".join(f"{p:.3f}" for p in parts)
                + f" = {sum(parts):.3f} against {total:.3f}: ")
        if terms["holds"] is None:
            n, of = terms["told_apart"]
            line += (f"the read was told apart on {n} of {of} dispatches, and a "
                     "split read is the dearer one (no overlap with the device)")
        else:
            line += "holds" if terms["holds"] else "DOES NOT HOLD"
        out.append(line)
    out.append("")
    path = doc["dispatch_path"]
    if path.get("inline_share") is not None:
        out.append(
            "dispatched by: " + ", ".join(
                f"{p} {n}" for p, n in path.items() if p != "inline_share")
            + f" (inline share {100 * path['inline_share']:.1f} %)")
    w = doc.get("worker")
    if w:
        out.append(
            "batch worker: " + ", ".join(
                f"{s} {w[s]:.3f} s" for s in _WORKER_STATES)
            + f" (busy {100 * w['busy_share']:.2f} %)")
    return "\n".join(out)


def _generator(windows_path: str, index: int, w: dict) -> dict | None:
    """The generator's own reading of window ``index``: mean sent ->
    done of the requests answered between the two scrapes (``gen.npz``
    beside the windows)."""
    npz = os.path.join(os.path.dirname(os.path.abspath(windows_path)), "gen.npz")
    if not os.path.exists(npz):
        return None
    import numpy as np

    res = np.load(npz)
    lo = w.get("metrics_open_at", w["t_open"])
    hi = w.get("metrics_close_at", w["t_close"])
    sel = (res["phase"] == index) & (res["status"] == 200) \
        & (res["done"] >= lo) & (res["done"] <= hi)
    if not sel.any():
        return None
    lat = (res["done"][sel] - res["sent"][sel]) * 1e3
    return {"requests": int(sel.sum()), "mean_ms": float(lat.mean()),
            "p50_ms": float(np.median(lat))}


def from_windows(path: str) -> list[tuple[str, dict]]:
    """(label, table) of every measured window of a ``gen.windows.json``."""
    with open(path) as fh:
        windows = json.load(fh)
    out = []
    for i, w in enumerate(windows):
        if not w.get("measure"):
            continue
        doc = table(
            parse_prometheus(w["metrics_open"]),
            parse_prometheus(w["metrics_close"]),
            w.get("metrics_close_at", w["t_close"])
            - w.get("metrics_open_at", w["t_open"]),
        )
        gen = _generator(path, i, w)
        if gen:
            doc["generator"] = gen
        out.append((w.get("label", f"window-{i}"), doc))
    return out


def from_url(base: str, seconds: float) -> dict:
    """Two scrapes of a live server, ``seconds`` apart."""
    import urllib.request

    def scrape():
        with urllib.request.urlopen(base.rstrip("/") + "/metrics", timeout=10) as r:
            return time.perf_counter(), parse_prometheus(r.read())

    t0, before = scrape()
    time.sleep(max(0.0, seconds))
    t1, after = scrape()
    return table(before, after, t1 - t0)
