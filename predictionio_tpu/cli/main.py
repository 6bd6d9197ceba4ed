"""`pio` command-line interface.

Capability parity with the reference console
(tools/.../console/Console.scala:37-768): the full verb set — app /
accesskey / channel management, train, deploy, undeploy, eval,
eventserver, adminserver, dashboard, export, import, status, version,
build (a no-op syntax check here: Python engines need no sbt assembly).

The reference forks spark-submit JVMs per verb (Runner.scala:185-308);
here drivers run in-process — the process boundary that mattered (CLI vs
long-running servers) is kept: ``deploy``/``eventserver`` stay in the
foreground unless backgrounded by the caller.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

from predictionio_tpu import __version__


def _enter_engine_dir(args) -> None:
    """``--engine-dir DIR`` (default: the cwd): run as if launched from
    an engine template directory — its ``engine.json`` becomes the
    default variant, and the directory holding the variant file joins
    ``sys.path`` so a local template package imports (the reference
    CLI's run-from-template-dir workflow; Console.scala resolves
    engine.json relative to the working directory). Idempotent: safe to
    call from any command prologue."""
    if getattr(args, "_engine_dir_entered", False):
        return
    args._engine_dir_entered = True
    engine_dir = os.path.abspath(
        getattr(args, "engine_dir", None) or os.getcwd()
    )
    if not getattr(args, "variant", None):
        candidate = os.path.join(engine_dir, "engine.json")
        if os.path.exists(candidate):
            args.variant = candidate
    # a console-script entry point has no cwd on sys.path: the directory
    # holding the variant file IS the engine dir, and its local template
    # package must import no matter how the variant was named (bare cwd
    # pickup, --engine-dir, or explicit --variant — including daemon
    # children that only receive --variant)
    dirs = []
    if getattr(args, "engine_dir", None):
        dirs.append(engine_dir)
    if getattr(args, "variant", None):
        dirs.append(os.path.dirname(os.path.abspath(args.variant)))
    for d in dirs:
        if d not in sys.path:
            sys.path.insert(0, d)


def _variant_label(args) -> str:
    """The engine-instance variant label: the variant FILE NAME, so
    `pio train --engine-dir d` and `cd d && pio deploy` agree on the
    label regardless of how the path was spelled."""
    return (
        os.path.basename(getattr(args, "variant", None) or "") or "default"
    )


def _engine_identity(args, variant: dict) -> tuple[str, str, str]:
    """(engine_id, version, variant label) — the instance lookup key.

    A variant without an ``id`` field falls back to the real path of its
    directory, so two different id-less engines never collide on the
    (default, 0, engine.json) key while the same engine resolves
    identically from every invocation style."""
    engine_id = variant.get("id")
    if not engine_id:
        v = getattr(args, "variant", None)
        engine_id = (
            os.path.dirname(os.path.realpath(v)) if v else "default"
        )
    return engine_id, variant.get("version", "0"), _variant_label(args)


def _engine_from_args(args) -> tuple:
    """Resolve (engine, variant dict, factory name) from --engine-factory /
    --variant (engine.json; defaults to ./engine.json like the
    reference) / --engine-dir."""
    from predictionio_tpu.core.engine import resolve_engine_factory
    from predictionio_tpu.core.workflow import load_variant

    _enter_engine_dir(args)
    variant: dict = {}
    if getattr(args, "variant", None):
        variant = load_variant(args.variant)
    factory = getattr(args, "engine_factory", None) or variant.get("engineFactory")
    if not factory:
        raise SystemExit(
            "error: specify --engine-factory dotted.path, a --variant JSON "
            "with an engineFactory field, or run from an engine directory "
            "containing engine.json (see --engine-dir)"
        )
    engine = resolve_engine_factory(factory)
    return engine, variant, factory


def cmd_version(args) -> int:
    print(__version__)
    return 0


def cmd_status(args) -> int:
    if getattr(args, "json", False):
        return _status_json()
    from predictionio_tpu.cli import commands

    try:
        info = commands.status()
    except ImportError as e:
        # driver-gated backends (postgres/psycopg2, s3/boto3): the
        # remedy is in the message — surface it, not a traceback
        print(f"storage verification failed: {e}", file=sys.stderr)
        return 1
    print(json.dumps(info, indent=2))
    print("(sanity check) All storage repositories verified.")
    line = _training_line()
    if line:
        print(line)
    for line in _supervisor_lines():
        print(line)
    for line in _slo_lines():
        print(line)
    for line in _variant_lines():
        print(line)
    for line in _replica_lines():
        print(line)
    return 0


def _variant_lines() -> list[str]:
    """Human per-tenant lines for ``pio status`` when a live engine
    daemon mounts more than one variant: one row per mount off its
    /stats.json ``variants`` block, e.g.
    ``variant[engine/b]: 124 reqs, p99 3.1ms, epoch 2``."""
    import urllib.request

    from predictionio_tpu.cli import daemon

    lines: list[str] = []
    for name in daemon.known_services():
        if daemon.read_pid(name) is None:
            continue
        port = daemon.service_port(name)
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/stats.json", timeout=2.0
            ) as r:
                stats = json.loads(r.read())
        except Exception:
            continue
        variants = (
            stats.get("variants") if isinstance(stats, dict) else None
        ) or {}
        if len(variants) <= 1:
            continue
        for vname, v in variants.items():
            parts = [f"{v.get('requestCount', 0)} reqs"]
            if v.get("p99Ms") is not None:
                parts.append(f"p99 {v['p99Ms']}ms")
            parts.append(f"epoch {v.get('epoch', '?')}")
            if v.get("secondsBehind") is not None:
                parts.append(f"{v['secondsBehind']}s behind")
            if v.get("modelAgeSec") is not None:
                parts.append(f"model age {v['modelAgeSec']}s")
            lines.append(f"variant[{name}/{vname}]: {', '.join(parts)}")
    return lines


def _replica_lines() -> list[str]:
    """Human per-replica lines for ``pio status`` when a router tier is
    up: one row per pool member off its /stats.json ``replicas`` block,
    e.g. ``replica[router/engine-0]: ready, 2 inflight, p99 31.0ms,
    124 reqs`` — ejected members lead with their state upper-cased."""
    import urllib.request

    from predictionio_tpu.cli import daemon

    lines: list[str] = []
    for name in daemon.known_services():
        if daemon.read_pid(name) is None:
            continue
        port = daemon.service_port(name)
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/stats.json", timeout=2.0
            ) as r:
                stats = json.loads(r.read())
        except Exception:
            continue
        replicas = (
            stats.get("replicas") if isinstance(stats, dict) else None
        ) or {}
        for rname, rr in replicas.items():
            state = str(rr.get("state", "?"))
            mark = state if state == "ready" else state.upper()
            parts = [
                f"{rr.get('inflight', 0)} inflight",
                f"p99 {rr.get('p99Ms', 0)}ms",
                f"{rr.get('requests', 0)} reqs",
            ]
            if rr.get("ejections"):
                parts.append(f"{rr['ejections']} ejections")
            lines.append(
                f"replica[{name}/{rname}]: {mark}, {', '.join(parts)}"
            )
    return lines


def _supervisor_lines() -> list[str]:
    """Human supervisor lines for ``pio status``, one per supervised
    service: ``supervisor[engine]: up (restarts 1)`` — with the last
    exit reason and next-retry ETA when it is mid-backoff or broken."""
    from predictionio_tpu.server import supervisor as sup_mod

    doc = sup_mod.read_state()
    if doc is None:
        return []
    lines: list[str] = []
    stale = "" if doc.get("live") else " [supervisor not running]"
    for name, s in (doc.get("services") or {}).items():
        parts = [f"restarts {s.get('restarts', 0)}"]
        if s.get("pid"):
            parts.append(f"pid {s['pid']}")
        if s.get("last_exit") and s.get("state") != "up":
            parts.append(f"last exit: {s['last_exit']}")
        if s.get("next_retry_in_s") is not None:
            parts.append(f"retry in {s['next_retry_in_s']}s")
        lines.append(
            f"supervisor[{name}]: {s.get('state', '?')} "
            f"({', '.join(parts)}){stale}"
        )
    rt = doc.get("retrain")
    if isinstance(rt, dict):
        parts = [
            f"every {rt.get('interval_s')}s"
            + (" (slo)" if rt.get("slo_driven") else ""),
            f"runs {rt.get('runs', 0)}",
            f"skips {rt.get('skips', 0)}",
            f"failures {rt.get('failures', 0)}",
        ]
        last = rt.get("last_run") or {}
        if last:
            parts.append(
                "last ok" if last.get("ok") else
                f"last failed ({last.get('exit')})"
            )
        if rt.get("next_in_s") is not None:
            parts.append(f"next in {rt['next_in_s']}s")
        lines.append(
            f"supervisor[retrain]: {rt.get('state', '?')} "
            f"({', '.join(parts)}){stale}"
        )
    return lines


def _training_progress() -> dict | None:
    """The live-training progress doc (obs/progress.py), or None when
    no checkpointed ``pio train`` is currently publishing."""
    from predictionio_tpu.obs import progress as obs_progress

    doc = obs_progress.read_progress()
    return doc if obs_progress.is_live(doc) else None


def _training_line() -> str | None:
    """Human one-liner for ``pio status``: "training: iter 7/20, ETA 41s"."""
    doc = _training_progress()
    if doc is None:
        return None
    # under --tol the iteration count is an upper bound (the solve may
    # plateau out early), so render "iter 7/<=20, ETA <=41s"
    bound = "<=" if doc.get("eta_is_bound") else ""
    parts = [f"iter {doc.get('iteration')}/{bound}{doc.get('total_iterations')}"]
    if doc.get("eta_s") is not None:
        parts.append(f"ETA {bound}{round(doc['eta_s'])}s")
    rmse = doc.get("rmse")
    if rmse:
        parts.append(f"RMSE {rmse[-1]:.4f}")
    if doc.get("events_per_s"):
        parts.append(f"{doc['events_per_s']:,.0f} events/s")
    return "training: " + ", ".join(parts)


def _fetch_slo_docs() -> dict[str, dict]:
    """``/slo.json`` per live daemon (pid file + answering port); silent
    on daemons that are down or predate the endpoint."""
    import urllib.request

    from predictionio_tpu.cli import daemon

    docs: dict[str, dict] = {}
    for name in daemon.known_services():
        if daemon.read_pid(name) is None:
            continue
        port = daemon.service_port(name)
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/slo.json", timeout=2.0
            ) as r:
                doc = json.loads(r.read())
        except Exception:
            continue
        if isinstance(doc, dict):
            docs[name] = doc
    return docs


def _slo_lines() -> list[str]:
    """Human SLO lines for ``pio status``: one per objective, e.g.
    ``slo[engine] engine.latency: OK (burn 0.2/0.1)``; violated and
    burning objectives lead with their state upper-cased. Follows with
    the newest state transitions off each daemon's alert ring."""
    lines: list[str] = []
    alerts: list[tuple[float, str]] = []
    for service, doc in _fetch_slo_docs().items():
        for s in doc.get("slos", []):
            state = str(s.get("state", "?"))
            mark = state.upper() if state != "ok" else "OK"
            burn = ""
            if s.get("burn_fast") is not None:
                burn = f" (burn {s['burn_fast']}/{s.get('burn_slow')})"
            cur = ""
            if s.get("current") is not None:
                cur = f", current {s['current']}"
            lines.append(
                f"slo[{service}] {s.get('name')}: {mark}{burn}{cur}"
            )
        for a in doc.get("alerts", []):
            t = float(a.get("t") or 0.0)
            alerts.append(
                (
                    t,
                    f"alert[{service}] {a.get('slo')}: "
                    f"{a.get('from')} -> {a.get('to')} "
                    f"(burn {a.get('burn_fast')}/{a.get('burn_slow')}, "
                    f"t={a.get('t')})",
                )
            )
    lines.extend(line for _, line in sorted(alerts)[-5:])
    return lines


def cmd_bench(args) -> int:
    """``pio bench --compare OLD.json [NEW.json]``: regression-diff two
    bench summary artifacts (>tolerance moves in the bad direction exit
    non-zero). Running the benchmarks themselves stays with bench.py."""
    if not getattr(args, "compare", None):
        print("usage: pio bench --compare OLD.json [NEW.json]",
              file=sys.stderr)
        return 2
    if len(args.compare) > 2:
        print("bench --compare takes at most OLD and NEW", file=sys.stderr)
        return 2
    from predictionio_tpu.cli import bench_compare

    try:
        return bench_compare.main(
            args.compare[0],
            args.compare[1] if len(args.compare) > 1 else None,
            tolerance=args.tolerance,
        )
    except (OSError, ValueError) as e:
        print(f"bench compare failed: {e}", file=sys.stderr)
        return 2


def cmd_profile(args) -> int:
    """``pio profile --seconds N [--url]``: on-demand jax.profiler trace
    capture — in-process (with a small jit workload so the trace is
    never empty), or via ``POST /profile`` on a running daemon so the
    capture sees that server's real traffic. Prints one compact JSON
    summary line either way (trace dir, window, file count/bytes)."""
    if args.url:
        import urllib.parse
        import urllib.request

        query = {"seconds": str(args.seconds)}
        if args.out:
            query["out"] = args.out
        if args.python_tracer:
            query["python_tracer"] = "1"
        url = (
            args.url.rstrip("/")
            + "/profile?"
            + urllib.parse.urlencode(query)
        )
        req = urllib.request.Request(url, method="POST")
        try:
            # the server captures synchronously: allow the window + slack
            with urllib.request.urlopen(
                req, timeout=args.seconds + 30.0
            ) as r:
                body = r.read()
        except Exception as e:
            print(f"profile request failed: {e}", file=sys.stderr)
            return 1
        print(body.decode().strip())
        return 0

    from predictionio_tpu.obs import device as obs_device

    try:
        result = obs_device.profile_capture(
            args.seconds, out_dir=args.out, burn=True,
            python_tracer=args.python_tracer,
        )
    except RuntimeError as e:
        print(f"profile failed: {e}", file=sys.stderr)
        return 1
    print(json.dumps(result, separators=(",", ":")))
    return 0


def cmd_layers(args) -> int:
    """``pio layers --url BASE --seconds N`` / ``--windows
    DIR/gen.windows.json``: where a query's time goes, per request and
    per dispatch, from two scrapes of a server's ``/metrics`` — a live
    server's, N seconds apart, or the two a benchmark run kept with
    ``--save-logs``. No profiler: the always-on histograms of the span
    chain, down to the uploads, launches and the read of a dispatch."""
    from predictionio_tpu.cli import layers

    if bool(args.url) == bool(args.windows):
        print("layers: give --url BASE or --windows FILE", file=sys.stderr)
        return 2
    try:
        if args.windows:
            docs = layers.from_windows(args.windows)
        else:
            docs = [(args.url, layers.from_url(args.url, args.seconds))]
    except (OSError, ValueError, KeyError) as e:
        print(f"layers failed: {e}", file=sys.stderr)
        return 1
    if not docs:
        print("layers: no measured window in the file", file=sys.stderr)
        return 1
    if args.json:
        print(json.dumps(
            {label: doc for label, doc in docs}, separators=(",", ":")
        ))
        return 0
    print("\n\n".join(layers.render(doc, label) for label, doc in docs))
    return 0


def cmd_incidents(args) -> int:
    """``pio incidents list|show|prune``: inspect the flight recorder's
    bundle directory (``$PIO_RUN_DIR/incidents``). ``show NAME`` prints
    a bundle summary (or one file verbatim with ``--file``); ``prune``
    keeps the newest ``--keep`` bundles."""
    from predictionio_tpu.obs import incident as obs_incident

    action = getattr(args, "incidents_command", None) or "list"
    if action == "list":
        bundles = obs_incident.list_incidents()
        if getattr(args, "json", False):
            print(json.dumps(bundles, separators=(",", ":")))
            return 0
        if not bundles:
            print(f"no incident bundles under {obs_incident.incidents_dir()}")
            return 0
        for b in bundles:
            print(
                f"{b['name']}  reason={b.get('reason')}  "
                f"files={len(b.get('files', []))}  "
                f"{b.get('bytes', 0):,} bytes"
            )
        return 0
    if action == "show":
        try:
            bundle = obs_incident.load_incident(args.name)
        except FileNotFoundError as e:
            print(str(e), file=sys.stderr)
            return 1
        if getattr(args, "file", None):
            doc = bundle.get(args.file)
            if doc is None:
                print(
                    f"no file {args.file!r} in bundle "
                    f"(have: {', '.join(sorted(bundle))})",
                    file=sys.stderr,
                )
                return 1
            print(doc if isinstance(doc, str) else json.dumps(doc, indent=2))
            return 0
        meta = bundle.get("meta.json", {})
        slo_doc = bundle.get("slo.json", {})
        traces = bundle.get("traces.json", {})
        hist = bundle.get("history.json", {})
        summary = {
            "name": args.name,
            "reason": meta.get("reason"),
            "iso": meta.get("iso"),
            "context": meta.get("context"),
            "slo_states": {
                s.get("name"): s.get("state")
                for s in slo_doc.get("slos", [])
            },
            "alerts": len(slo_doc.get("alerts", [])),
            "traces": len(traces.get("slowest", [])),
            "traces_slo_violated": len(traces.get("sloViolated", [])),
            "history_series": len(hist.get("series", {})),
            "files": sorted(bundle),
        }
        print(json.dumps(summary, indent=2))
        return 0
    if action == "prune":
        removed = obs_incident.prune(keep=args.keep)
        print(f"pruned {len(removed)} bundle(s)"
              + (f": {', '.join(removed)}" if removed else ""))
        return 0
    print(f"unknown incidents action {action!r}", file=sys.stderr)
    return 2


def _top_targets(urls: list[str] | None) -> list[tuple[str, str]]:
    """(name, base_url) pairs ``pio top`` polls: explicit ``--url``
    values, else every live daemon (pid file + default port)."""
    if urls:
        return [(u.split("//")[-1].rstrip("/"), u.rstrip("/")) for u in urls]
    from predictionio_tpu.cli import daemon

    out = []
    for name in daemon.known_services():
        if daemon.read_pid(name) is None:
            continue
        port = daemon.service_port(name)
        out.append((name, f"http://127.0.0.1:{port}"))
    return out


def _top_row(name: str, base: str) -> dict:
    """One daemon's live numbers, derived from its history rings: qps
    from the newest ``pio_http_requests_total`` delta, p99 from the
    newest request-latency quantile sample, ``seconds_behind`` and the
    worst fast-window burn rate from their gauge series."""
    import urllib.request

    def fetch(path: str) -> dict:
        with urllib.request.urlopen(base + path, timeout=2.0) as r:
            return json.loads(r.read())

    row: dict = {"service": name, "url": base}
    try:
        hist = fetch("/history.json")
    except Exception as e:
        row["error"] = f"{type(e).__name__}"
        return row
    step = float(hist.get("step_s") or 5.0)
    series = hist.get("series", {})

    def latest(key_prefix: str, suffix: str = "") -> float | None:
        vals = [
            doc["points"][-1][1]
            for key, doc in series.items()
            if key.startswith(key_prefix) and key.endswith(suffix)
            and doc.get("points")
        ]
        return max(vals) if vals else None

    req_delta = sum(
        doc["points"][-1][1]
        for key, doc in series.items()
        if key.startswith("pio_http_requests_total") and doc.get("points")
    )
    row["qps"] = round(req_delta / step, 2)
    p99 = latest("pio_http_request_seconds", ":p99")
    if p99 is not None:
        row["p99_ms"] = round(p99 * 1e3, 3)
    behind = latest("pio_realtime_seconds_behind")
    if behind is not None:
        row["seconds_behind"] = round(behind, 3)
    burn = latest("pio_slo_burn_rate")
    if burn is not None:
        row["burn"] = round(burn, 2)
    try:
        slo_doc = fetch("/slo.json")
        states = [str(s.get("state")) for s in slo_doc.get("slos", [])]
        row["slo"] = {
            st: states.count(st)
            for st in ("ok", "burning", "violated")
            if states.count(st)
        }
        row["alerts"] = len(slo_doc.get("alerts", []))
    except Exception:
        pass
    # multi-tenant engine servers: one sub-row per mounted variant
    # (/stats.json "variants" block); solo deploys render no sub-rows
    try:
        stats = fetch("/stats.json")
        variants = stats.get("variants") or {}
        if len(variants) > 1:
            row["variants"] = {
                vname: {
                    "requests": v.get("requestCount"),
                    "p99_ms": v.get("p99Ms"),
                    "epoch": v.get("epoch"),
                    "seconds_behind": v.get("secondsBehind"),
                    "model_age_s": v.get("modelAgeSec"),
                }
                for vname, v in variants.items()
            }
        # router tier: one sub-row per backend replica (/stats.json
        # "replicas" block), mirroring the variant sub-row convention
        replicas = stats.get("replicas") or {}
        if replicas:
            row["replicas"] = {
                rname: {
                    "state": r.get("state"),
                    "inflight": r.get("inflight"),
                    "p99_ms": r.get("p99Ms"),
                    "requests": r.get("requests"),
                    "ejections": r.get("ejections"),
                }
                for rname, r in replicas.items()
            }
    except Exception:
        pass
    return row


def cmd_top(args) -> int:
    """``pio top [--once] [--interval S] [--url BASE ...]``: live
    terminal view across daemons — qps, p99, seconds_behind, burn rates,
    all read from each server's ``/history.json`` rings (no server-side
    aggregation; the CLI only diffs what the rings already hold)."""
    interval = max(float(getattr(args, "interval", 2.0)), 0.2)
    once = bool(getattr(args, "once", False))
    while True:
        targets = _top_targets(getattr(args, "url", None))
        rows = [_top_row(name, base) for name, base in targets]
        if not once:
            sys.stdout.write("\x1b[2J\x1b[H")  # clear + home
        stamp = time.strftime("%H:%M:%S")
        print(f"pio top — {stamp} — {len(rows)} service(s)")
        header = (
            f"{'SERVICE':<14} {'QPS':>9} {'P99_MS':>9} {'BEHIND_S':>9} "
            f"{'BURN':>7} {'SLO':<22} {'ALERTS':>6}"
        )
        print(header)
        for row in rows:
            if "error" in row:
                print(f"{row['service']:<14} unreachable ({row['error']})")
                continue
            slo_str = (
                ",".join(f"{k}:{v}" for k, v in row.get("slo", {}).items())
                or "-"
            )
            print(
                f"{row['service']:<14} {row.get('qps', 0):>9} "
                f"{row.get('p99_ms', '-'):>9} "
                f"{row.get('seconds_behind', '-'):>9} "
                f"{row.get('burn', '-'):>7} {slo_str:<22} "
                f"{row.get('alerts', 0):>6}"
            )
            for vname, v in (row.get("variants") or {}).items():
                req = v.get("requests")
                print(
                    f"  ↳{vname:<12} {req if req is not None else '-':>9} "
                    f"{v.get('p99_ms') if v.get('p99_ms') is not None else '-':>9} "
                    f"{v.get('seconds_behind') if v.get('seconds_behind') is not None else '-':>9} "
                    f"{'':>7} epoch:{v.get('epoch', '-')}"
                )
            for rname, rr in (row.get("replicas") or {}).items():
                req = rr.get("requests")
                print(
                    f"  ↳{rname:<12} {req if req is not None else '-':>9} "
                    f"{rr.get('p99_ms') if rr.get('p99_ms') is not None else '-':>9} "
                    f"{'':>9} {'':>7} "
                    f"{rr.get('state', '?')} inflight:{rr.get('inflight', 0)} "
                    f"ejections:{rr.get('ejections', 0)}"
                )
        if not rows:
            print("no live daemons (and no --url given)")
        if once:
            return 0
        try:
            time.sleep(interval)
        except KeyboardInterrupt:
            return 0


def _status_json() -> int:
    """``pio status --json``: one compact JSON line merging ``/metrics``
    + ``/stats.json`` from every running daemon (live pid files), in
    the bench summary-line convention. Endpoints that refuse (the event
    server's /stats.json wants an access key) are skipped, not fatal."""
    import urllib.request

    from predictionio_tpu.cli import daemon
    from predictionio_tpu.obs import metrics as obs_metrics

    def fetch(url: str):
        try:
            with urllib.request.urlopen(url, timeout=2.0) as r:
                return r.read()
        except Exception:
            return None

    services: dict = {}
    for name in daemon.known_services():
        pid = daemon.read_pid(name)
        if pid is None:
            continue
        port = daemon.service_port(name)
        entry: dict = {"pid": pid, "port": port}
        base = f"http://127.0.0.1:{port}"
        raw = fetch(f"{base}/metrics")
        if raw is not None:
            entry["metrics"] = obs_metrics.parse_prometheus(raw)
        raw = fetch(f"{base}/stats.json")
        if raw is not None:
            try:
                entry["stats"] = json.loads(raw)
            except ValueError:
                pass
        raw = fetch(f"{base}/slo.json")
        if raw is not None:
            try:
                entry["slo"] = json.loads(raw)
            except ValueError:
                pass
        services[name] = entry
    summary: dict = {"services": services}
    # self-healing supervisor state (supervisor.json), when a fleet ran
    # (or runs) under `pio start-all --supervise`
    from predictionio_tpu.server import supervisor as sup_mod

    sup_doc = sup_mod.read_state()
    if sup_doc is not None:
        summary["supervisor"] = sup_doc
    # the SLO alert ring across services, oldest->newest, each record
    # tagged with the daemon it came from (satellite: alerts were
    # counted but not inspectable without scraping /slo.json)
    alerts = [
        {"service": name, **a}
        for name, entry in services.items()
        for a in (entry.get("slo") or {}).get("alerts", [])
    ]
    alerts.sort(key=lambda a: float(a.get("t") or 0.0))
    summary["alerts"] = alerts[-10:]
    # incident bundles on this host (flight-recorder output)
    from predictionio_tpu.obs import incident as obs_incident

    bundles = obs_incident.list_incidents()
    summary["incidents"] = {
        "count": len(bundles),
        "latest": bundles[0]["name"] if bundles else None,
        "dir": str(obs_incident.incidents_dir()),
    }
    # live checkpointed training on this host, if any (the per-service
    # device blocks already ride in services.*.stats.device)
    progress = _training_progress()
    if progress is not None:
        summary["training"] = progress
    print(json.dumps(summary, separators=(",", ":")))
    return 0


def cmd_build(args) -> int:
    """Python engines need no assembly; ``build`` verifies what sbt
    would have caught: the factory imports AND the variant's component
    names/params bind to the engine's registered classes — a broken
    template fails here, not at train."""
    _enter_engine_dir(args)  # idempotent; resolves ./engine.json pickup
    if getattr(args, "engine_factory", None) or getattr(args, "variant", None):
        engine, variant, factory = _engine_from_args(args)
        if variant:
            try:
                engine.params_from_variant(variant)
            except Exception as e:
                print(f"build failed: variant does not bind to "
                      f"{factory}: {e}", file=sys.stderr)
                return 1
        print("Engine factory resolves; build OK.")
    else:
        print("Nothing to build for Python engines; use --engine-factory to verify.")
    return 0


def cmd_app(args) -> int:
    from predictionio_tpu.cli import commands

    try:
        if args.app_command == "new":
            info = commands.app_new(
                args.name, app_id=args.id or 0, description=args.description,
                access_key=args.access_key or "",
            )
            print(f"Created a new app:")
            print(f"      Name: {info['name']}")
            print(f"        ID: {info['id']}")
            print(f"Access Key: {info['access_key']}")
        elif args.app_command == "list":
            for a in commands.app_list():
                print(f"{a['id']:>6} | {a['name']} | {a['access_key']}")
        elif args.app_command == "show":
            info = commands.app_show(args.name)
            print(json.dumps(info, indent=2))
        elif args.app_command == "delete":
            commands.app_delete(args.name)
            print(f"Deleted app {args.name}.")
        elif args.app_command == "data-delete":
            commands.app_data_delete(args.name, channel=args.channel)
            print(f"Deleted data of app {args.name}.")
        elif args.app_command == "channel-new":
            info = commands.channel_new(args.name, args.channel)
            print(f"Created channel {info['name']} (id {info['id']}).")
        elif args.app_command == "channel-delete":
            commands.channel_delete(args.name, args.channel)
            print(f"Deleted channel {args.channel}.")
        else:
            print(
                "usage: pio app "
                "{new,list,show,delete,data-delete,channel-new,channel-delete}",
                file=sys.stderr,
            )
            return 1
        return 0
    except commands.CommandError as e:
        print(str(e), file=sys.stderr)
        return 1


def cmd_accesskey(args) -> int:
    from predictionio_tpu.cli import commands

    try:
        if args.ak_command == "new":
            key = commands.accesskey_new(args.app_name, events=args.event or [])
            print(f"Created new access key: {key}")
        elif args.ak_command == "list":
            for k in commands.accesskey_list(args.app_name):
                print(f"{k['key']} | app {k['app_id']} | events {k['events'] or 'ALL'}")
        elif args.ak_command == "delete":
            commands.accesskey_delete(args.key)
            print(f"Deleted access key {args.key}.")
        else:
            print("usage: pio accesskey {new,list,delete}", file=sys.stderr)
            return 1
        return 0
    except commands.CommandError as e:
        print(str(e), file=sys.stderr)
        return 1


def _parse_mesh(spec: str | None) -> list[tuple[str, int]] | None:
    """'data=4,model=2' -> [("data", 4), ("model", 2)]."""
    from predictionio_tpu.parallel.mesh import parse_axes

    try:
        return parse_axes(spec)
    except ValueError as e:
        raise SystemExit(f"--mesh: {e}") from None


def cmd_train(args) -> int:
    from predictionio_tpu.core.engine import WorkflowParams
    from predictionio_tpu.core.workflow import run_train

    if getattr(args, "no_columnar_cache", False):
        os.environ["PIO_COLUMNAR_CACHE"] = "0"
    if getattr(args, "checkpoint_every", None):
        os.environ["PIO_CHECKPOINT_EVERY"] = str(args.checkpoint_every)
    if getattr(args, "resume", False):
        os.environ["PIO_RESUME"] = "1"
    if getattr(args, "checkpoint_dir", None):
        os.environ["PIO_CHECKPOINT_DIR"] = args.checkpoint_dir
    if getattr(args, "warm_start", False):
        os.environ["PIO_WARM_START"] = "1"
    if getattr(args, "tol", None) is not None:
        os.environ["PIO_TOL"] = str(args.tol)
    if getattr(args, "no_prep_cache", False):
        os.environ["PIO_PREP_CACHE"] = "0"
    if getattr(args, "prep_cache_dir", None):
        os.environ["PIO_PREP_CACHE_DIR"] = args.prep_cache_dir
    if getattr(args, "multihost", False):
        # join the global mesh BEFORE anything touches JAX: afterwards
        # jax.devices() is the pod-wide set and --mesh axes span hosts
        # (the cluster-submission analog of the reference's spark-submit
        # master flags, tools/.../Runner.scala:193-244)
        from predictionio_tpu.parallel.mesh import initialize_multihost

        initialize_multihost(
            coordinator_address=args.coordinator,
            num_processes=args.num_processes,
            process_id=args.process_id,
        )
    engine, variant, factory = _engine_from_args(args)
    engine_params = engine.params_from_variant(variant)
    wp = WorkflowParams(
        batch=args.batch or "",
        verbose=args.verbose,
        skip_sanity_check=args.skip_sanity_check,
        stop_after_read=args.stop_after_read,
        stop_after_prepare=args.stop_after_prepare,
        profile_dir=args.profile_dir,
        mesh_axes=_parse_mesh(getattr(args, "mesh", None)),
    )
    engine_id, engine_version, variant_label = _engine_identity(args, variant)
    instance_id = run_train(
        engine,
        engine_params,
        engine_id=engine_id,
        engine_version=engine_version,
        engine_variant=variant_label,
        engine_factory=factory,
        workflow_params=wp,
    )
    from predictionio_tpu.obs import device as obs_device

    where = ", ".join(f"{k}: {v}" for k, v in obs_device.where().items())
    print(f"Training completed. Engine instance ID: {instance_id} ({where})")
    return 0


def cmd_eval(args) -> int:
    from predictionio_tpu.core.workflow_eval import run_evaluation

    instance_id, result = run_evaluation(
        evaluation_class=args.evaluation_class,
        engine_params_generator_class=args.engine_params_generator_class,
        batch=args.batch or "",
    )
    print(result.to_one_liner())
    print(f"Evaluation completed. Evaluation instance ID: {instance_id}")
    # compact machine-readable summary as the FINAL stdout line (same
    # contract as bench.py: drivers that keep only a bounded tail of
    # stdout can json.loads the last line on its own)
    best = result.best_score
    summary = {
        "metric": result.metric_header,
        "best_index": result.best_idx,
        "best_params": result.best_engine_params.to_jsonable(),
        "best_scores": {
            result.metric_header: best.score,
            **dict(zip(result.other_metric_headers, best.other_scores)),
        },
        "scores": [ms.score for _, ms in result.engine_params_scores],
        "candidates": len(result.engine_params_scores),
        "fast_path_candidates": result.fast_path_candidates,
        "phase_seconds": {
            k: round(v, 3) for k, v in result.phase_seconds.items()
        },
        "cache": result.cache_stats,
        "instance_id": instance_id,
    }
    print(json.dumps(summary, sort_keys=True))
    return 0


def _load_server_config(args):
    """server.conf for key auth / SSL: --server-config flag, else the
    PIO_SERVER_CONF env var, else conf/server.conf when present
    (the reference loads server.conf from the classpath unconditionally)."""
    import os

    from predictionio_tpu.common import load_server_config

    path = (
        getattr(args, "server_config", None)
        or os.environ.get("PIO_SERVER_CONF")
        or "conf/server.conf"
    )
    return load_server_config(path=path)


def _maybe_run_workers(args) -> int | None:
    """The `--workers N` dispatch shared by deploy/eventserver: None
    means "continue single-process"."""
    if getattr(args, "workers", 1) <= 1:
        return None
    if args.port == 0:
        print("--workers needs an explicit --port", file=sys.stderr)
        return 1
    return _run_workers(args)


def _run_workers(args) -> int:
    """Spawn N copies of this exact CLI invocation (each binding the
    same port with SO_REUSEPORT) and supervise them: forward SIGTERM/
    SIGINT, exit nonzero if ANY worker dies (an external supervisor
    restarts the set). The reference's spray server scales with JVM
    threads inside one process; CPython serving is GIL-bound, so the
    scale-out unit here is the PROCESS."""
    import signal
    import subprocess
    import time

    # strip every --workers spelling (separate token, --workers=N, and
    # argparse prefix abbreviations): a surviving flag would make each
    # child spawn its own workers — a fork bomb
    argv = []
    tokens = iter(sys.argv[1:])
    for tok in tokens:
        if tok.startswith("--w") and "--workers".startswith(
            tok.split("=", 1)[0]
        ):
            if "=" not in tok:
                next(tokens, None)  # drop the value token too
            continue
        argv.append(tok)
    if "--reuse-port" not in argv:
        argv.append("--reuse-port")

    procs: list = []

    # install the forwarders BEFORE spawning: a SIGTERM landing in the
    # spawn window must still reach (and not orphan) early workers
    def forward(signum, _frame):
        for pr in procs:
            pr.send_signal(signum)

    signal.signal(signal.SIGTERM, forward)
    signal.signal(signal.SIGINT, forward)
    for _ in range(args.workers):
        procs.append(
            subprocess.Popen(
                [sys.executable, "-m", "predictionio_tpu.cli.main"] + argv
            )
        )
    rc = 0
    try:
        # poll ALL workers: the death of any one must surface (waiting
        # on one pid would let the set run degraded indefinitely)
        while procs and rc == 0:
            time.sleep(0.5)
            for pr in list(procs):
                code = pr.poll()
                if code is None:
                    continue
                procs.remove(pr)
                if code not in (0, -signal.SIGTERM, -signal.SIGINT):
                    rc = code or 1
    finally:
        for pr in procs:
            pr.terminate()
        for pr in procs:
            try:
                pr.wait(timeout=10)
            except Exception:
                pr.kill()
    return rc


def cmd_deploy(args) -> int:
    from predictionio_tpu.data.storage import get_storage
    from predictionio_tpu.server.engine_server import EngineServer

    rc = _maybe_run_workers(args)
    if rc is not None:
        return rc

    if getattr(args, "mesh", None):
        _parse_mesh(args.mesh)  # refuse a bad spec here, by name
        os.environ["PIO_MESH"] = args.mesh  # parallel/mesh.py serving_mesh
    engine, variant, factory = _engine_from_args(args)
    storage = get_storage()
    instances = storage.get_metadata_engine_instances()
    if args.engine_instance_id:
        instance = instances.get(args.engine_instance_id)
        if instance is None:
            print(f"engine instance {args.engine_instance_id} not found", file=sys.stderr)
            return 1
    else:
        engine_id, engine_version, variant_label = _engine_identity(
            args, variant
        )
        instance = instances.get_latest_completed(
            engine_id, engine_version, variant_label
        )
        if instance is None and getattr(args, "variant", None):
            # instances trained before the basename-label change carry
            # the as-typed path as their label; fall back so they stay
            # deployable without a retrain
            instance = instances.get_latest_completed(
                variant.get("id", "default"),
                engine_version,
                args.variant,
            )
        if instance is None:
            print(
                "No valid engine instance found for this engine; "
                "have you run `pio train` yet?",
                file=sys.stderr,
            )
            return 1
    try:
        extra_variants = _resolve_extra_variants(args, instances)
    except SystemExit:
        raise
    except Exception as e:
        print(f"--variants resolution failed: {e}", file=sys.stderr)
        return 1
    server = EngineServer(
        engine,
        instance,
        storage=storage,
        host=args.ip,
        port=args.port,
        feedback=args.feedback,
        event_server_url=(
            f"http://{args.event_server_ip}:{args.event_server_port}"
            if args.feedback
            else None
        ),
        access_key=args.accesskey,
        server_config=_load_server_config(args),
        log_url=args.log_url,
        log_prefix=args.log_prefix,
        batch_window_ms=args.batch_window_ms,
        reuse_port=args.reuse_port,
        query_cache_mb=args.query_cache_mb,
        extra_variants=extra_variants,
    )
    # AOT warmup BEFORE the port binds: the first real query hits a
    # compiled scoring program (and the compile itself persists across
    # restarts in the persistent cache — predictionio_tpu/__init__.py)
    if not getattr(args, "no_warmup", False):
        server.warmup()
    layers = []
    if getattr(args, "realtime", 0.0) and args.realtime > 0:
        from pathlib import Path

        from predictionio_tpu.realtime import SpeedLayer

        cursor = args.realtime_cursor or str(
            Path("~/.pio_tpu").expanduser()
            / "realtime"
            / f"cursor_{instance.engine_id}_{args.port}.json"
        )
        layers.append(
            SpeedLayer(server, interval=args.realtime, cursor_path=cursor)
        )
        # every co-tenant mount tails and folds independently — its
        # layer holds the _Variant, so patches land behind that mount's
        # own epoch fence, never a neighbor's
        for name, v in server.variants.items():
            if v is server._default_variant:
                continue
            vcursor = str(
                Path("~/.pio_tpu").expanduser()
                / "realtime"
                / f"cursor_{v.instance.engine_id}_{args.port}_{name}.json"
            )
            layers.append(
                SpeedLayer(v, interval=args.realtime, cursor_path=vcursor)
            )
        for layer in layers:
            layer.start()
    # foreground, like the reference: backgrounding is the caller's job
    # (shell &, supervisor); a daemon thread would die with this process
    try:
        server.start(background=False)
    finally:
        for layer in layers:
            layer.stop()
    return 0


def _resolve_extra_variants(args, instances) -> list:
    """``--variants a.json,b.json`` -> [(mount_name, engine, instance)].

    Each file resolves exactly like a solo ``pio deploy --variant`` of
    that path: its own engineFactory (falling back to the primary's),
    its own (id, version, basename-label) instance lookup. The mount
    name is the file's basename minus ``.json`` — the path prefix
    queries route on (``/<name>/queries.json``)."""
    spec = getattr(args, "variants", None) or ""
    paths = [p.strip() for p in spec.split(",") if p.strip()]
    if not paths:
        return []
    from predictionio_tpu.core.engine import resolve_engine_factory
    from predictionio_tpu.core.workflow import load_variant

    extra = []
    for path in paths:
        variant = load_variant(path)
        factory = variant.get("engineFactory") or getattr(
            args, "engine_factory", None
        )
        if not factory:
            raise SystemExit(
                f"error: variant file {path} has no engineFactory field "
                "and no --engine-factory was given"
            )
        engine = resolve_engine_factory(factory)
        engine_id = variant.get("id") or os.path.dirname(
            os.path.realpath(path)
        )
        label = os.path.basename(path)
        inst = instances.get_latest_completed(
            engine_id, variant.get("version", "0"), label
        )
        if inst is None:
            raise SystemExit(
                f"error: no completed engine instance for variant {path} "
                "(train it first: pio train --variant " + path + ")"
            )
        name = label[:-5] if label.endswith(".json") else label
        extra.append((name, engine, inst))
    return extra


def cmd_undeploy(args) -> int:
    import urllib.request

    url = f"http://{args.ip}:{args.port}/stop"
    try:
        urllib.request.urlopen(urllib.request.Request(url, data=b""), timeout=10)
        print("Undeployed.")
        return 0
    except Exception as e:
        print(f"undeploy failed: {e}", file=sys.stderr)
        return 1


def cmd_eventserver(args) -> int:
    from predictionio_tpu.server.event_server import EventServer

    rc = _maybe_run_workers(args)
    if rc is not None:
        return rc
    server = EventServer(
        host=args.ip, port=args.port, stats=args.stats,
        reuse_port=args.reuse_port,
    )
    server.start(background=False)
    return 0


def cmd_storageserver(args) -> int:
    """Serve this host's storage repositories over HTTP so remote
    processes (event server / trainer / engine server on other machines)
    can bind their repositories to it via the ``http`` backend — the
    client-server storage role JDBC Postgres plays in the reference."""
    from predictionio_tpu.server.storage_server import StorageServer

    StorageServer(
        host=args.ip,
        port=args.port,
        auth_key=args.auth_key,
        server_config=_load_server_config(args) if args.server_config else None,
    ).start(background=False)
    return 0


def cmd_adminserver(args) -> int:
    from predictionio_tpu.server.admin_server import AdminServer

    AdminServer(host=args.ip, port=args.port).start(background=False)
    return 0


def cmd_dashboard(args) -> int:
    from predictionio_tpu.server.dashboard import Dashboard

    Dashboard(
        host=args.ip, port=args.port, server_config=_load_server_config(args)
    ).start(background=False)
    return 0


def cmd_route(args) -> int:
    """``pio route``: the scale-out router tier — one front port
    spreading /queries.json across a replica set of engine servers with
    consistent-hash affinity, health-aware ejection, and hedged
    requests (server/router.py; docs/operations.md "Scale-out
    serving")."""
    from predictionio_tpu.server.router import RouterServer, parse_replica_spec

    replicas: list[tuple[str, str, int]] = []
    for i, spec in enumerate(args.replica or []):
        try:
            replicas.append(parse_replica_spec(spec, i))
        except ValueError as e:
            print(f"route: {e}", file=sys.stderr)
            return 1
    if args.replicas:
        host = args.engine_host
        base = args.engine_port
        replicas.extend(
            (f"engine-{i}", host, base + i) for i in range(args.replicas)
        )
    if not replicas:
        print(
            "route: name at least one backend (--replica HOST:PORT or "
            "--replicas N)", file=sys.stderr,
        )
        return 1
    server = RouterServer(
        replicas,
        host=args.ip,
        port=args.port,
        reuse_port=args.reuse_port,
        probe_interval_s=args.probe_interval or None,
        hedge=False if args.no_hedge else None,
    )
    server.start(background=False)
    return 0


def cmd_export(args) -> int:
    from predictionio_tpu.cli import commands
    from predictionio_tpu.data.store import EventStoreError

    try:
        n = commands.export_events(
            args.appid_or_name, args.output, channel=args.channel
        )
    except (commands.CommandError, EventStoreError) as e:
        print(str(e), file=sys.stderr)
        return 1
    print(f"Exported {n} events to {args.output}.")
    return 0


def cmd_import(args) -> int:
    from predictionio_tpu.cli import commands
    from predictionio_tpu.data.store import EventStoreError

    try:
        if getattr(args, "http", None):
            if not args.access_key:
                print("--http requires --access-key", file=sys.stderr)
                return 1
            n = commands.import_events_http(
                args.input, args.http, args.access_key,
                channel=args.channel,
            )
        else:
            n = commands.import_events(
                args.appid_or_name, args.input,
                channel=args.channel, jobs=args.jobs,
            )
    except (commands.CommandError, EventStoreError) as e:
        print(str(e), file=sys.stderr)
        return 1
    print(f"Imported {n} events.")
    if getattr(args, "warm_cache", False):
        from predictionio_tpu.data import store
        from predictionio_tpu.data.storage import get_storage

        storage = get_storage()
        rows = store.warm_columnar_cache(
            commands._resolve_app_name(args.appid_or_name, storage),
            channel_name=args.channel,
            storage=storage,
        )
        print(f"Columnar cache warmed ({rows} rating rows).")
    return 0


def cmd_run(args) -> int:
    """Run a user main function with storage configured (reference `pio
    run` — Console.scala:664-700 launches a main class on Spark with the
    pio classpath; here: import dotted path, call its main/entry)."""
    import importlib

    target = args.main_class
    mod_name, _, attr = target.partition(":")
    mod = importlib.import_module(mod_name)
    fn = getattr(mod, attr or "main", None)
    if fn is None:
        raise SystemExit(
            f"error: {mod_name} has no {attr or 'main'}(); use "
            "module:function to name an entry point"
        )
    result = fn(*args.args)
    # a bool return is success/failure, not an exit code (int(True) == 1)
    if isinstance(result, bool):
        return 0 if result else 1
    return int(result) if isinstance(result, int) else 0


def cmd_cache(args) -> int:
    """``pio cache list|evict|prune``: packed-prep cache lifecycle
    (core/prep_cache.py). Entries are derived data — evicting one only
    costs the next train a full scan+pack."""
    from predictionio_tpu.core import prep_cache

    verb = getattr(args, "cache_verb", None) or "list"
    if verb == "list":
        entries = prep_cache.cache_entries(detail=True)
        total = sum(e["bytes"] for e in entries)
        cap = prep_cache.max_bytes()
        if getattr(args, "json", False):
            print(json.dumps({
                "dir": str(prep_cache.cache_dir()),
                "total_bytes": total,
                "max_bytes": cap,
                "entries": entries,
            }, indent=2))
            return 0
        print(f"Prep cache: {prep_cache.cache_dir()}")
        if not entries:
            print("  (empty)")
            return 0
        for e in entries:
            packs = []
            if e.get("single_pack"):
                packs.append("single")
            if e.get("sharded_pack"):
                packs.append("sharded")
            age = time.time() - e["atime"]
            print(
                f"  {e['name']}: {e['bytes'] / 1e6:.1f} MB, "
                f"{e.get('n', 0):,} events, "
                f"packs [{', '.join(packs) or 'none'}], "
                f"last used {age:.0f}s ago"
            )
        cap_s = f" / cap {cap / 1e6:.1f} MB" if cap else ""
        print(f"  total {total / 1e6:.1f} MB{cap_s}")
        return 0
    if verb == "evict":
        if prep_cache.evict(args.entry):
            print(f"evicted {args.entry}")
            return 0
        print(f"cache: no such entry {args.entry!r}", file=sys.stderr)
        return 1
    if verb == "prune":
        limit = None
        if getattr(args, "max_mb", None) is not None:
            limit = int(float(args.max_mb) * 1024 * 1024)
        out = prep_cache.prune(limit=limit)
        if getattr(args, "json", False):
            print(json.dumps(out, indent=2))
        else:
            print(
                f"pruned: {len(out['husks'])} husk(s), "
                f"{len(out['evicted'])} entry(ies) evicted"
            )
        return 0
    print(f"cache: unknown verb {verb!r}", file=sys.stderr)
    return 1


def cmd_start_all(args) -> int:
    """Bring up the service fleet as detached daemons (reference
    bin/pio-start-all; see cli/daemon.py for the process model).
    With ``--supervise`` the fleet runs under a foreground supervisor
    (server/supervisor.py) that restarts crashed children with backoff."""
    from predictionio_tpu.cli import daemon

    # --reuse-port on the HTTP services so `pio rolling-restart` can
    # overlap a replacement instance on the same port later
    plan: list[tuple[str, list[str], int]] = [
        (
            "eventserver",
            ["eventserver", "--ip", args.ip, "--port", str(args.event_port),
             "--reuse-port"]
            + (["--stats"] if args.stats else []),
            args.event_port,
        )
    ]
    if not args.no_dashboard:
        plan.append(
            (
                "dashboard",
                ["dashboard", "--ip", args.ip, "--port", str(args.dashboard_port)],
                args.dashboard_port,
            )
        )
    if not args.no_adminserver:
        plan.append(
            (
                "adminserver",
                ["adminserver", "--ip", args.ip, "--port", str(args.admin_port)],
                args.admin_port,
            )
        )
    if args.variant or args.engine_factory or args.engine_dir:
        # beyond the reference's script: also deploy the latest trained
        # engine so one verb yields a fully queryable stack. Paths go
        # absolute — the daemon child's cwd is not this shell's.
        deploy = ["deploy", "--ip", args.ip, "--reuse-port"]
        if args.variant:
            deploy += ["--variant", os.path.abspath(args.variant)]
        if args.engine_factory:
            deploy += ["--engine-factory", args.engine_factory]
        if args.engine_dir:
            deploy += ["--engine-dir", os.path.abspath(args.engine_dir)]
        if getattr(args, "variants", None):
            deploy += [
                "--variants",
                ",".join(
                    os.path.abspath(p.strip())
                    for p in args.variants.split(",")
                    if p.strip()
                ),
            ]
        replicas = int(getattr(args, "replicas", 0) or 0)
        if replicas > 0:
            # scale-out: N engine replicas on consecutive ports, each a
            # first-class supervised service (engine-0..engine-N-1),
            # fronted by the router tier on --router-port
            router_host = args.ip if args.ip != "0.0.0.0" else "127.0.0.1"
            route = ["route", "--ip", args.ip,
                     "--port", str(args.router_port), "--reuse-port"]
            for i in range(replicas):
                port = args.engine_port + i
                plan.append((
                    f"engine-{i}",
                    deploy + ["--port", str(port)],
                    port,
                ))
                route += ["--replica", f"engine-{i}={router_host}:{port}"]
            plan.append(("router", route, args.router_port))
        else:
            plan.append(
                ("engine", deploy + ["--port", str(args.engine_port)],
                 args.engine_port)
            )

    if getattr(args, "supervise", False):
        return _run_supervised(args, plan)

    started: list[str] = []
    for name, argv, port in plan:
        host = args.ip if args.ip != "0.0.0.0" else "127.0.0.1"
        try:
            pid = daemon.start_service(name, argv, host, port)
        except RuntimeError as e:
            print(f"start-all: {e}", file=sys.stderr)
            for prev in reversed(started):  # roll back partial bring-up
                daemon.stop_service(prev)
            return 1
        started.append(name)
        print(f"{name}: up on port {port} (pid {pid})")
    print(f"Run dir: {daemon.run_dir()}")
    return 0


def _parse_duration(value: str) -> float:
    """``300`` / ``300s`` / ``15m`` / ``1h`` -> seconds."""
    s = str(value).strip().lower()
    mult = 1.0
    if s.endswith(("s", "m", "h")):
        mult = {"s": 1.0, "m": 60.0, "h": 3600.0}[s[-1]]
        s = s[:-1]
    try:
        out = float(s) * mult
    except ValueError:
        raise ValueError(f"bad duration {value!r} (want e.g. 300s, 15m, 1h)")
    if out <= 0:
        raise ValueError(f"duration must be positive, got {value!r}")
    return out


def _retrain_scheduler(args, plan, host):
    """Build the RetrainScheduler for ``--retrain-every``, or None."""
    from predictionio_tpu.server import supervisor as sup_mod

    raw = getattr(args, "retrain_every", None)
    if not raw:
        return None
    interval = _parse_duration(raw)
    engine_ports = [
        port for name, _argv, port in plan
        if name == "engine" or name.startswith("engine-")
    ]
    if not engine_ports:
        raise ValueError(
            "--retrain-every needs a deployed engine "
            "(--variant/--engine-factory/--engine-dir)"
        )
    train_argv = ["train", "--warm-start"]
    if args.variant:
        train_argv += ["--variant", os.path.abspath(args.variant)]
    if args.engine_factory:
        train_argv += ["--engine-factory", args.engine_factory]
    if args.engine_dir:
        train_argv += ["--engine-dir", os.path.abspath(args.engine_dir)]
    if getattr(args, "retrain_tol", None):
        train_argv += ["--tol", str(args.retrain_tol)]
    floor = getattr(args, "retrain_floor", None)
    return sup_mod.RetrainScheduler(
        interval,
        train_argv=train_argv,
        engine_ports=engine_ports,
        host=host,
        slo_driven=bool(getattr(args, "retrain_slo", False)),
        floor_s=_parse_duration(floor) if floor else None,
    )


def _run_supervised(args, plan) -> int:
    """``pio start-all --supervise`` / ``pio supervise``: run the fleet
    under the self-healing supervisor in the FOREGROUND (the supervisor
    is the thing an init system or terminal owns; its children are the
    detached daemons). SIGTERM/SIGINT request an orderly reverse-order
    stop — each child gets a drain-grace SIGTERM first."""
    import signal

    from predictionio_tpu.cli import daemon
    from predictionio_tpu.server import supervisor as sup_mod

    host = args.ip if args.ip != "0.0.0.0" else "127.0.0.1"
    specs = [
        sup_mod.ServiceSpec(name=name, argv=argv, host=host, port=port)
        for name, argv, port in plan
    ]
    try:
        retrain = _retrain_scheduler(args, plan, host)
    except ValueError as e:
        print(f"supervise: {e}", file=sys.stderr)
        return 1
    sup = sup_mod.Supervisor(specs, retrain=retrain)

    def _request_stop(signum, _frame):
        sup.request_stop()

    signal.signal(signal.SIGTERM, _request_stop)
    signal.signal(signal.SIGINT, _request_stop)

    stats = None
    stats_port = getattr(args, "supervise_port", 0) or 0
    if stats_port:
        stats = sup_mod.stats_app(sup, host=host, port=stats_port)
        stats.start(background=True)
        print(f"supervisor: stats on http://{host}:{stats_port}/stats.json")
    try:
        sup.start_all()
    except Exception as e:
        print(f"supervise: {e}", file=sys.stderr)
        sup.stop()
        if stats is not None:
            stats.stop()
        return 1
    for name, doc in sup.services().items():
        print(
            f"{name}: {doc['state']} on port {doc['port']} (pid {doc['pid']})"
        )
    if retrain is not None:
        mode = "SLO-adaptive" if retrain.slo_driven else "fixed"
        print(
            f"retrain: every {retrain.base_interval_s:.0f}s ({mode}) -> "
            f"{len(retrain.engine_ports)} engine(s)"
        )
    print(f"Run dir: {daemon.run_dir()} (supervised; ^C or SIGTERM to stop)")
    try:
        sup.run()
    finally:
        if stats is not None:
            stats.stop()
    return 0


def cmd_rolling_restart(args) -> int:
    """``pio rolling-restart <service>``: zero-downtime replacement of a
    recorded daemon — new instance overlaps on the same port via
    SO_REUSEPORT, must pass /readyz, then the old one drains out.

    ``pio rolling-restart engineserver`` walks the whole engine replica
    set (``engine`` and every ``engine-<i>``) ONE replica at a time: a
    router tier in front keeps serving off the others while each rolls,
    so the fleet upgrades with zero failed requests."""
    import re

    from predictionio_tpu.cli import daemon

    if args.service in ("engineserver", "engines"):
        names = [
            n for n in daemon.known_services()
            if n == "engine" or re.fullmatch(r"engine-\d+", n)
        ]
        if not names:
            print(
                "rolling-restart: no running engine replicas recorded",
                file=sys.stderr,
            )
            return 1
    else:
        names = [args.service]
    for name in names:
        try:
            info = daemon.rolling_restart(name, wait=args.wait)
        except RuntimeError as e:
            print(f"rolling-restart: {e}", file=sys.stderr)
            return 1
        print(
            f"{info['service']}: rolled pid {info['old_pid']} -> "
            f"{info['new_pid']} on port {info['port']} "
            f"(instance {info['instance']})"
        )
    return 0


def cmd_stop_all(args) -> int:
    """Tear down everything start-all recorded (reference bin/pio-stop-all)."""
    from predictionio_tpu.cli import daemon

    stopped = 0
    # reverse bring-up order: engine first, event server last
    for name in reversed(daemon.known_services()):
        if daemon.stop_service(name):
            print(f"{name}: stopped")
            stopped += 1
    if not stopped:
        print("Nothing to stop.")
    return 0


def cmd_unregister(args) -> int:
    # engine registration is implicit for Python factories (import-by-name,
    # no registry rows to delete) — no-op parity with Console.scala's
    # unregister verb
    print("Nothing to unregister: Python engine factories are resolved by import.")
    return 0


def cmd_shell(args) -> int:
    """Interactive REPL with the storage singleton wired (the pio-shell
    analog, bin/pio-shell — a Spark shell with pio jars preloaded)."""
    import code

    from predictionio_tpu.data import store
    from predictionio_tpu.data.storage import get_storage

    banner = (
        "predictionio-tpu shell\n"
        "  storage  -> configured Storage singleton\n"
        "  store    -> event-store facade (find/aggregate_properties)\n"
    )
    code.interact(
        banner=banner, local={"storage": get_storage(), "store": store}
    )
    return 0


def cmd_template(args) -> int:
    # deprecated no-op in the reference too (Console.scala template verbs)
    print(
        "The template command is deprecated; engine templates are Python "
        "packages — copy one from predictionio_tpu.models as a starting point."
    )
    return 0


def cmd_upgrade(args) -> int:
    # disabled in the reference too (Console.scala: "Upgrade is not
    # available"); storage-format migrations here go through
    # `pio export` + `pio import`
    print(
        "Upgrade is not available; migrate data between storage formats "
        "with `pio export` and `pio import`."
    )
    return 0


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="pio", description="PredictionIO-TPU console")
    sub = p.add_subparsers(dest="command")

    sub.add_parser("version").set_defaults(fn=cmd_version)
    st = sub.add_parser("status")
    st.add_argument(
        "--json",
        action="store_true",
        help="one compact JSON line merging /metrics + /stats.json "
        "from running daemons",
    )
    st.set_defaults(fn=cmd_status)

    bc = sub.add_parser("bench")
    bc.add_argument(
        "--compare", nargs="+", metavar="SUMMARY.json",
        help="diff two bench summary JSONs (OLD [NEW]; NEW defaults to "
        "the newest BENCH_r*.json in the cwd) and exit non-zero on any "
        ">tolerance regression",
    )
    bc.add_argument(
        "--tolerance", type=float, default=0.10,
        help="relative change treated as a regression (default 0.10)",
    )
    bc.set_defaults(fn=cmd_bench)

    pr = sub.add_parser("profile")
    pr.add_argument(
        "--seconds", type=float, default=5.0,
        help="capture window (clamped to 120s)",
    )
    pr.add_argument(
        "--url",
        help="POST /profile on a running daemon (e.g. "
        "http://127.0.0.1:8000) instead of capturing in-process",
    )
    pr.add_argument(
        "--out", help="trace output directory (default: a timestamped "
        "dir under $PIO_RUN_DIR/profiles)",
    )
    pr.add_argument(
        "--python-tracer", action="store_true",
        help="also record every Python call (slows the traced process; "
        "off by default: the host plane then holds the program's own "
        "regions and the runtime's events)",
    )
    pr.set_defaults(fn=cmd_profile)

    ly = sub.add_parser(
        "layers",
        help="where a query's time goes, from two /metrics scrapes",
    )
    ly.add_argument(
        "--url", help="scrape this server twice (e.g. http://127.0.0.1:8000)",
    )
    ly.add_argument(
        "--seconds", type=float, default=10.0,
        help="seconds between the two scrapes of --url (default 10)",
    )
    ly.add_argument(
        "--windows",
        help="a gen.windows.json kept by benchmark/run.py --save-logs: its "
        "measured windows' two scrapes",
    )
    ly.add_argument("--json", action="store_true", help="one JSON line")
    ly.set_defaults(fn=cmd_layers)

    inc = sub.add_parser("incidents")
    incsub = inc.add_subparsers(dest="incidents_command")
    incl = incsub.add_parser("list")
    incl.add_argument(
        "--json", action="store_true",
        help="machine-readable bundle listing",
    )
    incs = incsub.add_parser("show")
    incs.add_argument("name", help="bundle directory name (see list)")
    incs.add_argument(
        "--file",
        help="print one bundle file verbatim (e.g. slo.json, traces.json)",
    )
    incp = incsub.add_parser("prune")
    incp.add_argument(
        "--keep", type=int, default=None,
        help="bundles to retain (default $PIO_INCIDENT_KEEP or 20)",
    )
    inc.set_defaults(fn=cmd_incidents)

    tp = sub.add_parser("top")
    tp.add_argument(
        "--once", action="store_true",
        help="print one frame and exit (scripting/tests)",
    )
    tp.add_argument(
        "--interval", type=float, default=2.0,
        help="refresh interval in seconds (default 2)",
    )
    tp.add_argument(
        "--url", action="append",
        help="poll this base URL instead of discovering live daemons "
        "(repeatable, e.g. http://127.0.0.1:8000)",
    )
    tp.set_defaults(fn=cmd_top)

    b = sub.add_parser("build")
    b.add_argument("--engine-factory")
    b.add_argument("--variant")
    b.add_argument("--engine-dir")
    b.set_defaults(fn=cmd_build)

    a = sub.add_parser("app")
    asub = a.add_subparsers(dest="app_command")
    for name in ("new", "show", "delete", "data-delete"):
        ap = asub.add_parser(name)
        ap.add_argument("name")
        if name == "new":
            ap.add_argument("--id", type=int, default=0)
            ap.add_argument("--description")
            ap.add_argument("--access-key", default="")
        if name == "data-delete":
            ap.add_argument("--channel")
    asub.add_parser("list")
    for name in ("channel-new", "channel-delete"):
        cp = asub.add_parser(name)
        cp.add_argument("name")
        cp.add_argument("channel")
    a.set_defaults(fn=cmd_app)

    ak = sub.add_parser("accesskey")
    aksub = ak.add_subparsers(dest="ak_command")
    akn = aksub.add_parser("new")
    akn.add_argument("app_name")
    akn.add_argument("--event", action="append")
    akl = aksub.add_parser("list")
    akl.add_argument("app_name", nargs="?")
    akd = aksub.add_parser("delete")
    akd.add_argument("key")
    ak.set_defaults(fn=cmd_accesskey)

    t = sub.add_parser("train")
    t.add_argument("--engine-factory")
    t.add_argument("--variant")
    t.add_argument("--engine-dir")
    t.add_argument("--batch", default="")
    t.add_argument("--verbose", action="count", default=0)
    t.add_argument("--skip-sanity-check", action="store_true")
    t.add_argument("--stop-after-read", action="store_true")
    t.add_argument("--stop-after-prepare", action="store_true")
    t.add_argument("--profile-dir", help="write a JAX profiler trace here")
    t.add_argument(
        "--profile",
        dest="profile_dir",
        metavar="DIR",
        help="alias for --profile-dir: wrap the training loop in "
        "jax.profiler.trace and write the trace to DIR",
    )
    t.add_argument(
        "--mesh",
        help="device-mesh axes for the training run, e.g. 'data=8' or "
        "'data=4,model=2' (-1 once absorbs remaining devices)",
    )
    t.add_argument(
        "--multihost", action="store_true",
        help="join a multi-host JAX runtime before training: run the "
        "same command on every host (TPU pod slices auto-detect; "
        "elsewhere pass --coordinator/--num-processes/--process-id or "
        "the PIO_COORDINATOR_ADDRESS/PIO_NUM_PROCESSES/PIO_PROCESS_ID "
        "env vars); --mesh axes then span the global device set",
    )
    t.add_argument("--coordinator", help="host:port of process 0")
    t.add_argument("--num-processes", type=int)
    t.add_argument("--process-id", type=int)
    t.add_argument(
        "--no-columnar-cache", action="store_true",
        help="read training events from the row logs instead of the "
        "columnar segment cache (sets PIO_COLUMNAR_CACHE=0 for this run)",
    )
    t.add_argument(
        "--checkpoint-every", type=int, metavar="N",
        help="snapshot the ALS factor carry atomically every N "
        "iterations so a killed run can resume (sets "
        "PIO_CHECKPOINT_EVERY; see docs/robustness.md)",
    )
    t.add_argument(
        "--resume", action="store_true",
        help="restore the latest checkpoint whose data fingerprint "
        "matches this run and continue bit-identically from its "
        "iteration (sets PIO_RESUME=1; no-op when none matches)",
    )
    t.add_argument(
        "--checkpoint-dir", metavar="DIR",
        help="where checkpoints live (sets PIO_CHECKPOINT_DIR; "
        "default ~/.pio_tpu/checkpoints)",
    )
    t.add_argument(
        "--warm-start", action="store_true",
        help="seed the solve from the latest COMPLETED instance's "
        "model instead of random factors (sets PIO_WARM_START=1; an "
        "incompatible previous model — changed rank or storage dtype — "
        "falls back to cold start with a warning; docs/operations.md "
        "hot-retrain runbook)",
    )
    t.add_argument(
        "--tol", type=float, metavar="T",
        help="stop iterating when the per-segment train RMSE improves "
        "by less than T (sets PIO_TOL; rides the checkpoint-segmented "
        "dispatch, so combine with --warm-start to turn a good starting "
        "point into fewer iterations)",
    )
    t.add_argument(
        "--no-prep-cache", action="store_true",
        help="skip the packed-prep cache and rebuild the training "
        "representation from the event log (sets PIO_PREP_CACHE=0; "
        "docs/storage.md \"Packed-prep cache\")",
    )
    t.add_argument(
        "--prep-cache-dir", metavar="DIR",
        help="where packed-prep cache entries live (sets "
        "PIO_PREP_CACHE_DIR; default ~/.pio_tpu/prep_cache)",
    )
    t.set_defaults(fn=cmd_train)

    ev = sub.add_parser("eval")
    ev.add_argument("evaluation_class")
    ev.add_argument("engine_params_generator_class", nargs="?")
    ev.add_argument("--batch", default="")
    ev.set_defaults(fn=cmd_eval)

    d = sub.add_parser("deploy")
    d.add_argument("--engine-factory")
    d.add_argument("--variant")
    d.add_argument("--engine-dir")
    d.add_argument("--engine-instance-id")
    d.add_argument("--ip", default="0.0.0.0")
    d.add_argument("--port", type=int, default=8000)
    d.add_argument("--feedback", action="store_true")
    d.add_argument("--event-server-ip", default="0.0.0.0")
    d.add_argument("--event-server-port", type=int, default=7070)
    d.add_argument("--accesskey")
    d.add_argument("--server-config", help="server.conf path (key auth / SSL)")
    d.add_argument(
        "--log-url",
        help="POST serving errors to this URL (reference --log-url)",
    )
    d.add_argument(
        "--log-prefix", help="prefix prepended to remote log payloads"
    )
    d.add_argument(
        "--batch-window-ms", type=float, default=0.0,
        help="micro-batch concurrent queries for up to this many ms into "
        "one batched device call (0 = per-request serving); amortizes "
        "per-call dispatch on TPU attachments",
    )
    d.add_argument(
        "--workers", type=int, default=1,
        help="run this many server PROCESSES sharing the port via "
        "SO_REUSEPORT (the kernel balances accepts); scales serving "
        "past one interpreter's GIL. Needs an explicit --port.",
    )
    d.add_argument(
        "--reuse-port", action="store_true",
        help="bind with SO_REUSEPORT (set automatically for workers; "
        "useful when an external supervisor runs the processes)",
    )
    d.add_argument(
        "--query-cache-mb", type=float, default=0.0, metavar="MB",
        help="cache preserialized query responses in this many MB, "
        "invalidated exactly on every /reload and speed-layer patch via "
        "the epoch fence (0 = disabled); engines opt out per query via "
        "cacheable_query — see docs/serving.md",
    )
    d.add_argument(
        "--mesh", metavar="data=N",
        help="the devices a `sharded_serving` model splits its item rows "
        "over (one process owns them all); default: every device visible",
    )
    d.add_argument(
        "--no-warmup", action="store_true",
        help="skip the deploy-time throwaway predict that pre-compiles "
        "the scoring programs before the port binds",
    )
    d.add_argument(
        "--realtime", type=float, default=0.0, metavar="SECONDS",
        help="enable the speed layer: tail the app's event stream every "
        "SECONDS and fold new rating events into the live model between "
        "retrains (0 = batch-only serving); see docs/realtime.md",
    )
    d.add_argument(
        "--realtime-cursor",
        help="durable tailer cursor file (default: "
        "~/.pio_tpu/realtime/cursor_<engine>_<port>.json)",
    )
    d.add_argument(
        "--variants", metavar="A.JSON,B.JSON",
        help="mount additional trained engine variants in THIS process, "
        "routed by path prefix (/<name>/queries.json, name = file "
        "basename minus .json) or the X-PIO-Variant header; each mount "
        "keeps its own epoch fence, /reload, and speed layer while "
        "sharing the HTTP front end, micro-batcher, and jit cache — "
        "see docs/serving.md",
    )
    d.set_defaults(fn=cmd_deploy)

    u = sub.add_parser("undeploy")
    u.add_argument("--ip", default="0.0.0.0")
    u.add_argument("--port", type=int, default=8000)
    u.set_defaults(fn=cmd_undeploy)

    es = sub.add_parser("eventserver")
    es.add_argument("--ip", default="0.0.0.0")
    es.add_argument("--port", type=int, default=7070)
    es.add_argument("--stats", action="store_true")
    es.add_argument(
        "--workers", type=int, default=1,
        help="run this many ingest PROCESSES sharing the port via "
        "SO_REUSEPORT (storage appends are cross-process safe); needs "
        "an explicit --port",
    )
    es.add_argument("--reuse-port", action="store_true")
    es.set_defaults(fn=cmd_eventserver)

    ad = sub.add_parser("adminserver")
    ad.add_argument("--ip", default="0.0.0.0")
    ad.add_argument("--port", type=int, default=7071)
    ad.set_defaults(fn=cmd_adminserver)

    ss = sub.add_parser("storageserver")
    ss.add_argument("--ip", default="0.0.0.0")
    ss.add_argument("--port", type=int, default=7072)
    ss.add_argument("--auth-key", help="shared key clients must present")
    ss.add_argument("--server-config", help="server.conf path (TLS)")
    ss.set_defaults(fn=cmd_storageserver)

    db = sub.add_parser("dashboard")
    db.add_argument("--ip", default="0.0.0.0")
    db.add_argument("--port", type=int, default=9000)
    db.add_argument("--server-config", help="server.conf path (key auth / SSL)")
    db.set_defaults(fn=cmd_dashboard)

    rt = sub.add_parser(
        "route",
        help="scale-out router tier over a set of engine replicas",
    )
    rt.add_argument("--ip", default="0.0.0.0")
    rt.add_argument("--port", type=int, default=8100)
    rt.add_argument(
        "--replica", action="append", metavar="[NAME=]HOST:PORT",
        help="one backend engine replica (repeatable)",
    )
    rt.add_argument(
        "--replicas", type=int, default=0, metavar="N",
        help="route to N replicas on consecutive ports starting at "
        "--engine-port (names engine-0..engine-N-1, matching what "
        "`pio start-all --replicas N` spawns)",
    )
    rt.add_argument("--engine-host", default="127.0.0.1")
    rt.add_argument("--engine-port", type=int, default=8000)
    rt.add_argument(
        "--probe-interval", type=float, default=0.0, metavar="SECONDS",
        help="replica /readyz probe interval (default "
        "PIO_ROUTER_PROBE_INTERVAL_S or 1.0)",
    )
    rt.add_argument(
        "--no-hedge", action="store_true",
        help="disable hedged requests (equivalent to PIO_ROUTER_HEDGE=0)",
    )
    rt.add_argument("--reuse-port", action="store_true")
    rt.set_defaults(fn=cmd_route)

    ex = sub.add_parser("export")
    ex.add_argument("--appid-or-name", required=True)
    ex.add_argument("--output", required=True)
    ex.add_argument("--channel")
    ex.set_defaults(fn=cmd_export)

    im = sub.add_parser("import")
    im.add_argument("--appid-or-name", required=True)
    im.add_argument("--input", required=True)
    im.add_argument("--channel")
    im.add_argument(
        "--jobs", type=int, default=None,
        help="decode/append worker threads for the bulk import "
        "(default: PIO_IMPORT_JOBS env or min(4, cpus); 1 = sequential)",
    )
    im.add_argument(
        "--warm-cache", action="store_true",
        help="build the columnar segment cache right after the import "
        "so the first train reads mmap'ed column blocks",
    )
    im.add_argument(
        "--http", metavar="URL", default=None,
        help="import over the wire: POST the file as binary frames to "
        "URL/batch/events.bin on a live event server instead of writing "
        "storage directly (requires --access-key)",
    )
    im.add_argument(
        "--access-key", default=None,
        help="access key for --http mode (the target app's key)",
    )
    im.set_defaults(fn=cmd_import)

    tpl = sub.add_parser("template")
    tpl.add_argument("rest", nargs="*")
    tpl.set_defaults(fn=cmd_template)

    up = sub.add_parser("upgrade")
    up.add_argument("rest", nargs="*")
    up.set_defaults(fn=cmd_upgrade)

    r = sub.add_parser("run")
    r.add_argument("main_class", help="dotted module path, or module:function")
    r.add_argument("args", nargs="*")
    r.set_defaults(fn=cmd_run)

    def _fleet_args(parser) -> None:
        parser.add_argument("--ip", default="0.0.0.0")
        parser.add_argument("--event-port", type=int, default=7070)
        parser.add_argument("--dashboard-port", type=int, default=9000)
        parser.add_argument("--admin-port", type=int, default=7071)
        parser.add_argument("--engine-port", type=int, default=8000)
        parser.add_argument("--stats", action="store_true")
        parser.add_argument("--no-dashboard", action="store_true")
        parser.add_argument("--no-adminserver", action="store_true")
        parser.add_argument("--variant", help="also deploy this engine variant")
        parser.add_argument(
            "--engine-factory", help="also deploy this engine factory"
        )
        parser.add_argument(
            "--engine-dir", help="also deploy the engine in this dir"
        )
        parser.add_argument(
            "--variants", metavar="A.JSON,B.JSON",
            help="co-mount these trained engine variants in the "
            "deployed engine process (see pio deploy --variants)",
        )
        parser.add_argument(
            "--supervise-port", type=int, default=0,
            help="with --supervise: serve supervisor /stats.json and "
            "/metrics on this port",
        )
        parser.add_argument(
            "--replicas", type=int, default=0, metavar="N",
            help="deploy the engine as N replicas on consecutive ports "
            "starting at --engine-port, fronted by the `pio route` "
            "router tier on --router-port (see docs/operations.md "
            "\"Scale-out serving\")",
        )
        parser.add_argument(
            "--router-port", type=int, default=8100,
            help="router-tier port used with --replicas",
        )
        parser.add_argument(
            "--retrain-every", metavar="DUR", default=None,
            help="with --supervise: run a warm `pio train` + engine "
            "/reload on this cadence (e.g. 300s, 15m, 1h; see "
            "docs/operations.md \"Continuous retraining\")",
        )
        parser.add_argument(
            "--retrain-slo", action="store_true",
            help="adapt the retrain cadence to the serving.freshness "
            "SLO burn rate (halve while burning, decay back when ok)",
        )
        parser.add_argument(
            "--retrain-floor", metavar="DUR", default=None,
            help="shortest adaptive retrain interval "
            "(default: --retrain-every / 8)",
        )
        parser.add_argument(
            "--retrain-tol", type=float, default=None, metavar="T",
            help="pass --tol T to the scheduled warm trains "
            "(early-stop on an RMSE plateau)",
        )

    sa = sub.add_parser("start-all")
    _fleet_args(sa)
    sa.add_argument(
        "--supervise", action="store_true",
        help="stay in the foreground and restart crashed services "
        "with backoff (see docs/operations.md)",
    )
    sa.set_defaults(fn=cmd_start_all)

    sv = sub.add_parser(
        "supervise", help="start-all under the self-healing supervisor"
    )
    _fleet_args(sv)
    sv.set_defaults(fn=cmd_start_all, supervise=True)

    rr = sub.add_parser(
        "rolling-restart",
        help="zero-downtime replacement of one recorded service",
    )
    rr.add_argument("service", help="a service name from `pio status`")
    rr.add_argument(
        "--wait", type=float, default=90.0,
        help="seconds to wait for the replacement's /readyz (default 90)",
    )
    rr.set_defaults(fn=cmd_rolling_restart)

    ca = sub.add_parser(
        "cache", help="packed-prep cache lifecycle (list / evict / prune)"
    )
    casub = ca.add_subparsers(dest="cache_verb")
    cl = casub.add_parser("list", help="entries, LRU order, sizes")
    cl.add_argument("--json", action="store_true")
    ce = casub.add_parser("evict", help="drop one entry by name")
    ce.add_argument("entry", help="entry name from `pio cache list`")
    cp = casub.add_parser(
        "prune", help="sweep tmp husks + enforce the size budget"
    )
    cp.add_argument(
        "--max-mb", type=float, default=None,
        help="override PIO_PREP_CACHE_MAX_MB for this prune",
    )
    cp.add_argument("--json", action="store_true")
    ca.set_defaults(fn=cmd_cache)

    sub.add_parser("stop-all").set_defaults(fn=cmd_stop_all)

    sub.add_parser("unregister").set_defaults(fn=cmd_unregister)
    sub.add_parser("shell").set_defaults(fn=cmd_shell)

    return p


def main(argv: list[str] | None = None) -> int:
    parser = build_parser()
    raw = sys.argv[1:] if argv is None else list(argv)
    if raw[:1] == ["help"]:
        parser.print_help()
        return 0
    args = parser.parse_args(raw)
    if not getattr(args, "fn", None):
        parser.print_help()
        return 1
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
