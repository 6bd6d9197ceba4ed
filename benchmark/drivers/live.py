"""The live driver: the quantized serving driver's run (the int8 model written
in segments, one `pio deploy` child that owns the chip, one load-generator
child, the plain reference once the window has closed) for a deployment whose
model CHANGES while it is served: `pio deploy --realtime`, a `pio eventserver`
child beside it, `rate` events POSTed through it all through the window and a
refresh query after each — so set-up also fills the event store with the
writers' histories (write_live.py), warms every (B, K) shape of the fold's
programs with bursts of events, and `correct` holds every checked answer to
the row its user had AS FOLDED when the query was sent (reference_foldin.py,
the prefix rule), every acknowledged event to the store, and the resident
parts to what they were when the window opened.

Every wait is bounded: readiness of both servers, each warm-up burst's fold,
each child, each request of the generator (``timeout_s``), the
acknowledgement of each event (``ack_limit_s``). A program that cannot keep up
ends as a BenchFailure or as a result line that is not correct, not as a hang.

Everything cell-specific comes from the configuration file and the traffic
file; the phases, the readiness wait, the window's readings, the device block
and the trace reduction are the serving driver's own, unchanged.
"""

from __future__ import annotations

import json
import os
import sqlite3
import time

import numpy as np

import factors
import live_data
import reference
import reference_foldin as ref
import reference_int8
import stats
import traffic as traffic_mod
from drivers.common import BenchFailure, Run, free_port, http_call, reduce_trace
from drivers.serve import _device, _held, _parse_answer, _phases, _wait_ready, window_raw
from drivers.sharded import memory_by_device

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
now = time.perf_counter  # the generator's clock too: CLOCK_MONOTONIC


def _stats(port: int) -> dict:
    return json.loads(http_call(port, "GET", "/stats.json", timeout=30.0)[1])


def warm_up(run_: Run, server, port: int, ev_port: int, key: str, bursts,
            limit_s: float) -> list[dict]:
    """Send each warm-up burst and wait for the speed layer to fold it: every
    (B, K) shape of the fold's programs compiles now. Returns the events as
    the generator logs its own."""
    log = []
    folded = (_stats(port).get("realtime") or {}).get("events_folded", 0)
    for burst in bursts:
        for user, item, star in burst:
            t0 = now()
            status, body = http_call(ev_port, "POST", f"/events.json?accessKey={key}",
                                     live_data.event_body(user, item, star), timeout=30.0)
            if status != 201:
                raise BenchFailure(f"warm-up event -> {status} {body[:200]!r}")
            log.append({"kind": live_data.WRITER, "user": user, "item": item, "stars": star,
                        "phase": -1, "posted": t0, "acked": now(), "status": status,
                        "refresh": -1, "event_id": json.loads(body).get("eventId")})
        folded += len(burst)
        t_end = now() + limit_s
        while True:
            if server.poll() is not None:
                raise BenchFailure(f"server exited {server.returncode} while folding the "
                                   f"warm-up events\n{run_.log_tail('server.log')}")
            rt = _stats(port).get("realtime") or {}
            if rt.get("events_folded", 0) >= folded and rt.get("events_behind") == 0:
                break
            if now() > t_end:
                raise BenchFailure(f"a warm-up burst of {len(burst)} events was not folded in "
                                   f"{limit_s:.0f} s: realtime = {rt}\n{run_.log_tail('server.log')}")
            time.sleep(0.1)
    return log


class Timeline:
    """What the reference knows of every user the run touched: the history
    the store held before the watermark and, in order, every event posted
    after it with its times on the generator's clock."""

    def __init__(self, cfg: dict, seed: int, events: list[dict], guarantee_s: float):
        self.cfg, self.seed, self.guarantee_s = cfg, seed, guarantee_s
        self.dep = live_data.Deployment(cfg, seed)
        self.reg = float(cfg["variant"]["algorithms"][0]["params"]["lambda_"])
        self.by_user: dict[int, list[dict]] = {}
        for e in events:
            if e["posted"] is not None:
                self.by_user.setdefault(int(e["user"]), []).append(e)
        self.item_rows: dict[int, np.ndarray] = {}

    def prefixes(self, user: int, sent: float, answered: float, stale: bool = False):
        """The histories [(item, rating), ...] the answer to a query of
        ``user`` may rest on — one per allowed count of their events — or
        [None] for the row the model was written with (no event in). With
        ``stale`` the one history WITHOUT the last event that had to be in."""
        evs = self.by_user.get(user, [])
        acked = [e["acked"] if e["status"] == 201 else np.inf for e in evs]
        must, may = ref.required_and_allowed(
            acked, [e["posted"] for e in evs], sent, answered, self.guarantee_s)
        counts = [must - 1] if stale else range(must, may + 1)
        base = list(zip(*(a.tolist() for a in self.dep.history(user))))
        return [None if c <= 0 else base + [(e["item"], e["stars"]) for e in evs[:c]]
                for c in counts], must

    def load_items(self, histories) -> None:
        want = sorted({int(i) for h in histories if h for i, _ in h
                       if i < self.cfg["num_items"]} - set(self.item_rows))
        if want:
            rows = ref.item_rows(self.seed, self.cfg["num_items"], self.cfg["rank"], want)
            self.item_rows.update(zip(want, rows))

    def solved(self, history):
        """The f32 row of ``history``, or None where nothing can be solved."""
        items, ratings = ref.rated(history, self.cfg["num_items"])
        if not len(items):
            return None
        return ref.solve(np.stack([self.item_rows[int(i)] for i in items]), ratings, self.reg)

    def stored(self, users) -> np.ndarray:
        return reference_int8.table_rows(
            self.seed, factors.STREAM_USER_FACTORS, self.cfg["num_users"], self.cfg["rank"],
            users, True)


def check_answers(cfg: dict, mix: dict, seed: int, res, bodies, idx, events,
                  control: bool) -> tuple[list[dict], int]:
    """Every answer of the window for shape; a seeded sample of them and EVERY
    refresh answer against the plain reference over the whole dequantized
    catalog, the user's row taken as folded at the query's send time.
    Returns (numbers compared with their limits, answers malformed)."""
    k, lim = int(mix["num"]), cfg["limits"]
    tl = Timeline(cfg, seed, events, float(mix["events"]["guarantee_s"]))
    refresh_of = {e["refresh"]: e for e in events if e.get("refresh", -1) >= 0}
    users, sent, done = res["user"], res["sent"], res["done"]
    malformed, parsed = 0, {}
    for i in idx.tolist():
        items, scores = _parse_answer(bodies[i])
        if items is None:
            malformed += 1
        elif reference.well_formed(items, scores, k) is None or (
                not items and int(users[i]) >= cfg["num_users"]):
            parsed[i] = (items, scores)  # an empty answer: judged by the prefix rule
        else:
            malformed += 1
    plain = np.asarray(sorted(i for i in parsed if i not in refresh_of), dtype=np.int64)
    pick = plain[traffic_mod.sample_indices(seed, len(plain), int(cfg["check_sample"]))] \
        if len(plain) else plain
    checked = sorted(set(pick.tolist()) | (set(refresh_of) & set(parsed)))

    # the rows each checked answer may rest on: (answer, group, variant, row)
    groups = {}
    for i in checked:
        u = int(users[i])
        hists, must = tl.prefixes(u, float(sent[i]), float(done[i]))
        groups[i] = {"main": hists, "must": must}
        if control and i in refresh_of:
            groups[i]["stale"] = tl.prefixes(u, float(sent[i]), float(done[i]), stale=True)[0]
    tl.load_items([h for g in groups.values() for key in ("main", "stale") for h in g.get(key, ())])
    held = [i for i in checked if int(users[i]) < cfg["num_users"]]
    stored = dict(zip(held, tl.stored(users[held]))) if held else {}
    rows, empty_ok = [], set()
    for i in checked:
        for group in ("main", "stale"):
            for h in groups[i].get(group, ()):
                x = tl.solved(h) if h is not None else None
                if x is None:  # the row the model was written with, or no row at all
                    if i in stored:
                        rows.append((i, group, 0, stored[i]))
                    elif group == "main":
                        empty_ok.add(i)
                    continue
                variants, _, _ = ref.stored_variants(x, "int8", float(cfg["near_tie"]["distance"]))
                rows += [(i, group, v, row) for v, row in enumerate(variants)]
                if control and group == "main":
                    rows.append((i, "unrequantized", 0, x))
    rows = [r for r in rows if parsed[r[0]][0]]  # an empty answer has no score to compare
    checks = []
    compared = {}
    if rows:
        q = np.stack([r[3] for r in rows])
        served = np.asarray([parsed[r[0]][0] for r in rows], np.int64)
        main = np.asarray([r[1] == "main" for r in rows])
        controls = {"bfloat16": (q, "int8", "bfloat16")} if control else {}
        top_s, top_i, own, controlled = ref.scan(
            seed, cfg["num_items"], cfg["rank"], q, k, served=served, controls=controls,
            workers=ref.SCAN_PROCESSES)
        best: dict[tuple, tuple] = {}  # (answer, group) -> (gap, overlap, variant)
        for n, (i, group, v, _) in enumerate(rows):
            items, scores = parsed[i]
            c = reference.compare_answer(items, scores, top_i[n], top_s[n], own[n])
            if (i, group) not in best or c["score_gap"] < best[(i, group)][0]:
                best[(i, group)] = (c["score_gap"], c["overlap"], v)
        compared = {i: b for (i, g), b in best.items() if g == "main"}
        gaps = [b[0] for b in compared.values()]
        overlaps = [b[1] for b in compared.values()]
        fresh = [compared[i][0] for i in compared if i in refresh_of]
        checks = [
            _held("score_gap_max", max(gaps), lim["score_gap_max"]["limit"], True),
            _held("overlap_min", min(overlaps), lim["overlap_min"]["limit"], False),
            _held("overlap_mean_min", float(np.mean(overlaps)),
                  lim["overlap_mean_min"]["limit"], False),
            # the refresh check: every refresh answer rests on its user's
            # whole history, the event it follows included
            _held("refresh_score_gap_max", max(fresh) if fresh else float("inf"),
                  lim["score_gap_max"]["limit"], True),
            _held("near_tie_answers", sum(b[2] > 0 for b in compared.values()),
                  len(compared), True, informs=True),
        ]
        for name, title in (("stale", "control.refresh_score_gap_max(stale)"),
                            ("unrequantized", "control.score_gap_max(unrequantized)")):
            cg = [b[0] for (i, g), b in best.items() if g == name]
            if cg:
                checks.append(_held(title, max(cg), lim["score_gap_max"]["limit"], True,
                                    smallest=min(cg), control=True))
        for name, (c_s, _, exact) in controlled.items():
            cg = np.abs(c_s - exact).max(axis=1)[main]
            checks.append(_held(f"control.score_gap_max({name})", float(cg.max()),
                                lim["score_gap_max"]["limit"], True,
                                smallest=float(cg.min()), control=True))
    # an empty answer is right only for a user with no row yet
    wrong_empty = [i for i in checked if not parsed[i][0] and i not in empty_ok]
    window = set(idx.tolist())
    asked = [e for e in events if e.get("refresh", -1) in window]
    answered = set(compared) | {i for i in checked if not parsed[i][0] and i in empty_ok}
    new_items = [len(parsed[i][0]) for i, e in refresh_of.items()
                 if i in parsed and e["kind"] == live_data.NEW_USER and groups[i]["must"] > 0]
    checks += [
        _held("answers_compared", len(compared), 1, False),
        _held("refresh_answers_checked", len(answered & set(refresh_of)), len(asked), False),
        _held("empty_answers_unexplained", len(wrong_empty), 0, True),
        _held("new_user_answer_items_min", min(new_items) if new_items else k, k, False),
    ]
    return checks, malformed


def stored_events(db_path: str, app_id: int) -> set:
    """The ids in the event table, read from the file itself."""
    conn = sqlite3.connect(f"file:{db_path}?mode=ro", uri=True)
    try:
        return {r[0] for r in conn.execute(f"SELECT id FROM pio_event_{int(app_id)}")}
    finally:
        conn.close()


def run(ctx) -> dict:
    cfg, mix, args = ctx.config, ctx.traffic, ctx.args
    run_ = Run(ctx.root, keep=args.keep)
    ctx.on_close(run_.close)
    ctx.run_dirs = [run_.dir]
    times = {"parent_start": now() - ctx.t0}
    seed = int(args.seed)
    platform = "cpu" if args.dry_run_cpu else "tpu"
    ev = mix["events"]

    # 1. the quantized model and the writers' histories, by a child that
    # touches no device
    spec = {key: cfg[key] for key in ("num_users", "num_items", "rank", "variant")}
    spec.update(seed=seed, variant_label="engine.json",
                segment_bytes=cfg.get("segment_bytes"), config=cfg)
    with open(run_.path("model_spec.json"), "w") as fh:
        json.dump(spec, fh)
    with open(run_.path("engine.json"), "w") as fh:
        json.dump(cfg["variant"], fh)
    server_env = dict(cfg.get("server_env", {}))
    out, wall = run_.run_child(
        "write_live", [os.path.join(BENCH, "write_live.py"), run_.path("model_spec.json")],
        1500.0, run_.server_cores, JAX_PLATFORMS="cpu", **server_env,
    )
    written = json.loads(out.strip().splitlines()[-1])
    times["write_live"] = wall
    times["write_live_parts"] = written["seconds"]
    times["model_bytes"], times["history_events"] = written["bytes"], written["history_events"]

    # 2. the Event Server (no device; the parent's core: the parent sleeps
    # through the window), then the server that owns the chip
    ev_port, port = free_port(), free_port()
    events_proc = run_.spawn(
        ["-m", "predictionio_tpu.cli.main", "eventserver", "--ip", "127.0.0.1",
         "--port", str(ev_port)],
        "eventserver.log", run_.parent_cores, JAX_PLATFORMS="cpu", **server_env,
    )
    if args.dry_run_cpu:
        server_env["JAX_PLATFORMS"] = "cpu"
    t0 = now()
    server = run_.spawn(
        [*cfg.get("server_entry", ["-m", "predictionio_tpu.cli.main"]),
         "deploy", "--variant", "engine.json",
         "--engine-instance-id", written["instance"], "--ip", "127.0.0.1",
         "--port", str(port), "--realtime-cursor", run_.path("cursor.json"),
         *cfg.get("deploy_flags", [])],
        "server.log", run_.server_cores, **server_env,
    )
    _wait_ready(run_, events_proc, ev_port, "eventserver.log", 120.0)
    _wait_ready(run_, server, port, "server.log", 1100.0)
    times["deploy_ready"] = now() - t0
    device = _device(_stats(port))
    if device["platform"] != platform or device["count"] < ctx.cell["chips"]:
        raise BenchFailure(
            f"the server computes on {device['platform']!r} ({device['kind']} x"
            f"{device['count']}), not on {ctx.cell['chips']} TPU chip(s)"
        )

    # 3. the fold's shapes: bursts of warm-up events, each folded before the next
    t0 = now()
    dep = live_data.Deployment(cfg, seed)
    events = warm_up(run_, server, port, ev_port, written["access_key"],
                     dep.warm_bursts(), float(ev["warm_fold_limit_s"]))
    times["warm_folds"] = now() - t0

    # 4. the generator: warm-up bursts of queries, warm-in, the window with
    # its events and refresh queries
    trace_dir = run_.path("trace") if args.trace else None
    phases = _phases(mix, float(args.seconds), trace_dir, ctx.ladder)
    for ph in phases:  # the refresh queries are queries of the rate
        if ph.get("measure"):
            ph["rate_qps"] = ph["rate_qps"] - ev["rate_eps"]
    plan = {
        "host": "127.0.0.1", "port": port, "seed": seed, "num": mix["num"],
        "num_users": cfg["num_users"], "users": mix.get("users", "uniform-distinct"),
        "connections": max(
            [mix.get("connections", 64)] + [int(p.get("clients", 0)) + 8 for p in phases]
        ), "timeout_s": float(mix["timeout_s"]),
        "phases": phases, "out": run_.path("gen"),
        "live": {**ev, "config": cfg, "event_port": ev_port,
                 "access_key": written["access_key"]},
    }
    with open(run_.path("plan.json"), "w") as fh:
        json.dump(plan, fh)
    every_core = os.sched_getaffinity(0)
    os.sched_setaffinity(0, run_.parent_cores)
    t0 = now()
    total = sum(p["seconds"] + p.get("warm_in_s", 0) for p in phases)
    run_.run_child("loadgen", [os.path.join(BENCH, "loadgen_live.py"), run_.path("plan.json")],
                   total + 2.0 * float(mix["timeout_s"]) + 600.0, run_.gen_cores)
    times["loadgen"] = now() - t0
    last = _stats(port)
    device = _device(last)
    retrieval = last.get("retrieval") or {}
    realtime = last.get("realtime") or {}
    resident = {k: int(v) for k, v in (retrieval.get("resident_bytes") or {}).items()}
    times["memory_by_device"] = memory_by_device(last)
    times["resident_bytes"] = resident
    times["model_load"] = retrieval.get("load_seconds")
    times["realtime"] = {k: realtime.get(k) for k in (
        "foldin_epoch", "events_folded", "users_touched", "users_added",
        "cold_start_items", "events_behind", "last_fold_s")}
    try:
        http_call(port, "POST", "/stop")
    except OSError:
        pass
    try:
        server.wait(timeout=60)
    except Exception:
        pass
    run_.stop_all()  # the Event Server too
    os.sched_setaffinity(0, every_core)  # the reference may use them all now

    # 5. the readings
    res = np.load(run_.path("gen.npz"))
    with open(run_.path("gen.bodies.json")) as fh:
        bodies = json.load(fh)
    with open(run_.path("gen.windows.json")) as fh:
        windows = json.load(fh)
    with open(run_.path("gen.events.json")) as fh:
        events += json.load(fh)
    raws = []
    for pi, w in enumerate(windows):
        if not w["measure"]:
            continue
        raw = window_raw(w, res, pi, mix)
        raw["label"] = w["label"]
        raw["setup_s"] = w["t_open"] - ctx.t0
        raw["device"] = device
        # gauges as the closing scrape shows them (a delta of a gauge is 0)
        raw["gauges_close"] = stats.family(
            stats.parse_prometheus(w["metrics_close"]), "pio_model_resident_bytes")
        raws.append((w, raw))
    if ctx.ladder:
        return {"ladder": [r for _, r in raws], "times": times, "device": device}
    w, raw = raws[-1]
    for pi, x in enumerate(windows):  # how long each warm-up phase really took
        sel = res["phase"] == pi
        if not x["measure"] and sel.any():
            times.setdefault("warm_phases", {})[x["label"]] = float(
                np.nanmax(res["done"][sel]) - np.nanmin(res["sent"][sel]))
    sent = [e for e in events if e["phase"] >= 0]
    times["events"] = {
        "posted": len(sent), "acknowledged": sum(e["status"] == 201 for e in sent),
        "refresh_queries": sum(e["refresh"] >= 0 for e in sent),
        "ack_ms_max": max([1e3 * (e["acked"] - e["posted"]) for e in sent
                           if e["status"] == 201], default=None),
        "kinds": [sum(e["kind"] == kind for e in sent) for kind in range(3)],
    }
    raw["times"] = times

    # 6. correct: the plain reference, after the window, outside set-up
    t0 = now()
    checks, malformed = check_answers(
        cfg, mix, seed, res, bodies, raw["indices"], events, bool(args.control))
    times["reference"] = now() - t0
    late_p99 = stats.percentile(raw["late_ms"], 99) if raw["late_ms"] else 0.0
    d = raw["counters_delta"]
    opened = stats.family(stats.parse_prometheus(w["metrics_open"]), "pio_model_resident_bytes")
    closed = stats.parse_prometheus(w["metrics_close"])
    ids = stored_events(run_.store_env["PIO_STORAGE_SOURCES_DB_PATH"], written["app_id"])
    acked = [e for e in events if e["status"] == 201]
    unsure = sum(e["status"] != 201 for e in events if e["posted"] is not None)
    checks += [
        _held("compiles_in_window", raw["compiles_in_window"], 0, True),
        _held("exact_path_queries",
              d.get('pio_retrieval_queries_total{path="exact"}', 0.0), 0, True),
        # model: the int8 pair, ONE table and ONE set of tiles, at the close
        _held("table_bytes_a_value",
              resident.get("table", 0) / (cfg["num_items"] * cfg["rank"]), 1, True),
        _held("table_resident", resident.get("table", 0), 1, False),
        # steady: no part staged again, no growth of what is resident
        _held("restaged_parts", sum(stats.family(closed, "pio_foldin_restage_total").values()),
              0, True),
        _held("resident_bytes_growth", sum(raw["gauges_close"].values()) - sum(opened.values()),
              0, True),
        # durable: every acknowledged event is in the store, and nothing else
        _held("acknowledged_events_missing",
              sum(e.get("event_id") not in ids for e in acked), 0, True),
        _held("stored_events_unaccounted",
              len(ids) - written["history_events"] - len(acked), unsure, True),
        # the speed layer kept up: nothing behind, its breaker closed
        _held("events_behind_at_close",
              realtime["events_behind"] if isinstance(realtime.get("events_behind"), int) else 1e9,
              0, True),
        _held("breaker_closed", float((realtime.get("breaker") or {}).get("state") == "closed"),
              1, False),
        _held("events_acknowledged", times["events"]["acknowledged"],
              times["events"]["posted"], False),
        _held("refresh_queries_sent", times["events"]["refresh_queries"],
              times["events"]["posted"], False),
        # informs, never fails a run (drivers/serve.py; PERF.md section 6)
        _held("gen_late_ms_p99", late_p99, mix["late_limit_ms"], True, informs=True),
    ]
    raw["checks"] = checks
    raw["failed"] = raw["status_failed"] + malformed
    if trace_dir:
        prof = w.get("profile") or {}
        if prof.get("status") != 200:
            raise BenchFailure(f"POST /profile -> {prof}")
        t = reduce_trace(run_, trace_dir)
        t["window_s"] = json.loads(prof["reply"])["seconds"]
        raw["trace"] = t
    return raw
