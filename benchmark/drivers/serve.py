"""The serving driver: seeded factor tables persisted as a model, one `pio
deploy` child that owns the chip, one load-generator child, then the plain
reference over a seeded sample of the answers once the window has closed.

Everything cell-specific comes from the configuration file and the traffic
file; nothing here names a cell.
"""

from __future__ import annotations

import http.client
import json
import os
import time

import numpy as np

import factors
import reference
import stats
import traffic as traffic_mod
from drivers.common import BenchFailure, Run, free_port, http_call, reduce_trace

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _phases(mix: dict, seconds: float, trace_dir: str | None, ladder) -> list[dict]:
    """Warm-up bursts (closed loop, so that every batch bucket the mix can
    reach compiles now), then the measured phase or the ladder's rungs."""
    phases = []
    if mix.get("warm_dispatches"):
        # one client, one query to a dispatch: the first query pays the lazy
        # loads, and whatever the server runs every N-th dispatch compiles now
        phases.append({"label": "warm-dispatches", "loop": "closed", "clients": 1,
                       "seconds": 0.0, "min_requests": int(mix["warm_dispatches"]),
                       "max_requests": int(mix["warm_dispatches"]) + 40})
    # a ladder can reach every batch bucket the server has (16 handler threads)
    for n in ([3, 5, 9, 16] if ladder else mix.get("warm_clients", [])):
        phases.append({
            "label": f"warm-{n}", "loop": "closed", "clients": n,
            "seconds": mix.get("warm_seconds_each", 1.0),
            "min_requests": 4 * n + 4, "max_requests": 300,
        })

    def measured(label, value, secs):
        ph = {"label": label, "loop": mix["loop"], "seconds": secs,
              "warm_in_s": mix.get("warm_in_s", 3.0), "measure": True}
        if mix["loop"] == "open":
            ph["rate_qps"] = value
        else:
            ph["clients"] = int(value)
            ph["max_requests"] = int(mix.get("max_requests", 100000))
        return ph

    key = "rate_qps" if mix["loop"] == "open" else "clients"
    if ladder:
        values, secs = ladder
        for v in values:
            phases.append(measured(f"rung-{v:g}", v, secs))
    else:
        ph = measured("window", mix[key], seconds)
        if trace_dir:
            ph["profile"] = {
                "seconds": min(seconds, mix.get("trace_seconds", 5.0)),
                "out": trace_dir,
            }
        phases.append(ph)
    return phases


def _wait_ready(run: Run, proc, port: int, log: str, limit_s: float) -> float:
    t0 = time.perf_counter()
    while True:  # model load + warmup compile happen before the bind
        if proc.poll() is not None:
            raise BenchFailure(f"server exited {proc.returncode} before serving\n{run.log_tail(log)}")
        if time.perf_counter() - t0 > limit_s:
            raise BenchFailure(f"server not ready after {limit_s:.0f} s\n{run.log_tail(log)}")
        try:
            if http_call(port, "GET", "/readyz", timeout=2.0)[0] == 200:
                return time.perf_counter() - t0
        except (OSError, http.client.HTTPException):
            pass
        time.sleep(0.25)


def _device(stats_body: dict) -> dict:
    devs = stats_body["device"]["devices"]
    if not devs:
        raise BenchFailure("the server reports no device")
    peak = max((d.get("memory") or {}).get("peak", 0) for d in devs)
    return {
        "platform": devs[0]["device"].split(":")[0], "kind": devs[0]["kind"],
        "count": len(devs), "memory_peak_bytes": int(peak),
    }


def _parse_answer(body: str | None):
    try:
        got = json.loads(body)["itemScores"]
        return [int(e["item"][1:]) for e in got], [e["score"] for e in got]
    except (TypeError, ValueError, KeyError, IndexError):
        return None, None


def window_raw(w: dict, res, pi: int, mix: dict) -> dict:
    """The raw readings of one measured phase."""
    sel = np.nonzero(res["phase"] == pi)[0]
    due, sent, done = res["due"][sel], res["sent"][sel], res["done"][sel]
    status = res["status"][sel]
    t_open, t_close = w["t_open"], w["t_close"]
    window_s = t_close - t_open
    if mix["loop"] == "open":
        inw = due >= t_open  # every request due in the window counts
    else:
        inw = (done >= t_open) & (done < t_close)  # completions inside it
    idx = sel[inw]
    before = stats.parse_prometheus(w["metrics_open"])
    after = stats.parse_prometheus(w["metrics_close"])
    delta = stats.delta(before, after)
    compiles = sum(stats.family(delta, "pio_jit_compiles_total").values())
    ok = status[inw] == 200
    return {
        "indices": idx,
        "window_s": window_s,
        "attempted": int(inw.sum()),
        "status_failed": int((~ok).sum()),
        "completed": int(ok.sum()),
        "latencies_ms": ((done[inw] - due[inw]) * 1e3).tolist(),
        "late_ms": ((sent[inw] - due[inw]) * 1e3).tolist(),
        "counters_delta": delta,
        "compiles_in_window": compiles,
    }


def _held(name: str, value: float, limit: float, at_most: bool, **more) -> dict:
    """One number compared, beside its limit."""
    ok = value <= limit if at_most else value >= limit
    return {"name": name, "value": value, "limit": limit, "pass": bool(ok), **more}


def check_answers(cfg: dict, seed: int, users, bodies, idx, k: int,
                  control: bool) -> tuple[list[dict], int]:
    """Every answer of the window for shape; a seeded sample of them against
    the plain reference over the whole catalog. Returns (numbers compared
    with their limits, answers that are malformed)."""
    malformed = 0
    parsed = {}
    for i in idx.tolist():
        items, scores = _parse_answer(bodies[i])
        if items is None or reference.well_formed(items, scores, k) is not None:
            malformed += 1
        else:
            parsed[i] = (items, scores)
    good = np.asarray(sorted(parsed), dtype=np.int64)
    pick = good[traffic_mod.sample_indices(seed, len(good), int(cfg["check_sample"]))] if len(good) else good
    U = factors.user_factors(seed, cfg["num_users"], cfg["rank"])
    V = factors.item_factors(seed, cfg["num_items"], cfg["rank"])
    q = U[users[pick]]
    del U
    lim = cfg["limits"]
    checks = []
    if len(pick):
        top_s, top_i = reference.top_k_scan(q, V, k)
        gaps, overlaps = [], []
        for row, i in enumerate(pick.tolist()):
            items, scores = parsed[i]
            own = reference.score_items(q[row], V, np.asarray(items))
            c = reference.compare_answer(items, scores, top_i[row], top_s[row], own)
            gaps.append(c["score_gap"])
            overlaps.append(c["overlap"])
        checks = [
            _held("score_gap_max", max(gaps), lim["score_gap_max"]["limit"], True),
            _held("overlap_min", min(overlaps), lim["overlap_min"]["limit"], False),
            _held("overlap_mean_min", float(np.mean(overlaps)),
                  lim["overlap_mean_min"]["limit"], False),
        ]
        if control:
            # the reference in the program's place, one precision down
            c_s, c_i = reference.top_k_scan(q, V, k, precision="bfloat16")
            cg = []
            for row in range(len(pick)):
                own = reference.score_items(q[row], V, c_i[row])
                cg.append(float(np.abs(c_s[row] - own).max()))
            checks.append(_held("control.score_gap_max(bfloat16)", max(cg),
                                lim["score_gap_max"]["limit"], True,
                                smallest=min(cg), control=True))
    checks.append(_held("answers_compared", len(pick), 1, False))
    return checks, malformed


def run(ctx) -> dict:
    cfg, mix, args = ctx.config, ctx.traffic, ctx.args
    run_ = Run(ctx.root, keep=args.keep)
    ctx.on_close(run_.close)
    ctx.run_dirs = [run_.dir]
    times = {"parent_start": time.perf_counter() - ctx.t0}
    seed = int(args.seed)
    platform = "cpu" if args.dry_run_cpu else "tpu"

    # 1. the model, persisted by a child that touches no device
    spec = {
        "seed": seed, "num_users": cfg["num_users"], "num_items": cfg["num_items"],
        "rank": cfg["rank"], "variant": cfg["variant"], "variant_label": "engine.json",
    }
    with open(run_.path("model_spec.json"), "w") as fh:
        json.dump(spec, fh)
    with open(run_.path("engine.json"), "w") as fh:
        json.dump(cfg["variant"], fh)
    out, wall = run_.run_child(
        "write_model", [os.path.join(BENCH, "write_model.py"), run_.path("model_spec.json")],
        900.0, run_.server_cores, JAX_PLATFORMS="cpu",
    )
    written = json.loads(out.strip().splitlines()[-1])
    times["write_model"] = wall
    times["write_model_parts"] = written["seconds"]

    # 2. the server: the one process that owns the chip
    port = free_port()
    server_env = dict(cfg.get("server_env", {}))
    if args.dry_run_cpu:
        server_env["JAX_PLATFORMS"] = "cpu"
    t0 = time.perf_counter()
    server = run_.spawn(
        [*cfg.get("server_entry", ["-m", "predictionio_tpu.cli.main"]),
         "deploy", "--variant", "engine.json",
         "--engine-instance-id", written["instance"], "--ip", "127.0.0.1",
         "--port", str(port), *cfg.get("deploy_flags", [])],
        "server.log", run_.server_cores, **server_env,
    )
    _wait_ready(run_, server, port, "server.log", 1100.0)
    times["deploy_ready"] = time.perf_counter() - t0
    first = json.loads(http_call(port, "GET", "/stats.json")[1])
    device = _device(first)
    if device["platform"] != platform or device["count"] < ctx.cell["chips"]:
        raise BenchFailure(
            f"the server computes on {device['platform']!r} ({device['kind']} x"
            f"{device['count']}), not on {ctx.cell['chips']} TPU chip(s)"
        )

    # 3. the generator: warm-up bursts, warm-in, the window
    trace_dir = run_.path("trace") if args.trace else None
    phases = _phases(mix, float(args.seconds), trace_dir, ctx.ladder)
    plan = {
        "host": "127.0.0.1", "port": port, "seed": seed, "num": mix["num"],
        "num_users": cfg["num_users"], "users": mix.get("users", "uniform-distinct"),
        "connections": max(
            [mix.get("connections", 64)] + [int(p.get("clients", 0)) + 8 for p in phases]
        ), "timeout_s": 900.0,
        "phases": phases, "out": run_.path("gen"),
    }
    with open(run_.path("plan.json"), "w") as fh:
        json.dump(plan, fh)
    every_core = os.sched_getaffinity(0)
    os.sched_setaffinity(0, run_.parent_cores)
    t0 = time.perf_counter()
    total = sum(p["seconds"] + p.get("warm_in_s", 0) for p in phases)
    run_.run_child("loadgen", [os.path.join(BENCH, "loadgen.py"), run_.path("plan.json")],
                   total + 1500.0, run_.gen_cores)
    times["loadgen"] = time.perf_counter() - t0
    last = json.loads(http_call(port, "GET", "/stats.json")[1])
    device = _device(last)
    try:
        http_call(port, "POST", "/stop")
    except OSError:
        pass
    try:
        server.wait(timeout=60)
    except Exception:
        pass
    run_.stop_all()
    os.sched_setaffinity(0, every_core)  # the reference may use them all now

    # 4. the readings
    res = np.load(run_.path("gen.npz"))
    with open(run_.path("gen.bodies.json")) as fh:
        bodies = json.load(fh)
    with open(run_.path("gen.windows.json")) as fh:
        windows = json.load(fh)
    raws = []
    for pi, w in enumerate(windows):
        if not w["measure"]:
            continue
        raw = window_raw(w, res, pi, mix)
        raw["label"] = w["label"]
        raw["setup_s"] = w["t_open"] - ctx.t0
        raw["device"] = device
        raws.append((w, raw))
    if ctx.ladder:
        return {"ladder": [r for _, r in raws], "times": times, "device": device}
    w, raw = raws[-1]
    for pi, x in enumerate(windows):  # how long each warm-up phase really took
        sel = res["phase"] == pi
        if not x["measure"] and sel.any():
            times.setdefault("warm_phases", {})[x["label"]] = float(
                np.nanmax(res["done"][sel]) - np.nanmin(res["sent"][sel]))
    raw["times"] = times

    # 5. correct: the plain reference, after the window, outside set-up
    t0 = time.perf_counter()
    checks, malformed = check_answers(
        cfg, seed, res["user"], bodies, raw["indices"], mix["num"], bool(args.control)
    )
    times["reference"] = time.perf_counter() - t0
    late_p99 = stats.percentile(raw["late_ms"], 99) if raw["late_ms"] else 0.0
    checks += [
        _held("compiles_in_window", raw["compiles_in_window"], 0, True),
        # informs, never fails a run: on a chip machine that shares its host's
        # cores the generator's own process is stalled for ~0.1 s now and then
        # (PERF.md section 6); a starved generator shows in the metric
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
