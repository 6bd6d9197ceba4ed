"""The item-page driver: the serving driver's run (model written, one `pio
deploy` child that owns the chip, one load-generator child, the plain
reference once the window has closed) for the Similar Product template, whose
every query carries rules of its own — its items are never served back, a
session black-lists items, a same-category query names a category. The
generator encodes three kinds of request; `correct` holds EVERY answer to the
rules by set look-ups and a seeded sample to the reference, per kind and
overall. The template reads no events at query time: there is no event store
and nothing live to probe.

Everything cell-specific comes from the configuration file and the traffic
file; the phases, the readiness wait, the window's readings and the device
block are the serving driver's own (drivers/serve.py), unchanged.
"""

from __future__ import annotations

import json
import os
import time

import numpy as np

import ecomm_data
import factors
import itempage_data
import reference
import reference_similarproduct as ref
import stats
import traffic as traffic_mod
from drivers.common import BenchFailure, Run, free_port, http_call, reduce_trace
from drivers.serve import _device, _held, _parse_answer, _phases, _wait_ready, window_raw

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def rules_of(req: dict, i: int, item_cat: np.ndarray):
    """(the query's items, its sorted excluded rows, its categories or None)
    of request i."""
    items, black, cat = itempage_data.query_of(req, i, item_cat)
    return items, np.union1d(items, black), (None if cat is None else [cat])


def fault(num_items: int, item_cat, items, scores, k: int, excluded, cats) -> str | None:
    """None when an answer is well formed; a shorter answer than k only where
    the allowed set is smaller."""
    if len(items) != len(set(items)) or len(items) > k:
        return "items repeated or too many"
    s = np.asarray(scores, np.float64)
    if not np.isfinite(s).all() or (np.diff(s) > 0).any():
        return "scores not finite and descending"
    if len(items) < k and len(items) != min(k, ref.allowed_count(
            num_items, excluded=excluded, item_category=item_cat, query_categories=cats)):
        return f"{len(items)} items where {k} are allowed"
    return None


def check_answers(cfg, seed, req, item_cat, bodies, idx, k, control):
    """Every answer of the window for shape and for excluded items; a seeded
    sample of them against the plain reference over the whole catalog.
    Returns (numbers compared with their limits, answers that are malformed)."""
    lim = cfg["limits"]
    malformed = served_excluded = 0
    parsed = {}
    for i in idx.tolist():
        items, scores = _parse_answer(bodies[i])
        if items is None:
            malformed += 1
            continue
        own, ex, cats = rules_of(req, i, item_cat)
        if fault(cfg["num_items"], item_cat, items, scores, k, ex, cats) is not None:
            malformed += 1
            continue
        served_excluded += ref.excluded_served(
            items, excluded=ex, item_category=item_cat, query_categories=cats)
        parsed[i] = (items, scores, own, ex, cats)
    checks = [_held("excluded_served", served_excluded, lim["excluded_served"]["limit"], True)]
    good = np.asarray(sorted(parsed), dtype=np.int64)
    pick = good[traffic_mod.sample_indices(seed, len(good), int(cfg["check_sample"]))] \
        if len(good) else good
    unit = ref.unit_rows(factors.item_factors(seed, cfg["num_items"], cfg["rank"]))
    rows = [parsed[i] for i in pick.tolist()]
    kinds = [itempage_data.KINDS[int(req["kind"][i])] for i in pick.tolist()]
    if rows:
        q = ref.query_vectors(unit, [r[2] for r in rows])
        rules = dict(excluded=[r[3] for r in rows], item_category=item_cat,
                     query_categories=[r[4] for r in rows])
        top_s, top_i = ref.top_k_allowed(q, unit, k, **rules)
        gaps, overlaps = [], []
        for n, (items, scores, *_) in enumerate(rows):
            own = reference.score_items(q[n], unit, np.asarray(items, np.int64))
            c = ref.compare_answer(items, scores, top_i[n], top_s[n], own)
            gaps.append(c["score_gap"])
            overlaps.append(c["overlap"])
        gaps, overlaps = np.asarray(gaps), np.asarray(overlaps)
        for name in ["all", *itempage_data.KINDS]:
            sel = np.asarray([name in ("all", kd) for kd in kinds])
            if not sel.any():
                continue
            tag = "" if name == "all" else "." + name
            checks += [
                _held("score_gap_max" + tag, float(gaps[sel].max()),
                      lim["score_gap_max"]["limit"], True, answers=int(sel.sum())),
                _held("overlap_min" + tag, float(overlaps[sel].min()),
                      lim["overlap_min"]["limit"], False),
                _held("overlap_mean_min" + tag, float(overlaps[sel].mean()),
                      lim["overlap_mean_min"]["limit"], False),
            ]
        if control:
            # the reference in the program's place, one precision down ...
            c_s, c_i = ref.top_k_allowed(q, unit, k, precision="bfloat16", **rules)
            cg = []
            for n in range(len(rows)):
                live = c_i[n] >= 0
                own = reference.score_items(q[n], unit, c_i[n][live])
                cg.append(float(np.abs(c_s[n][live] - own).max()))
            checks.append(_held("control.score_gap_max(bfloat16)", max(cg),
                                lim["score_gap_max"]["limit"], True,
                                smallest=min(cg), control=True))
    if control:
        # ... and without the category rule, over every answer of the window
        every = [parsed[i] for i in good.tolist()[: int(cfg.get("control_answers", 0))]]
        if every:
            _, n_i = ref.top_k_allowed(
                ref.query_vectors(unit, [r[2] for r in every]), unit, k,
                excluded=[r[3] for r in every], item_category=item_cat,
                query_categories=[r[4] for r in every], apply_category=False)
            served = sum(ref.excluded_served(
                n_i[n][n_i[n] >= 0], excluded=r[3], item_category=item_cat,
                query_categories=r[4]) for n, r in enumerate(every))
            checks.append(_held("control.excluded_served(no category rule)", served,
                                lim["excluded_served"]["limit"], True,
                                answers=len(every), control=True))
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
    server_env = dict(cfg.get("server_env", {}))
    if args.dry_run_cpu:
        server_env["JAX_PLATFORMS"] = "cpu"

    # 1. the model, by a child that touches no device (and refuses at once a
    # program whose model cannot hold the category block)
    spec = {key: cfg[key] for key in ("num_items", "num_categories", "rank", "variant")}
    spec.update(seed=seed, variant_label="engine.json")
    with open(run_.path("model_spec.json"), "w") as fh:
        json.dump(spec, fh)
    with open(run_.path("engine.json"), "w") as fh:
        json.dump(cfg["variant"], fh)
    out, wall = run_.run_child(
        "write_similar", [os.path.join(BENCH, "write_similar.py"), run_.path("model_spec.json")],
        900.0, run_.server_cores, JAX_PLATFORMS="cpu",
    )
    written = json.loads(out.strip().splitlines()[-1])
    times["write_similar"] = wall
    times["write_similar_parts"] = written["seconds"]
    times["model_file_bytes"] = written["bytes"]

    # 2. the server: the one process that owns the chip
    port = free_port()
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
    device = _device(json.loads(http_call(port, "GET", "/stats.json")[1]))
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
        "num_users": cfg["num_items"], "users": "uniform",
        "itempage": {"num_items": cfg["num_items"], "num_categories": cfg["num_categories"],
                     "shares": mix["shares"]},
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
    run_.run_child("loadgen", [os.path.join(BENCH, mix["generator"]), run_.path("plan.json")],
                   total + 1500.0, run_.gen_cores)
    times["loadgen"] = time.perf_counter() - t0
    device = _device(json.loads(http_call(port, "GET", "/stats.json")[1]))
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
    req = itempage_data.requests(seed, len(res["user"]), cfg["num_items"], mix["shares"])
    item_cat = ecomm_data.item_categories(seed, cfg["num_items"], cfg["num_categories"])
    checks, malformed = check_answers(
        cfg, seed, req, item_cat, bodies, raw["indices"], mix["num"], bool(args.control))
    times["reference"] = time.perf_counter() - t0
    late_p99 = stats.percentile(raw["late_ms"], 99) if raw["late_ms"] else 0.0
    d = raw["counters_delta"]
    checks += [
        _held("compiles_in_window", raw["compiles_in_window"], 0, True),
        # no filter keeps a query from two-stage retrieval
        _held("exact_path_queries",
              d.get('pio_retrieval_queries_total{path="exact"}', 0.0), 0, True),
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
