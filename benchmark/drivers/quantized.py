"""The quantized serving driver: the serving driver's run (model written, one
`pio deploy` child that owns the chip, one load-generator child, the plain
reference once the window has closed) for a model that IS a stored int8 pair.
Three things differ from drivers/serve.py, which may not be edited and
hard-codes the other choice in each:

- the model is written QUANTIZED and spans files (write_int8.py: int8 values
  in segments of at most 1 GiB, f32 row scales). The writer asks the program
  by name for what serves such a model as it is stored and exits 2 where it is
  absent, before a byte is written;
- the reference regenerates, quantizes and dequantizes the item table a chunk
  at a time (reference_int8.py); its controls are the bf16 rounding and the
  f32 rows the pair was made from;
- `correct` also holds the deployment's own guarantee as far as a run can show
  it: every query through two-stage retrieval, and the resident item table one
  byte a value (``pio_model_resident_bytes``, /stats.json) — no f32 copy of it
  on the chip.

Everything cell-specific comes from the configuration file and the traffic
file; the phases, the readiness wait, the window's readings, the device block
and the trace reduction are the serving driver's own, unchanged, and the
per-device memory report is the sharded driver's.
"""

from __future__ import annotations

import json
import os
import time

import numpy as np

import factors
import reference
import reference_int8
import stats
import traffic as traffic_mod
from drivers.common import BenchFailure, Run, free_port, http_call, reduce_trace
from drivers.serve import _device, _held, _parse_answer, _phases, _wait_ready, window_raw
from drivers.sharded import memory_by_device

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONTROLS = {"bfloat16": ("int8", "bfloat16"), "unquantized": ("unquantized", "float32")}


def check_answers(cfg: dict, seed: int, users, bodies, idx, k: int,
                  control: bool) -> tuple[list[dict], int]:
    """Every answer of the window for shape; a seeded sample of them against
    the plain reference over the whole dequantized catalog, a chunk of it at a
    time. Returns (numbers compared with their limits, answers malformed)."""
    malformed = 0
    parsed = {}
    for i in idx.tolist():
        items, scores = _parse_answer(bodies[i])
        if items is None or reference.well_formed(items, scores, k) is not None:
            malformed += 1
        else:
            parsed[i] = (items, scores)
    good = np.asarray(sorted(parsed), dtype=np.int64)
    pick = good[traffic_mod.sample_indices(seed, len(good), int(cfg["check_sample"]))] \
        if len(good) else good
    lim = cfg["limits"]
    checks = []
    if len(pick):
        def user_rows(quantized: bool):
            return reference_int8.table_rows(
                seed, factors.STREAM_USER_FACTORS, cfg["num_users"], cfg["rank"],
                users[pick], quantized)

        q = user_rows(True)
        served = np.asarray([parsed[i][0] for i in pick.tolist()], np.int64)
        controls = {
            name: (q if table == "int8" else user_rows(False), table, precision)
            for name, (table, precision) in CONTROLS.items()
        } if control else {}
        top_s, top_i, own, controlled = reference_int8.scan(
            seed, cfg["num_items"], cfg["rank"], q, k, served=served,
            controls=controls, workers=max(8, len(os.sched_getaffinity(0)) - 1))
        gaps, overlaps = [], []
        for row, i in enumerate(pick.tolist()):
            items, scores = parsed[i]
            c = reference.compare_answer(items, scores, top_i[row], top_s[row], own[row])
            gaps.append(c["score_gap"])
            overlaps.append(c["overlap"])
        checks = [
            _held("score_gap_max", max(gaps), lim["score_gap_max"]["limit"], True),
            _held("overlap_min", min(overlaps), lim["overlap_min"]["limit"], False),
            _held("overlap_mean_min", float(np.mean(overlaps)),
                  lim["overlap_mean_min"]["limit"], False),
        ]
        for name, (c_s, _, exact) in controlled.items():
            # the reference in the program's place, one step away, held to the
            # exact scores of the rows it serves
            cg = np.abs(c_s - exact).max(axis=1)
            checks.append(_held(f"control.score_gap_max({name})", float(cg.max()),
                                lim["score_gap_max"]["limit"], True,
                                smallest=float(cg.min()), control=True))
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

    # 1. the quantized model, in segments, by a child that touches no device
    spec = {key: cfg[key] for key in ("num_users", "num_items", "rank", "variant")}
    spec.update(seed=seed, variant_label="engine.json",
                segment_bytes=cfg.get("segment_bytes"))
    with open(run_.path("model_spec.json"), "w") as fh:
        json.dump(spec, fh)
    with open(run_.path("engine.json"), "w") as fh:
        json.dump(cfg["variant"], fh)
    out, wall = run_.run_child(
        "write_int8", [os.path.join(BENCH, "write_int8.py"), run_.path("model_spec.json")],
        1500.0, run_.server_cores, JAX_PLATFORMS="cpu",
    )
    written = json.loads(out.strip().splitlines()[-1])
    times["write_int8"] = wall
    times["write_int8_parts"] = written["seconds"]
    times["model_bytes"], times["model_segments"] = written["bytes"], written["segments"]

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
    retrieval = last.get("retrieval") or {}
    resident = {k: int(v) for k, v in (retrieval.get("resident_bytes") or {}).items()}
    times["memory_by_device"] = memory_by_device(last)
    times["resident_bytes"] = resident
    times["model_load"] = retrieval.get("load_seconds")
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
    raw["times"] = times

    # 5. correct: the plain reference, after the window, outside set-up
    t0 = time.perf_counter()
    checks, malformed = check_answers(
        cfg, seed, res["user"], bodies, raw["indices"], mix["num"], bool(args.control)
    )
    times["reference"] = time.perf_counter() - t0
    late_p99 = stats.percentile(raw["late_ms"], 99) if raw["late_ms"] else 0.0
    d = raw["counters_delta"]
    checks += [
        _held("compiles_in_window", raw["compiles_in_window"], 0, True),
        # the served path: every query through the shortlist and the rescore
        _held("exact_path_queries",
              d.get('pio_retrieval_queries_total{path="exact"}', 0.0), 0, True),
        # the model IS the int8 pair: the resident item table is one byte a
        # value (an f32 copy would read four), and it is there
        _held("table_bytes_a_value",
              resident.get("table", 0) / (cfg["num_items"] * cfg["rank"]), 1, True),
        _held("table_resident", resident.get("table", 0), 1, False),
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
