"""The sharded serving driver: the serving driver's run (model written, one
`pio deploy` child that owns the chips, one load-generator child, the plain
reference once the window has closed) for a catalog one chip cannot hold. Three
things differ from drivers/serve.py, which may not be edited and hard-codes
the other choice in each:

- the model SPANS FILES (write_sharded.py: segments of 1 GiB written side by
  side by the program's own spanning writer). Before a byte is written the
  driver asks the program for that format by name and fails by name where it
  is absent — a program without it exits in seconds, not after 12 GB;
- the reference regenerates the item table from the seed a chunk at a time
  (reference_sharded.py) and never holds it: 12.34 GB beside nothing;
- the trace is reduced a device plane at a time as well as over all of them
  (xplane_sharded.py): busy time per chip, and the device time of the
  collective and the merge inside the one sharded program.

Everything cell-specific comes from the configuration file and the traffic
file; the phases, the readiness wait, the window's readings and the device
block are the serving driver's own, unchanged.
"""

from __future__ import annotations

import json
import os
import time

import numpy as np

import factors
import reference
import reference_sharded
import stats
import traffic as traffic_mod
from drivers.common import BenchFailure, Run, free_port, http_call
from drivers.serve import _device, _held, _parse_answer, _phases, _wait_ready, window_raw

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FORMAT = ("write_spanning", "Fields", "EncodedIds", "SEGMENT_BYTES")


def require_spanning_format() -> None:
    """The program's model-file module has to write a model that spans files.
    Imports no jax (run.py holds this process off it and checks)."""
    try:
        from predictionio_tpu.models import modelfile
    except ImportError as e:
        raise BenchFailure(f"the program has no predictionio_tpu.models.modelfile: {e}") from None
    missing = [n for n in FORMAT if not hasattr(modelfile, n)]
    if missing:
        raise BenchFailure(
            f"predictionio_tpu.models.modelfile lacks {missing}: this program cannot "
            "write or load a model that spans files, and the cell's model fits no "
            "single file; nothing was written")


def check_answers(cfg: dict, seed: int, users, bodies, idx, k: int,
                  control: bool) -> tuple[list[dict], int]:
    """Every answer of the window for shape; a seeded sample of them against
    the plain reference over the whole catalog, a chunk of it at a time.
    Returns (numbers compared with their limits, answers that are malformed)."""
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
        q = factors.user_factors(seed, cfg["num_users"], cfg["rank"])[users[pick]]
        served = np.asarray([parsed[i][0] for i in pick.tolist()], np.int64)
        workers = max(8, len(os.sched_getaffinity(0)) // 2)
        top_s, top_i, own = reference_sharded.scan(
            seed, cfg["num_items"], cfg["rank"], q, k, served=served, workers=workers)
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
        if control:
            # the reference in the program's place, one precision down, held to
            # the f32 reference's own scores of the rows it serves
            c_s, c_i, _ = reference_sharded.scan(
                seed, cfg["num_items"], cfg["rank"], q, k, precision="bfloat16",
                workers=workers)
            _, _, c_own = reference_sharded.scan(
                seed, cfg["num_items"], cfg["rank"], q, k, served=c_i, workers=workers)
            cg = np.abs(c_s - c_own).max(axis=1)
            checks.append(_held("control.score_gap_max(bfloat16)", float(cg.max()),
                                lim["score_gap_max"]["limit"], True,
                                smallest=float(cg.min()), control=True))
    checks.append(_held("answers_compared", len(pick), 1, False))
    return checks, malformed


def memory_by_device(stats_body: dict) -> list[dict]:
    return [{"device": d["device"], **{k: int(v) for k, v in (d.get("memory") or {}).items()}}
            for d in stats_body["device"]["devices"]]


def reduce_trace(run: Run, trace_dir: str) -> dict:
    """xplane.py's reduction over all device planes, plus the per-plane and
    per-op readings (xplane_sharded.py), in a child held to the CPU."""
    out = run.path("trace.json")
    run.run_child(
        "xplane", [os.path.join(BENCH, "xplane_sharded.py"), trace_dir, out],
        600.0, JAX_PLATFORMS="cpu",
    )
    with open(out) as fh:
        return json.load(fh)


def run(ctx) -> dict:
    cfg, mix, args = ctx.config, ctx.traffic, ctx.args
    require_spanning_format()  # before the work directory exists
    run_ = Run(ctx.root, keep=args.keep)
    ctx.on_close(run_.close)
    ctx.run_dirs = [run_.dir]
    times = {"parent_start": time.perf_counter() - ctx.t0}
    seed = int(args.seed)
    platform = "cpu" if args.dry_run_cpu else "tpu"

    # 1. the model, in segments, by a child that touches no device
    spec = {key: cfg[key] for key in ("num_users", "num_items", "rank", "variant")}
    spec.update(seed=seed, variant_label="engine.json",
                segment_bytes=cfg.get("segment_bytes"))
    with open(run_.path("model_spec.json"), "w") as fh:
        json.dump(spec, fh)
    with open(run_.path("engine.json"), "w") as fh:
        json.dump(cfg["variant"], fh)
    out, wall = run_.run_child(
        "write_sharded", [os.path.join(BENCH, "write_sharded.py"), run_.path("model_spec.json")],
        1500.0, run_.server_cores, JAX_PLATFORMS="cpu",
    )
    written = json.loads(out.strip().splitlines()[-1])
    times["write_sharded"] = wall
    times["write_sharded_parts"] = written["seconds"]
    times["model_bytes"], times["model_segments"] = written["bytes"], written["segments"]

    # 2. the server: the one process that owns the chips
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
    times["memory_by_device"] = memory_by_device(last)
    times["model_load"] = (last.get("retrieval") or {}).get("load_seconds")
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
    d = raw["counters_delta"]
    # blocking reads beyond one a dispatch and one a recall probe; a dispatch in
    # flight when the window opens or closes is counted on one side only
    extra = (d.get("pio_retrieval_host_reads_total", 0.0)
             - d.get("pio_retrieval_probes_total", 0.0)
             - d.get("pio_retrieval_shortlist_seconds_count", 0.0))
    checks += [
        _held("compiles_in_window", raw["compiles_in_window"], 0, True),
        # the served path: every query through the sharded chain, one blocking
        # read a dispatch
        _held("unsharded_queries",
              d.get('pio_retrieval_queries_total{path="two_stage"}', 0.0)
              + d.get('pio_retrieval_queries_total{path="exact"}', 0.0), 0, True),
        _held("extra_host_reads", extra, 1, True),
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
