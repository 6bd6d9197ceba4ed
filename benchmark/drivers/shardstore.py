"""The sharded storefront driver: the storefront driver's run (model and event
store written, one `pio deploy` child that owns the chips, one load-generator
child sending three kinds of request, every answer held to the business rules,
the live guarantee probed after the window) for a catalog one chip cannot
hold. What differs from drivers/storefront.py, which may not be edited:

- the model SPANS FILES and the server is `pio deploy --mesh data=4` of the
  E-Commerce template with ``sharded_serving`` (write_shardstore.py). The
  writer asks the program for both BY NAME before a byte is written: a program
  without them fails in seconds, not after 13 GB;
- the reference regenerates the item table from the seed a chunk at a time
  (reference_ecommerce_sharded.py) and never holds it;
- the second control drops the unavailable rule on ONE shard's rows only;
- the served path is checked: every query through the masked sharded chain,
  one blocking read a dispatch (drivers/sharded.py's checks);
- the trace is reduced a device plane at a time (xplane_shardstore.py);
- the live probe writes a ``buy`` (the source has no views).

Everything cell-specific comes from the configuration file and the traffic
file; the deployment's seeded data, the phases, the readiness wait, the
window's readings and the device block are the other drivers' own, unchanged.
"""

from __future__ import annotations

import json
import os
import time

import numpy as np

import ecomm_data
import factors
import reference_ecommerce as ref
import reference_ecommerce_sharded as ref_sharded
import stats
import traffic as traffic_mod
from drivers.common import BenchFailure, Run, free_port, http_call
from drivers.serve import _device, _held, _parse_answer, _phases, _wait_ready, window_raw
from drivers.sharded import memory_by_device, require_spanning_format
from drivers.storefront import APP, PROBE_EVENTS, Deployment

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PROBE_BUY = PROBE_EVENTS.replace('event="view"', 'event="buy"')


def _scan(cfg, seed, dep, q, k, rules, **kw):
    """The plain reference for queries ``q`` under ``rules`` [(excluded rows,
    category or None)], over the whole catalog a chunk at a time."""
    return ref_sharded.scan(
        seed, cfg["num_items"], cfg["rank"], q, k, unavailable=dep.unavailable,
        excluded=[r[0] for r in rules], item_category=dep.item_cat,
        query_category=[r[1] for r in rules],
        workers=max(8, len(os.sched_getaffinity(0)) // 2), **kw)


def answered(users, req, bodies, dep, most: int) -> list:
    """(user, excluded rows, category) of the run's first ``most`` requests
    that came back as an answer, warm-up phases included."""
    out = []
    for i, body in enumerate(bodies):
        if len(out) == most:
            break
        if _parse_answer(body)[0] is not None:
            out.append((int(users[i]), *dep.rules_of(int(users[i]), req, i)))
    return out


def _padded(lists, k: int) -> np.ndarray:
    out = np.full((len(lists), k), -1, np.int64)
    for n, items in enumerate(lists):
        out[n, : len(items)] = items
    return out


def check_answers(cfg, dep, seed, users, req, bodies, idx, k, control, probe):
    """Every answer of the window for shape and for excluded items; a seeded
    sample of them, and the probe's second answer, against the plain
    reference over the whole catalog. Returns (numbers compared with their
    limits, answers that are malformed)."""
    lim = cfg["limits"]
    malformed = served_excluded = 0
    parsed = {}
    for i in idx.tolist():
        items, scores = _parse_answer(bodies[i])
        if items is None:
            malformed += 1
            continue
        ex, cat = dep.rules_of(int(users[i]), req, i)
        if dep.fault(items, scores, k, ex, cat) is not None:
            malformed += 1
            continue
        served_excluded += ref.excluded_served(
            items, excluded=ex, unavailable_flags=dep.flags,
            item_category=dep.item_cat, query_category=cat)
        parsed[i] = (items, scores, ex, cat)
    checks = [_held("excluded_served", served_excluded, lim["excluded_served"]["limit"], True)]
    good = np.asarray(sorted(parsed), dtype=np.int64)
    pick = good[traffic_mod.sample_indices(seed, len(good), int(cfg["check_sample"]))] \
        if len(good) else good
    U = factors.user_factors(seed, cfg["num_users"], cfg["rank"])
    rows = [(int(users[i]), *parsed[i]) for i in pick.tolist()]
    kinds = [ecomm_data.KINDS[int(req["kind"][i])] for i in pick.tolist()]
    if probe.get("second") is not None:
        rows.append((probe["user"], *probe["second"], probe["excluded"], None))
        kinds.append("probe")
    every = answered(users, req, bodies, dep, int(cfg["control_answers"])) if control else []
    q = U[[r[0] for r in rows]]
    q_every = U[[r[0] for r in every]]
    del U
    if rows:
        rules = [r[3:] for r in rows]
        top_s, top_i, own = _scan(cfg, seed, dep, q, k, rules,
                                  served=_padded([r[1] for r in rows], k))
        gaps, overlaps = [], []
        for n, (_, items, scores, _, _) in enumerate(rows):
            c = ref.compare_answer(items, scores, top_i[n], top_s[n], own[n, : len(items)])
            gaps.append(c["score_gap"])
            overlaps.append(c["overlap"])
        gaps, overlaps = np.asarray(gaps), np.asarray(overlaps)
        for name in ["all", *ecomm_data.KINDS]:
            sel = np.asarray([name == "all" and kd != "probe" or kd == name for kd in kinds])
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
        if kinds[-1] == "probe":
            first, second = set(probe["first"][0]), set(probe["second"][0])
            gone = probe["first"][0][0] not in second and probe["first"][0][1] not in second
            checks += [
                _held("live_probe.removed_items_served", 0 if gone else 1, 0, True),
                _held("live_probe.score_gap_max", float(gaps[-1]),
                      lim["score_gap_max"]["limit"], True),
                _held("live_probe.overlap_min", float(overlaps[-1]),
                      lim["overlap_min"]["limit"], False, kept=len(first & second)),
            ]
        if control:
            # the reference in the program's place, one precision down, held to
            # the f32 reference's own scores of the rows it serves
            c_s, c_i, _ = _scan(cfg, seed, dep, q, k, rules, precision="bfloat16")
            _, _, c_own = _scan(cfg, seed, dep, q, k, rules, served=c_i)
            live = c_i >= 0
            cg = np.where(live, np.abs(c_s - np.where(live, c_own, 0)), 0).max(axis=1)
            checks.append(_held("control.score_gap_max(bfloat16)", float(cg.max()),
                                lim["score_gap_max"]["limit"], True,
                                smallest=float(cg.min()), control=True))
    if control and every:
        # ... and without the unavailable rule on ONE shard's rows: what a shard
        # that lost its availability vector would serve to the run's requests
        # (the warm-up's too: a quarter of 0.1 % of the catalog shows in one
        # answer of num 10 in 400)
        per = -(-cfg["num_items"] // int(cfg["mesh"]["data"]))
        rules = [r[1:] for r in every]
        _, n_i, _ = _scan(cfg, seed, dep, q_every, k, rules,
                          no_unavailable_rows=(per, 2 * per))
        served = sum(ref.excluded_served(
            n_i[n][n_i[n] >= 0], excluded=r[0], unavailable_flags=dep.flags,
            item_category=dep.item_cat, query_category=r[1])
            for n, r in enumerate(rules))
        checks.append(_held("control.excluded_served(no unavailable rule on one shard)",
                            served, lim["excluded_served"]["limit"], True,
                            answers=len(every), control=True))
    checks.append(_held("answers_compared", len(pick), 1, False))
    return checks, malformed


def live_probe(run_: Run, port: int, dep: Deployment, user: int, k: int, env: dict) -> dict:
    """After the window, outside every timing: the probe user's answer, then
    a ``$set unavailableItems`` that adds its top item and a ``buy`` of its
    second (written by another process, as an event server would), then the
    answer again."""
    body = json.dumps({"user": f"u{user}", "num": k}).encode()

    def ask():
        return _parse_answer(http_call(port, "POST", "/queries.json", body)[1].decode())

    first = ask()
    probe = {"user": user, "first": first, "second": None}
    if first[0] is None or len(first[0]) < 2:
        return probe
    top, bought = first[0][0], first[0][1]
    with open(run_.path("probe.json"), "w") as fh:
        json.dump({"app_name": APP, "user": f"u{user}", "viewed": f"i{bought}",
                   "unavailable": [f"i{i}" for i in dep.unavailable.tolist()] + [f"i{top}"]}, fh)
    run_.run_child("probe_events", ["-c", PROBE_BUY, run_.path("probe.json")], 120.0,
                   run_.parent_cores, JAX_PLATFORMS="cpu", **env)
    second = ask()
    if second[0] is not None:
        seen = dep.seen[int(np.searchsorted(dep.active, user))]
        probe["second"] = second
        probe["excluded"] = np.union1d(seen, [top, bought])
    return probe


def reduce_trace(run: Run, trace_dir: str) -> dict:
    """xplane_sharded.py's reduction with the masked program in the unmasked
    one's place (xplane_shardstore.py), in a child held to the CPU."""
    out = run.path("trace.json")
    run.run_child(
        "xplane", [os.path.join(BENCH, "xplane_shardstore.py"), trace_dir, out],
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
    server_env = dict(cfg.get("server_env", {}))
    store_env = {k: v for k, v in server_env.items() if k.startswith("PIO_STORAGE_")}
    if args.dry_run_cpu:
        server_env["JAX_PLATFORMS"] = "cpu"

    # 1. the model, in segments, and the event store, by a child that touches
    # no device and first asks the program whether it can serve them
    spec = {key: cfg[key] for key in ("num_users", "num_items", "num_categories", "rank",
                                      "unavailable_items", "events", "variant")}
    spec.update(seed=seed, variant_label="engine.json", app_name=APP,
                segment_bytes=cfg.get("segment_bytes"))
    with open(run_.path("model_spec.json"), "w") as fh:
        json.dump(spec, fh)
    with open(run_.path("engine.json"), "w") as fh:
        json.dump(cfg["variant"], fh)
    out, wall = run_.run_child(
        "write_shardstore",
        [os.path.join(BENCH, "write_shardstore.py"), run_.path("model_spec.json")],
        1500.0, run_.server_cores, JAX_PLATFORMS="cpu", **store_env,
    )
    written = json.loads(out.strip().splitlines()[-1])
    times["write_shardstore"] = wall
    times["write_shardstore_parts"] = written["seconds"]
    times["model_bytes"], times["model_segments"] = written["bytes"], written["segments"]

    # 2. the server: the one process that owns the chips
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
        "num_users": cfg["events"]["active_users"], "users": mix["users"],
        "storefront": {
            "num_users": cfg["num_users"], "active_users": cfg["events"]["active_users"],
            "num_items": cfg["num_items"], "num_categories": cfg["num_categories"],
            "shares": mix["shares"],
        },
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
    last = json.loads(http_call(port, "GET", "/stats.json")[1])
    device = _device(last)
    times["memory_by_device"] = memory_by_device(last)
    times["model_load"] = (last.get("retrieval") or {}).get("load_seconds")
    times["resident_bytes"] = (last.get("retrieval") or {}).get("resident_bytes")

    # 4. the live guarantee, after the window and outside every timing
    t0 = time.perf_counter()
    os.sched_setaffinity(0, every_core)  # 48 M-row permutations: every core
    dep = Deployment(cfg, seed)
    probe = {"second": None, "skipped": True}
    if not ctx.ladder:
        probe = live_probe(run_, port, dep, int(dep.active[len(dep.active) // 3]),
                           mix["num"], store_env)
    times["live_probe"] = time.perf_counter() - t0
    try:
        http_call(port, "POST", "/stop")
    except OSError:
        pass
    try:
        server.wait(timeout=60)
    except Exception:
        pass
    run_.stop_all()

    # 5. the readings
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

    # 6. correct: the plain reference, after the window, outside set-up
    t0 = time.perf_counter()
    req = ecomm_data.requests(seed, len(res["user"]), cfg["num_items"],
                              cfg["num_categories"], mix["shares"])
    checks, malformed = check_answers(
        cfg, dep, seed, res["user"], req, bodies, raw["indices"], mix["num"],
        bool(args.control), probe)
    if probe["second"] is None:
        checks.append(_held("live_probe.answered", 0, 1, False))
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
        # the served path: every query through the MASKED sharded chain, one
        # blocking read a dispatch
        _held("unsharded_queries",
              d.get('pio_retrieval_queries_total{path="two_stage"}', 0.0)
              + d.get('pio_retrieval_queries_total{path="exact"}', 0.0), 0, True),
        _held("unmasked_sharded_queries",
              d.get('pio_retrieval_queries_total{path="sharded"}', 0.0)
              - d.get("pio_retrieval_sharded_masked_total", 0.0), 0, True),
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
