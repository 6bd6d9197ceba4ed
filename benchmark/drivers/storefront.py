"""The storefront driver: the serving driver's run (model written, one `pio
deploy` child that owns the chip, one load-generator child, the plain
reference once the window has closed) for a deployment whose every query
carries live business rules — so set-up also fills the event store, the
generator encodes three kinds of request, `correct` holds EVERY answer to the
rules and a sample to the reference per kind, and after the window, outside
every timing, the live guarantee is probed: an item made unavailable and an
item viewed are gone from the next answer.

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
import reference
import reference_ecommerce as ref
import stats
import traffic as traffic_mod
from drivers.common import BenchFailure, Run, free_port, http_call, reduce_trace
from drivers.serve import _device, _held, _parse_answer, _phases, _wait_ready, window_raw

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
APP = "Shop"
PROBE_EVENTS = '''
import json, sys
from predictionio_tpu.data.event import Event
from predictionio_tpu.data.storage import get_storage
from predictionio_tpu.data import store
spec = json.load(open(sys.argv[1]))
app_id, _ = store.app_name_to_id(spec["app_name"])
events = get_storage().get_events()
events.insert(Event(event="$set", entity_type="constraint", entity_id="unavailableItems",
                    properties={"items": spec["unavailable"]}), app_id)
events.insert(Event(event="view", entity_type="user", entity_id=spec["user"],
                    target_entity_type="item", target_entity_id=spec["viewed"]), app_id)
'''


class Deployment:
    """The deployment's data as the reference sees it, regenerated from the
    seed: nothing here comes back from the program."""

    def __init__(self, cfg: dict, seed: int):
        ev = cfg["events"]
        self.num_items = cfg["num_items"]
        self.item_cat = ecomm_data.item_categories(seed, cfg["num_items"], cfg["num_categories"])
        self.unavailable = ecomm_data.unavailable_items(
            seed, cfg["num_items"], cfg["unavailable_items"])
        self.flags = np.zeros(cfg["num_items"], bool)
        self.flags[self.unavailable] = True
        self.active = ecomm_data.active_users(seed, cfg["num_users"], ev["active_users"])
        who, item, _ = ecomm_data.user_events(
            seed, cfg["num_items"], ev["active_users"], ev["count"], ev["buy_share"])
        self.seen = ecomm_data.seen_sets(who, item, ev["active_users"])

    def rules_of(self, user: int, req: dict, i: int):
        """(excluded rows, category or None) of request i, asked for ``user``."""
        seen = self.seen[int(np.searchsorted(self.active, user))]
        kind = int(req["kind"][i])
        if kind == ecomm_data.CART:
            own = req["list_items"][i, : int(req["list_len"][i])]
            return np.union1d(seen, own), None
        return seen, (int(req["category"][i]) if kind == ecomm_data.CATEGORY else None)

    def fault(self, items, scores, k: int, excluded, category) -> str | None:
        """None when an answer is well formed and no rule forbids an item of
        it; a shorter answer than k only where the allowed set is smaller."""
        if len(items) != len(set(items)) or len(items) > k:
            return "items repeated or too many"
        s = np.asarray(scores, np.float64)
        if not np.isfinite(s).all() or (np.diff(s) > 0).any():
            return "scores not finite and descending"
        if len(items) < k and len(items) != min(k, ref.allowed_count(
                self.num_items, excluded=excluded, unavailable_flags=self.flags,
                item_category=self.item_cat, query_category=category)):
            return f"{len(items)} items where {k} are allowed"
        return None


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
    V = factors.item_factors(seed, cfg["num_items"], cfg["rank"])
    rows = [(int(users[i]), *parsed[i]) for i in pick.tolist()]
    kinds = [ecomm_data.KINDS[int(req["kind"][i])] for i in pick.tolist()]
    if probe.get("second") is not None:
        rows.append((probe["user"], *probe["second"], probe["excluded"], None))
        kinds.append("probe")
    every = good.tolist()[: int(cfg.get("control_answers", 0))] if control else []
    q_all = U[[r[0] for r in rows] + [int(users[i]) for i in every]]
    del U
    if rows:
        q = q_all[: len(rows)]
        top_s, top_i = ref.top_k_allowed(
            q, V, k, unavailable=dep.unavailable, excluded=[r[3] for r in rows],
            item_category=dep.item_cat, query_category=[r[4] for r in rows])
        gaps, overlaps = [], []
        for n, (_, items, scores, _, _) in enumerate(rows):
            own = reference.score_items(q[n], V, np.asarray(items, np.int64))
            c = ref.compare_answer(items, scores, top_i[n], top_s[n], own)
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
            # the reference in the program's place, one precision down ...
            c_s, c_i = ref.top_k_allowed(
                q, V, k, unavailable=dep.unavailable, excluded=[r[3] for r in rows],
                item_category=dep.item_cat, query_category=[r[4] for r in rows],
                precision="bfloat16")
            cg = []
            for n in range(len(rows)):
                live = c_i[n] >= 0
                own = reference.score_items(q[n], V, c_i[n][live])
                cg.append(float(np.abs(c_s[n][live] - own).max()))
            checks.append(_held("control.score_gap_max(bfloat16)", max(cg),
                                lim["score_gap_max"]["limit"], True,
                                smallest=min(cg), control=True))
    if control and every:
        # ... and without the unavailable rule, over every answer of the window
        rules = [parsed[i][2:] for i in every]
        _, n_i = ref.top_k_allowed(
            q_all[len(rows):], V, k, unavailable=dep.unavailable,
            excluded=[r[0] for r in rules], item_category=dep.item_cat,
            query_category=[r[1] for r in rules], apply_unavailable=False)
        served = sum(ref.excluded_served(
            n_i[n][n_i[n] >= 0], excluded=r[0], unavailable_flags=dep.flags,
            item_category=dep.item_cat, query_category=r[1])
            for n, r in enumerate(rules))
        checks.append(_held("control.excluded_served(no unavailable rule)", served,
                            lim["excluded_served"]["limit"], True,
                            answers=len(every), control=True))
    checks.append(_held("answers_compared", len(pick), 1, False))
    return checks, malformed


def live_probe(run_: Run, port: int, dep: Deployment, user: int, k: int, env: dict) -> dict:
    """After the window, outside every timing: the probe user's answer, then
    a ``$set unavailableItems`` that adds its top item and a ``view`` of its
    second (written by another process, as an event server would), then the
    answer again."""
    body = json.dumps({"user": f"u{user}", "num": k}).encode()

    def ask():
        return _parse_answer(http_call(port, "POST", "/queries.json", body)[1].decode())

    first = ask()
    probe = {"user": user, "first": first, "second": None}
    if first[0] is None or len(first[0]) < 2:
        return probe
    top, viewed = first[0][0], first[0][1]
    with open(run_.path("probe.json"), "w") as fh:
        json.dump({"app_name": APP, "user": f"u{user}", "viewed": f"i{viewed}",
                   "unavailable": [f"i{i}" for i in dep.unavailable.tolist()] + [f"i{top}"]}, fh)
    run_.run_child("probe_events", ["-c", PROBE_EVENTS, run_.path("probe.json")], 120.0,
                   run_.parent_cores, JAX_PLATFORMS="cpu", **env)
    second = ask()
    if second[0] is not None:
        seen = dep.seen[int(np.searchsorted(dep.active, user))]
        probe["second"] = second
        probe["excluded"] = np.union1d(seen, [top, viewed])
    return probe


def run(ctx) -> dict:
    cfg, mix, args = ctx.config, ctx.traffic, ctx.args
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

    # 1. the model and the event store, by a child that touches no device
    spec = {key: cfg[key] for key in ("num_users", "num_items", "num_categories", "rank",
                                      "unavailable_items", "events", "variant")}
    spec.update(seed=seed, variant_label="engine.json", app_name=APP)
    with open(run_.path("model_spec.json"), "w") as fh:
        json.dump(spec, fh)
    with open(run_.path("engine.json"), "w") as fh:
        json.dump(cfg["variant"], fh)
    out, wall = run_.run_child(
        "write_ecomm", [os.path.join(BENCH, "write_ecomm.py"), run_.path("model_spec.json")],
        900.0, run_.server_cores, JAX_PLATFORMS="cpu", **store_env,
    )
    written = json.loads(out.strip().splitlines()[-1])
    times["write_ecomm"] = wall
    times["write_ecomm_parts"] = written["seconds"]
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
    device = _device(json.loads(http_call(port, "GET", "/stats.json")[1]))

    # 4. the live guarantee, after the window and outside every timing
    t0 = time.perf_counter()
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
    os.sched_setaffinity(0, every_core)  # the reference may use them all now

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
    checks += [
        _held("compiles_in_window", raw["compiles_in_window"], 0, True),
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
