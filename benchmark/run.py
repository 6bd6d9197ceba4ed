"""benchmark/run.py — the one command of the benchmark.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in BENCHMARK.json, its configuration file (sizes), its
traffic mix (benchmark/traffic/<mix>.json) and each metric's reader
(benchmark/metrics/<metric>.json|.py) BY NAME: a later PR adds a
configuration, a mix, a cell or a per-layer metric as new files plus one
entry, and edits nothing here. The traffic mix names its driver
(benchmark/drivers/<driver>.py), which sets the system up, warms every
shape, measures for --seconds, and checks the answers against the plain
reference once the window has closed.

This process never imports jax: the chip belongs to one child at a time.
The last line of stdout is one JSON object (correct, attempted, failed,
metrics, device[, breakdown]); a run that finds no TPU, or fewer chips
than the cell asks for, exits non-zero and prints no result.

Builder's switches (none gives a result line): --dry-run-cpu rehearses
every phase on XLA:CPU at the configuration's toy sizes (exit 3);
--ladder "v1,v2,..:secs" measures several rates or client counts in one
set-up (exit 3). --control 1 also reads the lower-precision control.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()  # set-up counts from here

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import signal  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(1, ROOT)


class Ctx:
    """What a driver is handed."""

    def __init__(self, **kw):
        self.__dict__.update(kw)
        self._closers = []

    def on_close(self, fn) -> None:
        self._closers.append(fn)

    def close(self) -> None:
        for fn in reversed(self._closers):
            fn()

    def save_logs(self, dest: str) -> None:
        import glob
        import shutil

        os.makedirs(dest, exist_ok=True)
        for run_dir in getattr(self, "run_dirs", []):
            for f in (glob.glob(os.path.join(run_dir, "*.log"))
                      + glob.glob(os.path.join(run_dir, "gen.npz"))
                      + glob.glob(os.path.join(run_dir, "gen.windows.json"))):
                shutil.copy(f, dest)


def load_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def apply_toy(cfg: dict) -> dict:
    """The configuration at its toy sizes (CPU rehearsal only)."""
    return {**cfg, **cfg.get("toy", {})}


def resolve(manifest: dict, workload: str, root: str) -> dict:
    """The cell with its configuration and traffic mix, found by name."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; have {sorted(cells)}")
    cell = dict(cells[workload])
    conf = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    cell["config_entry"] = conf
    cell["config"] = load_json(os.path.join(root, conf["file"]))
    cell["traffic_name"] = cell["traffic"]
    cell["traffic"] = load_json(os.path.join(BENCH, "traffic", cell["traffic"] + ".json"))
    return cell


def metrics_for(manifest: dict, cell_name: str, trace: bool) -> list[dict]:
    """The metrics this run reports: the cell's end-to-end metrics without
    a trace, its per-layer metrics with one."""
    e2e = [m for m in manifest["end_to_end"]
           if "workloads" not in m or cell_name in m["workloads"]]
    if not trace:
        return e2e
    reported = {m["name"] for m in e2e}
    return [m for m in manifest["per_layer"]
            if (cell_name in m["workloads"] if "workloads" in m
                else m["moves"] in reported)]


def evaluate(metric_defs, raw, cell) -> dict:
    import readers

    out = {}
    for m in metric_defs:
        v = readers.load_metric(os.path.join(BENCH, "metrics"), m["name"])(raw, cell)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--manifest", default=os.path.join(ROOT, "BENCHMARK.json"))
    ap.add_argument("--dry-run-cpu", action="store_true")
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--ladder", default="")
    ap.add_argument("--keep", action="store_true", help="keep the work directory")
    ap.add_argument("--save-logs", default="", help="copy the children's logs here")
    args = ap.parse_args(argv)

    try:
        import predictionio_tpu  # noqa: F401  (places the compile cache; no jax)
    except ImportError:
        print("benchmark: the program (predictionio_tpu) is not in this "
              "directory: nothing to measure", file=sys.stderr)
        return 2
    if "jax" in sys.modules:
        print("benchmark: the parent imported jax", file=sys.stderr)
        return 2
    from drivers.common import BenchFailure

    manifest = load_json(args.manifest)
    cell = resolve(manifest, args.workload, ROOT)
    if args.dry_run_cpu:
        cell["config"] = apply_toy(cell["config"])
        cell["traffic"] = {**cell["traffic"], **cell["traffic"].get("toy", {})}
    ladder = None
    if args.ladder:
        vals, _, secs = args.ladder.partition(":")
        ladder = ([float(v) for v in vals.split(",")], float(secs or 10))
    ctx = Ctx(root=ROOT, args=args, cell=cell, config=cell["config"],
              traffic=cell["traffic"], t0=T0, ladder=ladder)
    driver = importlib.import_module("drivers." + cell["traffic"]["driver"])
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))  # run the finally
    try:
        raw = driver.run(ctx)
    except BenchFailure as e:
        print(f"benchmark: FAILED: {e}", file=sys.stderr)
        return 1
    finally:
        if args.save_logs:
            ctx.save_logs(args.save_logs)
        ctx.close()

    if ladder:
        import stats

        for r in raw["ladder"]:
            xs = r["latencies_ms"]
            third = max(1, len(xs) // 3)
            print(json.dumps({
                "lat_first_third_ms": sum(xs[:third]) / third if xs else None,
                "lat_last_third_ms": sum(xs[-third:]) / third if xs else None,
                "rung": r["label"], "attempted": r["attempted"], "failed": r["status_failed"],
                "qps": r["completed"] / r["window_s"],
                "p50_ms": stats.percentile(xs, 50) if xs else None,
                "p95_ms": stats.percentile(xs, 95) if xs else None,
                "p99_ms": stats.percentile(xs, 99) if xs else None,
                "late_p99_ms": stats.percentile(r["late_ms"], 99) if r["late_ms"] else None,
                "batch_mean": stats.histogram_mean(r["counters_delta"], "pio_batch_size"),
                "dispatch_ms": 1e3 * (stats.histogram_mean(r["counters_delta"], "pio_batch_dispatch_seconds") or 0),
                "compiles": r["compiles_in_window"],
            }))
        print(json.dumps({"times": raw["times"], "device": raw["device"]}))
        print("ladder: NOT a result")
        return 3

    print("times: " + json.dumps(raw.get("times", {})))
    for c in raw["checks"]:
        print("check: " + json.dumps(c))
    correct = all(c["pass"] for c in raw["checks"]
                  if not (c.get("control") or c.get("informs"))) \
        and raw["failed"] == 0
    defs = metrics_for(manifest, args.workload, bool(args.trace))
    metrics = evaluate(defs, raw, cell)
    device = dict(raw["device"])
    result = {"correct": bool(correct), "attempted": raw["attempted"],
              "failed": raw["failed"], "metrics": metrics, "device": device}
    if args.trace:
        t = raw["trace"]
        device["busy_s"], device["window_s"] = t["busy_s"], t["window_s"]
        result["breakdown"] = {"device_ops": t["device_ops"], "idle_gaps": t["idle_gaps"]}
    if args.dry_run_cpu:
        print("would print: " + json.dumps(result)[:2000])
        print("dry run on cpu: every phase passed — this is NOT a result"
              if correct else "dry run on cpu: NOT correct")
        return 3 if correct else 1
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
