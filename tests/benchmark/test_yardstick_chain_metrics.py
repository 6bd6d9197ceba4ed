"""The per-layer metrics of the serving span chain (PR 24): every metric the
manifest names has a reader that loads; the sixteen new ones read a value
from the program's counters and nothing — None, no raise — from a program
that has no such counter (the parent commit); a traced rehearsal of each
cell prints its eight."""

from __future__ import annotations

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.join(REPO, "benchmark"))
sys.path.insert(0, HERE)

import readers  # noqa: E402
from test_yardstick_dry_run import bench  # noqa: E402

METRICS_DIR = os.path.join(REPO, "benchmark", "metrics")
with open(os.path.join(REPO, "BENCHMARK.json")) as _fh:
    MANIFEST = json.load(_fh)

CHAIN = (
    "http_handoff_ms", "serve_submit_ms", "serve_wake_ms", "serve_tail_ms",
    "http_write_ms", "dispatch_self_ms", "batch_useful_rows_share",
    "batch_small_share",
)
NEW = [n + s for s in ("", ".saturated") for n in CHAIN]

# two /metrics scrapes' difference, as the serve driver hands it over:
# 20 dispatches, 12 of them of one or two queries, 53 queries in 64 rows
DELTA = {
    'pio_http_handoff_seconds_sum{server="engine"}': 0.053,
    'pio_http_handoff_seconds_count{server="engine"}': 53.0,
    "pio_serving_submit_seconds_sum": 0.0106, "pio_serving_submit_seconds_count": 53.0,
    "pio_serving_wake_seconds_sum": 0.159, "pio_serving_wake_seconds_count": 53.0,
    "pio_serving_tail_seconds_sum": 0.0212, "pio_serving_tail_seconds_count": 53.0,
    'pio_http_write_seconds_sum{server="engine"}': 0.106,
    'pio_http_write_seconds_count{server="engine"}': 53.0,
    "pio_batch_dispatch_self_seconds_sum": 0.05,
    "pio_batch_dispatch_self_seconds_count": 20.0,
    'pio_batch_rows_total{kind="real"}': 53.0,
    'pio_batch_rows_total{kind="padded"}': 64.0,
    'pio_batch_size_bucket{le="1"}': 8.0, 'pio_batch_size_bucket{le="2"}': 12.0,
    'pio_batch_size_bucket{le="+Inf"}': 20.0, "pio_batch_size_count": 20.0,
}
EXPECT = {
    "http_handoff_ms": 1.0, "serve_submit_ms": 0.2, "serve_wake_ms": 3.0,
    "serve_tail_ms": 0.4, "http_write_ms": 2.0, "dispatch_self_ms": 2.5,
    "batch_useful_rows_share": 100.0 * 53 / 64, "batch_small_share": 60.0,
}


@pytest.mark.parametrize(
    "name", [m["name"] for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]])
def test_every_named_metric_loads(name):
    read = readers.load_metric(METRICS_DIR, name)
    assert read({}, {"config": {}, "traffic": {}}) is None  # nothing measured


@pytest.mark.parametrize("name", NEW)
def test_chain_metric_reads_the_counters_or_nothing(name):
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
    saturated = name.endswith(".saturated")
    assert entry["moves"] == ("serve_qps" if saturated else "query_p50_ms")
    assert entry["source"] == "program_counter"
    read = readers.load_metric(METRICS_DIR, name)
    got = read({"counters_delta": DELTA}, {})
    assert got == pytest.approx(EXPECT[name.removesuffix(".saturated")])
    # the parent commit has none of these counters: nothing, not a raise
    old = {"pio_batch_dispatch_seconds_sum": 1.0, "pio_batch_dispatch_seconds_count": 9.0}
    if "batch_small_share" not in name:  # pio_batch_size is older than PR 24
        assert read({"counters_delta": old}, {}) is None
    assert read({"counters_delta": {}}, {}) is None
    assert read({}, {}) is None


@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
def test_traced_rehearsal_prints_the_chain(cell, tmp_path):
    proc = bench(["--workload", cell, "--seed", str(2**31 + 24), "--seconds", "3",
                  "--trace", "1", "--dry-run-cpu"], tmp_path)
    assert proc.returncode == 3, proc.stdout[-3000:] + proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    would = json.loads(next(ln for ln in lines if ln.startswith("would print: "))[13:])
    suffix = ".saturated" if cell.endswith("saturated") else ""
    for n in CHAIN:
        v = would["metrics"][n + suffix]["value"]
        assert v == v and 0.0 <= v < float("inf"), (n, v)
    m = would["metrics"]
    assert m["batch_useful_rows_share" + suffix]["value"] <= 100.0
    assert m["batch_small_share" + suffix]["value"] <= 100.0
    # every dispatch is counted: the dispatch histogram and the batch-size
    # histogram see the same events, so their means describe one population
    assert m["dispatch_self_ms" + suffix]["value"] <= m["dispatch_ms" + suffix]["value"]
