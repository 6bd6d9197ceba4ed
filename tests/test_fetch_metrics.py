"""The ``fetch_ms*`` per-layer metrics (PR 29): the one blocking read that
ends a two-stage dispatch. Each is a data file beside ``shortlist_ms*``
that reads ``pio_retrieval_fetch_seconds`` from a counters delta, or nothing
— None, no raise — from a program that has no such histogram (the parent
commit); a traced CPU rehearsal of a cell prints its own. Kept outside
tests/benchmark/: this PR adds data files to the benchmark, no code. The
sharded cell (PR 32) runs both stages and the merge as ONE program, so its
chain is ``shortlist_ms.sharded`` (the enqueue) and ``fetch_ms.sharded``.

``fetch_ms.storefront`` has its file and NO ``per_layer`` entry yet: the
accepted tests/benchmark/test_storefront_cell.py counts that cell's traced
metrics (7 + 8 + 7), and only a ``benchmark`` PR may change the count with
the entry. Until then the cell's read is ``dispatch_ms`` less its stages."""

from __future__ import annotations

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, os.path.join(REPO, "benchmark"))
sys.path.insert(0, os.path.join(HERE, "benchmark"))

import readers  # noqa: E402
import run as bench_run  # noqa: E402
from test_yardstick_dry_run import bench  # noqa: E402

METRICS_DIR = os.path.join(REPO, "benchmark", "metrics")
with open(os.path.join(REPO, "BENCHMARK.json")) as _fh:
    MANIFEST = json.load(_fh)

FETCH = {  # metric -> (the end-to-end metric it moves, the cells that list it)
    "fetch_ms": ("query_p50_ms", ["retrieval-yambda.serve-steady",
                                  "recommendation-amazon23-int8.serve-onechip-steady",  # PR 41
                                  "recommendation-amazon23-int8-live.serve-foldin-steady"]),  # PR 45
    "fetch_ms.saturated": ("serve_qps", ["retrieval-yambda.serve-saturated"]),
    "fetch_ms.storefront": ("query_p50_ms", ["ecommerce-taobao.serve-storefront"]),
    "fetch_ms.itempage": ("query_p50_ms", ["similarproduct-taobao.serve-itempage"]),  # PR 30
    "fetch_ms.sharded": ("query_p50_ms", ["recommendation-amazon23.serve-sharded-steady"]),  # PR 32
    "fetch_ms.shardstore": ("query_p50_ms",
                            ["ecommerce-amazon23.serve-storefront-sharded"]),  # PR 48
}
LISTED = [n for n in FETCH if n != "fetch_ms.storefront"]  # see the docstring


@pytest.mark.parametrize("name", FETCH)
def test_fetch_metric_reads_its_histogram_or_nothing(name):
    entries = [m for m in MANIFEST["per_layer"] if m["name"] == name]
    assert len(entries) == (name in LISTED)
    for entry in entries:
        moves, cells = FETCH[name]
        twin = next(m for m in MANIFEST["per_layer"]
                    if m["name"] == name.replace("fetch_ms", "shortlist_ms"))
        assert {**entry, "name": twin["name"]} == twin  # beside shortlist_ms*, alike
        assert (entry["moves"], entry["workloads"], entry["layer"]) == (moves, cells, "score")
        # beside its twin: behind it in the list, its file next to the twin's
        # (what later PRs append behind both is theirs to check)
        assert MANIFEST["per_layer"].index(twin) < MANIFEST["per_layer"].index(entry)
        assert os.path.exists(os.path.join(METRICS_DIR, twin["name"] + ".json"))
    with open(os.path.join(METRICS_DIR, name + ".json")) as fh:
        assert json.load(fh) == {"reader": "histogram_mean", "scale": 1000.0,
                                 "series": "pio_retrieval_fetch_seconds"}
    read = readers.load_metric(METRICS_DIR, name)
    delta = {"pio_retrieval_fetch_seconds_sum": 0.55,
             "pio_retrieval_fetch_seconds_count": 100.0,
             "pio_retrieval_host_reads_total": 100.0}
    assert read({"counters_delta": delta}, {}) == pytest.approx(5.5)
    # the parent reads four times a dispatch and times none of them
    old = {"pio_retrieval_shortlist_seconds_sum": 0.7,
           "pio_retrieval_shortlist_seconds_count": 100.0,
           "pio_retrieval_rescore_seconds_sum": 0.23,
           "pio_retrieval_rescore_seconds_count": 100.0}
    assert read({"counters_delta": old}, {}) is None
    assert read({"counters_delta": {}}, {}) is None
    assert read({}, {}) is None


@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
def test_each_cell_reports_its_own_fetch_metric_and_no_other(cell):
    """A traced run reports the cell's listed ``fetch_ms*`` and no other, an
    untraced run none."""
    traced = {d["name"] for d in bench_run.metrics_for(MANIFEST, cell, True)}
    want = {n for n in LISTED if cell in FETCH[n][1]}
    assert {n for n in traced if n.startswith("fetch_ms")} == want
    untraced = {d["name"] for d in bench_run.metrics_for(MANIFEST, cell, False)}
    assert not any(n.startswith("fetch_ms") for n in untraced)


@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
def test_traced_rehearsal_prints_the_three_stages(cell, tmp_path):
    proc = bench(["--workload", cell, "--seed", str(2**31 + 29), "--seconds", "3",
                  "--trace", "1", "--dry-run-cpu"], tmp_path)
    assert proc.returncode == 3, proc.stdout[-3000:] + proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    would = json.loads(next(ln for ln in lines if ln.startswith("would print: "))[13:])
    m = would["metrics"]
    sharded = "sharded" in cell  # PR 32: one program a dispatch, no second enqueue
    sfx = ".shardstore" if cell.endswith("storefront-sharded") else \
        ".sharded" if sharded else \
        "." + cell.rsplit("-", 1)[1] if not cell.endswith("steady") else ""
    # the score layer's three stages lie inside a dispatch, one after the
    # other, and the wait for the device is in the last of them
    listed = "fetch_ms" + sfx in LISTED
    assert ("fetch_ms" + sfx in m) == listed
    assert ("rescore_ms" + sfx in m) != sharded
    names = ["shortlist_ms"] + ["rescore_ms"] * (not sharded) + ["fetch_ms"] * listed
    stages = [m[n + sfx]["value"] for n in names]
    assert all(v == v and v > 0.0 for v in stages), stages
    dispatch = "dispatch_ms" + (".saturated" if cell.endswith("saturated") else "")
    assert sum(stages) <= m[dispatch]["value"]
