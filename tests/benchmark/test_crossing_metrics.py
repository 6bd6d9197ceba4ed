"""The seven crossing metrics (PR 50): each reads the program's series from a
window's counters_delta, rounds to four significant digits, and returns
None — no raise — from a program that has no such series (the parent commit);
the manifest stays clean; they are listed in the two yambda cells only, and
the other six cells' traced metric sets are what they were."""

from __future__ import annotations

import copy
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.join(REPO, "benchmark"))

import manifest  # noqa: E402
import readers  # noqa: E402
import run as bench_run  # noqa: E402

METRICS_DIR = os.path.join(REPO, "benchmark", "metrics")
with open(os.path.join(REPO, "BENCHMARK.json")) as _fh:
    MANIFEST = json.load(_fh)

STEADY = "retrieval-yambda.serve-steady"
SATURATED = "retrieval-yambda.serve-saturated"
# name -> (cell, unit, layer)
NEW = {
    "launch_ms": (STEADY, "ms", "dispatch"),
    "upload_ms": (STEADY, "ms", "dispatch"),
    "fetch_wait_ms": (STEADY, "ms", "score"),
    "fetch_read_ms": (STEADY, "ms", "dispatch"),
    "capture_stop_s": (STEADY, "s", "runtime"),
    "launch_ms.saturated": (SATURATED, "ms", "dispatch"),
    "upload_ms.saturated": (SATURATED, "ms", "dispatch"),
}

H2D = 'pio_device_transfer_seconds%s{direction="h2d",op="%s"}'
D2H = 'pio_device_transfer_seconds%s{direction="d2h",op="serve.answers"}'
# a window of 220 dispatches, as the serve driver hands it over
DELTA = {
    "pio_batch_dispatch_seconds_sum": 0.9218, "pio_batch_dispatch_seconds_count": 220.0,
    'pio_jit_call_seconds_sum{fn="retrieval.coarse_topk"}': 0.0891234,
    'pio_jit_call_seconds_count{fn="retrieval.coarse_topk"}': 220.0,
    'pio_jit_call_seconds_sum{fn="retrieval.rescore_gather"}': 0.0868766,
    'pio_jit_call_seconds_count{fn="retrieval.rescore_gather"}': 220.0,
    'pio_jit_call_seconds_sum{fn="topk.gather_top_k_batch"}': 0.0,
    'pio_jit_call_seconds_count{fn="topk.gather_top_k_batch"}': 0.0,
    H2D % ("_sum", "serve.dispatch"): 0.1100044, H2D % ("_count", "serve.dispatch"): 440.0,
    H2D % ("_sum", "serve.rules"): 0.00088, H2D % ("_count", "serve.rules"): 3.0,
    H2D % ("_sum", "serve.model_put"): 3.3, H2D % ("_count", "serve.model_put"): 1.0,
    D2H % "_sum": 0.099, D2H % "_count": 220.0,
    "pio_retrieval_fetch_wait_seconds_sum": 0.33004411,
    "pio_retrieval_fetch_wait_seconds_count": 220.0,
    'pio_profile_seconds_total{phase="start"}': 0.21,
    'pio_profile_seconds_total{phase="capture"}': 5.0004,
    'pio_profile_seconds_total{phase="stop"}': 8.87654321,
}
EXPECT = {
    "launch_ms": 0.8,  # 1e3 x 0.176 / 220
    "upload_ms": 0.504,  # 1e3 x (0.1100044 + 0.00088) / 220 = 0.50402
    "fetch_wait_ms": 1.5,  # 1.50020...
    "fetch_read_ms": 0.45,
    "capture_stop_s": 8.877,
}
# what the parent's two scrapes hold of a window: its stages, none of the
# crossings' series, and the transfer family without a duration
PARENT = {
    "pio_batch_dispatch_seconds_sum": 0.9218, "pio_batch_dispatch_seconds_count": 220.0,
    "pio_retrieval_fetch_seconds_sum": 0.44, "pio_retrieval_fetch_seconds_count": 220.0,
    "pio_retrieval_uploads_total": 440.0,
    'pio_jit_cache_hits_total{fn="retrieval.coarse_topk"}': 220.0,
    'pio_device_transfer_bytes_total{direction="h2d",op="serve.model_put"}': 9e9,
    'pio_device_transfers_total{direction="h2d",op="serve.model_put"}': 1.0,
}


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_crossing_metric_reads_the_window_or_nothing(name):
    read = readers.load_metric(METRICS_DIR, name)
    got = read({"counters_delta": DELTA}, {})
    assert got == EXPECT[name.removesuffix(".saturated")]  # four digits, exactly
    assert len(repr(got)) <= 6
    for nothing in ({"counters_delta": PARENT}, {"counters_delta": {}}, {}):
        assert read(nothing, {}) is None


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_crossing_metric_is_listed_in_its_yambda_cell_alone(name):
    cell, unit, layer = NEW[name]
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
    assert entry == {
        "name": name, "unit": unit, "better": "lower", "source": "program_counter",
        "layer": layer,
        "moves": "serve_qps" if cell == SATURATED else "query_p50_ms",
        "workloads": [cell],
    }
    assert os.path.exists(os.path.join(METRICS_DIR, name + ".py"))


def test_the_entries_were_appended_and_the_manifest_is_clean():
    assert [m["name"] for m in MANIFEST["per_layer"][-7:]] == [
        "launch_ms", "upload_ms", "fetch_wait_ms", "fetch_read_ms", "capture_stop_s",
        "launch_ms.saturated", "upload_ms.saturated",
    ]
    assert manifest.validate(MANIFEST, REPO) == []


@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
def test_only_the_yambda_cells_traced_sets_grew(cell):
    """A cell's traced metric set with and without the seven entries: the
    two yambda cells gained theirs, the other six report what they did."""
    before = copy.deepcopy(MANIFEST)
    before["per_layer"] = [m for m in before["per_layer"] if m["name"] not in NEW]

    def traced(m):
        return [d["name"] for d in bench_run.metrics_for(m, cell, True)]

    gained = [n for n, (c, _, _) in NEW.items() if c == cell]
    assert traced(MANIFEST) == traced(before) + gained
    assert len(gained) == {STEADY: 5, SATURATED: 2}.get(cell, 0)
    assert [d["name"] for d in bench_run.metrics_for(MANIFEST, cell, False)] == \
        [d["name"] for d in bench_run.metrics_for(before, cell, False)]


def test_the_identities_hold_on_the_synthetic_window():
    def val(name):
        return readers.load_metric(METRICS_DIR, name)({"counters_delta": {
            **DELTA,
            "pio_retrieval_shortlist_seconds_sum": 0.198, "pio_retrieval_shortlist_seconds_count": 220.0,
            "pio_retrieval_rescore_seconds_sum": 0.198, "pio_retrieval_rescore_seconds_count": 220.0,
            "pio_retrieval_fetch_seconds_sum": 0.4422, "pio_retrieval_fetch_seconds_count": 220.0,
        }}, {})

    assert val("upload_ms") + val("launch_ms") <= val("shortlist_ms") + val("rescore_ms")
    assert val("fetch_wait_ms") + val("fetch_read_ms") <= val("fetch_ms")
