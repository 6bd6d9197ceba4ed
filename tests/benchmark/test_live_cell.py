"""The live cell's own pieces (PR 45): the manifest with it (every list it
joined, by MEMBERSHIP), its configuration against the twin's, everything found
by name with no harness edit, the seeded deployment (histories, warm-up
bursts, the stream's shares), the reference's solve against float64 normal
equations, the prefix rule on a hand-made timeline, the readers against hand
sums, a traced CPU rehearsal of every phase (Event Server, speed layer,
refresh queries, the three controls) with its line under the cut, and the
rest of two broken paths — a patch that is dropped, a solve whose products
round their operands to bf16: `correct` has to come out false."""

from __future__ import annotations

import importlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.join(REPO, "benchmark"))

import live_data  # noqa: E402
import manifest as manifest_rules  # noqa: E402
import readers  # noqa: E402
import reference_foldin as ref  # noqa: E402
import run as bench_run  # noqa: E402

MANIFEST = os.path.join(REPO, "BENCHMARK.json")
RUN = os.path.join(REPO, "benchmark", "run.py")
CELL = "recommendation-amazon23-int8-live.serve-foldin-steady"
CONFIG = "recommendation-amazon23-int8-live"
TWIN = "recommendation-amazon23-int8"
TWIN_CELL = "recommendation-amazon23-int8.serve-onechip-steady"
METRICS = os.path.join(REPO, "benchmark", "metrics")
OWN = {"foldin_cycle_ms.live", "event_to_patch_ms.live", "patch_h2d_mb.live",
       "foldin_device_ms.live", "resident_gb.live"}
JOINED = {"shortlist_ms", "rescore_ms", "fetch_ms", "shortlist_roofline",
          "worker_busy_share", "proc_stall_ms_max"}
CHAIN = {"http_handoff_ms", "serve_submit_ms", "serve_wake_ms", "serve_tail_ms",
         "http_write_ms", "dispatch_self_ms", "batch_useful_rows_share", "batch_small_share"}
LISTLESS = {"gen_late_ms_p99", "query_p95_ms.steady", "query_p99_ms.steady",
            "batch_queue_wait_ms", "batch_size_mean", "dispatch_ms", "device_idle_share"}
TWINS_ALONE = {"coarse_int8_dot_share.int8", "rescore_device_ms.int8", "resident_gb.int8"}


def _manifest() -> dict:
    with open(MANIFEST) as fh:
        return json.load(fh)


def _cell():
    return bench_run.resolve(_manifest(), CELL, REPO)


def _config(name: str) -> dict:
    with open(os.path.join(REPO, "benchmark", "configs", name + ".json")) as fh:
        return json.load(fh)


class TestManifest:
    def test_the_manifest_with_the_cell_keeps_the_rules(self):
        assert manifest_rules.validate(_manifest(), REPO) == []

    def test_one_one_chip_cell_of_one_new_configuration(self):
        m = _manifest()
        cell = next(w for w in m["workloads"] if w["name"] == CELL)
        assert (cell["chips"], cell["config"], cell["traffic"]) == \
            (1, CONFIG, "amazon23-int8-foldin-steady")
        assert [w["name"] for w in m["workloads"] if w["config"] == CONFIG] == [CELL]
        assert len(cell["why"]) <= 200 and "1%" in cell["why"]  # the honest size
        conf = next(c for c in m["configs"] if c["name"] == CONFIG)
        assert conf["reduced"] == ["users", "events"] and len(conf["source"]) <= 200
        sources = [c["source"] for c in m["configs"]]
        assert sources.count(conf["source"]) == 1
        assert len({c["file"] for c in m["configs"]}) == len(m["configs"])

    @pytest.mark.parametrize("name", sorted(JOINED | CHAIN))
    def test_the_cell_is_in_every_list_it_joined(self, name):
        """By membership, never by position: a later PR appends behind it."""
        metric = next(p for p in _manifest()["per_layer"] if p["name"] == name)
        assert metric["workloads"].count(CELL) == 1 and metric["moves"] == "query_p50_ms"

    @pytest.mark.parametrize("name", sorted(TWINS_ALONE))
    def test_the_twins_own_metrics_stay_the_twins(self, name):
        metric = next(p for p in _manifest()["per_layer"] if p["name"] == name)
        assert CELL not in metric["workloads"]

    @pytest.mark.parametrize("name", sorted(OWN))
    def test_its_own_metrics_list_it_alone(self, name):
        metric = next(p for p in _manifest()["per_layer"] if p["name"] == name)
        assert metric["workloads"] == [CELL] and metric["moves"] == "query_p50_ms"
        assert metric["layer"] == "realtime" and metric["better"] == "lower"
        assert metric["source"] == ("device_trace" if name == "foldin_device_ms.live"
                                    else "program_counter")

    def test_the_cell_reports_query_p50_and_setup(self):
        m = _manifest()
        p50 = next(e for e in m["end_to_end"] if e["name"] == "query_p50_ms")
        assert CELL in p50["workloads"] and p50["bound"] == 0.05
        assert [d["name"] for d in bench_run.metrics_for(m, CELL, trace=False)] \
            == ["query_p50_ms", "setup_s"]

    def test_everything_is_found_by_name(self):
        cell = _cell()
        assert cell["traffic"]["driver"] == "live"
        assert importlib.import_module("drivers.live").run
        defs = bench_run.metrics_for(_manifest(), CELL, trace=True)
        assert {d["name"] for d in defs} == OWN | JOINED | CHAIN | LISTLESS
        for d in defs:
            assert callable(readers.load_metric(METRICS, d["name"]))

    def test_every_size_and_width_is_the_twins(self):
        cfg, twin = _cell()["config"], _config(TWIN)
        for key in ("num_users", "num_items", "rank", "factor_dtype", "chips", "retrieval",
                    "quantize", "variant", "check_sample", "architecture"):
            assert cfg[key] == twin[key], key
        assert cfg["limits"]["score_gap_max"]["limit"] == twin["limits"]["score_gap_max"]["limit"]
        assert {k: v for k, v in cfg["published"].items() if k in twin["published"]} \
            == twin["published"]
        assert cfg["reduced"] == ["users", "events"]
        assert cfg["deploy_flags"] == twin["deploy_flags"] + ["--realtime", "1"]
        assert cfg["server_env"] == {"PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "DB"}
        assert set(cfg["guarantees"]) >= {"model", "fresh", "durable", "steady"}
        for key in ("release_dates", "history_lengths", "ratings", "writers"):
            assert key in cfg["assumed"]

    def test_the_traffic_is_the_issues(self):
        mix = _cell()["traffic"]
        ev = mix["events"]
        assert (mix["rate_qps"], mix["num"], mix["connections"]) == (20.0, 10, 256)
        assert (ev["rate_eps"], ev["refresh_after_s"], ev["guarantee_s"], ev["interval_s"]) \
            == (2.2, 3.0, 2.0, 1.0)
        assert ev["shares"] == {"new_user": 0.095, "cold_item": 0.084}
        assert (ev["start_after_s"], ev["stop_before_s"]) == (1.0, 3.5)
        assert ev["guarantee_s"] == 2 * ev["interval_s"] < ev["refresh_after_s"]
        toy = mix["toy"]["events"]
        assert toy["guarantee_s"] == 2 * toy["interval_s"] < toy["refresh_after_s"]
        assert mix["timeout_s"] <= 120 and ev["ack_limit_s"] <= 10  # every wait bounded
        twin_mix = bench_run.resolve(_manifest(), TWIN_CELL, REPO)["traffic"]
        assert mix["rate_qps"] == twin_mix["rate_qps"] == 0.1 * mix["knee_qps"]

    def test_the_window_opens_on_a_settled_phase(self):
        # the first ~10 s of a measured phase answer slower and less evenly on
        # the chip's host (why_warm_in): the window opens after them, the
        # rehearsal keeps the short clock, and the refresh queries that fall
        # in the window are the ISSUE's 2.2 a second within a tenth
        import loadgen_live

        mix = _cell()["traffic"]
        ev = mix["events"]
        assert mix["warm_in_s"] >= 10.0 and "why_warm_in" in mix
        assert mix["toy"]["warm_in_s"] == 3.0
        assert ev["start_after_s"] < mix["warm_in_s"]  # events from the warm-in's first second
        ph = {"warm_in_s": mix["warm_in_s"], "seconds": 20.0}
        assert loadgen_live.event_count(ev, ph) == round(
            ev["rate_eps"] * (mix["warm_in_s"] + 20.0 - 4.5))
        # acknowledged from refresh_after_s before the opening to stop_before_s
        # before the close: their refresh queries are due inside the window
        in_window = ev["rate_eps"] * (20.0 - ev["stop_before_s"] + ev["refresh_after_s"])
        assert abs(in_window / 20.0 - ev["rate_eps"]) <= 0.1

    def test_the_rates_arithmetic(self):
        p = _cell()["config"]["published"]
        assert round(p["reviews"] / p["users"], 2) == 10.49
        assert round(p["users"] / p["reviews"], 3) == 0.095
        assert round(p["items"] / p["reviews"], 3) == 0.084
        assert round((p["reviews"] - 233.1e6) / 155e6, 1) == 2.2


class TestTheDeployment:
    CFG = {"num_users": 8000, "num_items": 40000,
           "events": {"writers": 400, "history_mean": 10.49, "history_longest": 24,
                      "rating_shares": [0.10, 0.05, 0.08, 0.17, 0.60]}}

    def test_histories_have_the_sources_mean_and_never_pass_the_longest(self):
        n = live_data.history_lengths(3, 200_000, 10.49, 24)
        assert n.min() == 1 and n.max() == 24 and abs(n.mean() - 10.49) < 0.05

    def test_the_same_seed_gives_the_same_store_and_stream(self):
        a, b = live_data.Deployment(self.CFG, 2**31 + 5), live_data.Deployment(self.CFG, 2**31 + 5)
        assert a.warm_bursts() == b.warm_bursts()
        shares = {"new_user": 0.095, "cold_item": 0.084}
        assert a.stream(60, shares) == b.stream(60, shares)
        c = live_data.Deployment(self.CFG, 2**31 + 6)
        c.warm_bursts()
        assert c.stream(60, shares) != a.stream(60, shares)

    def test_the_warm_up_bursts_reach_every_shape(self):
        dep = live_data.Deployment(self.CFG, 9)
        bursts = dep.warm_bursts()
        assert [len(b) for b in bursts] == [1, 9, 1, 9, 1, 9]
        for burst, k in zip(bursts, (8, 8, 16, 16, 32, 32)):
            need = [dep.distinct(u) for u, _, _ in burst]
            assert max(need) <= k and (k == 8 or max(need) > k // 2)
        assert len({u for b in bursts for u, _, _ in b}) == 30  # no user twice

    def test_the_stream_keeps_the_shares_and_rates_no_item_twice(self):
        dep = live_data.Deployment(self.CFG, 4)
        dep.warm_bursts()
        stream = dep.stream(4000, {"new_user": 0.095, "cold_item": 0.084})
        kinds = np.bincount([k for k, _, _, _ in stream], minlength=3) / 4000.0
        assert abs(kinds[live_data.NEW_USER] - 0.095) < 0.015
        assert abs(kinds[live_data.COLD_ITEM] - 0.084) < 0.015
        seen = {}
        for kind, user, item, star in stream:
            assert 1 <= star <= 5
            assert (user >= 8000) == (kind == live_data.NEW_USER)
            assert (item >= 40000) == (kind == live_data.COLD_ITEM)
            mine = seen.setdefault(user, set(dep.history(user)[0].tolist()))
            assert item not in mine
            mine.add(item)
        stars = np.bincount([s for _, _, _, s in stream], minlength=6)[1:] / 4000.0
        assert np.abs(stars - np.asarray(self.CFG["events"]["rating_shares"])).max() < 0.03

    def test_an_event_is_the_quickstarts(self):
        e = json.loads(live_data.event_body(12, 345, 4))
        assert e == {"event": "rate", "entityType": "user", "entityId": "u12",
                     "targetEntityType": "item", "targetEntityId": "i345",
                     "properties": {"rating": 4}}


class TestTheReference:
    def test_the_solve_is_float64_normal_equations(self):
        rng = np.random.default_rng(1)
        v = rng.standard_normal((7, 64)).astype(np.float32)
        r = rng.integers(1, 6, 7).astype(np.float64)
        a = v.astype(np.float64)
        want = np.linalg.solve(a.T @ a + 0.05 * 7 * np.eye(64), a.T @ r)
        got = ref.solve(v, r, 0.05)
        assert got.dtype == np.float32 and np.abs(got - want).max() < 1e-6 * np.abs(want).max()
        # the K x K form the program solves is the same row
        dual = a.T @ np.linalg.solve(a @ a.T + 0.05 * 7 * np.eye(7), r)
        assert np.abs(dual - want).max() < 1e-12

    def test_a_history_is_replayed_in_order_and_cold_items_skipped(self):
        items, ratings = ref.rated([(5, 1), (9, 4), (5, 3), (100, 5), (2, 2)], num_items=100)
        assert items.tolist() == [5, 9, 2] and ratings.tolist() == [3.0, 4.0, 2.0]
        assert len(ref.rated([(100, 5)], num_items=100)[0]) == 0

    def test_the_prefix_rule_on_a_hand_made_timeline(self):
        """Three events of one user; a query sent at t = 10 with a 2 s
        guarantee: an event acknowledged 2.1 s before it MUST be in, one 1.9 s
        before it MAY be out, one posted after the answer came back is out."""
        posted = [7.89, 8.09, 10.5]
        acked = [7.9, 8.1, 10.51]
        assert ref.required_and_allowed(acked, posted, 10.0, 10.02, 2.0) == (1, 2)
        assert ref.required_and_allowed(acked, posted, 10.11, 10.13, 2.0) == (2, 2)
        assert ref.required_and_allowed(acked, posted, 10.0, 10.6, 2.0) == (1, 3)
        assert ref.required_and_allowed(acked, posted, 5.0, 5.02, 2.0) == (0, 0)
        assert ref.required_and_allowed([], [], 5.0, 5.02, 2.0) == (0, 0)
        # an acknowledgement that never came: never required, but it may be in
        assert ref.required_and_allowed([7.9, np.inf], [7.89, 8.09], 12.0, 12.02, 2.0) == (1, 2)


def test_a_chunk_a_process_is_the_twins_scan():
    """``reference_foldin.scan`` (a chunk a process, for the machine's memory)
    gives ``reference_int8.scan``'s answer: scores, ids, the served items'
    own scores and both kinds of control, equal to the bit, over a catalog of
    two chunks the last of them short."""
    import reference_int8

    rng = np.random.default_rng(0)
    n = 2_300_000
    q = rng.standard_normal((5, 64)).astype(np.float32)
    served = rng.integers(0, n, (5, 10))
    served[3, 5:] = -1
    controls = {"bfloat16": (q, "int8", "bfloat16"), "unquantized": (q, "unquantized", "float32")}
    a = reference_int8.scan(5, n, 64, q, 10, served=served, controls=controls, workers=2)
    b = ref.scan(5, n, 64, q, 10, served=served, controls=controls, workers=2)
    for x, y in zip(a[:3], b[:3]):
        assert np.array_equal(x, y, equal_nan=True)
    for name in controls:
        for x, y in zip(a[3][name], b[3][name]):
            assert np.array_equal(x, y, equal_nan=True)


class TestReaders:
    def test_patch_h2d_mb_is_bytes_a_patch(self):
        read = readers.load_metric(METRICS, "patch_h2d_mb.live")
        site = '{direction="h2d",op="serve.model_patch"}'
        d = {"pio_device_transfer_bytes_total" + site: 18 * 576.0,
             "pio_device_transfers_total" + site: 18.0,
             'pio_device_transfer_bytes_total{direction="h2d",op="serve.model_put"}': 9e9}
        assert read({"counters_delta": d}, {}) == pytest.approx(0.000576)
        whole = {"pio_device_transfer_bytes_total" + site: 3 * 6.5e9,
                 "pio_device_transfers_total" + site: 3.0}
        assert read({"counters_delta": whole}, {}) == pytest.approx(6500.0)  # the parent's
        assert read({"counters_delta": {}}, {}) is None and read({}, {}) is None

    def test_foldin_device_ms_is_a_cycles_mean(self):
        read = readers.load_metric(METRICS, "foldin_device_ms.live")
        t = {"programs": {"jit__solve_rows": 0.004, "jit_patch_rows": 0.002, "jit__coarse_topk": 1.0},
             "program_calls": {"jit__solve_rows": 2, "jit_patch_rows": 2}}
        assert read({"trace": t}, {}) == pytest.approx(3.0)
        assert read({"trace": {"programs": {"jit__coarse_topk": 1.0}}}, {}) is None  # the parent
        assert read({}, {}) is None

    def test_resident_gb_is_the_twins_reader(self):
        series = "pio_model_resident_bytes"
        g = {series + '{part="%s"}' % p: v for p, v in (
            ("table", 3084160000.0), ("table_scales", 192760000.0), ("coarse", 3087007744.0),
            ("coarse_scales", 192937984.0), ("coarse_ids", 192937984.0),
            ("users", 1048576 * 68.0))}
        live = readers.load_metric(METRICS, "resident_gb.live")({"gauges_close": g}, {})
        assert live == readers.load_metric(METRICS, "resident_gb.int8")({"gauges_close": g}, {})
        assert live == pytest.approx(6.821, abs=1e-3)
        assert readers.load_metric(METRICS, "resident_gb.live")({}, {}) is None

    @pytest.mark.parametrize("name,series", [
        ("foldin_cycle_ms.live", "pio_foldin_solve_seconds"),
        ("event_to_patch_ms.live", "pio_serving_freshness_seconds")])
    def test_the_two_histogram_means(self, name, series):
        read = readers.load_metric(METRICS, name)
        d = {series + "_sum": 0.09, series + "_count": 18.0}
        assert read({"counters_delta": d}, {}) == pytest.approx(5.0)
        assert read({"counters_delta": {}}, {}) is None


def _bench(tmp_path, *args, manifest=None):
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", TMPDIR=str(tmp_path), PYTHONPATH="", BENCH_RUN="ignored",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jc"))
    cmd = [sys.executable, RUN, "--workload", CELL, "--dry-run-cpu", *args]
    if manifest:
        cmd += ["--manifest", str(manifest)]
    return subprocess.run(cmd, capture_output=True, text=True, env=env, cwd=REPO, timeout=900)


def _checks(proc):
    return {c["name"]: c for c in (
        json.loads(ln[7:]) for ln in proc.stdout.splitlines() if ln.startswith("check: "))}


def test_a_traced_cpu_rehearsal_passes_every_phase_and_each_control_fails(tmp_path):
    proc = _bench(tmp_path, "--seed", str(2**31 + 45), "--seconds", "3", "--trace", "1",
                  "--control", "1")
    assert proc.returncode == 3, proc.stdout[-3000:] + proc.stderr[-3000:]
    assert "dry run on cpu: every phase passed" in proc.stdout
    checks = _checks(proc)
    for name, c in checks.items():
        if not c.get("control"):
            assert c["pass"], c
    for name in ("refresh_score_gap_max", "refresh_answers_checked", "restaged_parts",
                 "resident_bytes_growth", "acknowledged_events_missing", "breaker_closed",
                 "events_behind_at_close", "new_user_answer_items_min", "compiles_in_window"):
        assert name in checks, name
    assert checks["refresh_answers_checked"]["value"] >= 3
    for name in ("control.refresh_score_gap_max(stale)", "control.score_gap_max(unrequantized)",
                 "control.score_gap_max(bfloat16)"):
        assert checks[name]["control"] and not checks[name]["pass"], checks[name]
    times = json.loads(next(ln for ln in proc.stdout.splitlines()
                            if ln.startswith("times: "))[7:])
    ev = times["events"]
    assert ev["posted"] == ev["acknowledged"] == ev["refresh_queries"] >= 3
    assert times["realtime"]["events_folded"] >= 30 + ev["posted"]  # the warm-up's, the run's
    assert times["realtime"]["events_behind"] == 0
    line = next(ln for ln in proc.stdout.splitlines() if ln.startswith("would print: "))[13:]
    assert len(line) < 2000  # run.py cuts a rehearsal's line there
    m = json.loads(line)["metrics"]
    assert set(m) >= (OWN - {"foldin_device_ms.live"}) | (JOINED - {"shortlist_roofline"}) | CHAIN
    assert m["patch_h2d_mb.live"]["value"] < 0.01
    assert m["foldin_cycle_ms.live"]["value"] > 0 and m["event_to_patch_ms.live"]["value"] > 0


DROPPED = '''
import sys
from predictionio_tpu.server import engine_server
# the fold runs and is told its patch was applied; the served model never changes
engine_server._Variant.apply_patch = lambda self, models, expected_epoch: True
from predictionio_tpu.cli.main import main
sys.exit(main(sys.argv[1:]))
'''

ROUNDED = '''
import sys
import jax.numpy as jnp
from predictionio_tpu.ops import retrieval
from predictionio_tpu.realtime import foldin
_rows, _solve = retrieval._table_rows, foldin._solve_rows
def _rounded(table, ixs):
    # what a default-precision f32 product does to its operands on a TPU:
    # the gathered rows reach both of the solve's products rounded to bf16
    return _rows(table, ixs).astype(jnp.bfloat16).astype(jnp.float32)
def _solve_rows(*args, **kwargs):  # the fold's program alone is traced so
    retrieval._table_rows = _rounded
    try:
        return _solve(*args, **kwargs)
    finally:
        retrieval._table_rows = _rows
foldin._solve_rows = _solve_rows
from predictionio_tpu.cli.main import main
sys.exit(main(sys.argv[1:]))
'''


@pytest.mark.parametrize("name,entry_text,fails", [
    ("a patch that is dropped", DROPPED, "refresh_score_gap_max"),
    ("a solve at default precision", ROUNDED, "refresh_score_gap_max"),
])
def test_a_broken_path_is_not_correct(tmp_path, name, entry_text, fails):
    entry = tmp_path / "broken_server.py"
    entry.write_text(entry_text)
    m = _manifest()
    cfg = _config(CONFIG)
    cfg["server_entry"] = [str(entry)]
    (tmp_path / "broken.json").write_text(json.dumps(cfg))
    for c in m["configs"]:
        if c["name"] == CONFIG:
            c["file"] = str(tmp_path / "broken.json")
    (tmp_path / "manifest.json").write_text(json.dumps(m))
    proc = _bench(tmp_path, "--seed", "77", "--seconds", "3", "--trace", "0",
                  manifest=tmp_path / "manifest.json")
    assert proc.returncode == 1, proc.stdout[-2000:] + proc.stderr[-2000:]
    assert "dry run on cpu: NOT correct" in proc.stdout
    checks = _checks(proc)
    assert not checks[fails]["pass"], checks[fails]
    assert checks["compiles_in_window"]["pass"] and checks["restaged_parts"]["pass"]
