"""The serving driver's own arithmetic, with no server: which phases a
traffic mix turns into, which requests a window counts, which metrics a
run reports, and the answer check against the plain reference."""

from __future__ import annotations

import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(REPO, "benchmark"))

import factors  # noqa: E402
import reference  # noqa: E402
import run as bench_run  # noqa: E402
import traffic  # noqa: E402
from drivers import serve  # noqa: E402

OPEN = {"loop": "open", "rate_qps": 12.0, "num": 10, "warm_dispatches": 5,
        "warm_clients": [3, 16], "warm_seconds_each": 1.0, "warm_in_s": 3.0}
CLOSED = {**OPEN, "loop": "closed", "clients": 16, "max_requests": 999}


class TestPhases:
    def test_open_mix_warms_then_measures_once(self):
        ph = serve._phases(OPEN, 20.0, None, None)
        assert [p["label"] for p in ph] == ["warm-dispatches", "warm-3", "warm-16", "window"]
        assert ph[0]["clients"] == 1 and ph[0]["min_requests"] == 5
        w = ph[-1]
        assert w["measure"] and w["rate_qps"] == 12.0 and w["seconds"] == 20.0
        assert w["warm_in_s"] == 3.0 and "profile" not in w
        assert not any(p.get("measure") for p in ph[:-1])

    def test_closed_mix_measures_its_clients(self):
        w = serve._phases(CLOSED, 20.0, None, None)[-1]
        assert w["clients"] == 16 and w["max_requests"] == 999 and "rate_qps" not in w

    def test_a_traced_run_profiles_a_part_of_the_window(self):
        w = serve._phases({**OPEN, "trace_seconds": 5.0}, 20.0, "/t", None)[-1]
        assert w["profile"] == {"seconds": 5.0, "out": "/t"}
        w = serve._phases({**OPEN, "trace_seconds": 5.0}, 2.0, "/t", None)[-1]
        assert w["profile"]["seconds"] == 2.0

    def test_a_ladder_warms_every_bucket_and_measures_each_rung(self):
        ph = serve._phases(OPEN, 20.0, None, ([6.0, 40.0], 10.0))
        assert [p["label"] for p in ph if not p.get("measure")] == [
            "warm-dispatches", "warm-3", "warm-5", "warm-9", "warm-16"]
        rungs = [p for p in ph if p.get("measure")]
        assert [(p["label"], p["rate_qps"], p["seconds"]) for p in rungs] == [
            ("rung-6", 6.0, 10.0), ("rung-40", 40.0, 10.0)]


def _window(loop):
    # phase 0 is warm-up; phase 1 opens at t=10 and closes at t=20
    res = {
        "phase": np.array([0, 1, 1, 1, 1]),
        "due": np.array([1.0, 9.0, 10.5, 19.9, 12.0]),
        "sent": np.array([1.0, 9.0, 10.501, 19.9, 12.004]),
        "done": np.array([1.1, 10.2, 10.52, 20.3, 12.03]),
        "status": np.array([200, 200, 200, 200, 500]),
    }
    scrape = "pio_jit_compiles_total{{fn=\"a\"}} {c}\npio_batch_size_sum {s}\npio_batch_size_count {n}\n"
    w = {"t_open": 10.0, "t_close": 20.0,
         "metrics_open": scrape.format(c=7, s=10, n=10),
         "metrics_close": scrape.format(c=7, s=40, n=20)}
    return serve.window_raw(w, res, 1, {"loop": loop})


class TestWindow:
    def test_open_loop_counts_every_request_due_in_the_window(self):
        raw = _window("open")
        # due 9.0 is warm-in; due 19.9 counts though it finished after the close
        assert raw["indices"].tolist() == [2, 3, 4]
        assert raw["attempted"] == 3 and raw["status_failed"] == 1 and raw["completed"] == 2
        assert raw["latencies_ms"] == pytest.approx([20.0, 400.0, 30.0])  # from the DUE time
        assert raw["late_ms"] == pytest.approx([1.0, 0.0, 4.0])
        assert raw["window_s"] == 10.0 and raw["compiles_in_window"] == 0

    def test_closed_loop_counts_completions_inside_the_window(self):
        raw = _window("closed")
        assert raw["indices"].tolist() == [1, 2, 4]  # done at 20.3 is outside
        assert raw["completed"] == 2 and raw["status_failed"] == 1

    def test_counter_deltas_span_the_window(self):
        raw = _window("open")
        assert raw["counters_delta"]["pio_batch_size_sum"] == 30.0
        assert raw["counters_delta"]["pio_batch_size_count"] == 10.0


class TestWhichMetrics:
    @pytest.fixture(scope="class")
    def manifest(self):
        with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
            return json.load(fh)

    def test_untraced_run_reports_the_cells_end_to_end_metrics(self, manifest):
        for cell in manifest["workloads"]:
            names = {m["name"] for m in bench_run.metrics_for(manifest, cell["name"], False)}
            assert "setup_s" in names and len(names) >= 2
            for m in manifest["end_to_end"]:
                assert (m["name"] in names) == (cell["name"] in m.get("workloads", [cell["name"]]))

    def test_traced_run_reports_only_metrics_that_move_what_the_cell_reports(self, manifest):
        for cell in manifest["workloads"]:
            e2e = {m["name"] for m in bench_run.metrics_for(manifest, cell["name"], False)}
            layer = bench_run.metrics_for(manifest, cell["name"], True)
            assert layer and all(m["moves"] in e2e for m in layer)


class TestAnswerCheck:
    CFG = {"num_users": 50, "num_items": 3000, "rank": 64, "check_sample": 8,
           "limits": {"score_gap_max": {"limit": 1e-4}, "overlap_min": {"limit": 0.9},
                      "overlap_mean_min": {"limit": 0.999}}}

    def _served(self, seed, spoil=None):
        U = factors.user_factors(seed, 50, 64)
        V = factors.item_factors(seed, 3000, 64)
        users = traffic.user_order(seed, 50, 12)
        s, i = reference.top_k_scan(U[users], V, 10, block=1024)
        bodies = []
        for r in range(len(users)):
            sc = s[r].astype(float).tolist()
            if spoil is not None and r == spoil:
                sc[3] -= 1e-3
            bodies.append(json.dumps({"itemScores": [
                {"item": f"i{int(it)}", "score": v} for it, v in zip(i[r], sc)]}))
        return users, bodies

    @pytest.mark.parametrize("seed", [5, 2**31 + 9])
    def test_sound_answers_pass_every_limit(self, seed):
        users, bodies = self._served(seed)
        checks, malformed = serve.check_answers(self.CFG, seed, users, bodies, np.arange(12), 10, True)
        by = {c["name"]: c for c in checks}
        assert malformed == 0 and by["answers_compared"]["value"] == 8
        assert all(c["pass"] for c in checks if not c.get("control"))
        assert not by["control.score_gap_max(bfloat16)"]["pass"]  # the control fails
        assert by["control.score_gap_max(bfloat16)"]["smallest"] > 3e-4

    def test_one_altered_score_in_any_answer_fails_when_sampled(self):
        seed = 5
        users, bodies = self._served(seed)
        pick = traffic.sample_indices(seed, 12, 8)
        users, bodies = self._served(seed, spoil=int(pick[0]))
        checks, _ = serve.check_answers(self.CFG, seed, users, bodies, np.arange(12), 10, False)
        by = {c["name"]: c for c in checks}
        assert not by["score_gap_max"]["pass"] and by["overlap_min"]["pass"]

    def test_a_malformed_answer_counts_as_failed(self):
        users, bodies = self._served(5)
        bodies[0] = None
        bodies[1] = json.dumps({"itemScores": json.loads(bodies[1])["itemScores"][:9]})
        _, malformed = serve.check_answers(self.CFG, 5, users, bodies, np.arange(12), 10, False)
        assert malformed == 2
