"""The yardstick's own arithmetic: percentiles, lateness, the stratified exponential
schedule, the byte/op functions against hand sums, the peaks table, the
trace reduction on a small recorded trace."""

from __future__ import annotations

import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(REPO, "benchmark"))

import costs  # noqa: E402
import peaks  # noqa: E402
import stats  # noqa: E402
import traffic  # noqa: E402
import xplane  # noqa: E402

TRACE = os.path.join(os.path.dirname(__file__), "data", "tiny.xplane.pb")


class TestStats:
    @pytest.mark.parametrize("q,want", [(0, 1.0), (50, 2.5), (100, 4.0), (95, 3.85)])
    def test_percentile_interpolates_like_numpy(self, q, want):
        xs = [4.0, 1.0, 3.0, 2.0]
        assert stats.percentile(xs, q) == pytest.approx(want)
        assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))

    def test_percentile_of_nothing_is_an_error(self):
        with pytest.raises(ValueError):
            stats.percentile([], 50)

    def test_quartile_spread_is_iqr_over_median(self):
        xs = [10.0, 10.2, 10.1, 9.9, 10.3, 9.8]
        import statistics

        q1, _, q3 = statistics.quantiles(xs, n=4)
        assert stats.quartile_spread(xs) == pytest.approx((q3 - q1) / statistics.median(xs))

    def test_histogram_mean_from_two_scrapes(self):
        a = stats.parse_prometheus('h_sum 1.0\nh_count 4\nh_sum{x="1"} 1.0\nh_count{x="1"} 1\n# c\n')
        b = stats.parse_prometheus('h_sum 3.0\nh_count 8\nh_sum{x="1"} 2.0\nh_count{x="1"} 2\n')
        d = stats.delta(a, b)
        assert stats.histogram_mean(d, "h") == pytest.approx((2.0 + 1.0) / (4 + 1))
        assert stats.histogram_mean(d, "absent") is None


class TestTraffic:
    def test_one_seed_one_schedule(self):
        a = traffic.stratified_exponential_schedule(7, 40.0, 10.0)
        b = traffic.stratified_exponential_schedule(7, 40.0, 10.0)
        assert np.array_equal(a, b)

    def test_two_seeds_same_work_another_order(self):
        a = traffic.stratified_exponential_schedule(7, 40.0, 10.0)
        b = traffic.stratified_exponential_schedule(2**31 + 5, 40.0, 10.0)
        assert len(a) == len(b) == 400
        assert not np.array_equal(a, b)
        for s in (a, b):
            assert (np.diff(s) > 0).all() and 0 < s[0] and s[-1] < 10.0

    def test_gaps_are_exponential(self):
        gaps = np.diff(traffic.stratified_exponential_schedule(3, 1000.0, 20.0))
        assert gaps.mean() == pytest.approx(1e-3, rel=0.02)
        assert gaps.std() == pytest.approx(1e-3, rel=0.05)  # cv of 1

    def test_users_distinct_and_seeded(self):
        u = traffic.user_order(5, 1000, 600)
        assert len(set(u.tolist())) == 600
        assert np.array_equal(u, traffic.user_order(5, 1000, 600))
        assert not np.array_equal(u, traffic.user_order(6, 1000, 600))
        with pytest.raises(ValueError):
            traffic.user_order(5, 10, 11)

    def test_requests_are_whole_http(self):
        (r,) = traffic.encode_requests(np.array([12]), 10, "h:1")
        head, body = r.split(b"\r\n\r\n")
        assert body == b'{"user":"u12","num":10}'
        assert f"Content-Length: {len(body)}".encode() in head
        assert head.startswith(b"POST /queries.json HTTP/1.1\r\n")

    def test_lateness_is_sent_minus_due(self):
        due, sent = np.array([0.0, 1.0, 2.0]), np.array([0.001, 1.0, 2.004])
        assert stats.percentile(((sent - due) * 1e3).tolist(), 100) == pytest.approx(4.0)


class TestCosts:
    def test_shortlist_bytes_hand_sum(self):
        # 5 items, tile 4 -> 2 tiles = 8 stored rows; rank 3 bf16 = 6 B + 4 B id
        assert costs.shortlist_bytes(5, 3, 4, "bfloat16", batch=2) == 8 * 10 + 2 * 3 * 4
        # int8 adds one f32 scale per row
        assert costs.shortlist_bytes(5, 3, 4, "int8") == 8 * (3 + 4 + 4) + 12
        assert costs.shortlist_flops(5, 3, 4, batch=2) == 2 * 8 * 3 * 2

    def test_yambda_shortlist_bytes(self):
        b = costs.shortlist_bytes(9_390_000, 64, 1 << 18, "bfloat16")
        assert costs.coarse_tiles(9_390_000, 1 << 18) == 36
        assert b == 36 * (1 << 18) * (64 * 2 + 4) + 64 * 4

    def test_roofline_says_which_peak_binds(self):
        peak = peaks.peaks_for("TPU v5 lite")
        t, binds = costs.roofline_seconds(1e9, 819e6, peak)
        assert binds == "bandwidth" and t == pytest.approx(1e-3)
        t, binds = costs.roofline_seconds(197e12, 1.0, peak)
        assert binds == "compute" and t == pytest.approx(1.0)


class TestPeaks:
    def test_v5e(self):
        p = peaks.peaks_for("TPU v5 lite")
        assert (p["bf16_flops"], p["int8_ops"], p["hbm_bytes_per_s"], p["hbm_bytes"]) == (
            197e12, 393e12, 819e9, 16e9)
        assert "v5e" in p["source"]

    def test_unknown_kind_raises(self):
        with pytest.raises(KeyError, match="TPU v9"):
            peaks.peaks_for("TPU v9")


class TestTraceReduction:
    def test_union_and_gaps(self):
        iv = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.5, 3.6)]
        assert xplane.union_length(iv) == pytest.approx(3.0)
        assert xplane.gaps_between(iv) == [(2.0, 3.0)]
        assert xplane.union_length([]) == 0.0

    def test_names(self):
        assert xplane.program_name("jit__coarse_topk(123)") == "jit__coarse_topk"
        assert xplane.op_label(
            "%fusion.14 = f32[262144]{0:T(1024)} fusion(f32[1]{0} %p)") == "fusion.14 f32[262144]"

    def test_synthetic_planes(self):
        planes = [
            ("/device:TPU:0", [
                ("XLA Modules", [("jit_f(1)", 0.0, 1.0), ("jit_f(1)", 2.0, 3.0), ("jit_g(2)", 3.0, 3.5)]),
                ("XLA Ops", [("%a = f32[2]{0} add()", 0.0, 0.4), ("%a = f32[2]{0} add()", 0.2, 1.0),
                             ("%b = f32[2]{0} mul()", 2.0, 3.5)]),
            ]),
            ("/host:CPU", [("python3", [("$time sleep", 0.9, 2.1), ("$outer", 0.0, 4.0)])]),
        ]
        r = xplane.reduce_planes(planes)
        assert r["busy_s"] == pytest.approx(2.5)
        assert r["span_s"] == pytest.approx(3.5)
        assert r["programs"] == {"jit_f": pytest.approx(2.0), "jit_g": pytest.approx(0.5)}
        assert r["program_calls"] == {"jit_f": 2, "jit_g": 1}
        assert r["device_ops"][0] == ["b f32[2]", pytest.approx(1.5)]
        assert r["idle_gaps"] == [["python3:$time sleep", pytest.approx(1.0)]]
        idle_share = 1 - r["busy_s"] / 4.0
        assert idle_share == pytest.approx(0.375)

    def test_recorded_tpu_trace(self):
        """A 0.3 s trace recorded on a TPU v5 lite (PR 23): five rounds of a
        512x512 matmul, a 10 ms sleep, a top-k."""
        r = xplane.reduce_file(TRACE)
        assert r["device_planes"] == 1
        assert r["program_calls"] == {"jit_small_matmul": 5, "jit_small_topk": 5}
        assert r["programs"]["jit_small_topk"] == pytest.approx(4.07e-5, rel=0.02)
        # busy is the union of the op intervals: under the programs' sum
        assert 0 < r["busy_s"] <= sum(r["programs"].values())
        assert r["busy_s"] == pytest.approx(6.65e-5, rel=0.02)
        assert r["device_ops"][0][0].startswith("custom-call")
        # the long gaps are the sleeps between the rounds
        assert r["idle_gaps"][0][0] == "python3:$time sleep"
        assert r["idle_gaps"][0][1] == pytest.approx(0.012, rel=0.1)
