"""scan_alone.py (the chip measurement behind PERF.md's scan tables):
it takes no time off a TPU, and at toy shapes its two bodies run on one
input and agree — so the script cannot rot between the PRs that use it."""

import jax
import numpy as np
import pytest

import scan_alone
from predictionio_tpu.ops import retrieval


def test_it_refuses_to_time_anything_off_a_tpu(capsys, tmp_path):
    assert jax.devices()[0].platform != "tpu"
    out = tmp_path / "scan.json"
    assert scan_alone.main(["--out", str(out)]) == 2
    assert "not a TPU" in capsys.readouterr().out and not out.exists()


def test_the_five_configurations_scan_four_shapes():
    tiles = {n: -(-s["rows"] // scan_alone.TILE)
             for n, s in scan_alone.SHAPES.items()}
    assert tiles == {"retrieval-yambda": 36, "ecommerce-taobao": 16,
                     "similarproduct-taobao": 16,
                     "recommendation-amazon23": 46,
                     "recommendation-amazon23-int8": 184}
    assert scan_alone.SHAPES["recommendation-amazon23-int8"]["modes"] == \
        ("int8", "int8_dot")
    assert scan_alone.SHAPES["ecommerce-taobao"] == \
        scan_alone.SHAPES["similarproduct-taobao"]


@pytest.mark.parametrize("rules", [False, True])
@pytest.mark.parametrize("b", [1, 8])
def test_both_bodies_on_one_input_agree(monkeypatch, b, rules):
    monkeypatch.setattr(scan_alone, "TILE", 1 << 13)
    shape = dict(rows=2 * (1 << 13) + 1000, rank=64, rules=rules)
    args = scan_alone._arguments(shape, b, scan_alone._device_array)
    assert args[1].shape == (3, 1 << 13, 64) and int(args[2].min()) == -1
    assert retrieval.scan_select(b, 3, 1 << 13, 128, 64) == "deferred"
    (s0, i0), (s1, i1) = (
        jax.device_get(scan_alone._scan(128, body)(*args))
        for body in scan_alone.BODIES
    )
    np.testing.assert_array_equal(s0.view(np.uint32), s1.view(np.uint32))
    np.testing.assert_array_equal(i0, i1)
    assert i0.max() < shape["rows"]


@pytest.mark.parametrize("mode", ["int8", "int8_dot"])
@pytest.mark.parametrize("b", [1, 8])
def test_both_bodies_agree_over_int8_tiles(monkeypatch, b, mode):
    monkeypatch.setattr(scan_alone, "TILE", 1 << 13)
    shape = dict(rows=2 * (1 << 13) + 1000, rank=64, rules=False)
    args = scan_alone._arguments(shape, b, scan_alone._device_array, mode)
    assert args[1].dtype == np.int8 and args[2].shape == (3, 1 << 13)
    assert retrieval.scan_select(b, 3, 1 << 13, 128, 64, mode) == "deferred"
    (s0, i0), (s1, i1) = (
        jax.device_get(scan_alone._scan(128, body, mode)(*args))
        for body in scan_alone.BODIES
    )
    np.testing.assert_array_equal(s0.view(np.uint32), s1.view(np.uint32))
    np.testing.assert_array_equal(i0, i1)
    assert 0 <= i0.min() and i0.max() < shape["rows"]
