"""scan_alone.py (the chip measurement behind PERF.md's scan tables):
it takes no time off a TPU, and at toy shapes its two side forms, a
batch in chunks and a single's step forms run on one input and agree —
so the script cannot rot between the PRs that use it."""

import json
import re

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
                     "recommendation-amazon23-int8": 184,
                     "rank10-1m": 4, "rank10-9m": 36, "rank32-int8-1m": 4,
                     "rank32-int8-9m": 36, "rank10-48m": 184}
    assert scan_alone.SHAPES["recommendation-amazon23-int8"]["modes"] == \
        ("int8", "int8_dot")
    assert scan_alone.SHAPES["ecommerce-taobao"] == \
        scan_alone.SHAPES["similarproduct-taobao"]


@pytest.mark.parametrize("name,chunk", [
    ("retrieval-yambda", 16), ("ecommerce-taobao", 32),
    ("recommendation-amazon23", 16), ("recommendation-amazon23-int8", 8),
    ("rank10-1m", 64), ("rank10-9m", 16), ("rank32-int8-1m", 64),
    ("rank32-int8-9m", 16), ("rank10-48m", 2),
])
def test_what_a_pass_takes_of_64_queries_at_each_shape(monkeypatch, name, chunk):
    """With the bytes no pass is cut under (conftest.py takes them away
    for the CPU fixtures): the cells' shapes keep the chunk the first
    bound gives them, and below rank 64 only a catalog of tens of
    millions of rows is cut into small ones."""
    monkeypatch.setattr(retrieval, "_UNCUT", 16 * 36 * (1 << 18) * 4)
    shape = scan_alone.SHAPES[name]
    nt = -(-shape["rows"] // scan_alone.TILE)
    for mode in shape.get("modes", ("bf16",)):
        assert retrieval.scan_chunk(
            64, shape["rank"], mode, nt * scan_alone.TILE) == chunk


PAST = 1 << 30  # rows of a catalog whose stored scores are past retrieval._UNCUT
T = 1 << 13


def _answers(monkeypatch, shape, b, mode="bf16"):
    """{sides: host (scores, ids)} of every program a row of the table
    runs, at a toy tile."""
    monkeypatch.setattr(scan_alone, "TILE", T)
    out = {}
    for sides in scan_alone.SIDES:
        args = scan_alone._arguments(
            shape, b, scan_alone._device_array, mode, sides
        )
        side = args[-2] if shape["rules"] else args[-1]  # the row ids
        assert side.shape == {"lanes": (3, T // 128, 128), "flat": (3, T)}[sides]
        assert args[1].shape == (3, T, 64) and int(side.min()) == -1
        if mode != "bf16":
            assert args[1].dtype == np.int8 and args[2].shape == side.shape
        out[sides] = jax.device_get(scan_alone._scan(128, mode)(*args))
    return out


def _all_agree(answers):
    (s0, i0), *rest = answers.values()
    for s, i in rest:
        np.testing.assert_array_equal(s0.view(np.uint32), s.view(np.uint32))
        np.testing.assert_array_equal(i0, i)
    return i0


def _chunks_alone(monkeypatch, shape, b, mode="bf16"):
    """The batch a chunk of the queries (and of the rules' per-query
    rows) at a time, each a call of its own within the bound."""
    monkeypatch.setattr(scan_alone, "TILE", T)
    args = scan_alone._arguments(shape, b, scan_alone._device_array, mode)
    c = retrieval.scan_chunk(b, shape["rank"], mode, PAST)
    parts = []
    for lo in range(0, b, c):
        own = [args[0][lo: lo + c], *args[1:]]
        if shape["rules"]:
            own[-1] = retrieval._query_rows(args[-1], lo, lo + c)
        parts.append(jax.device_get(scan_alone._scan(128, mode)(*own)))
    return tuple(np.concatenate(p) for p in zip(*parts))


@pytest.mark.parametrize("rules", [False, True])
@pytest.mark.parametrize("b", [1, 8, 32])
def test_both_side_forms_and_the_chunks_alone_on_one_input_agree(
        monkeypatch, b, rules):
    """32 queries of rank 64 are two chunks of 16 in one program: the
    answer of two calls of 16."""
    shape = dict(rows=2 * T + 1000, rank=64, rules=rules)
    assert retrieval.scan_chunk(b, 64, "bf16", PAST) == min(b, 16)
    answers = _answers(monkeypatch, shape, b)
    answers["alone"] = _chunks_alone(monkeypatch, shape, b)
    ids = _all_agree(answers)
    assert ids.max() < shape["rows"]


@pytest.mark.parametrize("mode", ["int8", "int8_dot"])
@pytest.mark.parametrize("b", [1, 8, 16])
def test_they_agree_over_int8_tiles(monkeypatch, b, mode):
    shape = dict(rows=2 * T + 1000, rank=64, rules=False)
    assert retrieval.scan_chunk(b, 64, mode, PAST) == min(b, 8)
    answers = _answers(monkeypatch, shape, b, mode)
    answers["alone"] = _chunks_alone(monkeypatch, shape, b, mode)
    ids = _all_agree(answers)
    assert 0 <= ids.min() and ids.max() < shape["rows"]


@pytest.mark.parametrize("fill,dtype", [
    ("scales", np.float32), (("ids", 2 * T + 1000), np.int32),
])
def test_a_side_array_holds_the_same_values_in_either_form(fill, dtype):
    lanes = scan_alone._device_array((3, T // 128, 128), dtype, fill)
    flat = scan_alone._device_array((3, T), dtype, fill)
    np.testing.assert_array_equal(
        np.asarray(lanes).reshape(3, T), np.asarray(flat)
    )
    assert lanes.shape == retrieval.side_shape(3, T)


# -- a single's step forms (PR 43) ----------------------------------------------


def _single_arguments(monkeypatch, mode):
    monkeypatch.setattr(scan_alone, "TILE", T)
    shape = dict(rows=2 * T + 1000, rank=64, rules=False)
    assert retrieval.score_form(1, 64, mode) == "dot"
    return scan_alone._arguments(shape, 1, scan_alone._device_array, mode)


@pytest.mark.parametrize("mode", ["bf16", "int8"])
@pytest.mark.parametrize("step", scan_alone.STEPS)
def test_a_singles_step_forms_answer_as_the_served_step(monkeypatch, step, mode):
    """Every candidate step on the served step's input: scores
    bit-equal, ids equal — a padded last tile among the three."""
    args = _single_arguments(monkeypatch, mode)
    served = jax.device_get(scan_alone._scan(128, mode)(*args))
    got = jax.device_get(scan_alone._single(128, step, mode)(*args))
    ids = _all_agree({"served": served, step: got})
    assert 0 <= ids.min() and ids.max() < 2 * T + 1000


@pytest.mark.parametrize("mode", ["bf16", "int8"])
def test_the_twice_form_is_the_served_step_without_its_barrier(monkeypatch, mode):
    """``twice`` — the parent's step — is no copy that can drift: it
    lowers to the text of ``_coarse_scan`` itself once ``_kept_once``
    lets the pair through untouched, and the served step differs from
    it by one ``optimization_barrier`` in the loop."""
    def text(jitted):  # private functions are numbered as they are traced
        return re.sub(r"(@\w+?)_\d+\b", r"\1", jitted.lower(*args).as_text())

    args = _single_arguments(monkeypatch, mode)
    twice = text(scan_alone._single(128, "twice", mode))
    served = text(scan_alone._scan(128, mode))
    assert served.count("optimization_barrier") == 1
    assert "optimization_barrier" not in twice
    monkeypatch.setattr(retrieval, "_kept_once", lambda kept: kept)
    bare = text(scan_alone._scan(128, mode))
    assert bare == twice


def test_the_step_axis_is_for_dot_form_singles_alone(monkeypatch, capsys):
    """A row gets its ``steps`` where the served scan is a ``dot``-form
    single without rules; a batch, rank 128 and ``int8_dot`` rows have
    none (their step is not the one the forms vary)."""
    assert scan_alone.STEPS == ("twice", "scores_once", "after")
    seen = []
    real = scan_alone._single
    monkeypatch.setattr(
        scan_alone, "_single",
        lambda k, step, mode="bf16": seen.append((step, mode)) or real(k, step, mode),
    )
    monkeypatch.setattr(scan_alone, "TILE", T)
    monkeypatch.setattr(scan_alone, "described_chip", lambda: None)
    monkeypatch.setitem(scan_alone.SHAPES, "toy", dict(
        rows=2 * T + 1000, rank=64, rules=False, modes=("int8", "int8_dot")))
    monkeypatch.setitem(scan_alone.SHAPES, "toy128", dict(
        rows=2 * T + 1000, rank=128, rules=True))
    assert scan_alone.main(["--compile-only", "--shapes", "toy,toy128",
                            "--batches", "1,2", "--steps", "twice,after"]) == 0
    assert seen == [("twice", "int8"), ("after", "int8")]
    rows = [json.loads(line) for line in capsys.readouterr().out.splitlines()
            if line.startswith("{")]
    assert [set(r.get("steps", ())) for r in rows] == [
        {"twice", "after"}, set(), set(), set(), set(), set(),
    ]
    assert all(r["chunks"] == 1 and r["k"] == 128 and "scan" in r for r in rows)


@pytest.mark.parametrize("kp,group", [(128, 16), (8192, 0)])
def test_a_row_says_its_chunks_and_its_shortlist_size(
        monkeypatch, capsys, kp, group):
    """A batch beyond the bound: the row's ``chunks``, its ``k`` and its
    group width (none at a k' that nears the catalog), and one
    program's temporaries."""
    monkeypatch.setattr(scan_alone, "TILE", T)
    monkeypatch.setattr(scan_alone, "KP", kp)
    monkeypatch.setattr(scan_alone, "described_chip", lambda: None)
    monkeypatch.setitem(scan_alone.SHAPES, "toy", dict(
        rows=2 * T + 1000, rank=64, rules=False, modes=("bf16", "int8")))
    assert scan_alone.main(["--compile-only", "--shapes", "toy", "--sides",
                            "lanes", "--batches", "16,64", "--steps", ""]) == 0
    rows = [json.loads(line) for line in capsys.readouterr().out.splitlines()
            if line.startswith("{")]
    assert [(r["mode"], r["k"], r["b"], r["chunks"], r["group"]) for r in rows] == [
        ("bf16", kp, 16, 1, group), ("bf16", kp, 64, 4, group),
        ("int8", kp, 16, 2, group), ("int8", kp, 64, 8, group),
    ]
    assert all(set(r["scan"]) == {"temp_mb"} for r in rows)
