"""crossing_cost.py (the chip measurement behind PERF.md's crossing
tables): it takes no time off a TPU, and its rehearsal runs every line of
the table at toy size on the virtual devices — both upload routes in
front of the four sharded programs, their answers bit-equal — so the
script cannot rot between the PRs that use it."""

import json

import jax
import pytest

import crossing_cost

PROGRAMS = ("sharded_topk", "sharded_exact", "sharded_topk_masked",
            "sharded_exact_masked")


def test_it_refuses_to_time_anything_off_a_tpu(capsys, tmp_path):
    assert jax.devices()[0].platform != "tpu"
    out = tmp_path / "crossing.json"
    assert crossing_cost.main(["--out", str(out)]) == 2
    assert "not a TPU" in capsys.readouterr().out and not out.exists()


@pytest.mark.parametrize("devices,batch", [(1, 1), (4, 1), (4, 3)])
def test_the_rehearsal_runs_every_line_and_gives_no_time(
        capsys, tmp_path, devices, batch):
    out = tmp_path / "crossing.json"
    assert crossing_cost.main([
        "--dry-run-cpu", "--devices", str(devices), "--batch", str(batch),
        "--out", str(out)]) == 3
    assert not out.exists()
    printed = capsys.readouterr().out.splitlines()
    assert printed[-1].endswith("NOT a chip result")
    head, *lines = [json.loads(ln) for ln in printed if ln.startswith("{")]
    assert head["platform"] == "cpu" and head["devices"] == devices
    assert head["rows"] == 4096 and head["reps"] == 1
    assert all(set(ln) <= {"what", "buffer", "b", "answers_equal"} for ln in lines)
    names = [ln["what"] for ln in lines]
    for buffer in ("vectors", "packed"):
        assert [ln["what"] for ln in lines if ln["buffer"] == buffer][:7] == [
            "put.one", "put.each", "put.stitched", "stitch", "launch.noop_one",
            "launch.noop_all", "launch.handed_round"]
    assert {"read.one", "read.pair"} <= set(names)
    for program in PROGRAMS:
        assert {f"launch.{program}", f"dispatch.{program}.stitched",
                f"dispatch.{program}.each"} <= set(names)
    checks = [ln for ln in lines if "answers_equal" in ln]
    assert [ln["what"] for ln in checks] == [f"dispatch.{p}" for p in PROGRAMS]
    assert all(ln["answers_equal"] for ln in checks)


def test_the_buffers_are_the_four_chip_cells():
    """The catalog's size and the packed buffer's width are the sharded
    storefront's: 48.19 M x 64, and 64 + 1 + 1 + 128 columns."""
    from predictionio_tpu.models import ecommerce

    assert (crossing_cost.ROWS, crossing_cost.RANK) == (48_190_000, 64)
    assert crossing_cost.EXCLUDED == ecommerce._EXCLUDED_BUCKET
    catalog, _ = crossing_cost.stage(2048, crossing_cost.RANK, 2)
    bufs = crossing_cost.buffers(catalog, 1)
    assert bufs["vectors"][0].shape == (1, 64)
    assert bufs["packed"][0].shape == (1, 194)
    assert tuple(bufs["packed"][2]) == (64, 1, 128, 0)
