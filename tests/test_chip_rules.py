"""The rules that keep a run on the chip honest, checked on the CPU:
no fallback that hides the device, one placeable compile cache, jax-free
parents, a peak only for a known device, trainers that say where they ran.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE_VAR = "JAX_COMPILATION_CACHE_DIR"


def _python(args, env, cwd=REPO, timeout=240):
    return subprocess.run(
        [sys.executable, *args], capture_output=True, text=True, env=env,
        cwd=cwd, timeout=timeout,
    )


def _pio(args, env):
    proc = _python(["-m", "predictionio_tpu.cli.main", *args], env)
    assert proc.returncode == 0, f"pio {args}: {proc.stdout}\n{proc.stderr[-2000:]}"
    return proc.stdout


@pytest.fixture()
def env(tmp_path):
    e = dict(os.environ)
    e.update(
        PIO_FS_BASEDIR=str(tmp_path / "store"), PIO_RUN_DIR=str(tmp_path / "run"),
        JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
    )
    e.pop(CACHE_VAR, None)
    return e


class TestChipSmoke:
    def test_refuses_cpu_and_names_it(self, env):
        proc = _python(["chip_smoke.py"], env)
        assert proc.returncode != 0
        assert "'cpu'" in proc.stderr and "not a TPU" in proc.stderr
        assert '"ok"' not in proc.stdout

    def test_dry_run_passes_every_phase_but_is_never_a_result(self, env):
        """The whole harness (incl. the sharded leg on the virtual mesh)
        on a toy shape — so the script cannot rot unnoticed between chip
        runs — and still no success exit and no result line."""
        env[CACHE_VAR] = os.path.join(env["PIO_FS_BASEDIR"], "jc")
        proc = _python(["chip_smoke.py", "--dry-run-cpu"], env)
        assert proc.returncode == 3, proc.stdout + proc.stderr[-3000:]
        assert "sharded rmse" in proc.stdout
        assert '"cache_entries_added": 0' in proc.stdout  # train_2
        assert '"ok"' not in proc.stdout

    def test_disk_probe_finds_a_file_size_limit(self, env, tmp_path):
        """The driver's chip machine refused the 3 GB events file (EFBIG)
        that the builder's machine took: the limit shows only on a write."""
        proc = _python(["-c", (
            "import resource, sys, chip_smoke as cs; "
            "resource.setrlimit(resource.RLIMIT_FSIZE, (5 << 20, 5 << 20)); "
            "print(cs.disk_capacity(sys.argv[1], 64 << 20), "
            "cs.disk_capacity(sys.argv[1], 1 << 20))"
        ), str(tmp_path)], env)
        assert proc.stdout.split() == [str(5 << 20), str(1 << 20)], proc.stderr
        assert os.listdir(tmp_path) == []

    def test_event_cut_fits_what_the_disk_takes(self):
        sys.path.insert(0, REPO)
        try:
            import chip_smoke as cs
        finally:
            sys.path.remove(REPO)
        full = cs.disk_need(cs.FULL_EVENTS)
        assert cs.events_that_fit(int(full / cs.DISK_MARGIN) + 1) >= cs.FULL_EVENTS
        fit = cs.events_that_fit(1 << 30)  # a 1 GiB limit still holds the floor
        assert fit >= cs.MIN_EVENTS and cs.disk_need(fit) <= (1 << 30) * cs.DISK_MARGIN
        assert cs.events_that_fit(0) == 0


class TestCompileCachePlacement:
    def test_unset_means_the_checkout(self, env):
        out = _python(
            ["-c", "import predictionio_tpu, jax; "
             "print(jax.config.jax_compilation_cache_dir)"], env,
        ).stdout
        assert out.strip() == os.path.join(REPO, ".jax_cache")

    def test_set_is_used_and_a_second_train_adds_nothing(self, env, tmp_path):
        cache = tmp_path / "placed"
        env[CACHE_VAR] = str(cache)
        events = tmp_path / "events.jsonl"
        events.write_text("".join(
            json.dumps({
                "event": "rate", "entityType": "user", "entityId": f"u{u}",
                "targetEntityType": "item", "targetEntityId": f"i{(u + i) % 8}",
                "properties": {"rating": float((u * i) % 5 + 1)},
                "eventTime": "2020-01-01T00:00:00.000Z",
            }) + "\n"
            for u in range(10) for i in range(6)
        ))
        variant = tmp_path / "engine.json"
        variant.write_text(json.dumps({
            "id": "placed",
            "engineFactory": "predictionio_tpu.models.recommendation.engine",
            "datasource": {"params": {"app_name": "PlacedApp"}},
            "algorithms": [{"name": "als",
                            "params": {"rank": 4, "num_iterations": 2}}],
        }))
        _pio(["app", "new", "PlacedApp"], env)
        _pio(["import", "--appid-or-name", "PlacedApp", "--input", str(events)], env)
        out = _pio(["train", "--variant", str(variant)], env)
        assert "platform: cpu, device_kind: cpu, device_count: 8" in out
        first = sorted(os.listdir(cache))
        assert first, "pio train left the placed cache empty"
        _pio(["train", "--variant", str(variant)], env)
        assert sorted(os.listdir(cache)) == first

    def test_nothing_sets_the_cache_dir_in_code(self):
        """jax reads the variable itself; code that also sets the option
        would override an operator's placement."""
        hits = []
        for root, _, files in os.walk(os.path.join(REPO, "predictionio_tpu")):
            hits += [os.path.join(root, f) for f in files if f.endswith(".py")]
        hits += [os.path.join(REPO, "bench.py"), os.path.join(REPO, "chip_smoke.py")]
        for path in hits:
            with open(path, encoding="utf-8") as fh:
                assert "jax_compilation_cache_dir" not in fh.read(), path


def test_parents_stay_off_jax(env):
    """A parent that has touched jax holds the chip its children need."""
    proc = _python(["-c", (
        "import sys, chip_smoke, predictionio_tpu.server.supervisor, "
        "predictionio_tpu.cli.daemon, predictionio_tpu.server.router, "
        "predictionio_tpu.server.event_server; "
        "sys.exit('jax' in sys.modules)"
    )], env)
    assert proc.returncode == 0, proc.stderr[-2000:]


def test_peak_is_looked_up_by_device_kind():
    sys.path.insert(0, REPO)
    try:
        import bench
    finally:
        sys.path.remove(REPO)
    assert bench.peak_flops("TPU v5 lite") == 197e12
    with pytest.raises(ValueError, match="cpu"):
        bench.peak_flops("cpu")


def test_both_trainers_say_where_they_ran(tmp_path, monkeypatch):
    import jax

    from predictionio_tpu.obs import progress
    from predictionio_tpu.ops import als
    from predictionio_tpu.parallel.als_sharded import sharded_als_train
    from predictionio_tpu.parallel.mesh import make_mesh

    path = str(tmp_path / "progress.json")
    monkeypatch.setenv("PIO_PROGRESS_FILE", path)
    rng = np.random.default_rng(0)
    rows = rng.integers(0, 32, 300).astype(np.int32)
    cols = rng.integers(0, 20, 300).astype(np.int32)
    vals = (1 + rng.integers(0, 5, 300)).astype(np.float32)
    data = als.build_ratings_data(rows, cols, vals, 32, 20, bucket_widths=(8, 32))
    params = als.ALSParams(rank=4, iterations=1, reg=0.05)
    where = {"platform": "cpu", "device_kind": "cpu", "device_count": 8}
    assert len(jax.devices()) == 8, "conftest should provide 8 CPU devices"

    als.als_train(data, params)
    doc = progress.read_progress(path)
    assert doc["trainer"] == "single"
    assert {k: doc[k] for k in where} == where

    sharded_als_train(data, params, make_mesh([("data", 8)]))
    doc = progress.read_progress(path)
    assert doc["trainer"] == "sharded"
    assert {k: doc[k] for k in where} == where
