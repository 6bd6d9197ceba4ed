"""Packed-prep cache + warm-start solves (hot retrain).

The correctness contract under test: a probe that reports ``hit`` or
``splice`` hands back arrays BIT-IDENTICAL to a fresh scan+pack of the
same log — and anything the cache cannot prove (changed files, replayed
event ids, corrupt entries, faulted publishes) degrades to a clean
rebuild, never to wrong packed data. Warm starts convert the previous
model into fewer solve iterations at the same quality, and fall back to
cold — with a named warning — on any incompatibility.
"""

from __future__ import annotations

import os
import signal
import subprocess
import sys
import time
from datetime import datetime, timedelta, timezone
from pathlib import Path

import numpy as np
import pytest

from predictionio_tpu import faults
from predictionio_tpu.core import WorkflowContext, prep_cache
from predictionio_tpu.data import store as data_store
from predictionio_tpu.data.event import Event
from predictionio_tpu.data.storage import Storage
from predictionio_tpu.data.storage import base as storage_base
from predictionio_tpu.models import recommendation as rec
from predictionio_tpu.obs import metrics as obs_metrics
from predictionio_tpu.ops import als as als_ops

from tests.test_storage import _backend_env, _run_chaos_child

T0 = datetime(2020, 1, 1, tzinfo=timezone.utc)
# tiny widths so the blockbuster row segments across table rows — the
# splice must reproduce seg_row exactly, not just the plain buckets
WIDTHS = (4, 16)
FILTERS = dict(
    event_names=["rate"],
    entity_type="user",
    target_entity_type="item",
    rating_key="rating",
    default_ratings=None,
    override_ratings=None,
)


@pytest.fixture(params=["jsonl", "partitioned"])
def prep_storage(request, tmp_path, monkeypatch):
    """File-backed Storage (both log backends) + an isolated cache dir."""
    monkeypatch.setenv("PIO_PREP_CACHE_DIR", str(tmp_path / "prep"))
    monkeypatch.delenv("PIO_PREP_CACHE", raising=False)
    storage = Storage(env=_backend_env(request.param, tmp_path))
    app_id = storage.get_metadata_apps().insert(storage_base.App(0, "A"))
    storage.get_events().init(app_id)
    yield storage, app_id
    storage.close()


def _put(storage, app_id, i0, n, user=None):
    user = user or (lambda i: "hot" if i % 3 == 0 else f"u{i % 13}")
    storage.get_events().batch_insert(
        [
            Event(
                event="rate",
                entity_type="user",
                entity_id=user(i),
                target_entity_type="item",
                target_entity_id=f"i{i % 7}",
                properties={"rating": float(i % 5 + 1)},
                event_time=T0 + timedelta(minutes=i),
            )
            for i in range(i0, i0 + n)
        ],
        app_id,
    )


def _fresh_pack(batch):
    rb = als_ops.build_padded_buckets(batch.rows, batch.cols, batch.vals, WIDTHS)
    cb = als_ops.build_padded_buckets(batch.cols, batch.rows, batch.vals, WIDTHS)
    return rb, cb


def _publish(handle, batch, **kw):
    rb, cb = _fresh_pack(batch)
    data = als_ops.RatingsData(
        rows=batch.rows, cols=batch.cols, vals=batch.vals,
        num_rows=len(batch.entity_ids), num_cols=len(batch.target_ids),
        row_buckets=rb, col_buckets=cb,
    )
    return handle.publish(batch, data=data, bucket_widths=WIDTHS, **kw)


def _assert_batch_equal(got, want):
    assert got.entity_ids == want.entity_ids
    assert got.target_ids == want.target_ids
    assert np.array_equal(got.rows, want.rows)
    assert np.array_equal(got.cols, want.cols)
    assert np.array_equal(got.vals, want.vals)


def _assert_buckets_equal(got, want):
    assert len(got) == len(want)
    for a, b in zip(got, want):
        for f in ("row_ids", "col_ids", "ratings", "mask"):
            assert np.array_equal(getattr(a, f), getattr(b, f)), f
        assert (a.seg_row is None) == (b.seg_row is None)
        if a.seg_row is not None:
            assert np.array_equal(a.seg_row, b.seg_row)


def _tree_equal(a, b):
    import dataclasses

    if isinstance(a, np.ndarray) or isinstance(b, np.ndarray):
        return np.array_equal(np.asarray(a), np.asarray(b))
    if dataclasses.is_dataclass(a) and not isinstance(a, type):
        return type(a) is type(b) and all(
            _tree_equal(getattr(a, f.name), getattr(b, f.name))
            for f in dataclasses.fields(a)
        )
    if isinstance(a, (list, tuple)):
        return len(a) == len(b) and all(_tree_equal(x, y) for x, y in zip(a, b))
    return a == b


class TestSpliceBitIdentity:
    def test_miss_publish_hit_then_splice(self, prep_storage):
        storage, app_id = prep_storage
        _put(storage, app_id, 0, 120)  # "hot" holds 40 rows -> segmented
        h = prep_cache.probe("A", storage=storage, **FILTERS)
        assert h.status == "miss"
        batch = data_store.find_ratings("A", storage=storage, **FILTERS)
        assert _publish(h, batch)

        # unchanged store -> exact hit, batch AND buckets bit-identical
        h2 = prep_cache.probe("A", storage=storage, **FILTERS)
        assert h2.status == "hit"
        _assert_batch_equal(h2.batch, batch)
        rb, cb = h2.packed_buckets(WIDTHS)
        want_rb, want_cb = _fresh_pack(batch)
        _assert_buckets_equal(rb, want_rb)
        _assert_buckets_equal(cb, want_cb)

        # appended tail over the EXISTING id universe: surgical splice
        # on every backend, spliced buckets == fresh full pack
        _put(storage, app_id, 120, 30)
        h3 = prep_cache.probe("A", storage=storage, **FILTERS)
        assert h3.status == "splice"
        fresh = data_store.find_ratings("A", storage=storage, **FILTERS)
        _assert_batch_equal(h3.batch, fresh)
        assert h3.splice.surgical
        pk = h3.packed_buckets(WIDTHS)
        assert pk is not None
        want_rb, want_cb = _fresh_pack(fresh)
        _assert_buckets_equal(pk[0], want_rb)
        _assert_buckets_equal(pk[1], want_cb)

        # publish the spliced state -> next probe is an exact hit again
        assert _publish(h3, h3.batch)
        assert prep_cache.probe("A", storage=storage, **FILTERS).status == "hit"

    def test_splice_with_new_ids(self, prep_storage):
        """A tail introducing NEW users/items still yields a bit-identical
        batch (the renumber path); buckets come back only when the splice
        is surgical (single tail file, as on jsonl), else None — never a
        wrong pack."""
        storage, app_id = prep_storage
        _put(storage, app_id, 0, 90)
        h = prep_cache.probe("A", storage=storage, **FILTERS)
        batch = data_store.find_ratings("A", storage=storage, **FILTERS)
        assert _publish(h, batch)

        _put(storage, app_id, 90, 24, user=lambda i: f"new{i % 5}")
        h2 = prep_cache.probe("A", storage=storage, **FILTERS)
        assert h2.status == "splice"
        fresh = data_store.find_ratings("A", storage=storage, **FILTERS)
        _assert_batch_equal(h2.batch, fresh)
        pk = h2.packed_buckets(WIDTHS)
        if h2.splice.surgical:
            want_rb, want_cb = _fresh_pack(fresh)
            _assert_buckets_equal(pk[0], want_rb)
            _assert_buckets_equal(pk[1], want_cb)
        else:
            assert pk is None

    def test_replayed_event_id_forces_rebuild(self, prep_storage):
        """A tail carrying an event id the cached entry already holds is
        a replay/compaction, not an append — the splice must refuse."""
        storage, app_id = prep_storage
        events = storage.get_events()
        events.insert(
            Event(
                event="rate", entity_type="user", entity_id="u1",
                target_entity_type="item", target_entity_id="i1",
                properties={"rating": 3.0}, event_id="dup0",
                event_time=T0,
            ),
            app_id,
        )
        _put(storage, app_id, 1, 40)
        h = prep_cache.probe("A", storage=storage, **FILTERS)
        batch = data_store.find_ratings("A", storage=storage, **FILTERS)
        assert _publish(h, batch)

        before = obs_metrics.counter(
            "pio_prep_cache_rebuilds_total", reason="duplicate"
        ).value()
        events.insert(
            Event(
                event="rate", entity_type="user", entity_id="u1",
                target_entity_type="item", target_entity_id="i2",
                properties={"rating": 5.0}, event_id="dup0",
                event_time=T0 + timedelta(days=1),
            ),
            app_id,
        )
        h2 = prep_cache.probe("A", storage=storage, **FILTERS)
        assert h2.status == "miss"
        assert obs_metrics.counter(
            "pio_prep_cache_rebuilds_total", reason="duplicate"
        ).value() == before + 1

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int8"])
    def test_sharded_pack_roundtrip(self, prep_storage, dtype):
        """The 8-shard superstructures (SideLayout + PackedSide, the
        virtual-mesh layout of tests/conftest.py) round-trip through the
        cache bit-identically, keyed on the params that shape them."""
        from predictionio_tpu.parallel import als_sharded

        storage, app_id = prep_storage
        _put(storage, app_id, 0, 120)
        h = prep_cache.probe("A", storage=storage, **FILTERS)
        batch = data_store.find_ratings("A", storage=storage, **FILTERS)
        rb, cb = _fresh_pack(batch)
        data = als_ops.RatingsData(
            rows=batch.rows, cols=batch.cols, vals=batch.vals,
            num_rows=len(batch.entity_ids), num_cols=len(batch.target_ids),
            row_buckets=rb, col_buckets=cb,
        )
        params = als_ops.ALSParams(
            rank=4, iterations=2, seed=1, storage_dtype=dtype
        )
        fresh = als_sharded.prepare_sharded_pack(data, params, 8, "auto")
        assert h.publish(
            batch, data=data, bucket_widths=WIDTHS,
            sharded=fresh, params=params, sharded_requested="auto",
        )

        h2 = prep_cache.probe("A", storage=storage, **FILTERS)
        assert h2.status == "hit"
        got = h2.sharded_pack(params, 8, "auto")
        assert got is not None
        assert got[0] == fresh[0]  # resolved mode
        assert _tree_equal(got[1:], fresh[1:])

        # any key ingredient changing -> no cached pack, never a stale one
        other = "int8" if dtype != "int8" else "float32"
        p2 = als_ops.ALSParams(
            rank=4, iterations=2, seed=1, storage_dtype=other
        )
        assert h2.sharded_pack(p2, 8, "auto") is None
        assert h2.sharded_pack(params, 4, "auto") is None


class TestFallbacks:
    def test_faulted_publish_skips_then_rebuilds_clean(self, prep_storage):
        """train.prep_cache raise: the publish is skipped (False, no
        file), training is unaffected, and the next probe is a clean
        miss whose publish succeeds."""
        storage, app_id = prep_storage
        _put(storage, app_id, 0, 60)
        h = prep_cache.probe("A", storage=storage, **FILTERS)
        assert h.status == "miss"
        batch = data_store.find_ratings("A", storage=storage, **FILTERS)
        with faults.injected("train.prep_cache:raise"):
            assert not _publish(h, batch)
        assert not list(Path(prep_cache.cache_dir()).glob("*.prep"))

        h2 = prep_cache.probe("A", storage=storage, **FILTERS)
        assert h2.status == "miss"
        assert _publish(h2, batch)
        h3 = prep_cache.probe("A", storage=storage, **FILTERS)
        assert h3.status == "hit"
        _assert_batch_equal(h3.batch, batch)

    def test_corrupt_entry_falls_back_to_rebuild(self, prep_storage):
        storage, app_id = prep_storage
        _put(storage, app_id, 0, 60)
        h = prep_cache.probe("A", storage=storage, **FILTERS)
        batch = data_store.find_ratings("A", storage=storage, **FILTERS)
        assert _publish(h, batch)
        [entry] = Path(prep_cache.cache_dir()).glob("*.prep")
        blob = entry.read_bytes()

        before = obs_metrics.counter(
            "pio_prep_cache_rebuilds_total", reason="corrupt"
        ).value()
        entry.write_bytes(blob[: len(blob) // 2])  # torn write
        h2 = prep_cache.probe("A", storage=storage, **FILTERS)
        assert h2.status == "miss"
        assert obs_metrics.counter(
            "pio_prep_cache_rebuilds_total", reason="corrupt"
        ).value() == before + 1
        assert _publish(h2, batch)
        assert prep_cache.probe("A", storage=storage, **FILTERS).status == "hit"

    def test_disabled_by_env(self, prep_storage, monkeypatch):
        storage, app_id = prep_storage
        _put(storage, app_id, 0, 30)
        monkeypatch.setenv("PIO_PREP_CACHE", "0")
        h = prep_cache.probe("A", storage=storage, **FILTERS)
        assert not h.active
        assert h.status == "off"


def test_event_id_hash_does_not_depend_on_the_chunking(monkeypatch):
    """hash_event_ids works in bounded-memory chunks (one vectorized step
    over a 20M-event corpus needed ~25 GB); hashes are per id, so any
    chunking gives the same array, and a bad id in ANY chunk voids it."""
    ids = [f"ev-{i:03d}" * (1 + i % 3) for i in range(11)]
    whole = prep_cache.hash_event_ids(ids)
    assert whole.dtype == np.uint64 and len(set(whole.tolist())) == len(ids)
    monkeypatch.setattr(prep_cache, "_HASH_CHUNK_IDS", 3)
    np.testing.assert_array_equal(prep_cache.hash_event_ids(ids), whole)
    assert prep_cache.hash_event_ids(ids[:7] + [""] + ids[8:]) is None
    assert prep_cache.hash_event_ids(ids[:10] + [None]) is None
    assert len(prep_cache.hash_event_ids([])) == 0


class TestWarmStart:
    def _data(self, rng, n, nu, ni):
        rows = rng.integers(0, nu, n)
        cols = rng.integers(0, ni, n)
        vals = rng.integers(1, 6, n).astype(np.float64)
        return rows, cols, vals

    def test_warm_start_fewer_iterations_same_quality(self, monkeypatch):
        """The hot-retrain contract: warm factors + tol reach the cold
        final RMSE (±1e-3) in strictly fewer iterations."""
        # the plateau check rides per-iteration segments; an ambient
        # checkpoint cadence (ckpt.from_env) would coarsen it to
        # every-N and mask the early stop
        for k in ("PIO_CHECKPOINT_EVERY", "PIO_RESUME", "PIO_CHECKPOINT_DIR"):
            monkeypatch.delenv(k, raising=False)
        rng = np.random.default_rng(7)
        n, nu, ni = 20_000, 300, 60
        rows, cols, vals = self._data(rng, n, nu, ni)
        data = als_ops.build_ratings_data(rows, cols, vals, nu, ni)
        params = als_ops.ALSParams(rank=4, iterations=8, seed=1)
        U0, V0 = als_ops.als_train(data, params)

        # the ~1% appended delta, then cold vs warm on identical data
        dn = 200
        r2 = np.concatenate([rows, rng.integers(0, nu, dn)])
        c2 = np.concatenate([cols, rng.integers(0, ni, dn)])
        v2 = np.concatenate([vals, rng.integers(1, 6, dn).astype(np.float64)])
        data2 = als_ops.build_ratings_data(r2, c2, v2, nu, ni)

        als_ops.als_train(data2, params, tol=1e-12)
        cold = dict(als_ops.LAST_TRAIN_INFO)
        assert not cold["warm_start"]

        warm_carry = (np.asarray(U0, np.float32), np.asarray(V0, np.float32))
        als_ops.als_train(data2, params, warm_start=warm_carry, tol=2e-3)
        warm = dict(als_ops.LAST_TRAIN_INFO)
        assert warm["warm_start"] and warm["early_stopped"]
        assert warm["iterations_run"] < cold["iterations_run"]
        assert warm["final_rmse"] <= cold["final_rmse"] + 1e-3

    def test_incompatible_previous_model_warns_and_goes_cold(self, caplog):
        """Changed rank / storage dtype / foreign model type: a named
        warning and a cold start, never a crash or a silent re-trace."""
        algo = rec.ALSAlgorithm(rec.ALSAlgorithmParams(rank=4, num_iterations=1))
        td = rec.TrainingData(user_ids=["u0", "u1"], item_ids=["i0"])
        ctx = WorkflowContext(mode="Test")

        def resolve(prev):
            caplog.clear()
            ctx.runtime_conf["warm_start_model"] = prev
            with caplog.at_level("WARNING"):
                return algo._resolve_warm_start(ctx, td)

        assert resolve(object()) is None
        assert "not ALSModel" in caplog.text

        def model(rank, scales=False):
            u = np.zeros((2, rank), np.int8 if scales else np.float32)
            i = np.zeros((1, rank), np.int8 if scales else np.float32)
            return rec.ALSModel(
                user_index=rec.BiMap({"u0": 0, "uX": 1}),
                item_index=rec.BiMap({"i0": 0}),
                user_factors=u, item_factors=i,
                user_scales=np.ones(2, np.float32) if scales else None,
                item_scales=np.ones(1, np.float32) if scales else None,
            )

        assert resolve(model(rank=6)) is None
        assert "rank mismatch" in caplog.text

        assert resolve(model(rank=4, scales=True)) is None
        assert "storage dtype mismatch" in caplog.text

        carry = resolve(model(rank=4))
        assert carry is not None
        U0, V0 = carry
        assert U0.shape == (2, 4) and V0.shape == (1, 4)
        # u1 is unknown to the previous model -> NaN row (cold draw)
        assert not np.isnan(U0[0]).any()
        assert np.isnan(U0[1]).all()


_KILL_CHILD = """
import json, sys
from predictionio_tpu.data.storage import Storage
from predictionio_tpu.data import store as data_store
from predictionio_tpu.core import prep_cache

cfg = json.load(open(sys.argv[1]))
st = Storage(env=cfg["env"])
FILTERS = dict(event_names=["rate"], entity_type="user",
               target_entity_type="item", rating_key="rating",
               default_ratings=None, override_ratings=None)
h = prep_cache.probe("A", storage=st, **FILTERS)
print("STATUS", h.status, flush=True)
batch = h.batch
if batch is None:
    batch = data_store.find_ratings("A", storage=st, **FILTERS)
h.publish(batch)
print("PUBLISHED", flush=True)  # must never be reached under the kill
"""


@pytest.mark.chaos
class TestKill9MidPublish:
    def test_husk_only_old_entry_intact_next_train_rebuilds(self, tmp_path):
        """SIGKILL between the tmp write and the rename: the final name
        never changes (old entry byte-identical), only a ``.tmp`` husk is
        left, and the next probe still serves the old entry."""
        env_dict = _backend_env("jsonl", tmp_path)
        storage = Storage(env=env_dict)
        app_id = storage.get_metadata_apps().insert(storage_base.App(0, "A"))
        storage.get_events().init(app_id)
        assert app_id == 1  # _chaos_child cfg convention

        cache_dir = tmp_path / "prep"
        # seed the log through the shared chaos child (no faults: clean run)
        proc, acked, done, _sig = _run_chaos_child(tmp_path, env_dict, "")
        assert done and len(acked) == 40

        prev = os.environ.get("PIO_PREP_CACHE_DIR")
        os.environ["PIO_PREP_CACHE_DIR"] = str(cache_dir)
        try:
            h = prep_cache.probe("A", storage=storage, **FILTERS)
            assert h.status == "miss"
            batch = data_store.find_ratings("A", storage=storage, **FILTERS)
            assert _publish(h, batch)
            [entry] = cache_dir.glob("*.prep")
            old_bytes = entry.read_bytes()

            # grow the log, then publish from a child armed to die at the
            # pre-rename fsync of the prep store
            _put(storage, app_id, 1000, 25, user=lambda i: f"u{i % 9}")
            child_env = dict(os.environ)
            child_env.update(
                PIO_FAULTS="storage.fsync:nth=1:kill",
                PIO_COLUMNAR_CACHE="0",
                PIO_PREP_CACHE_DIR=str(cache_dir),
                JAX_PLATFORMS="cpu",
            )
            child_env.setdefault(
                "PYTHONPATH", str(Path(__file__).parent.parent)
            )
            cfg = tmp_path / "kill_cfg.json"
            cfg.write_text(__import__("json").dumps({"env": env_dict}))
            cp = subprocess.run(
                [sys.executable, "-c", _KILL_CHILD, str(cfg)],
                capture_output=True, text=True, env=child_env, timeout=120,
            )
            assert cp.returncode == -signal.SIGKILL, cp.stderr
            assert "STATUS splice" in cp.stdout
            assert "PUBLISHED" not in cp.stdout

            # only a husk; the published name is byte-identical
            assert [p.name for p in cache_dir.glob("*.prep")] == [entry.name]
            assert entry.read_bytes() == old_bytes
            assert list(cache_dir.glob("*.tmp.*"))

            # the old entry still splices; a clean publish then hits
            h2 = prep_cache.probe("A", storage=storage, **FILTERS)
            assert h2.status == "splice"
            fresh = data_store.find_ratings("A", storage=storage, **FILTERS)
            _assert_batch_equal(h2.batch, fresh)
            assert _publish(h2, h2.batch)
            assert (
                prep_cache.probe("A", storage=storage, **FILTERS).status
                == "hit"
            )
        finally:
            if prev is None:
                os.environ.pop("PIO_PREP_CACHE_DIR", None)
            else:
                os.environ["PIO_PREP_CACHE_DIR"] = prev
            storage.close()


def _publish_sharded(handle, batch, params, shards):
    """Publish with both the single-chip pack AND a stable-shapes
    sharded pack, the way a `sharded_train` engine run does."""
    from predictionio_tpu.parallel import als_sharded

    rb, cb = _fresh_pack(batch)
    data = als_ops.RatingsData(
        rows=batch.rows, cols=batch.cols, vals=batch.vals,
        num_rows=len(batch.entity_ids), num_cols=len(batch.target_ids),
        row_buckets=rb, col_buckets=cb,
    )
    sharded = als_sharded.prepare_sharded_pack(
        data, params, shards, "gather", stable_shapes=True
    )
    return handle.publish(
        batch, data=data, bucket_widths=WIDTHS, sharded=sharded,
        params=params, sharded_requested="gather",
    )


class TestShardedLayoutReuse:
    """sharded_pack() off a splice probe: a small delta keeps the cached
    SideLayout verbatim (zero-recompile warm retrain); a layout-shifting
    delta falls back clean, counted reason=layout_drift."""

    SHARDS = 4

    def _seed(self, storage, app_id, params):
        _put(storage, app_id, 0, 400)
        h = prep_cache.probe("A", storage=storage, **FILTERS)
        assert h.status == "miss"
        batch = data_store.find_ratings("A", storage=storage, **FILTERS)
        assert _publish_sharded(h, batch, params, self.SHARDS)
        return batch

    def test_small_delta_reuses_the_cached_layout(self, prep_storage):
        from predictionio_tpu.parallel import als_sharded

        from tests.test_als import TestPackedLayoutProperty

        storage, app_id = prep_storage
        params = als_ops.ALSParams(rank=4, iterations=2)
        seed_batch = self._seed(storage, app_id, params)
        reuse0 = obs_metrics.counter(
            "pio_prep_cache_layout_reuse_total"
        ).value()

        _put(storage, app_id, 400, 8)  # reuses existing user/item ids
        h = prep_cache.probe("A", storage=storage, **FILTERS)
        assert h.status == "splice"
        pack = h.sharded_pack(params, self.SHARDS, "gather")
        assert pack is not None
        assert (
            obs_metrics.counter(
                "pio_prep_cache_layout_reuse_total"
            ).value()
            == reuse0 + 1
        )
        mode, rl, cl, rp, cp = pack

        # the reused layout IS the seed batch's layout — placement (and
        # with it the compiled fused program) survived the delta
        rb, cb = _fresh_pack(seed_batch)
        data0 = als_ops.RatingsData(
            rows=seed_batch.rows, cols=seed_batch.cols,
            vals=seed_batch.vals, num_rows=len(seed_batch.entity_ids),
            num_cols=len(seed_batch.target_ids),
            row_buckets=rb, col_buckets=cb,
        )
        _, rl0, cl0, rp0, cp0 = als_sharded.prepare_sharded_pack(
            data0, params, self.SHARDS, "gather", stable_shapes=True
        )
        np.testing.assert_array_equal(rl.assign, rl0.assign)
        np.testing.assert_array_equal(cl.assign, cl0.assign)
        for got, ref in ((rp, rp0), (cp, cp0)):
            for f in ("row_ids", "col_ids", "ratings", "mask", "seg"):
                assert getattr(got, f).shape == getattr(ref, f).shape, f

        # and the spliced pack holds exactly the fresh scan's COO
        fresh = data_store.find_ratings("A", storage=storage, **FILTERS)
        _assert_batch_equal(h.batch, fresh)
        want = sorted(
            zip(fresh.rows.tolist(), fresh.cols.tolist(),
                fresh.vals.tolist())
        )
        got = TestPackedLayoutProperty._packed_triples(
            rp, rl, cl, self.SHARDS
        )
        assert got == want

    def test_layout_drift_falls_back_clean(self, prep_storage):
        storage, app_id = prep_storage
        params = als_ops.ALSParams(rank=4, iterations=2)
        self._seed(storage, app_id, params)
        drift0 = obs_metrics.counter(
            "pio_prep_cache_rebuilds_total", reason="layout_drift"
        ).value()

        # 60 brand-new users against a ~14-user side: way past the 5%
        # layout-reuse envelope
        _put(storage, app_id, 400, 60, user=lambda i: f"new{i}")
        h = prep_cache.probe("A", storage=storage, **FILTERS)
        assert h.status == "splice"
        assert h.sharded_pack(params, self.SHARDS, "gather") is None
        assert (
            obs_metrics.counter(
                "pio_prep_cache_rebuilds_total", reason="layout_drift"
            ).value()
            == drift0 + 1
        )
        # the fallback is only about the sharded pack: the spliced
        # batch itself stays authoritative for the fresh-layout train
        fresh = data_store.find_ratings("A", storage=storage, **FILTERS)
        _assert_batch_equal(h.batch, fresh)

    def test_key_mismatch_returns_none_without_drift(self, prep_storage):
        storage, app_id = prep_storage
        params = als_ops.ALSParams(rank=4, iterations=2)
        self._seed(storage, app_id, params)
        _put(storage, app_id, 400, 8)
        drift0 = obs_metrics.counter(
            "pio_prep_cache_rebuilds_total", reason="layout_drift"
        ).value()
        h = prep_cache.probe("A", storage=storage, **FILTERS)
        assert h.status == "splice"
        # different rank -> different pack key: not drift, just absent
        other = als_ops.ALSParams(rank=6, iterations=2)
        assert h.sharded_pack(other, self.SHARDS, "gather") is None
        assert h.sharded_pack(params, self.SHARDS + 1, "gather") is None
        assert (
            obs_metrics.counter(
                "pio_prep_cache_rebuilds_total", reason="layout_drift"
            ).value()
            == drift0
        )
        # iterations are solve-time, not pack-time: key still matches
        more = als_ops.ALSParams(rank=4, iterations=9)
        assert h.sharded_pack(more, self.SHARDS, "gather") is not None


class TestCacheLifecycle:
    """pio cache list/evict/prune semantics: LRU order by atime, byte
    budget enforcement, husk sweeps, and eviction under a live reader."""

    def _entry(self, storage, app_id, n=120):
        _put(storage, app_id, 0, n)
        h = prep_cache.probe("A", storage=storage, **FILTERS)
        batch = data_store.find_ratings("A", storage=storage, **FILTERS)
        assert _publish(h, batch)
        (entry,) = prep_cache.cache_entries()
        return entry, batch

    def test_lru_budget_eviction(self, prep_storage):
        import shutil

        storage, app_id = prep_storage
        entry, _ = self._entry(storage, app_id)
        src = Path(entry["path"])
        size = entry["bytes"]
        # three byte-identical tenants with older last-use times
        for i, name in enumerate(("aaa", "bbb", "ccc")):
            dst = src.with_name(f"{name}{prep_cache.SUFFIX}")
            shutil.copy2(src, dst)
            t = entry["atime"] - 100.0 * (3 - i)
            os.utime(dst, (t, t))
        names = [e["name"] for e in prep_cache.cache_entries()]
        assert names[:3] == [
            f"aaa{prep_cache.SUFFIX}",
            f"bbb{prep_cache.SUFFIX}",
            f"ccc{prep_cache.SUFFIX}",
        ]
        assert names[3] == src.name  # newest-atime last

        evicted = prep_cache.enforce_budget(limit=2 * size)
        assert evicted == names[:2]  # oldest two went
        left = prep_cache.cache_entries()
        assert [e["name"] for e in left] == names[2:]
        assert obs_metrics.gauge("pio_prep_cache_bytes").value() == float(
            sum(e["bytes"] for e in left)
        )
        # unbounded (no limit, no env cap): a no-op
        assert prep_cache.max_bytes() is None
        assert prep_cache.enforce_budget() == []

    def test_evict_by_name_and_bad_names(self, prep_storage):
        storage, app_id = prep_storage
        entry, _ = self._entry(storage, app_id)
        assert not prep_cache.evict("nope.prep")  # absent
        assert not prep_cache.evict(entry["name"] + ".bak")  # bad suffix
        assert prep_cache.evict(entry["name"])
        assert prep_cache.cache_entries() == []
        assert obs_metrics.gauge("pio_prep_cache_bytes").value() == 0.0

    def test_prune_sweeps_aged_husks_only(self, prep_storage):
        storage, app_id = prep_storage
        entry, _ = self._entry(storage, app_id)
        d = prep_cache.cache_dir()
        old_husk = d / "x.prep.tmp.123"
        new_husk = d / "y.prep.tmp.456"
        for husk in (old_husk, new_husk):
            husk.write_bytes(b"partial")
        t = time.time() - 1000.0
        os.utime(old_husk, (t, t))
        res = prep_cache.prune(max_age_s=600.0)
        assert res["husks"] == [old_husk.name]
        assert res["evicted"] == []
        assert new_husk.exists()  # a live writer's tmp is left alone
        assert Path(entry["path"]).exists()

    def test_eviction_race_with_live_reader(self, prep_storage):
        storage, app_id = prep_storage
        entry, batch = self._entry(storage, app_id)
        h = prep_cache.probe("A", storage=storage, **FILTERS)
        assert h.status == "hit"  # holds the entry's mmap
        assert prep_cache.evict(entry["name"])
        # unlink doesn't tear the mapping: the reader's arrays survive
        _assert_batch_equal(h.batch, batch)
        rb, cb = h.packed_buckets(WIDTHS)
        _assert_buckets_equal(rb, _fresh_pack(batch)[0])
        # the NEXT probe sees a cold cache
        assert (
            prep_cache.probe("A", storage=storage, **FILTERS).status
            == "miss"
        )
