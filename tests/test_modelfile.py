"""Zero-copy model file format (models/modelfile.py): round-trips for
the ALS-template model classes across f32/bf16/int8 storage, lazy id
dictionaries, corruption/truncation -> ModelFileError (never garbage
scores), the serve.model_mmap fault-point fallback, the persistence
integration both ways (PIO_MODEL_MMAP on/off), and the kill-9
publish-atomicity drill against the localfs store."""

from __future__ import annotations

import os
import pickle
import signal
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from predictionio_tpu import faults
from predictionio_tpu.data.bimap import BiMap
from predictionio_tpu.models import modelfile
from predictionio_tpu.models.modelfile import ModelFileError


def _als(storage_dtype="float32", n_users=40, n_items=16, rank=4):
    from predictionio_tpu.models.recommendation import ALSModel

    rng = np.random.default_rng(7)
    kw = dict(
        user_index=BiMap({f"u{i}": i for i in range(n_users)}),
        item_index=BiMap({f"i{i}": i for i in range(n_items)}),
    )
    if storage_dtype == "int8":
        kw.update(
            user_factors=rng.integers(
                -127, 128, (n_users, rank), dtype=np.int8
            ),
            item_factors=rng.integers(
                -127, 128, (n_items, rank), dtype=np.int8
            ),
            user_scales=rng.random(n_users, dtype=np.float32),
            item_scales=rng.random(n_items, dtype=np.float32),
        )
    else:
        if storage_dtype == "bfloat16":
            import ml_dtypes

            dt = np.dtype(ml_dtypes.bfloat16)
        else:
            dt = np.dtype("float32")
        kw.update(
            user_factors=rng.standard_normal(
                (n_users, rank), dtype=np.float32
            ).astype(dt),
            item_factors=rng.standard_normal(
                (n_items, rank), dtype=np.float32
            ).astype(dt),
        )
    return ALSModel(**kw)


def _roundtrip(model):
    blob = modelfile.serialize([("arrays", model)], model_id="t")
    entries = modelfile.deserialize(blob)
    assert len(entries) == 1 and entries[0][0] == "arrays"
    return entries[0][1]


class TestRoundTrip:
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int8"])
    def test_als_all_storage_dtypes(self, dtype):
        m = _als(dtype)
        assert modelfile.can_encode(m)
        back = _roundtrip(m)
        assert type(back) is type(m)
        assert back.user_factors.dtype == m.user_factors.dtype
        np.testing.assert_array_equal(
            np.asarray(back.user_factors), np.asarray(m.user_factors)
        )
        np.testing.assert_array_equal(
            np.asarray(back.item_factors), np.asarray(m.item_factors)
        )
        if dtype == "int8":
            np.testing.assert_array_equal(back.user_scales, m.user_scales)
            np.testing.assert_array_equal(back.item_scales, m.item_scales)
        else:
            assert back.user_scales is None and back.item_scales is None
        # decoded arrays are read-only views over the blob, not copies
        assert not back.user_factors.flags.writeable
        assert dict(back.user_index._m) == dict(m.user_index._m)
        assert back.item_index["i3"] == 3
        assert back.item_index.inverse[3] == "i3"

    def test_other_template_models(self):
        from predictionio_tpu.models.ecommerce import ECommModel
        from predictionio_tpu.models.recommendeduser import (
            RecommendedUserModel,
        )
        from predictionio_tpu.models.similarproduct import SimilarProductModel

        rng = np.random.default_rng(3)
        sims = SimilarProductModel(
            item_index=BiMap({f"i{i}": i for i in range(9)}),
            item_factors=rng.standard_normal((9, 4), dtype=np.float32),
            categories={"i0": ["a", "b"], "i3": ["b"]},
        )
        ecom = ECommModel(
            user_index=BiMap({f"u{i}": i for i in range(5)}),
            item_index=BiMap({f"i{i}": i for i in range(7)}),
            user_factors=rng.integers(-127, 128, (5, 4), dtype=np.int8),
            item_factors=rng.integers(-127, 128, (7, 4), dtype=np.int8),
            categories={"i1": ["x"]},
            user_scales=rng.random(5, dtype=np.float32),
            item_scales=rng.random(7, dtype=np.float32),
        )
        reco = RecommendedUserModel(
            followed_index=BiMap({f"f{i}": i for i in range(6)}),
            followed_factors=rng.standard_normal((6, 4), dtype=np.float32),
        )
        for m in (sims, ecom, reco):
            assert modelfile.can_encode(m)
            back = _roundtrip(m)
            assert type(back) is type(m)
        assert _roundtrip(sims).categories == sims.categories
        np.testing.assert_array_equal(
            _roundtrip(ecom).user_scales, ecom.user_scales
        )
        assert _roundtrip(reco).followed_index["f5"] == 5

    def test_mixed_manifest_kinds(self):
        m = _als("int8")
        payload = {"weights": [1.0, 2.0]}
        blob = modelfile.serialize(
            [
                ("arrays", m),
                ("pickle", pickle.dumps(payload)),
                ("retrain", None),
                ("persistent", ("some.module", "SomeClass")),
            ],
            model_id="mixed",
        )
        entries = modelfile.deserialize(blob)
        kinds = [k for k, _ in entries]
        assert kinds == ["arrays", "pickle", "retrain", "persistent"]
        assert pickle.loads(entries[1][1]) == payload
        assert list(entries[3][1]) == ["some.module", "SomeClass"]

    def test_lazy_bimap_defers_decode_and_repickles_plain(self):
        m = _als("float32", n_users=100)
        back = _roundtrip(m)
        idx = back.user_index
        # len is O(1) off the offsets table; the dict is not built yet
        assert idx._fwd is None
        assert len(idx) == 100
        assert idx._fwd is None
        assert idx["u42"] == 42  # a look-up searches the hashes: nothing materializes
        assert idx._fwd is None
        assert idx.inverse[42] == "u42"
        # repickling must yield a plain BiMap, never leak mmap views
        clone = pickle.loads(pickle.dumps(idx))
        assert type(clone) is BiMap
        assert clone["u42"] == 42 and len(clone) == 100
        assert idx._fwd is not None  # a pickle walks the mapping: the one decode left


class TestValidation:
    def test_bad_magic(self):
        with pytest.raises(ModelFileError):
            modelfile.deserialize(b"NOTMODEL" + b"\x00" * 64)

    def test_header_corruption_is_named_error(self):
        blob = bytearray(modelfile.serialize([("arrays", _als())], "t"))
        hdr_at = len(modelfile.MAGIC) + 12  # inside the JSON header
        blob[hdr_at] ^= 0xFF
        with pytest.raises(ModelFileError):
            modelfile.deserialize(bytes(blob))

    def test_truncation_sweep_never_garbage(self):
        blob = modelfile.serialize([("arrays", _als())], "t")
        # every prefix either loads equal or raises the NAMED error —
        # sweep a stride of cut points through header and blocks
        for cut in range(4, len(blob) - 1, max(1, len(blob) // 64)):
            with pytest.raises(ModelFileError):
                modelfile.deserialize(blob[:cut])

    def test_block_corruption_caught_under_verify(self, monkeypatch):
        m = _als("int8")
        blob = bytearray(modelfile.serialize([("arrays", m)], "t"))
        blob[-3] ^= 0x55  # flip a byte inside the last array block
        monkeypatch.setenv("PIO_MODEL_VERIFY", "1")
        with pytest.raises(ModelFileError):
            modelfile.deserialize(bytes(blob))

    def test_load_path_truncated_file(self, tmp_path):
        blob = modelfile.serialize([("arrays", _als())], "t")
        p = tmp_path / "trunc.bin"
        p.write_bytes(blob[: len(blob) // 2])
        with pytest.raises(ModelFileError):
            modelfile.load_path(p)


class TestLoadPath:
    def test_mmap_fault_falls_back_to_bytes(self, tmp_path):
        from predictionio_tpu.obs import metrics as obs_metrics

        m = _als("int8")
        p = tmp_path / "model.bin"
        p.write_bytes(modelfile.serialize([("arrays", m)], "t"))
        ctr = obs_metrics.counter(
            "pio_model_mmap_fallback_total",
            "model file loads that fell back from mmap to a byte read",
        )
        before = ctr.value()
        with faults.injected("serve.model_mmap:nth=1:raise=OSError"):
            mf = modelfile.load_path(p)
        assert ctr.value() == before + 1
        back = mf.entries()[0][1]
        np.testing.assert_array_equal(
            back.user_factors, m.user_factors
        )

    def test_shared_entries_identity_across_mounts(self, tmp_path):
        p = tmp_path / "model.bin"
        p.write_bytes(modelfile.serialize([("arrays", _als())], "t"))
        modelfile._clear_shared()
        a = modelfile.shared_entries(p)
        b = modelfile.shared_entries(p)
        assert a is b  # N tenants of one file share ONE decoded list
        modelfile._clear_shared()

    def test_shared_entries_sees_new_version(self, tmp_path):
        p = tmp_path / "model.bin"
        p.write_bytes(modelfile.serialize([("arrays", _als())], "v1"))
        modelfile._clear_shared()
        a = modelfile.shared_entries(p)
        blob2 = modelfile.serialize([("arrays", _als("int8"))], "v2")
        p.write_bytes(blob2)
        os.utime(p, ns=(1, 1))  # force a distinct mtime_ns
        b = modelfile.shared_entries(p)
        assert b is not a
        assert b[0][1].user_factors.dtype == np.int8
        modelfile._clear_shared()


class TestPersistence:
    class _Algo:
        """Minimal algorithm surface for serialize_models."""

        def make_persistent_model(self, model):
            return model

    def test_roundtrip_via_persistence(self):
        from predictionio_tpu.core import persistence

        m = _als("int8")
        blob = persistence.serialize_models([self._Algo()], [m], "inst1")
        assert modelfile.is_modelfile(blob)
        out = persistence.deserialize_models(blob, [self._Algo()], "inst1")
        np.testing.assert_array_equal(out[0].user_factors, m.user_factors)

    def test_mmap_opt_out_writes_legacy_pickle(self, monkeypatch):
        from predictionio_tpu.core import persistence

        monkeypatch.setenv("PIO_MODEL_MMAP", "0")
        m = _als()
        blob = persistence.serialize_models([self._Algo()], [m], "inst1")
        assert not modelfile.is_modelfile(blob)
        out = persistence.deserialize_models(blob, [self._Algo()], "inst1")
        np.testing.assert_array_equal(out[0].user_factors, m.user_factors)

    def test_deserialize_model_path(self, tmp_path):
        from predictionio_tpu.core import persistence

        m = _als()
        p = tmp_path / "model.bin"
        p.write_bytes(modelfile.serialize([("arrays", m)], "inst1"))
        modelfile._clear_shared()
        a = persistence.deserialize_model_path(p, [self._Algo()], "inst1")
        b = persistence.deserialize_model_path(p, [self._Algo()], "inst1")
        assert a[0] is b[0]  # same objects: the density win
        # a legacy pickle file is not claimed — caller falls back
        legacy = tmp_path / "legacy.bin"
        legacy.write_bytes(pickle.dumps([("x", 1)]))
        assert (
            persistence.deserialize_model_path(
                legacy, [self._Algo()], "inst1"
            )
            is None
        )
        modelfile._clear_shared()


class TestPublishAtomicity:
    def test_kill9_during_publish_leaves_only_old_model(self, tmp_path):
        """kill -9 at the storage.rename point mid-publish: the served
        model file must still be the OLD version, byte for byte, and
        must still deserialize — a torn write may leave a tmp file but
        never a torn model."""
        from predictionio_tpu.data.storage import base
        from predictionio_tpu.data.storage.localfs import (
            LocalFSModels,
            LocalFSStorageClient,
        )

        store_dir = tmp_path / "store"
        models = LocalFSModels(LocalFSStorageClient({"path": str(store_dir)}))
        v1 = modelfile.serialize([("arrays", _als("float32"))], "v1")
        models.insert(base.Model("chaos", v1))
        v2_path = tmp_path / "v2.blob"
        v2_path.write_bytes(
            modelfile.serialize([("arrays", _als("int8"))], "v2")
        )
        child = textwrap.dedent(
            """
            import sys
            from predictionio_tpu.data.storage import base
            from predictionio_tpu.data.storage.localfs import (
                LocalFSModels, LocalFSStorageClient,
            )
            m = LocalFSModels(LocalFSStorageClient({"path": sys.argv[1]}))
            with open(sys.argv[2], "rb") as f:
                m.insert(base.Model("chaos", f.read()))
            print("PUBLISHED", flush=True)
            """
        )
        env = dict(os.environ)
        env["PIO_FAULTS"] = "storage.rename:nth=1:kill"
        env["JAX_PLATFORMS"] = "cpu"
        proc = subprocess.run(
            [sys.executable, "-c", child, str(store_dir), str(v2_path)],
            capture_output=True, text=True, timeout=120, env=env,
        )
        assert proc.returncode == -signal.SIGKILL, (
            proc.returncode, proc.stderr[-500:]
        )
        assert "PUBLISHED" not in proc.stdout
        # the store still serves v1, byte-identical and loadable
        got = models.get("chaos")
        assert got is not None and got.models == v1
        entries = modelfile.deserialize(got.models)
        assert entries[0][1].user_factors.dtype == np.float32


MIB = 1 << 20


def _span(tmp_path, model, segment_bytes=MIB, **kw):
    head = tmp_path / "pio_model_span.bin"
    info = modelfile.write_spanning(
        head, [("arrays", model)], "span", segment_bytes=segment_bytes, **kw
    )
    return head, info


class TestSpanningModel:
    """A model that spans files: a head file + segments of bounded size."""

    def test_span_and_reload_round_trip_at_a_one_mib_segment(self, tmp_path):
        m = _als(n_users=300, n_items=50_000, rank=16)  # item table 3.2 MB
        head, info = _span(tmp_path, m)
        segs = sorted(p.name for p in tmp_path.iterdir() if ".seg" in p.name)
        assert info["segments"] == len(segs) >= 4
        assert all((tmp_path / s).stat().st_size <= MIB for s in segs)
        assert head.stat().st_size < 8192  # the head holds the header only
        mf = modelfile.load_path(head)
        assert [s["file"] for s in mf.segments] == segs
        got = mf.entries()[0][1]
        assert isinstance(got.item_factors, modelfile.SpannedArray)
        assert len(got.item_factors.parts) == 4  # 16,384 rows of 64 B a segment
        assert isinstance(got.user_factors, np.ndarray)  # fits one block
        np.testing.assert_array_equal(np.asarray(got.item_factors), m.item_factors)
        np.testing.assert_array_equal(got.user_factors, m.user_factors)
        assert got.user_index == m.user_index and len(got.item_index) == 50_000

    def test_an_int8_pair_spans_as_values_in_parts_and_scales_whole(self, tmp_path):
        """A quarter of the f32 model: int8 values cut row-wise over the
        segments, one f32 scale a row in a block of its own — both loaded
        as stored, no dequantized copy anywhere."""
        m = _als("int8", n_users=300, n_items=100_000, rank=32)  # values 3.2 MB
        (tmp_path / "f32").mkdir()
        f32 = _span(tmp_path / "f32", _als(n_users=300, n_items=100_000, rank=32))[1]
        head, info = _span(tmp_path, m)
        assert info["bytes"] < f32["bytes"] / 2
        got = modelfile.load_path(head).entries()[0][1]
        assert isinstance(got.item_factors, modelfile.SpannedArray)
        assert got.item_factors.dtype == np.int8 and len(got.item_factors.parts) == 4
        assert all(p.dtype == np.int8 and p.nbytes <= MIB for p in got.item_factors.parts)
        assert isinstance(got.item_scales, np.ndarray) and got.item_scales.dtype == np.float32
        assert got.user_factors.dtype == np.int8 and got.user_scales.shape == (300,)
        np.testing.assert_array_equal(np.asarray(got.item_factors), m.item_factors)
        np.testing.assert_array_equal(got.item_scales, m.item_scales)
        ix = np.array([0, 99_999, 32_768, 32_767])
        np.testing.assert_array_equal(got.item_factors[ix], m.item_factors[ix])
        np.testing.assert_array_equal(got.user_rows(ix[:1]), m.user_rows(ix[:1]))
        assert got.item_table()[0] is got.item_factors  # the scorer's pair, as stored

    def test_spanned_array_reads_rows_where_they_lie(self, tmp_path):
        m = _als(n_users=4, n_items=50_000, rank=16)
        V = modelfile.load_path(_span(tmp_path, m)[0]).fields(0)["item_factors"]
        assert V.shape == (50_000, 16) and len(V) == 50_000 and V.dtype == np.float32
        ix = np.array([0, 49_999, 16_384, 16_383, 777, -1])
        np.testing.assert_array_equal(V[ix], m.item_factors[ix])
        np.testing.assert_array_equal(V[[[1, 2], [40_000, 3]]], m.item_factors[[[1, 2], [40_000, 3]]])
        np.testing.assert_array_equal(V[16_000:33_000], m.item_factors[16_000:33_000])
        np.testing.assert_array_equal(V.rows(49_990, 60_000), m.item_factors[49_990:])
        inside = V.rows(100, 200)  # one part: a view of the mapping, no copy
        assert inside.base is not None and not inside.flags.writeable
        assert V.rows(7, 7).shape == (0, 16)

    def test_row_sources_and_encoded_ids_are_never_whole(self, tmp_path):
        """What a writer of a 12 GB table hands over: a row source asked for
        one block at a time, ids already in their stored form."""
        rng = np.random.default_rng(3)
        V = rng.standard_normal((40_000, 16)).astype(np.float32)
        asked = []

        class Rows:
            shape, dtype = V.shape, V.dtype

            def rows(self, lo, hi):
                asked.append((lo, hi))
                return V[lo:hi]

        ids = modelfile.EncodedIds(*modelfile._encode_ids([f"i{n}" for n in range(40_000)]))
        fields = modelfile.Fields(
            ("predictionio_tpu.models.recommendation", "ALSModel"),
            {"user_index": BiMap({"u0": 0}), "item_index": ids,
             "user_factors": V[:1].copy(), "item_factors": Rows(),
             "user_scales": None, "item_scales": None},
        )
        head = tmp_path / "pio_model_rows.bin"
        modelfile.write_spanning(head, [("arrays", fields)], "rows",
                                 segment_bytes=MIB, workers=3)
        assert sorted(asked) == [(0, 16_384), (16_384, 32_768), (32_768, 40_000)]
        got = modelfile.load_path(head).entries()[0][1]
        np.testing.assert_array_equal(np.asarray(got.item_factors), V)
        assert got.item_index.inverse[39_999] == "i39999"

    def test_block_checksums_cover_every_segment(self, tmp_path, monkeypatch):
        head, _ = _span(tmp_path, _als(n_users=4, n_items=50_000, rank=16))
        seg = tmp_path / (head.name + ".seg0002")
        raw = bytearray(seg.read_bytes())
        raw[1000] ^= 0xFF
        seg.write_bytes(bytes(raw))
        modelfile.load_path(head)  # O(pages touched): not read, not caught
        monkeypatch.setenv("PIO_MODEL_VERIFY", "1")
        with pytest.raises(ModelFileError, match="checksum mismatch"):
            modelfile.load_path(head)

    def test_a_missing_or_short_segment_is_a_named_error(self, tmp_path):
        head, _ = _span(tmp_path, _als(n_users=4, n_items=50_000, rank=16))
        seg = tmp_path / (head.name + ".seg0001")
        whole = seg.read_bytes()
        seg.write_bytes(whole[: len(whole) // 2])
        with pytest.raises(ModelFileError, match="truncated"):
            modelfile.load_path(head)
        seg.unlink()
        with pytest.raises(ModelFileError, match="missing"):
            modelfile.load_path(head)

    def test_a_spanning_head_does_not_load_from_bytes(self, tmp_path):
        head, _ = _span(tmp_path, _als(n_users=4, n_items=50_000, rank=16))
        with pytest.raises(ModelFileError, match="head file's path"):
            modelfile.deserialize(head.read_bytes())

    def test_one_row_wider_than_a_segment_is_refused(self, tmp_path):
        with pytest.raises(ModelFileError, match="exceeds a segment"):
            _span(tmp_path, _als(rank=64), segment_bytes=128)
        assert not [p for p in tmp_path.iterdir() if ".seg" in p.name]

    def test_one_file_models_still_load_and_are_written_as_before(self, tmp_path):
        m = _als()
        blob = modelfile.serialize([("arrays", m)], "one")
        assert b'"version": 1' in blob and b"segments" not in blob[:4096]
        p = tmp_path / "one.bin"
        p.write_bytes(blob)
        mf = modelfile.load_path(p)
        assert mf.segments == []
        got = mf.entries()[0][1]
        assert isinstance(got.item_factors, np.ndarray)
        np.testing.assert_array_equal(got.item_factors, m.item_factors)

    def test_a_loaded_spanning_model_goes_back_into_one_file(self, tmp_path):
        m = _als(n_users=4, n_items=50_000, rank=16)
        got = modelfile.load_path(_span(tmp_path, m)[0]).entries()[0][1]
        assert modelfile.can_encode(got)
        again = modelfile.deserialize(modelfile.serialize([("arrays", got)], "x"))[0][1]
        np.testing.assert_array_equal(again.item_factors, m.item_factors)

    def test_persistence_spans_a_model_over_a_segment(self, tmp_path, monkeypatch):
        """`pio train`'s save (core/persistence.py save_models): a model
        over a segment's size goes to a local store spanning, and
        `prepare_deploy`'s path loads it; the store's delete takes the
        segments along."""
        from predictionio_tpu.core import persistence
        from predictionio_tpu.data.storage.localfs import (
            LocalFSModels, LocalFSStorageClient,
        )
        from predictionio_tpu.models.recommendation import ALSAlgorithm

        monkeypatch.setattr(modelfile, "SEGMENT_BYTES", MIB)
        store = LocalFSModels(LocalFSStorageClient({"path": str(tmp_path)}))
        algo = ALSAlgorithm()
        big, small = _als(n_users=4, n_items=50_000, rank=16), _als()
        persistence.save_models(store, [algo], [big], "big")
        persistence.save_models(store, [algo], [small], "small")
        names = sorted(p.name for p in tmp_path.iterdir())
        assert [n for n in names if "big" in n and ".seg" in n]
        assert not [n for n in names if "small" in n and ".seg" in n]
        got = persistence.deserialize_model_path(store.local_path("big"), [algo], "big")[0]
        np.testing.assert_array_equal(np.asarray(got.item_factors), big.item_factors)
        assert store.delete("big")
        assert not [p for p in tmp_path.iterdir() if "big" in p.name]


class TestIdsFromTheBlob:
    """An answer needs k ids: read from the blob and its offsets, the id
    list never built."""

    def _index(self, monkeypatch, n=1000):
        blob, offs = modelfile._encode_ids([f"item-{i}-é" for i in range(n)])
        bm = modelfile._LazyDenseBiMap(blob, offs)
        built = []
        monkeypatch.setattr(
            modelfile._LazyDenseBiMap, "_ids",
            lambda self: built.append(1) or pytest.fail("the id list was built"),
        )
        return bm, built

    def test_inverse_lookups_decode_one_id(self, monkeypatch):
        bm, built = self._index(monkeypatch)
        inv = bm.inverse
        assert inv[0] == "item-0-é" and inv[999] == "item-999-é"
        assert inv[np.int32(17)] == "item-17-é"
        assert inv.get(1000) is None and inv.get(-1, "x") == "x"
        assert 5 in inv and 1000 not in inv and "item-5-é" not in inv
        assert len(inv) == len(bm) == 1000
        assert inv.inverse is bm
        with pytest.raises(KeyError):
            inv[1000]
        with pytest.raises(KeyError):
            inv[True]
        assert not built and bm._fwd is None

    def test_a_served_answer_builds_no_id_list(self, monkeypatch):
        """batch_predict over a model loaded from a file: the item index's
        inverse answers k ids from the blob, and the USER index finds the
        query's user by the hashes of its blob — neither id list is built."""
        from predictionio_tpu.models import recommendation as rec

        m = _als(n_users=8, n_items=64, rank=4)
        got = modelfile.deserialize(modelfile.serialize([("arrays", m)], "x"))[0][1]
        monkeypatch.setattr(
            modelfile._LazyDenseBiMap, "_ids",
            lambda self: pytest.fail("an id list was built"),
        )
        algo = rec.ALSAlgorithm(rec.ALSAlgorithmParams(rank=4))
        res = algo.predict(got, rec.Query(user="u3", num=5))
        want = rec.ALSAlgorithm(rec.ALSAlgorithmParams(rank=4)).predict(
            m, rec.Query(user="u3", num=5))
        assert [s.item for s in res.itemScores] == [s.item for s in want.itemScores]
        assert got.item_index._fwd is None and got.user_index._fwd is None

    def test_walking_the_whole_mapping_still_works(self):
        blob, offs = modelfile._encode_ids(["a", "b", "c"])
        inv = modelfile._LazyDenseBiMap(blob, offs).inverse
        assert dict(inv.items()) == {0: "a", 1: "b", 2: "c"}
        assert inv[2] == "c" and list(inv) == [0, 1, 2]
        assert inv == BiMap({0: "a", 1: "b", 2: "c"})


# ids of mixed lengths: 1 byte to 40, multi-byte UTF-8, an id that is a
# prefix of another, NUL bytes, ids a word (8 bytes) and a word + 1 long
_MIXED_IDS = (
    ["a", "ab", "abc", "abcdefg", "abcdefgh", "abcdefghi", "x" * 40, "é",
     "ünï-" * 9, "日本語のID", "a\0", "ab\0\0", "i1", "i10", "i100", "i1000000"]
    + [f"item-{n}" for n in range(300)]
)
_ASKED = _MIXED_IDS[:20] + [
    "nope", "", "abcd", "a\0\0", "abcdefgh\0", "x" * 41, "É", 7, None, 2.5,
    ("a",), "item-299", "item-300",
]


def _lazy(ids=_MIXED_IDS):
    return modelfile._LazyDenseBiMap(*modelfile._encode_ids(list(ids)))


def _id_counts():
    b = modelfile.id_stats_block()
    return b["lookups"]["hashed"], b["lookups"]["decoded"], b["index_builds"], b["decodes"]


class TestHashedLookups:
    """Every point look-up of a map over an encoded dictionary is answered
    from the blob's sorted hashes; nothing is decoded."""

    @pytest.mark.parametrize("ask", [
        pytest.param(lambda m, k: m[k] if k in m else None, id="getitem+in"),
        pytest.param(lambda m, k: m.get(k), id="get"),
        pytest.param(lambda m, k: m.get(k, "dflt"), id="get-default"),
        pytest.param(lambda m, k: k in m, id="in"),
        pytest.param(lambda m, k: m.index_of([k]).tolist(), id="index_of-single"),
    ])
    def test_point_lookups_equal_a_plain_bimaps(self, ask):
        lazy, plain = _lazy(), BiMap.from_dense(_MIXED_IDS)
        for key in _ASKED:
            assert ask(lazy, key) == ask(plain, key), key
        with pytest.raises(KeyError):
            lazy["nope"]
        with pytest.raises(KeyError):
            lazy[7]
        assert type(lazy["abc"]) is int and lazy._fwd is None

    @pytest.mark.parametrize("keys", [
        pytest.param(_ASKED, id="mixed"),  # non-strings: the Python loop
        pytest.param([k for k in _ASKED if isinstance(k, str)], id="strings"),
        pytest.param([f"item-{n}" for n in range(280, 320)], id="ascii"),  # one NumPy pass
        pytest.param(["abc"], id="single"),
        pytest.param([], id="empty"),
        pytest.param(["item-5"] * 30, id="repeated"),
    ])
    def test_index_of_equals_a_plain_bimaps(self, keys):
        lazy, plain = _lazy(), BiMap.from_dense(_MIXED_IDS)
        for _ in range(2):
            got = lazy.index_of(keys)
            assert got.dtype == np.int64
            assert got.tolist() == plain.index_of(keys).tolist()
        assert lazy._fwd is None

    def test_the_numpy_pass_equals_the_python_loop(self):
        lazy = _lazy()
        ascii_keys = [f"item-{n}" for n in range(250, 350)] + ["a", "abcdefghi", "i100"]
        assert lazy._find_all(ascii_keys).tolist() == [
            lazy._find(k) for k in ascii_keys
        ]
        # keys that are not their own bytes are left to the loop
        assert lazy._find_all(ascii_keys + ["é"]) is None
        nul = ascii_keys + ["a\0", "ab\0\0", "a\0\0", "abcdefgh\0"]  # NULs are bytes like any other
        assert lazy._find_all(nul).tolist() == [lazy._find(k) for k in nul]
        assert lazy._find_all(nul)[-4:].tolist() == [10, 11, -1, -1]
        assert lazy._find_all(ascii_keys + ["x" * 100]) is None
        assert lazy.index_of(ascii_keys + ["é", "a\0"]).tolist()[-2:] == [7, 10]

    @pytest.mark.parametrize("mul", [0, 1], ids=["all-equal", "xor-of-words"])
    def test_ids_forced_onto_one_hash_are_told_apart_by_their_bytes(self, monkeypatch, mul):
        monkeypatch.setattr(modelfile, "_HASH_MUL", np.uint64(mul))
        lazy, plain = _lazy(), BiMap.from_dense(_MIXED_IDS)
        words = lazy._hashed().keys >> np.uint64(lazy._hashed().shift)
        assert len(np.unique(words)) < len(_MIXED_IDS)  # they do collide
        keys = [k for k in _ASKED if isinstance(k, str)]
        assert lazy.index_of(keys).tolist() == plain.index_of(keys).tolist()
        ascii_keys = [f"item-{n}" for n in range(290, 310)]
        assert lazy._find_all(ascii_keys).tolist() == plain.index_of(ascii_keys).tolist()
        assert all(lazy.get(k) == plain.get(k) for k in keys)

    @pytest.mark.parametrize("chunk", [1, 7, 100, len(_MIXED_IDS), 1 << 18])
    def test_chunked_hashes_equal_the_whole_arrays_bit_for_bit(self, chunk):
        blob, offs = modelfile._encode_ids(list(_MIXED_IDS))
        assert len(_MIXED_IDS) % 7 and len(_MIXED_IDS) % 100  # uneven last chunks
        whole = modelfile._hash_ids(blob, offs, len(_MIXED_IDS) + 1)
        assert whole.dtype == np.uint64
        np.testing.assert_array_equal(modelfile._hash_ids(blob, offs, chunk), whole)
        assert whole.tolist() == [
            modelfile._hash_key(s.encode("utf-8")) for s in _MIXED_IDS
        ]

    def test_the_index_is_one_sorted_word_an_id(self):
        lazy = _lazy()
        index = lazy._hashed()
        keys, shift, cells = index.keys, index.shift, index.cells
        assert keys.dtype == np.uint64 and len(keys) == len(_MIXED_IDS) == len(cells)
        assert (np.diff(keys.astype(object)) > 0).all()
        low = (1 << shift) - 1
        assert sorted(int(k) & low for k in keys) == list(range(len(_MIXED_IDS)))
        blob, offs = modelfile._encode_ids(list(_MIXED_IDS))
        h = modelfile._hash_ids(blob, offs)
        for k in keys.tolist():
            assert k >> shift == int(h[k & low]) >> shift

    def test_an_empty_map_and_a_map_of_one(self):
        empty, one = _lazy([]), _lazy(["only"])
        assert empty.get("a") is None and "a" not in empty and len(empty) == 0
        assert empty.index_of(["a", 3]).tolist() == [-1, -1]
        assert one["only"] == 0 and one.get("other") is None
        assert one.index_of(["only", "x"] * 6).tolist() == [0, -1] * 6
        blank = _lazy([""])  # one id, no bytes at all
        assert blank[""] == 0 and blank.index_of(["x", ""] * 6).tolist() == [-1, 0] * 6

    def test_two_threads_asking_at_once_build_once(self, monkeypatch):
        import sys as _sys
        import threading
        import time

        built = []
        real = modelfile._hash_ids

        def slow(blob, offs, *a):
            built.append(threading.get_ident())
            time.sleep(0.05)  # hold the build open while the others arrive
            return real(blob, offs, *a)

        monkeypatch.setattr(modelfile, "_hash_ids", slow)
        lazy, got = _lazy(), []
        _, _, builds, _ = _id_counts()
        start = threading.Barrier(8)

        def ask(n):
            start.wait(timeout=10)
            got.append((lazy.get(f"item-{n}"), lazy.index_of([f"item-{n}", "nope"]).tolist()))

        old = _sys.getswitchinterval()
        _sys.setswitchinterval(1e-5)
        try:
            threads = [threading.Thread(target=ask, args=(n,)) for n in range(8)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=20)
        finally:
            _sys.setswitchinterval(old)
        assert not any(t.is_alive() for t in threads)
        assert len(built) == 1
        assert sorted(got) == [(16 + n, [16 + n, -1]) for n in range(8)]
        assert _id_counts()[2] == builds + 1

    @pytest.mark.parametrize("n", [1, 2, 7, 8, 9, 64, 1000])
    def test_the_directory_opens_every_words_bucket(self, n):
        """``first[b]`` is where the words whose top ``bits`` bits are b
        begin, no bucket holds more than ``most``, and a search that
        starts there finds every id — at sizes around the bucket's."""
        ids = [f"id-{k}" for k in range(n)]
        lazy = _lazy(ids)
        index = lazy._hashed()
        top = (index.keys >> np.uint64(64 - index.bits)).astype(np.int64)
        assert len(index.first) == (1 << index.bits) + 1
        assert index.first[0] == 0 and index.first[-1] == n
        np.testing.assert_array_equal(
            index.first[:-1], np.searchsorted(top, np.arange(1 << index.bits))
        )
        assert index.most == np.bincount(top).max() and (np.diff(top) >= 0).all()
        assert [lazy._find(k) for k in ids] == list(range(n))
        asked = ids + ["id-x", "nope"] * 4
        assert lazy.index_of(asked).tolist() == list(range(n)) + [-1] * 8
        assert lazy._find_all(asked).tolist() == lazy.index_of(asked).tolist()

    def test_a_long_list_is_looked_up_a_part_at_a_time(self, monkeypatch):
        monkeypatch.setattr(modelfile, "_VECTOR_MOST", 16)
        monkeypatch.setattr(modelfile, "_VECTOR_FROM", 8)  # a part is a NumPy pass where it can be
        lazy, plain = _lazy(), BiMap.from_dense(_MIXED_IDS)
        keys = [f"item-{n}" for n in range(290, 330)] + ["é", 7] + ["abc"] * 11  # 53: parts of 16, 16, 16, 5
        got = lazy.index_of(keys)
        assert got.dtype == np.int64 and got.tolist() == plain.index_of(keys).tolist()

    def test_appended_maps_read_through_to_the_hashes(self):
        lazy = _lazy()
        grown = lazy.appended(["new-a", "new-b"])
        n = len(_MIXED_IDS)
        assert grown["new-b"] == n + 1 and grown["abc"] == 2 and "nope" not in grown
        assert grown.index_of(["new-a", "item-7", "nope"]).tolist() == [n, 23, -1]
        assert lazy._fwd is None
        with pytest.raises(Exception):
            lazy.appended(["abc"])


class TestIdCounters:
    def test_lookups_count_by_what_answered_and_a_walk_is_the_one_decode(self):
        lazy = _lazy()
        h0, d0, b0, w0 = _id_counts()
        assert lazy["abc"] == 2 and "nope" not in lazy
        lazy.index_of(["a", "ab", 7])
        assert _id_counts() == (h0 + 5, d0, b0 + 1, w0)
        assert dict(lazy.items())["abc"] == 2  # a walk: the dictionary is decoded, once
        assert list(lazy)[:2] == ["a", "ab"] and lazy == BiMap.from_dense(_MIXED_IDS)
        assert _id_counts() == (h0 + 5, d0, b0 + 1, w0 + 1)
        assert lazy["abc"] == 2 and lazy.get("nope") is None  # ... and answers from then on
        lazy.index_of(["a", "ab", 7])
        assert _id_counts() == (h0 + 5, d0 + 5, b0 + 1, w0 + 1)

    def test_the_counters_are_in_metrics_and_stats(self):
        from predictionio_tpu.obs import metrics as obs_metrics

        _lazy()["abc"]
        series = obs_metrics.parse_prometheus(obs_metrics.render_prometheus())
        assert series['pio_model_id_lookups_total{path="hashed"}'] >= 1
        assert 'pio_model_id_lookups_total{path="decoded"}' in series
        assert series["pio_model_id_index_build_seconds_count"] >= 1
        assert "pio_model_id_decodes_total" in series
        block = modelfile.id_stats_block()
        assert set(block) == {"lookups", "index_builds", "index_build_seconds", "decodes"}


def _template_cases():
    """(name, model, algorithm, queries) of each of the four ALS templates,
    small enough for the exact path."""
    from predictionio_tpu.models import ecommerce as ec
    from predictionio_tpu.models import recommendation as rec
    from predictionio_tpu.models import recommendeduser as ru
    from predictionio_tpu.models import similarproduct as sp

    rng = np.random.default_rng(11)
    users = BiMap.from_dense([f"u{n}" for n in range(12)])
    items = BiMap.from_dense([f"i{n}" for n in range(48)])
    U = rng.standard_normal((12, 8), dtype=np.float32)
    V = rng.standard_normal((48, 8), dtype=np.float32)
    cats = {f"i{n}": [f"c{n % 3}"] for n in range(48)}
    yield (
        "recommendation",
        rec.ALSModel(user_index=users, item_index=items, user_factors=U, item_factors=V),
        rec.ALSAlgorithm(rec.ALSAlgorithmParams(rank=8)),
        [rec.Query(user="u3", num=5), rec.Query(user="stranger", num=5)],
    )
    yield (
        "ecommerce",
        ec.ECommModel(user_index=users, item_index=items, user_factors=U,
                      item_factors=V, categories=cats),
        ec.ECommAlgorithm(ec.ECommAlgorithmParams(app_name="IdsApp")),
        [ec.Query(user="u3", num=5),
         ec.Query(user="u4", num=5, categories=["c1"], blackList=["i1", "nope"]),
         ec.Query(user="u5", num=4, whiteList=["i2", "i3", "i5", "i8", "i13", "zz"]),
         ec.Query(user="newcomer", num=3)],
    )
    yield (
        "similarproduct",
        sp.SimilarProductModel(item_index=items, item_factors=V, categories=cats),
        sp.ALSAlgorithm(sp.ALSAlgorithmParams(rank=8)),
        [sp.Query(items=["i3"], num=5),
         sp.Query(items=["i4", "i9", "nope"], num=5, blackList=["i1"], categories=["c2"]),
         sp.Query(items=["i6"], num=3, whiteList=["i2", "i3", "i5", "zz"]),
         sp.Query(items=["nope"], num=3)],
    )
    yield (
        "recommendeduser",
        ru.RecommendedUserModel(followed_index=users, followed_factors=U),
        ru.ALSAlgorithm(ru.ALSAlgorithmParams(rank=8)),
        [ru.Query(users=["u3"], num=4),
         ru.Query(users=["u4", "nope"], num=4, blackList=["u1"]),
         ru.Query(users=["u5"], num=2, whiteList=["u2", "u7", "zz"])],
    )


@pytest.mark.parametrize("name", ["recommendation", "ecommerce", "similarproduct", "recommendeduser"])
def test_a_template_served_from_a_model_file_decodes_no_dictionary(name, storage, monkeypatch):
    """``predict`` of each of the four ALS templates over a model loaded
    from a file: the in-memory model's answers, no id list ever built,
    ``pio_model_id_decodes_total`` still, ``{path="hashed"}`` risen."""
    from predictionio_tpu.data.event import Event
    from predictionio_tpu.data.storage import App

    app_id = storage.get_metadata_apps().insert(App(0, "IdsApp"))
    storage.get_events().init(app_id)
    storage.get_events().batch_insert(
        [Event(event="view", entity_type="user", entity_id=u,
               target_entity_type="item", target_entity_id=f"i{n}")
         for u in ("u3", "u4", "newcomer") for n in (2, 7, 11)]
        + [Event(event="$set", entity_type="constraint", entity_id="unavailableItems",
                 properties={"items": ["i20", "i21", "gone"]})],
        app_id,
    )
    _, model, algo, queries = next(c for c in _template_cases() if c[0] == name)
    loaded = modelfile.deserialize(modelfile.serialize([("arrays", model)], "x"))[0][1]
    want = [algo.predict(model, q) for q in queries]
    monkeypatch.setattr(
        modelfile._LazyDenseBiMap, "_ids",
        lambda self: pytest.fail("an id list was built"),
    )
    hashed, _, _, decodes = _id_counts()
    got = [type(algo)(algo.params).predict(loaded, q) for q in queries]
    assert got == want and any(
        getattr(r, "itemScores", None) or getattr(r, "userScores", None)
        for r in got
    )
    assert _id_counts()[3] == decodes and _id_counts()[0] > hashed
    assert all(
        v._fwd is None for v in vars(loaded).values()
        if isinstance(v, modelfile._LazyDenseBiMap)
    )
