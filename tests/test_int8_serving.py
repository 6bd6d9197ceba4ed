"""An int8-stored model on the serving path, at a small size on the CPU:
written spanning, loaded, staged as stored, served through
``retrieval.top_k(UserRows)`` at B = 1, 8 and 16 (both scan bodies) in both
int8 coarse modes, against the benchmark's plain reference of the same seeded,
quantized tables — and the load path holds no f32 copy of the item table."""

from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))

import factors  # noqa: E402
import reference  # noqa: E402
import reference_int8  # noqa: E402

from predictionio_tpu.models import modelfile  # noqa: E402
from predictionio_tpu.models.recommendation import ALSModel  # noqa: E402
from predictionio_tpu.obs import metrics as obs_metrics  # noqa: E402
from predictionio_tpu.ops import retrieval  # noqa: E402

SEED, USERS, ITEMS, RANK, TILE, K = 41, 300, 20_000, 64, 8192, 16
PAST = 1 << 30  # rows of a catalog whose stored scores are past retrieval._UNCUT
LIMIT = 1e-4  # the cell's score_gap_max (benchmark/configs/recommendation-amazon23-int8.json)
CLS = ("predictionio_tpu.models.recommendation", "ALSModel")


@pytest.fixture(autouse=True)
def _two_stage(monkeypatch):
    monkeypatch.setenv("PIO_RETRIEVAL_THRESHOLD", "1000")
    monkeypatch.setenv("PIO_RETRIEVAL_TILE", str(TILE))
    monkeypatch.setenv("PIO_RETRIEVAL_PROBE_EVERY", "0")


def _pair(stream, rows):
    return reference_int8.quantize_rows(factors.factor_table(SEED, stream, rows, RANK))


@pytest.fixture(scope="module")
def head(tmp_path_factory):
    """The seeded, quantized tables as a model that spans files (segments of
    512 KiB: the item values lie in three parts)."""
    from write_sharded import dense_ids

    path = str(tmp_path_factory.mktemp("int8") / "model.bin")
    uq, us = _pair(factors.STREAM_USER_FACTORS, USERS)
    vq, vs = _pair(factors.STREAM_ITEM_FACTORS, ITEMS)
    fields = modelfile.Fields(CLS, {
        "user_index": modelfile.EncodedIds(*dense_ids(b"u", USERS)),
        "item_index": modelfile.EncodedIds(*dense_ids(b"i", ITEMS)),
        "user_factors": uq, "item_factors": vq, "user_scales": us, "item_scales": vs,
    })
    modelfile.write_spanning(path, [("arrays", fields)], "int8", segment_bytes=1 << 19)
    return path


@pytest.fixture()
def model(head) -> ALSModel:
    (kind, m), = modelfile.load_path(head).entries()
    assert kind == "arrays" and isinstance(m, ALSModel)
    return m


def _serve(model, uixs, mode):
    users, table = model.device_factors()
    model._coarse = retrieval.CoarseCatalog(table, mode=mode)
    return retrieval.top_k(
        retrieval.UserRows(np.asarray(uixs, np.int32), users, model.user_rows),
        table, ITEMS, model.coarse_catalog, K)


class TestServedAgainstTheReference:
    @pytest.mark.parametrize("mode", ["int8", "int8_dot"])
    @pytest.mark.parametrize("b,chunk", [(1, 1), (8, 8), (16, 8)])
    def test_top_k_of_user_rows(self, model, mode, b, chunk):
        """Sixteen queries over rank-64 int8 values are two chunks of
        eight, in one program."""
        assert retrieval.scan_chunk(b, RANK, mode, PAST) == chunk
        assert retrieval.select_group(TILE, 128, -(-ITEMS // TILE))
        uixs = np.arange(7, 7 + b)
        s, ids = _serve(model, uixs, mode)
        q = reference_int8.table_rows(SEED, factors.STREAM_USER_FACTORS, USERS, RANK, uixs)
        top_s, top_i, own, _ = reference_int8.scan(SEED, ITEMS, RANK, q, K, served=ids)
        for row in range(b):
            c = reference.compare_answer(ids[row].tolist(), s[row], top_i[row], top_s[row], own[row])
            assert c["score_gap"] <= LIMIT and c["score_gap"] < 2e-5
            assert c["overlap"] == 1.0

    def test_a_query_vector_is_the_dequantized_user_row(self, model):
        uixs = np.asarray([0, 5, USERS - 1])
        np.testing.assert_array_equal(
            model.user_rows(uixs),
            reference_int8.table_rows(SEED, factors.STREAM_USER_FACTORS, USERS, RANK, uixs))

    def test_requantizing_a_user_row_gives_the_stored_values_back(self, model):
        """Why ``int8_dot`` loses nothing on THIS form: a dequantized int8 row's
        largest value is 127 scales."""
        rows = model.user_rows(np.arange(USERS))
        np.testing.assert_array_equal(
            reference_int8.requantized(rows), np.asarray(model.user_factors))

    def test_the_rescore_is_the_f32_product_of_the_dequantized_rows(self, model):
        users, table = model.device_factors()
        cand = np.arange(128, dtype=np.int32)[None, :] * 3
        s, ids = retrieval.rescore_gather_top_k_batch(
            np.asarray([3], np.int32), users, table, cand, K)
        vq, vs = np.asarray(model.item_factors), np.asarray(model.item_scales)
        want = reference_int8.dequantize(vq[cand[0]], vs[cand[0]]) @ model.user_rows([3])[0]
        order = np.argsort(-want, kind="stable")[:K]
        np.testing.assert_array_equal(ids[0], cand[0][order])
        np.testing.assert_allclose(s[0], want[order], rtol=0, atol=2e-6)


class TestTheLoadPath:
    def test_the_item_table_spans_parts_and_is_never_one_host_array(self, model, monkeypatch):
        assert isinstance(model.item_factors, modelfile.SpannedArray)
        assert len(model.item_factors.parts) >= 3 and model.item_factors.dtype == np.int8

        def never(self, *a, **k):
            raise AssertionError("the spanned table was concatenated on the host")

        monkeypatch.setattr(modelfile.SpannedArray, "__array__", never)
        users, (values, scales) = model.device_factors()
        coarse = model.coarse_catalog()
        assert values.dtype == jnp.int8 and scales.dtype == jnp.float32
        assert users[0].dtype == jnp.int8
        assert coarse._tiles.dtype == jnp.int8 and coarse._scales.dtype == jnp.float32
        assert values.shape == (ITEMS, RANK)

    def test_the_resident_table_is_the_stored_values(self, model):
        _, (values, scales) = model.device_factors()
        vq, vs = _pair(factors.STREAM_ITEM_FACTORS, ITEMS)
        np.testing.assert_array_equal(np.asarray(values), vq)
        np.testing.assert_array_equal(np.asarray(scales), vs)

    def test_resident_bytes_by_part(self, model):
        model.device_factors()
        model.coarse_catalog()
        nt = -(-ITEMS // TILE)
        got = retrieval.stats_block()["resident_bytes"]
        # (PR 48's part: the E-Commerce template's, whatever this process last served)
        assert got.pop("rules") >= 0
        assert got == {
            "table": ITEMS * RANK, "table_scales": ITEMS * 4,  # one byte a value
            "coarse": nt * TILE * RANK, "coarse_scales": nt * TILE * 4,
            "coarse_ids": nt * TILE * 4, "users": USERS * (RANK + 4),
        }
        series = obs_metrics.parse_prometheus(obs_metrics.render_prometheus())
        assert series['pio_model_resident_bytes{part="table"}'] == ITEMS * RANK
        assert series['pio_model_resident_bytes{part="coarse_ids"}'] == nt * TILE * 4

    def test_a_dense_model_reports_no_scales(self):
        from predictionio_tpu.data.bimap import BiMap

        m = ALSModel(BiMap.from_dense(["u0", "u1"]), BiMap.from_dense(["i0", "i1", "i2"]),
                     np.ones((2, 8), np.float32), np.ones((3, 8), np.float32))
        m.device_factors()
        got = retrieval.stats_block()["resident_bytes"]
        assert (got["table"], got["table_scales"], got["users"]) == (96, 0, 64)

    def test_staging_records_its_stages(self, model):
        before = {k: v["count"] for k, v in retrieval.stats_block()["load_seconds"].items()}
        model.device_factors()
        model.coarse_catalog()
        after = {k: v["count"] for k, v in retrieval.stats_block()["load_seconds"].items()}
        assert after["stage_to_device"] == before["stage_to_device"] + 1
        assert after["coarse_build"] == before["coarse_build"] + 1
        assert after["read"] == before["read"]


class TestPutRowsAndTiles:
    def test_a_spanned_array_goes_up_a_part_at_a_time(self, model):
        up = retrieval.put_rows(model.item_factors)
        assert isinstance(up, jax.Array) and up.dtype == jnp.int8
        np.testing.assert_array_equal(
            np.asarray(up), np.concatenate(model.item_factors.parts))

    @pytest.mark.parametrize("given", ["host", "device", "one_part"])
    def test_what_else_it_takes(self, given):
        a = np.arange(24, dtype=np.int8).reshape(6, 4)
        x = {"host": a, "device": jnp.asarray(a),
             "one_part": modelfile.SpannedArray([a], a.shape)}[given]
        np.testing.assert_array_equal(np.asarray(retrieval.put_rows(x)), a)

    def test_the_tiles_are_the_stored_values_padded(self):
        vq, vs = _pair(factors.STREAM_ITEM_FACTORS, 1000)
        tiles, scales = retrieval._quantized_tiles(jnp.asarray(vq), jnp.asarray(vs), nt=2, t=512)
        assert tiles.shape == (2, 512, RANK) and tiles.dtype == jnp.int8
        assert scales.shape == (2, 4, 128) == retrieval.side_shape(2, 512)
        flat = np.asarray(tiles).reshape(-1, RANK)
        np.testing.assert_array_equal(flat[:1000], vq)
        assert not flat[1000:].any()
        np.testing.assert_array_equal(np.asarray(scales).reshape(-1)[:1000], vs)
        assert (np.asarray(scales).reshape(-1)[1000:] == 1.0).all()

    @pytest.mark.parametrize("mode", ["int8", "int8_dot"])
    def test_a_catalog_from_the_device_pair_is_the_one_from_the_host_pair(self, mode):
        vq, vs = _pair(factors.STREAM_ITEM_FACTORS, 3000)
        a = retrieval.CoarseCatalog((vq, vs), tile=1024, mode=mode)
        b = retrieval.CoarseCatalog((jnp.asarray(vq), jnp.asarray(vs)), tile=1024, mode=mode)
        for x, y in ((a._tiles, b._tiles), (a._scales, b._scales), (a._ids, b._ids)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        assert a.nbytes() == 3 * 1024 * (RANK + 8) and int(np.asarray(a._ids).min()) == -1
        assert a._ids.shape == a._scales.shape == (3, 8, 128) and a.stored_rows == 3 * 1024

    def test_a_dense_table_in_an_int8_mode_is_quantized_once(self):
        f = factors.factor_table(SEED, 9, 700, RANK)
        cat = retrieval.CoarseCatalog(f, tile=256, mode="int8")
        vq, vs = reference_int8.quantize_rows(f)
        np.testing.assert_array_equal(np.asarray(cat._tiles).reshape(-1, RANK)[:700], vq)
        np.testing.assert_array_equal(np.asarray(cat._scales).reshape(-1)[:700], vs)
        assert cat._scales.shape == cat._ids.shape == (3, 2, 128)


class TestCountersAndScopes:
    @pytest.mark.parametrize("mode", ["int8", "int8_dot"])
    def test_a_dispatch_counts_its_coarse_mode(self, model, mode):
        before = dict(retrieval.stats_block()["coarse_mode"])
        _serve(model, [1, 2, 3], mode)
        after = retrieval.stats_block()["coarse_mode"]
        assert after[mode] == before[mode] + 1
        assert all(after[m] == before[m] for m in after if m != mode)

    def test_a_bf16_catalog_counts_bf16(self):
        before = dict(retrieval.stats_block()["coarse_mode"])
        f = factors.factor_table(SEED, 9, 3000, RANK)
        retrieval.CoarseCatalog(f, tile=1024).shortlist(f[:2], 16)
        after = retrieval.stats_block()["coarse_mode"]
        assert after["bf16"] == before["bf16"] + 1 and after["int8"] == before["int8"]

    def test_the_scopes_name_the_int8_ops(self):
        vq, vs = _pair(factors.STREAM_ITEM_FACTORS, 2048)
        q = jnp.ones((1, RANK), jnp.float32)
        scan = retrieval._coarse_topk.lower(
            q, jnp.asarray(vq).reshape(2, 1024, RANK), jnp.asarray(vs).reshape(2, 1024),
            jnp.arange(2048, dtype=jnp.int32).reshape(2, 1024), k=16, mode="int8_dot",
        ).as_text(debug_info=True)
        assert "retrieval.shortlist.quantize_query" in scan
        rescore = jax.jit(retrieval._table_rows).lower(
            (jnp.asarray(vq), jnp.asarray(vs)), jnp.zeros((1, 4), jnp.int32),
        ).as_text(debug_info=True)
        assert "retrieval.rescore.dequant" in rescore
