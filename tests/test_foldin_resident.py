"""A fold-in patches the resident tables where they lie (PR 45): a folded row
equals the plain reference's (benchmark/reference_foldin.py) in all three
storage dtypes; the item side — the exact table, its scales, the coarse
catalog — is the SAME device arrays after any number of patches; an appended
user within the capacity compiles nothing and the capacity's doubling is
counted; a query that races a patch reads an old row or a new one; the server
books the rows a patch sent, not the model."""

from __future__ import annotations

import os
import sys
import threading

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))

import reference_foldin as ref  # noqa: E402
import reference_int8  # noqa: E402

from predictionio_tpu.data.bimap import BiMap  # noqa: E402
from predictionio_tpu.data.event import Event  # noqa: E402
from predictionio_tpu.models import recommendation as rec  # noqa: E402
from predictionio_tpu.obs import metrics as obs_metrics  # noqa: E402
from predictionio_tpu.ops import als as als_ops  # noqa: E402
from predictionio_tpu.ops import retrieval  # noqa: E402
from predictionio_tpu.realtime import ALSFoldIn, FoldInConfig  # noqa: E402

APP, RANK, USERS, ITEMS = 3, 16, 40, 3000
DTYPES = ["float32", "bfloat16", "int8"]
REG = 0.05


def _rate(uid, iid, rating):
    return Event(event="rate", entity_type="user", entity_id=uid,
                 target_entity_type="item", target_entity_id=iid,
                 properties={"rating": float(rating)})


def _events():
    from predictionio_tpu.data.storage.memory import MemoryEvents, MemoryStorageClient

    return MemoryEvents(MemoryStorageClient({}))


def _model(storage_dtype: str, users: int = USERS) -> rec.ALSModel:
    rng = np.random.default_rng(5)
    scale = np.float32(RANK ** -0.25)
    U = rng.standard_normal((users, RANK), dtype=np.float32) * scale
    V = rng.standard_normal((ITEMS, RANK), dtype=np.float32) * scale
    us = vs = None
    if storage_dtype == "int8":
        U, us = reference_int8.quantize_rows(U)
        V, vs = reference_int8.quantize_rows(V)
    elif storage_dtype == "bfloat16":
        U = np.asarray(als_ops.to_storage(U, storage_dtype))
        V = np.asarray(als_ops.to_storage(V, storage_dtype))
    return rec.ALSModel(
        user_index=BiMap.from_dense([f"u{i}" for i in range(users)]),
        item_index=BiMap.from_dense([f"i{i}" for i in range(ITEMS)]),
        user_factors=U, item_factors=V, user_scales=us, item_scales=vs)


def _item_rows(model) -> np.ndarray:
    v = np.asarray(model.item_factors).astype(np.float32)
    return v * model.item_scales[:, None] if model.item_scales is not None else v


def _counter(name: str, **labels) -> float:
    return obs_metrics.counter(name, "", **labels).value()


def _fold(model, history, uid="u3", events=None):
    events = events or _events()
    batch = [_rate(uid, f"i{i}", r) for i, r in history]
    for e in batch:
        events.insert(e, APP)
    foldin = ALSFoldIn(events, APP, config=FoldInConfig(reg=REG))
    patched, stats = foldin.fold(model, batch)
    return patched, stats, foldin, events


HISTORIES = {
    "one rating": [(7, 5)],
    "three": [(7, 5), (90, 1), (2999, 4)],
    "a repeat: the last rating wins": [(7, 5), (90, 1), (7, 2), (1500, 3)],
    "twenty": [(i * 131 % ITEMS, 1 + i % 5) for i in range(20)],
    "more than the rank": [(i * 37 % ITEMS, 1 + (i * 7) % 5) for i in range(40)],
}


@pytest.mark.parametrize("storage_dtype", DTYPES)
@pytest.mark.parametrize("case", HISTORIES)
def test_a_folded_row_is_the_references(storage_dtype, case):
    """float64 normal equations over the dequantized item rows, stored by the
    model's rule: the same codes (int8, but for an enumerated near-tie), the
    same bf16 values, f32 to a few parts in a million."""
    model = _model(storage_dtype)
    history = HISTORIES[case]
    patched, stats, _, _ = _fold(model, history)
    assert stats.users_added == 0 and stats.users_touched == 1
    items, ratings = ref.rated(history, ITEMS)
    x = ref.solve(_item_rows(model)[items], ratings, REG)
    ix = model.user_index["u3"]
    if storage_dtype == "int8":
        rows, codes, scale = ref.stored_variants(x, "int8")
        assert any((patched.user_factors[ix] == c).all() for c in codes)
        assert patched.user_scales[ix] == pytest.approx(scale, rel=2e-6)
    else:
        rows, _, _ = ref.stored_variants(x, storage_dtype)
        got = np.asarray(patched.user_factors[ix]).astype(np.float32)
        tol = 2e-6 if storage_dtype == "float32" else 2.0 ** -8  # one bf16 step
        assert np.abs(got - rows[0]).max() <= tol * np.abs(rows[0]).max()
    # the resident row is the host row
    dev = patched.device_factors()[0]
    dev_row = np.asarray((dev[0] if isinstance(dev, tuple) else dev)[ix])
    assert (dev_row == np.asarray(patched.user_factors[ix])).all()


def test_the_reference_solve_is_the_normal_equations():
    rng = np.random.default_rng(2)
    v, r = rng.standard_normal((9, RANK)), rng.integers(1, 6, 9).astype(float)
    x = ref.solve(v.astype(np.float32), r, REG)
    a = v.astype(np.float32).astype(np.float64)
    want = np.linalg.inv(a.T @ a + REG * 9 * np.eye(RANK)) @ a.T @ r
    assert np.abs(x - want).max() < 1e-6


def test_near_ties_are_enumerated_both_ways():
    x = np.zeros(RANK, np.float32)
    x[0], x[1], x[2] = 127.0, 10.5001, -3.4999  # scale 1: two coordinates at a half
    rows, codes, scale = ref.stored_variants(x, "int8", tie=0.01)
    assert scale == 1.0 and len(codes) == 4
    assert {(int(c[1]), int(c[2])) for c in codes} == {(10, -3), (11, -3), (10, -4), (11, -4)}
    assert tuple(codes[0][:3]) == (127, 11, -3)  # the reference's own rounding first
    assert (rows == codes.astype(np.float32)).all()
    assert len(ref.stored_variants(np.asarray([127.0, 10.3] + [0] * 14, np.float32))[1]) == 1


@pytest.mark.parametrize("storage_dtype", DTYPES)
def test_patches_leave_the_item_side_where_it_lies(storage_dtype, monkeypatch):
    """After N patches the exact table, its scales and the coarse catalog are
    the SAME device arrays, nothing was restaged, and the programs of the
    scan, the rescore and the user-row gather compiled nothing new."""
    monkeypatch.setenv("PIO_RETRIEVAL_THRESHOLD", "1000")
    monkeypatch.setenv("PIO_RETRIEVAL_TILE", "1024")
    model = _model(storage_dtype)
    model.reserve_user_rows()
    algo = rec.ALSAlgorithm(rec.ALSAlgorithmParams(rank=RANK, storage_dtype=storage_dtype))
    ask = [(0, rec.Query(user="u3", num=4)), (1, rec.Query(user="u9", num=4))]
    first = algo.batch_predict(model, ask)
    table, coarse = model.device_factors()[1], model.coarse_catalog()
    programs = [retrieval._coarse_topk, retrieval._rescore_gather]
    sizes = [p._cache_size() for p in programs]
    restaged = sum(_counter("pio_foldin_restage_total", part=p)
                   for p in ("users", "table", "coarse", "sharded"))
    events, m = _events(), model
    for n in range(5):
        m, stats, _, _ = _fold(m, [(100 + n, 5), (200 + n, 1)], uid=f"u{3 + n}", events=events)
        m, stats, _, _ = _fold(m, [(300 + n, 4)], uid=f"brand-new-{n}", events=events)
        assert stats.users_added == 1
        got = m.device_factors()[1]
        for a, b in zip(got if isinstance(got, tuple) else (got,),
                        table if isinstance(table, tuple) else (table,)):
            assert a is b
        assert m.coarse_catalog() is coarse
        assert m.item_factors is model.item_factors
    assert len(m.user_index) == USERS + 5 and m.user_capacity() == 64
    users = m.device_factors()[0]
    assert (users[0] if isinstance(users, tuple) else users).shape[0] == 64
    after = algo.batch_predict(m, ask) + algo.batch_predict(
        m, [(2, rec.Query(user="brand-new-4", num=4)), (3, rec.Query(user="u0", num=4))])
    assert [p._cache_size() for p in programs] == sizes  # nothing compiled
    assert sum(_counter("pio_foldin_restage_total", part=p)
               for p in ("users", "table", "coarse", "sharded")) == restaged
    by_ix = dict(after)
    assert len(by_ix[2].itemScores) == 4  # a user the model did not hold
    assert by_ix[1] == dict(first)[1]  # u9 was never touched
    assert by_ix[0] != dict(first)[0]  # u3 was
    # the model that was served first still answers as it did
    assert algo.batch_predict(model, ask) == first


def test_the_capacity_doubles_outside_the_table_and_is_counted():
    model = _model("int8", users=8)
    assert model.reserve_user_rows() == 16  # the power of two ABOVE the rows held
    before = _counter("pio_foldin_restage_total", part="users")
    events, m = _events(), model
    for n in range(8):  # 8 -> 16 users: the table is full, not over
        m, _, _, _ = _fold(m, [(n, 5)], uid=f"new{n}", events=events)
    assert m.user_capacity() == 16
    assert _counter("pio_foldin_restage_total", part="users") == before
    m, _, _, _ = _fold(m, [(9, 5)], uid="one-more", events=events)
    assert m.user_capacity() == 32 and len(m.user_index) == 17
    assert _counter("pio_foldin_restage_total", part="users") == before + 1
    assert obs_metrics.gauge("pio_model_user_capacity_rows", "").value() == 32.0
    assert m.device_factors()[0][0].shape == (32, RANK)
    # and a model that never reserved grows the same way, from the rows held
    plain = _model("float32", users=8)
    grown, _, _, _ = _fold(plain, [(1, 5)], uid="newcomer")
    assert grown.user_capacity() == 16 and grown.device_factors()[0].shape == (16, RANK)


def test_an_appended_index_costs_its_keys_not_the_map():
    base = BiMap.from_dense([f"u{i}" for i in range(1000)])
    one = base.appended(["a"])
    two = one.appended(["b", "c"])
    assert (len(base), len(one), len(two)) == (1000, 1001, 1003)
    assert "a" not in base and "b" not in one and two["c"] == 1002
    assert two.inverse[1001] == "b" and two.inverse[5] == "u5" and one["u7"] == 7
    assert two._base is base and len(two._extra) == 3  # read through, never copied
    assert list(two)[-3:] == ["a", "b", "c"] and two.to_dict()["a"] == 1000
    with pytest.raises(ValueError):
        two.appended(["u1"])


@pytest.mark.parametrize("storage_dtype", ["float32", "int8"])
def test_a_query_racing_a_patch_reads_an_old_row_or_a_new_one(storage_dtype, monkeypatch):
    """Queries of u3 run while patch after patch rewrites u3's row: every
    answer is the answer of ONE of the models (before, or after some patch),
    never a shortlist of one row rescored with another."""
    monkeypatch.setenv("PIO_RETRIEVAL_THRESHOLD", "1000")
    monkeypatch.setenv("PIO_RETRIEVAL_TILE", "1024")
    model = _model(storage_dtype)
    model.reserve_user_rows()
    algo = rec.ALSAlgorithm(rec.ALSAlgorithmParams(rank=RANK, storage_dtype=storage_dtype))
    ask = [(0, rec.Query(user="u3", num=8))]
    served = {"m": model}
    models, answers, stop = [model], [], threading.Event()

    def query():
        while not stop.is_set():
            answers.append(algo.batch_predict(served["m"], ask)[0][1])

    algo.batch_predict(model, ask)  # compiled before the race
    t = threading.Thread(target=query)
    t.start()
    events, m = _events(), model
    try:
        for n in range(50):
            m, _, _, _ = _fold(m, [(50 * n + 1, 5), (50 * n + 2, 1)], events=events)
            models.append(m)
            served["m"] = m
            if len(answers) >= 40 and n >= 12:
                break
    finally:
        stop.set()
        t.join(timeout=60)
    assert len(answers) >= 12
    whole = [algo.batch_predict(x, ask)[0][1] for x in models]
    assert len({str(w) for w in whole}) == len(whole)  # each patch moved the answer
    assert all(a in whole for a in answers)


def test_apply_patch_books_the_rows_it_sent():
    from predictionio_tpu.server import engine_server

    model = _model("int8")
    model.reserve_user_rows()
    model.device_factors()
    patched, _, _, _ = _fold(model, [(7, 5), (8, 1)])
    rows = 8 * (RANK + 4 + 4)  # padded to 8: int8 values, f32 scale, int32 index
    assert patched.patch_h2d_bytes == rows
    assert engine_server._patch_cost(model, patched) == (rows, [])
    assert engine_server._patch_cost(model, model) == (0, [])
    # a model put in the served one's place WITHOUT its resident parts goes
    # up whole, and the server says which parts
    import dataclasses

    other = dataclasses.replace(model, user_factors=model.user_factors.copy())
    nbytes, lost = engine_server._patch_cost(model, other)
    assert nbytes == engine_server._model_bytes(other) > 1000 * rows // 100
    assert lost == ["table"]
    model.coarse_catalog()
    assert engine_server._patch_cost(model, other)[1] == ["table", "coarse"]
    assert engine_server._patch_cost(model, _fold(model, [(9, 3)])[0])[1] == []


def test_a_handful_of_ids_never_decodes_a_catalogs_dictionary():
    """``index_of``: the fold's item look-ups against a map over an encoded
    dictionary (a model file's 48 M item ids) search hashes made from the
    blob; the dictionary stays undecoded, absent ids read -1."""
    from predictionio_tpu.models import modelfile

    ids = [f"i{n}" for n in range(5000)] + ["ünï", "i5000x"]
    raw = [s.encode("utf-8") for s in ids]
    blob = np.frombuffer(b"".join(raw), np.uint8)
    offs = np.concatenate([[0], np.cumsum([len(r) for r in raw])]).astype(np.int64)
    lazy = modelfile._LazyDenseBiMap(blob, offs)
    ask = ["i0", "i4999", "i5000", "ünï", "i5000x", "", "i50", 7]
    want = [0, 4999, -1, 5000, 5001, -1, 50, -1]
    assert lazy.index_of(ask).tolist() == want
    assert lazy._fwd is None  # nothing was decoded
    plain = BiMap.from_dense(ids)
    assert plain.index_of(ask).tolist() == want
    grown = lazy.appended(["new-a", "new-b"])
    assert grown.index_of(["new-b", "i7", "nope"]).tolist() == [5003, 7, -1]
    assert lazy._fwd is None
    assert lazy["i77"] == 77 and lazy.index_of(["i77"]).tolist() == [77]  # decoded: the dictionary answers
