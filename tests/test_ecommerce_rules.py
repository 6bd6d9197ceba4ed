"""The E-Commerce template's business rules where the scores are produced:
served answers against the plain reference (benchmark/reference_ecommerce.py,
NumPy, nothing of the program) on seeded random tables, every query kind x
both storages x batch 1, 3, 16, on the two-stage path and on the masked exact
one; batching parity; the live rules; fixed compiled shapes; the model file's
category block."""

from __future__ import annotations

import dataclasses
import os
import sys

import numpy as np
import pytest

from predictionio_tpu.data.bimap import BiMap
from predictionio_tpu.data.event import Event
from predictionio_tpu.data.storage import App, Storage, set_storage
from predictionio_tpu.models import ecommerce as ec
from predictionio_tpu.models import modelfile
from predictionio_tpu.obs import device as obs_device
from predictionio_tpu.ops import als as als_ops
from predictionio_tpu.ops import retrieval

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))
import reference  # noqa: E402
import reference_ecommerce as ref  # noqa: E402

APP = "ShopApp"
I, U, D, C = 2000, 40, 16, 12
KINDS = ("home", "category", "blackList", "whiteList", "cold")
SCORE_TOL = 1e-4  # the cell's score_gap_max limit; XLA:CPU reads ~1e-6


def _view(user, item, name="view"):
    return Event(event=name, entity_type="user", entity_id=user,
                 target_entity_type="item", target_entity_id=f"i{item}")


def _unavailable(items):
    return Event(event="$set", entity_type="constraint",
                 entity_id="unavailableItems",
                 properties={"items": [f"i{i}" for i in items]})


class World:
    """Seeded tables, categories, events and constraint — and what the
    reference needs to know of them."""

    def __init__(self, storage, storage_dtype):
        rng = np.random.default_rng(7)
        self.storage = storage
        self.app_id = storage.get_metadata_apps().insert(App(0, APP))
        self.events = storage.get_events()
        self.events.init(self.app_id)
        Uf = rng.standard_normal((U, D)).astype(np.float32) * D ** -0.25
        Vf = rng.standard_normal((I, D)).astype(np.float32) * D ** -0.25
        self.item_cat = rng.integers(0, C, I).astype(np.int32)
        self.item_cat[np.flatnonzero(self.item_cat == C - 1)[4:]] = 0  # 4 items only
        kw = {}
        if storage_dtype == "int8":
            Uf, us = als_ops.quantize_rows(Uf)
            Vf, vs = als_ops.quantize_rows(Vf)
            Uf, us, Vf, vs = (np.asarray(a) for a in (Uf, us, Vf, vs))
            kw = {"user_scales": us, "item_scales": vs}
            self.U = Uf.astype(np.float32) * us[:, None]
            self.V = Vf.astype(np.float32) * vs[:, None]
        else:
            self.U, self.V = Uf, Vf
        self.model = ec.ECommModel(
            user_index=BiMap.from_dense([f"u{n}" for n in range(U)]),
            item_index=BiMap.from_dense([f"i{n}" for n in range(I)]),
            user_factors=Uf, item_factors=Vf,
            categories={f"i{n}": [f"c{c}"] for n, c in enumerate(self.item_cat)},
            **kw,
        )
        self.algo = ec.ECommAlgorithm(ec.ECommAlgorithmParams(app_name=APP))
        self.seen = {}
        batch = []
        for u in range(U):
            items = rng.integers(0, I, 25)
            self.seen[f"u{u}"] = set(int(i) for i in items)
            batch += [_view(f"u{u}", int(i), "buy" if n % 9 == 0 else "view")
                      for n, i in enumerate(items)]
        for n in range(6):  # cold-start users: views, no factors
            items = rng.integers(0, I, 5)
            self.seen[f"new{n}"] = set(int(i) for i in items)
            batch += [_view(f"new{n}", int(i)) for i in items]
        self.events.batch_insert(batch, self.app_id)
        self.unavailable = np.sort(rng.choice(I, 60, replace=False))
        self.events.insert(_unavailable(self.unavailable), self.app_id)
        self.rng = rng

    def query(self, kind, n):
        user, extra = f"u{n}", {}
        if kind == "category":
            extra["categories"] = [f"c{C - 1 if n == 0 else n % C}"]
        elif kind == "blackList":
            extra["blackList"] = [f"i{i}" for i in self.rng.integers(0, I, 4)] + ["nope"]
        elif kind == "whiteList":
            extra["whiteList"] = [f"i{i}" for i in self.rng.choice(I, 150, replace=False)]
            if n % 2:
                extra["categories"] = [f"c{n % C}", "c0"]
        elif kind == "cold":
            user = f"new{n % 6}"
        return ec.Query(user=user, num=10, **extra)

    def vector(self, q):
        if q.user.startswith("u"):
            return self.U[int(q.user[1:])]
        return self.V[sorted(self.seen[q.user])].mean(axis=0)

    def expected(self, q, unavailable=None, seen=None):
        """(reference top items, scores, excluded rows, category)."""
        unavailable = self.unavailable if unavailable is None else unavailable
        ex = set(self.seen.get(q.user, ())) if seen is None else set(seen)
        ex |= {int(i[1:]) for i in q.blackList or () if i[1:].isdigit()}
        if q.whiteList is not None:
            ex |= set(range(I)) - {int(i[1:]) for i in q.whiteList}
        ex = np.asarray(sorted(ex), np.int64)
        cats = None
        if q.categories is not None:
            cats = [int(c[1:]) for c in q.categories]
        item_cat = self.item_cat
        if cats is not None and len(cats) > 1:  # "any of": fold into one id
            item_cat = np.where(np.isin(item_cat, cats), cats[0], -1)
        cat = cats[0] if cats else None
        s, i = ref.top_k_allowed(
            self.vector(q)[None], self.V, q.num, unavailable=unavailable,
            excluded=[ex], item_category=item_cat, query_category=[cat])
        return i[0], s[0], ex, cat, item_cat

    def check(self, q, result, exact, **kw):
        ref_i, ref_s, ex, cat, item_cat = self.expected(q, **kw)
        items = [int(s.item[1:]) for s in result.itemScores]
        scores = [s.score for s in result.itemScores]
        flags = np.zeros(I, bool)
        flags[self.unavailable if kw.get("unavailable") is None else kw["unavailable"]] = True
        assert ref.excluded_served(
            items, excluded=ex, unavailable_flags=flags, item_category=item_cat,
            query_category=cat) == 0
        n = int((ref_i >= 0).sum())
        assert len(items) == n and len(set(items)) == n
        if n == 0:
            return
        own = reference.score_items(self.vector(q), self.V, np.asarray(items))
        c = ref.compare_answer(items, scores, ref_i, ref_s, own)
        assert c["score_gap"] <= SCORE_TOL
        assert c["overlap"] >= (1.0 if exact else 0.9)
        if exact:
            assert items == [int(i) for i in ref_i[:n]]


@pytest.fixture(params=["float32", "int8"])
def world(request, storage):
    return World(storage, request.param)


@pytest.fixture()
def two_stage(monkeypatch):
    monkeypatch.setenv("PIO_RETRIEVAL_THRESHOLD", "500")
    monkeypatch.setenv("PIO_RETRIEVAL_TILE", "256")
    monkeypatch.setenv("PIO_RETRIEVAL_PROBE_EVERY", "2")


@pytest.mark.parametrize("batch", [1, 3, 16])
@pytest.mark.parametrize("kind", KINDS)
def test_two_stage_answers_agree_with_the_reference(world, two_stage, kind, batch):
    queries = [(n, world.query(kind, n)) for n in range(batch)]
    before = retrieval.stats_block()
    out = dict(world.algo.batch_predict(world.model, queries))
    after = retrieval.stats_block()
    assert after["exact_queries"] == before["exact_queries"]  # none left two-stage
    assert after["two_stage_queries"] > before["two_stage_queries"]
    for n, q in queries:
        world.check(q, out[n], exact=(kind == "whiteList"))


@pytest.mark.parametrize("kind", KINDS)
def test_exact_path_answers_equal_the_reference(world, kind):
    queries = [(n, world.query(kind, n)) for n in range(3)]
    before = retrieval.stats_block()["two_stage_queries"]
    out = dict(world.algo.batch_predict(world.model, queries))
    if kind != "whiteList":  # a whiteList is rescored as a candidate list
        assert retrieval.stats_block()["two_stage_queries"] == before
    for n, q in queries:
        world.check(q, out[n], exact=True)


def test_a_small_category_gives_a_short_answer(world, two_stage):
    q = world.query("category", 0)  # category C-1 holds four items
    got = world.algo.predict(world.model, q)
    assert 0 < len(got.itemScores) <= 4
    world.check(q, got, exact=True)
    unknown = ec.Query(user="u1", num=5, categories=["no-such-category"])
    assert world.algo.predict(world.model, unknown).itemScores == []


def test_a_mixed_batch_answers_as_the_same_queries_alone(world, two_stage):
    """Same items in the same order, scores to f32 rounding: the rescore's
    dot sums in an order that moves with the batch's size on every backend
    (PERF.md, PR 25), so across sizes the last place can differ; the same
    batch asked twice answers byte for byte."""
    queries = [(n, world.query(kind, n + 1)) for n, kind in enumerate(KINDS * 2)]
    together = dict(world.algo.batch_predict(world.model, queries))
    again = dict(world.algo.batch_predict(world.model, queries))
    for n, q in queries:
        alone = world.algo.predict(world.model, q)
        assert [s.item for s in together[n].itemScores] == \
            [s.item for s in alone.itemScores], (n, q)
        np.testing.assert_allclose(
            [s.score for s in together[n].itemScores],
            [s.score for s in alone.itemScores], rtol=2e-6, atol=2e-6)
        assert [(s.item, s.score) for s in together[n].itemScores] == \
            [(s.item, s.score) for s in again[n].itemScores]


@pytest.mark.parametrize("engaged", [True, False], ids=["two_stage", "exact"])
def test_a_set_and_a_view_take_effect_on_the_next_query(world, monkeypatch, engaged):
    if engaged:
        monkeypatch.setenv("PIO_RETRIEVAL_THRESHOLD", "500")
        monkeypatch.setenv("PIO_RETRIEVAL_TILE", "256")
    q = ec.Query(user="u3", num=10)
    first = world.algo.predict(world.model, q).itemScores
    top, second = int(first[0].item[1:]), int(first[1].item[1:])
    unavailable = np.sort(np.append(world.unavailable, top))
    world.events.insert(_unavailable(unavailable), world.app_id)
    world.events.insert(_view("u3", second), world.app_id)
    again = world.algo.predict(world.model, q)
    served = {int(s.item[1:]) for s in again.itemScores}
    assert top not in served and second not in served
    world.check(q, again, exact=not engaged, unavailable=unavailable,
                seen=world.seen["u3"] | {second})


def test_the_unavailable_list_changes_no_shape(world, two_stage, monkeypatch):
    sizes = []
    real = retrieval.CoarseCatalog.launch

    def spy(self, queries, k, *rest):
        sizes.append(k)
        return real(self, queries, k, *rest)

    monkeypatch.setattr(retrieval.CoarseCatalog, "launch", spy)
    tracked = ("retrieval.coarse_topk_masked", "retrieval.rescore_vectors_masked",
               "topk.top_k_items_batch_masked")
    q = ec.Query(user="u5", num=10)
    for _ in range(2):  # the second dispatch runs the recall probe too
        world.algo.predict(world.model, q)
    before = {f: obs_device.compile_snapshot()[f]["compiles"] for f in tracked}
    refreshes = ec._m_refresh.value()
    for count in (5, 400, 1500):
        world.events.insert(
            _unavailable(world.rng.choice(I, count, replace=False)), world.app_id)
        for _ in range(2):
            got = world.algo.predict(world.model, q)
        assert len(got.itemScores) == 10
    assert {f: obs_device.compile_snapshot()[f]["compiles"] for f in tracked} == before
    assert set(sizes) == {128}  # oversample 8 x pow2(10), whatever the list's length
    assert ec._m_refresh.value() == refreshes + 3
    # a write that leaves the constraint as it is rebuilds nothing
    world.events.insert(_view("u9", 1), world.app_id)
    world.algo.predict(world.model, q)
    assert ec._m_refresh.value() == refreshes + 3


def test_a_long_exclusion_list_is_counted_and_still_exact(world, two_stage):
    before = ec._m_overflow.value()
    q = ec.Query(user="u2", num=10,
                 blackList=[f"i{i}" for i in world.rng.choice(I, 300, replace=False)])
    world.check(q, world.algo.predict(world.model, q), exact=False)
    assert ec._m_overflow.value() == before + 1


def test_spans_and_counters_of_the_rules(world, two_stage):
    from predictionio_tpu.obs import trace as obs_trace

    counts = {k: m.value() for k, m in ec._m_queries.items()}
    trace = obs_trace.Trace("t")
    with obs_trace.use_trace(trace):
        world.algo.batch_predict(world.model, [
            (0, world.query("home", 1)), (1, world.query("category", 2)),
            (2, world.query("blackList", 3)), (3, world.query("whiteList", 4))])
    assert {k: m.value() - counts[k] for k, m in ec._m_queries.items()} == \
        {"home": 1, "category": 1, "list": 2}
    spans = {s[0]: s for s in trace.spans}
    assert {"rules.build", "rules.seen_read", "dispatch.shortlist",
            "dispatch.rescore", "dispatch.fetch"} <= set(spans)
    assert spans["rules.seen_read"][3] == "rules.build"
    assert ec._m_rules.summary()["count"] >= 1
    assert ec._m_excluded.summary()["count"] >= 4


def test_rules_build_makes_no_upload(world, two_stage, region_uploads):
    """The build turns queries into host index lists; what of them the
    device needs goes up inside ``dispatch.shortlist``, once."""
    world.algo.predict(world.model, world.query("home", 1))  # the constraint read
    builds = region_uploads("rules.build")
    before = retrieval.stats_block()
    world.algo.batch_predict(world.model, [
        (0, world.query("home", 2)), (1, world.query("category", 3)),
        (2, world.query("blackList", 4))])
    world.algo.predict(world.model, world.query("cold", 1))
    after = retrieval.stats_block()
    assert builds == [(0, [])] * 2
    dispatches = after["shortlist_seconds"]["count"] - before["shortlist_seconds"]["count"]
    probes = after["probes"] - before["probes"]  # each: device_rules' three
    assert dispatches == 2
    assert after["uploads"] - before["uploads"] == dispatches + 3 * probes


def _jit_compiles():
    return {f: s["compiles"] for f, s in obs_device.compile_snapshot().items()}


def test_no_layout_is_first_compiled_after_the_warm_up(storage, two_stage):
    """The storefront cell warms by closed-loop traffic of its mix
    (benchmark/drivers/serve.py ``_phases``): singles up to the recall
    probe's turn, then bursts that fill every batch bucket. A packed
    layout is a shape like any other — the bucket and widths that no
    kind of the cell's queries moves — so a warm-up that met each bucket
    with the mix's most frequent kind alone has compiled what every kind
    runs: home, category page and cart at B = 1, 2, 4, 8 compile
    nothing."""
    world = World(storage, "float32")

    def batch(kind, b, first):
        return [(n, world.query(kind, first + n)) for n in range(b)]

    for b in (1, 1, 2, 4, 8):  # the probe every second dispatch (two_stage)
        world.algo.batch_predict(world.model, batch("home", b, 1))
    before = _jit_compiles()
    for kind in ("home", "category", "blackList"):
        for b in (1, 2, 4, 8):
            out = world.algo.batch_predict(world.model, batch(kind, b, 9 + b))
            assert all(len(r.itemScores) > 0 for _, r in out)
    assert _jit_compiles() == before


def test_sqlite_reads_seen_items_by_projection(tmp_path, monkeypatch):
    s = Storage(env={
        "PIO_STORAGE_SOURCES_DB_TYPE": "sqlite",
        "PIO_STORAGE_SOURCES_DB_PATH": str(tmp_path / "pio.db"),
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "DB",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "DB",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "DB",
    })
    set_storage(s)
    try:
        world = World(s, "float32")
        ev = s.get_events()
        assert ev.entity_indexed
        args = (world.app_id, None, "user", "u4", ["view", "buy"], "item")
        assert ev.find_target_ids(*args) == {f"i{i}" for i in world.seen["u4"]}
        assert ev.find_target_ids(*args) == \
            type(ev).__mro__[1].find_target_ids(ev, *args)  # the default, through find
        assert ev.find_target_ids(world.app_id, None, "user", "u4", [], "item") == set()
        q = world.query("home", 4)
        world.check(q, world.algo.predict(world.model, q), exact=True)
    finally:
        set_storage(None)


class TestModelFile:
    def test_the_category_block_round_trips(self):
        w = np.random.default_rng(3)
        m = ec.ECommModel(
            user_index=BiMap.from_dense(["u0", "u1"]),
            item_index=BiMap.from_dense([f"i{n}" for n in range(5)]),
            user_factors=w.standard_normal((2, 4)).astype(np.float32),
            item_factors=w.standard_normal((5, 4)).astype(np.float32),
            categories={"i0": ["a", "b"], "i3": ["b"], "ghost": ["z"]},
        )
        assert m.categories is None and m.item_categories.shape == (5, 2)
        blob = modelfile.serialize([("arrays", m)], "t")
        header, _ = modelfile._parse_header(blob)
        fields = header["entries"][0]["fields"]
        assert fields["item_categories"]["t"] == "array"  # a block, not JSON
        assert fields["categories"] == {"t": "none"}
        back = modelfile.deserialize(blob)[0][1]
        np.testing.assert_array_equal(back.item_categories, m.item_categories)
        assert dict(back.category_index.items()) == {"a": 0, "b": 1, "z": 2}
        assert back.item_categories[0].tolist() == [0, 1]
        assert back.item_categories[3].tolist() == [1, -1]

    def test_a_file_with_json_categories_still_loads(self):
        @dataclasses.dataclass
        class Old:  # the model as files written before the block hold it
            user_index: BiMap
            item_index: BiMap
            user_factors: np.ndarray
            item_factors: np.ndarray
            categories: dict
            user_scales: None = None
            item_scales: None = None

        Old.__module__, Old.__qualname__ = ec.ECommModel.__module__, "ECommModel"
        w = np.random.default_rng(4)
        old = Old(BiMap.from_dense(["u0"]), BiMap.from_dense(["i0", "i1", "i2"]),
                  w.standard_normal((1, 4)).astype(np.float32),
                  w.standard_normal((3, 4)).astype(np.float32),
                  {"i0": ["x"], "i2": ["y", "x"]})
        blob = modelfile.serialize([("arrays", old)], "t")
        header, _ = modelfile._parse_header(blob)
        assert header["entries"][0]["fields"]["categories"]["t"] == "json"
        back = modelfile.deserialize(blob)[0][1]
        assert type(back) is ec.ECommModel
        assert back.item_categories.tolist() == [[0, -1], [-1, -1], [1, 0]]
        assert back.category_index["y"] == 1

    def test_a_pickle_from_before_the_block_still_loads(self):
        import pickle

        m = ec.ECommModel(
            user_index=BiMap.from_dense(["u0"]),
            item_index=BiMap.from_dense(["i0", "i1"]),
            user_factors=np.ones((1, 2), np.float32),
            item_factors=np.ones((2, 2), np.float32), categories={"i1": ["k"]},
        )
        state = m.__getstate__()
        del state["category_index"], state["item_categories"]
        state["categories"] = {"i1": ["k"]}
        old = ec.ECommModel.__new__(ec.ECommModel)
        old.__setstate__(state)
        assert old.item_categories.tolist() == [[-1], [0]]
        back = pickle.loads(pickle.dumps(m))
        np.testing.assert_array_equal(back.item_categories, m.item_categories)
