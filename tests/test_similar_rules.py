"""The cosine templates' filters where the scores are produced: a query's own
entities, its blackList and its categories as ``ops.topk.Rules`` inside the
scan, the rescore and the masked exact program. Served answers against the
plain reference (benchmark/reference_similarproduct.py, NumPy, nothing of the
program) on seeded random tables, every query kind x both storages x batch
1, 3, 16 x one / three category columns, on the two-stage path and on the
masked exact one; batching parity; the answers the removed dense-mask path
gave (pinned from the parent commit); the model file's category block; the
recommended-user template through the same scorer."""

from __future__ import annotations

import dataclasses
import json
import os
import pickle
import sys

import numpy as np
import pytest

from predictionio_tpu.data.bimap import BiMap
from predictionio_tpu.models import filters, modelfile
from predictionio_tpu.models import recommendeduser as ru
from predictionio_tpu.models import similarproduct as sp
from predictionio_tpu.obs import metrics as obs_metrics
from predictionio_tpu.ops import als as als_ops
from predictionio_tpu.ops import retrieval, topk

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "benchmark"))
import reference  # noqa: E402
import reference_similarproduct as ref  # noqa: E402

I, D, C = 1500, 16, 9
KINDS = ("similar", "same_category", "session", "whiteList", "small_category")
SCORE_TOL = 1e-5  # the item-page cell's score_gap_max limit; XLA:CPU reads ~2e-7


class World:
    """A seeded raw item table with its categories, as the template's model
    and as the reference sees them."""

    def __init__(self, storage_dtype: str, columns: int):
        rng = np.random.default_rng(17)
        raw = rng.standard_normal((I, D)).astype(np.float32)
        cat = np.full((I, columns), -1, np.int32)
        cat[:, 0] = rng.integers(0, C - 1, I)
        for w in range(1, columns):  # a second and third category for some
            some = rng.random(I) < 0.4
            cat[some, w] = rng.integers(0, C - 1, int(some.sum()))
        cat[rng.choice(I, 4, replace=False)] = [C - 1] + [-1] * (columns - 1)
        cat[7] = -1  # an item without any
        self.item_cat = cat if columns > 1 else cat[:, 0]
        kw = {}
        if storage_dtype == "int8":
            vq, vs = (np.asarray(a) for a in als_ops.quantize_rows(raw))
            kw = {"item_scales": vs}
            self.raw, stored = vq.astype(np.float32) * vs[:, None], vq
        else:
            self.raw = stored = raw
        self.unit = ref.unit_rows(self.raw)
        self.model = sp.SimilarProductModel(
            item_index=BiMap.from_dense([f"i{n}" for n in range(I)]),
            item_factors=stored,
            categories={f"i{n}": [f"c{c}" for c in row if c >= 0]
                        for n, row in enumerate(cat)},
            **kw,
        )
        self.users = ru.RecommendedUserModel(
            followed_index=BiMap.from_dense([f"i{n}" for n in range(I)]),
            followed_factors=stored, followed_scales=kw.get("item_scales"),
        )
        self.algo, self.user_algo = sp.ALSAlgorithm(), ru.ALSAlgorithm()
        self.rng = rng

    def query(self, kind: str, n: int) -> sp.Query:
        rng = self.rng
        lead = int(rng.integers(0, I))
        if kind == "similar":
            return sp.Query(items=[f"i{lead}", "ghost"][: 1 + n % 2], num=10)
        if kind == "same_category":
            own = [c for c in np.atleast_1d(self.item_cat[lead]) if c >= 0]
            return sp.Query(items=[f"i{lead}"], num=10,
                            categories=[f"c{c}" for c in own] + ["no-such"])
        if kind == "session":
            return sp.Query(
                items=[f"i{i}" for i in rng.choice(I, 2 + n % 7, replace=False)],
                num=10, blackList=[f"i{i}" for i in rng.integers(0, I, 1 + n % 5)] + ["nope"])
        if kind == "whiteList":
            extra = {"categories": [f"c{n % C}", "c0"]} if n % 2 else {}
            return sp.Query(items=[f"i{lead}"], num=10, whiteList=[
                f"i{i}" for i in rng.choice(I, 120, replace=False)] + [f"i{lead}"], **extra)
        return sp.Query(items=[f"i{lead}"], num=10, categories=[f"c{C - 1}"])

    def rules(self, q):
        """(the query's rows, its sorted excluded rows, its categories)."""
        own = [int(i[1:]) for i in q.items if i[1:].isdigit()]
        ex = set(own) | {int(i[1:]) for i in q.blackList or () if i[1:].isdigit()}
        if q.whiteList is not None:
            ex |= set(range(I)) - {int(i[1:]) for i in q.whiteList}
        cats = None
        if getattr(q, "categories", None) is not None:
            cats = [int(c[1:]) for c in q.categories if c[1:].isdigit()]
        return own, np.asarray(sorted(ex), np.int64), cats

    def check(self, q, pairs, exact: bool):
        """``pairs`` [(id, score)] of one served answer against the reference."""
        own, ex, cats = self.rules(q)
        items = [int(i[1:]) for i, _ in pairs]
        scores = [s for _, s in pairs]
        if not own:
            assert items == []
            return
        assert ref.excluded_served(
            items, excluded=ex, item_category=self.item_cat, query_categories=cats) == 0
        qv = ref.query_vectors(self.unit, [own])
        ref_s, ref_i = ref.top_k_allowed(
            qv, self.unit, q.num, excluded=[ex], item_category=self.item_cat,
            query_categories=[cats], block=512)
        n = int((ref_i[0] >= 0).sum())
        assert n == min(q.num, ref.allowed_count(
            I, excluded=ex, item_category=self.item_cat, query_categories=cats))
        assert len(items) == n and len(set(items)) == n
        if n == 0:
            return
        served = reference.score_items(qv[0], self.unit, np.asarray(items))
        c = ref.compare_answer(items, scores, ref_i[0], ref_s[0], served)
        assert c["score_gap"] <= SCORE_TOL
        assert c["overlap"] >= (1.0 if exact else 0.9)
        if exact:
            assert items == [int(i) for i in ref_i[0, :n]]


def _items(result) -> list:
    return [(s.item, s.score) for s in result.itemScores]


def _users(result) -> list:
    return [(s.user, s.score) for s in result.userScores]


@pytest.fixture(params=["float32", "int8"])
def storage_dtype(request):
    return request.param


@pytest.fixture(params=[1, 3], ids=["one_column", "three_columns"])
def world(request, storage_dtype):
    return World(storage_dtype, request.param)


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
    # one blocking read a dispatch (whiteList: the host-facing rescore's)
    assert after["host_reads"] == before["host_reads"] + 1
    for n, q in queries:
        world.check(q, _items(out[n]), exact=(kind == "whiteList"))


@pytest.mark.parametrize("kind", KINDS)
def test_exact_path_answers_equal_the_reference(world, kind):
    queries = [(n, world.query(kind, n)) for n in range(3)]
    before = retrieval.stats_block()["two_stage_queries"]
    out = dict(world.algo.batch_predict(world.model, queries))
    if kind != "whiteList":  # a whiteList is rescored as a candidate list
        assert retrieval.stats_block()["two_stage_queries"] == before
    for n, q in queries:
        world.check(q, _items(out[n]), exact=True)


def test_a_small_category_gives_a_short_exact_answer(world, two_stage):
    q = world.query("small_category", 0)  # category C-1 holds four items
    got = _items(world.algo.predict(world.model, q))
    assert 3 <= len(got) <= 4
    world.check(q, got, exact=True)
    unknown = sp.Query(items=["i1"], num=5, categories=["no-such-category"])
    assert world.algo.predict(world.model, unknown).itemScores == []
    assert world.algo.predict(world.model, sp.Query(items=["ghost"])).itemScores == []


@pytest.mark.parametrize("regime", ["two_stage", "below_threshold"])
def test_a_mixed_batch_equals_the_same_queries_alone(world, regime, monkeypatch):
    if regime == "two_stage":
        monkeypatch.setenv("PIO_RETRIEVAL_THRESHOLD", "500")
        monkeypatch.setenv("PIO_RETRIEVAL_TILE", "256")
    queries = [(n, world.query(KINDS[n % len(KINDS)], n)) for n in range(11)]
    queries.append((11, sp.Query(items=["ghost"], num=3)))
    mixed = dict(world.algo.batch_predict(world.model, queries))
    for n, q in queries:
        alone, together = _items(world.algo.predict(world.model, q)), _items(mixed[n])
        assert [i for i, _ in alone] == [i for i, _ in together]
        np.testing.assert_allclose(
            [s for _, s in alone], [s for _, s in together], atol=2e-6, rtol=0)


@pytest.mark.parametrize("batch", [1, 3, 16])
@pytest.mark.parametrize("kind", ["similar", "session", "whiteList"])
def test_recommended_user_through_the_same_scorer(world, two_stage, kind, batch):
    """The same cases with the entity names swapped: no categories there."""
    queries = []
    for n in range(batch):
        q = world.query(kind, n)
        queries.append((n, ru.Query(users=q.items, num=q.num, whiteList=q.whiteList,
                                    blackList=q.blackList)))
    before = retrieval.stats_block()["exact_queries"]
    out = dict(world.user_algo.batch_predict(world.users, queries))
    assert retrieval.stats_block()["exact_queries"] == before
    for n, q in queries:
        as_items = sp.Query(items=q.users, num=q.num, whiteList=q.whiteList,
                            blackList=q.blackList)
        world.check(as_items, _users(out[n]), exact=(kind == "whiteList"))


class TestPinnedDenseMaskAnswers:
    """What ``score_similar_batch``'s dense-mask loop and its headroom-k
    regime answered at the parent commit (tests/data/similar_dense_mask_pins.json,
    written by running the parent's code on these seeded tables before the
    path was removed): the rules give the same items, scores to 2e-6."""

    with open(os.path.join(HERE, "data", "similar_dense_mask_pins.json")) as fh:
        PINS = json.load(fh)

    @staticmethod
    def tables():
        n, d, c = (TestPinnedDenseMaskAnswers.PINS[k] for k in ("N", "D", "C"))
        raw = np.random.default_rng(11).standard_normal((n, d)).astype(np.float32)
        cats = {}
        for i in range(n):
            cs = [f"c{i % c}"]
            if i % 5 == 0:
                cs.append(f"c{(i // 5) % c}")
            if i % 97 == 3:
                cs = ["tiny"]  # 7 items: fewer than num 10
            if i % 50 == 49:
                cs = []
            cats[f"i{i}"] = cs
        return raw, cats

    @pytest.mark.parametrize("regime", ["two_stage", "below_threshold"])
    @pytest.mark.parametrize("case", range(4), ids=[
        f"{c['template']}-{c['storage']}" for c in PINS["cases"]])
    def test_the_rules_answer_what_the_dense_mask_path_did(self, case, regime, monkeypatch):
        if regime == "two_stage":
            monkeypatch.setenv("PIO_RETRIEVAL_THRESHOLD", "64")
            monkeypatch.setenv("PIO_RETRIEVAL_TILE", "128")
        case = self.PINS["cases"][case]
        raw, cats = self.tables()
        scales = None
        if case["storage"] == "int8":
            raw, scales = (np.asarray(a) for a in als_ops.quantize_rows(raw))
        index = BiMap.from_dense([f"i{i}" for i in range(len(raw))])
        if case["template"] == "similarproduct":
            model = sp.SimilarProductModel(
                item_index=index, item_factors=raw, categories=cats, item_scales=scales)
            out = sp.ALSAlgorithm().batch_predict(
                model, [(n, sp.Query(**q)) for n, q in enumerate(case["queries"])])
            got = [_items(r) for _, r in out]
        else:
            model = ru.RecommendedUserModel(
                followed_index=index, followed_factors=raw, followed_scales=scales)
            out = ru.ALSAlgorithm().batch_predict(
                model, [(n, ru.Query(**q)) for n, q in enumerate(case["queries"])])
            got = [_users(r) for _, r in out]
        assert len(got) == len(case["answers"]) >= 13
        assert sum(len(a) for a in case["answers"]) > 40
        for mine, pinned in zip(got, case["answers"]):
            assert [i for i, _ in mine] == [i for i, _ in pinned]
            np.testing.assert_allclose(
                [s for _, s in mine], [s for _, s in pinned], atol=2e-6, rtol=0)


class TestOneRegime:
    def test_no_dense_array_and_no_headroom(self, world, two_stage, monkeypatch):
        """Every kind shares one masked scan + one masked rescore at k =
        pow2(num); nothing of catalog length is built a query."""
        seen = []
        real = retrieval.top_k
        monkeypatch.setattr(
            retrieval, "top_k",
            lambda query, table, n, coarse, k, probe_n=None: seen.append((query, k))
            or real(query, table, n, coarse, k, probe_n))
        queries = [(n, world.query(KINDS[n % 3], n)) for n in range(5)]
        world.algo.batch_predict(world.model, queries)
        (query, k), = seen
        assert isinstance(query, retrieval.SumRows) and k == 16
        rules = query.rules
        assert rules.ex.shape == (8, 16) and rules.qcat.shape[0] == 8
        assert query.ixs.shape == (8, 8) and rules.has_cat.shape == (8,)
        stored = world.model.coarse_catalog().stored_rows
        assert rules.avail.shape == (stored,) and int(rules.avail.sum()) == I
        assert len(rules.cats) == np.atleast_2d(world.item_cat.T).shape[0]
        assert not hasattr(query, "exclude_mask") and not hasattr(query, "exact_only")
        # the resident vectors are built once per stored-row count
        assert world.model.rule_vectors(stored)[0] is rules.avail

    def test_a_longer_exclusion_list_takes_the_next_bucket(self, world):
        q = sp.Query(items=["i1"], num=4, blackList=[f"i{n}" for n in range(2, 40)])
        got = _items(world.algo.predict(world.model, q))
        world.check(q, got, exact=True)
        avail, cats = world.model.rule_vectors(I)
        rules = filters.query_rules(avail, cats, [np.arange(39)], [None], 16)
        assert rules.ex.shape == (1, 64)

    def test_counters_and_the_build_region(self, world):
        def snap():
            return obs_metrics.parse_prometheus(obs_metrics.render_prometheus())

        def count(kind):
            return snap().get(f'pio_similar_queries_total{{kind="{kind}"}}', 0.0)

        before = {k: count(k) for k in ("plain", "category", "blacklist", "whitelist")}
        builds = snap().get("pio_similar_build_seconds_count", 0.0)
        rows = snap().get("pio_similar_query_rows_sum", 0.0)
        world.algo.batch_predict(world.model, [
            (0, sp.Query(items=["i1"])), (1, sp.Query(items=["i1"], categories=["c0"])),
            (2, sp.Query(items=["i1", "i2", "i3"], blackList=["i9"])),
            (3, sp.Query(items=["i1"], whiteList=["i5", "i6"], blackList=["i5"])),
        ])
        assert {k: count(k) - v for k, v in before.items()} == {
            "plain": 1, "category": 1, "blacklist": 1, "whitelist": 1}
        assert snap()["pio_similar_build_seconds_count"] == builds + 1
        assert snap()["pio_similar_query_rows_sum"] == rows + 6


def test_similar_build_makes_no_upload(world, two_stage, region_uploads):
    """The build leaves summed-row indices, weights and rules on the
    host; ``top_k`` sends them up with the vectors, once a dispatch."""
    # a model's first query stages its coarse copy and resident vectors
    world.algo.predict(world.model, world.query("similar", 0))
    world.user_algo.predict(world.users, ru.Query(users=["i1"], num=4))
    builds = region_uploads("similar.build")
    before = retrieval.stats_block()
    world.algo.batch_predict(world.model, [
        (0, world.query("similar", 1)), (1, world.query("same_category", 2)),
        (2, world.query("session", 3))])
    world.user_algo.predict(world.users, ru.Query(users=["i3", "i9"], num=4))
    after = retrieval.stats_block()
    assert builds == [(0, [])] * 2
    dispatches = after["shortlist_seconds"]["count"] - before["shortlist_seconds"]["count"]
    probes = after["probes"] - before["probes"]  # each: device_rules' three
    assert dispatches == 2
    assert after["uploads"] - before["uploads"] == dispatches + 3 * probes


def test_no_layout_is_first_compiled_after_the_warm_up(two_stage):
    """The item-page cell warms by closed-loop traffic of its mix
    (benchmark/drivers/serve.py ``_phases``): singles up to the recall
    probe's turn, then bursts that fill every batch bucket. A packed
    layout is a shape like any other — the bucket and widths that no
    kind of the cell's queries moves (one category an item, a session's
    2-8 items in the bucket of 8, its own rows and blackList in the
    bucket of 16) — so a warm-up that met each bucket with the mix's
    most frequent kind alone has compiled what every kind runs."""
    from predictionio_tpu.obs import device as obs_device

    def compiles():
        return {f: s["compiles"] for f, s in obs_device.compile_snapshot().items()}

    world = World("float32", 1)

    def batch(kind, b):
        return [(n, world.query(kind, n)) for n in range(b)]

    for b in (1, 1, 2, 4, 8):  # the probe every second dispatch (two_stage)
        world.algo.batch_predict(world.model, batch("similar", b))
    before = compiles()
    for kind in ("similar", "same_category", "session"):
        for b in (1, 2, 4, 8):
            out = world.algo.batch_predict(world.model, batch(kind, b))
            assert all(len(r.itemScores) > 0 for _, r in out)
    assert compiles() == before


class TestExactProgramIsF32:
    def test_the_masked_sum_rows_program_asks_for_highest(self, world):
        """On a TPU a default-precision f32 product is bf16 passes; the
        program has to ask for HIGHEST on every product, and a bf16 product
        at this test's size is off by more than the tolerance."""
        import jax.numpy as jnp

        avail, cats = world.model.rule_vectors(I)
        rules = filters.query_rules(avail, cats, [np.asarray([3])], [None], 16)
        ixs, weights = np.asarray([[3, 5]], np.int32), np.ones((1, 2), np.float32)
        table = world.model.device_factors()
        text = topk.sum_rows_top_k_batch_masked.__wrapped__.lower(
            jnp.asarray(ixs), jnp.asarray(weights), table, rules, k=16).as_text()
        dots = [ln for ln in text.splitlines() if "dot_general" in ln]
        assert dots and all("HIGHEST" in ln for ln in dots)
        qv = ref.query_vectors(world.unit, [[3, 5]])
        s16, i16 = ref.top_k_allowed(
            qv, world.unit, 10, excluded=[np.asarray([3, 5])], item_category=world.item_cat,
            query_categories=[None], precision="bfloat16")
        f32 = reference.score_items(qv[0], world.unit, i16[0])
        assert np.abs(s16[0] - f32).max() > SCORE_TOL

    def test_the_rescore_twin_is_tracked_under_its_own_name(self, world, two_stage):
        from predictionio_tpu.obs import device as obs_device

        world.algo.predict(world.model, sp.Query(items=["i4"], num=10))
        names = {p.name for p in retrieval._RESCORE_PROGRAMS}
        assert "retrieval.rescore_sum_rows_masked" in names
        snap = obs_device.compile_snapshot()
        assert "retrieval.rescore_sum_rows_masked" in json.dumps(snap)
        assert "retrieval.coarse_topk_masked" in json.dumps(snap)


class TestModelFile:
    def _model(self):
        w = np.random.default_rng(3)
        return sp.SimilarProductModel(
            item_index=BiMap.from_dense([f"i{n}" for n in range(5)]),
            item_factors=w.standard_normal((5, 4)).astype(np.float32),
            categories={"i0": ["a", "b"], "i3": ["b"], "ghost": ["z"]},
        )

    def test_the_category_block_round_trips(self):
        m = self._model()
        assert m.categories is None and m.item_categories.shape == (5, 2)
        blob = modelfile.serialize([("arrays", m)], "t")
        header, _ = modelfile._parse_header(blob)
        fields = header["entries"][0]["fields"]
        assert fields["item_categories"]["t"] == "array"  # a block, not JSON
        assert fields["categories"] == {"t": "none"}
        back = modelfile.deserialize(blob)[0][1]
        np.testing.assert_array_equal(back.item_categories, m.item_categories)
        assert dict(back.category_index.items()) == {"a": 0, "b": 1, "z": 2}
        assert back.item_categories[3].tolist() == [1, -1]

    def test_a_file_with_json_categories_still_loads(self):
        @dataclasses.dataclass
        class Old:  # the model as files written before the block hold it
            item_index: BiMap
            item_factors: np.ndarray
            categories: dict
            item_scales: None = None

        Old.__module__, Old.__qualname__ = sp.SimilarProductModel.__module__, "SimilarProductModel"
        w = np.random.default_rng(4)
        old = Old(BiMap.from_dense(["i0", "i1", "i2"]),
                  w.standard_normal((3, 4)).astype(np.float32),
                  {"i0": ["x"], "i2": ["y", "x"]})
        blob = modelfile.serialize([("arrays", old)], "t")
        header, _ = modelfile._parse_header(blob)
        assert header["entries"][0]["fields"]["categories"]["t"] == "json"
        back = modelfile.deserialize(blob)[0][1]
        assert type(back) is sp.SimilarProductModel
        assert back.item_categories.tolist() == [[0, -1], [-1, -1], [1, 0]]
        got = sp.ALSAlgorithm().predict(back, sp.Query(items=["i0"], num=2, categories=["y"]))
        assert [s.item for s in got.itemScores] == ["i2"]

    def test_a_pickle_from_before_the_block_still_loads(self):
        m = self._model()
        state = m.__getstate__()
        del state["category_index"], state["item_categories"]
        state["categories"] = {"i0": ["a", "b"], "i3": ["b"], "ghost": ["z"]}
        old = sp.SimilarProductModel.__new__(sp.SimilarProductModel)
        old.__setstate__(state)
        np.testing.assert_array_equal(old.item_categories, m.item_categories)
        assert old.rule_vectors(5)[0].shape == (5,)
        back = pickle.loads(pickle.dumps(m))
        np.testing.assert_array_equal(back.item_categories, m.item_categories)
        assert back.categories is None


class TestCosineAlgorithmKeepsItsSetLookups:
    """The DIMSUM variant serves from host neighbour lists: filters by set
    look-ups, no catalog-length mask."""

    def test_filters(self):
        index = BiMap.from_dense([f"i{n}" for n in range(6)])
        model = sp.CosineModel(
            item_index=index,
            sim_scores=np.asarray([[0.9, 0.8, 0.7, 0.6, 0.5]] * 6, np.float32),
            sim_ids=np.asarray([[j for j in range(6) if j != i] for i in range(6)]),
            categories={"i1": ["a"], "i2": ["b"], "i3": ["a", "b"], "i4": []},
        )
        algo = sp.CosineAlgorithm()

        def ask(**kw):
            return [s.item for s in algo.predict(model, sp.Query(items=["i0"], num=5, **kw)).itemScores]

        assert ask() == ["i1", "i2", "i3", "i4", "i5"]
        assert ask(categories=["a"]) == ["i1", "i3"]
        assert ask(blackList=["i2", "nope"]) == ["i1", "i3", "i4", "i5"]
        assert ask(whiteList=["i5", "i3", "i0"]) == ["i3", "i5"]
        assert ask(categories=["b"], whiteList=["i3", "i1"]) == ["i3"]
        assert algo.predict(model, sp.Query(items=["ghost"])).itemScores == []
