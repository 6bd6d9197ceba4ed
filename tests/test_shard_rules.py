"""The E-Commerce template's business rules on a sharded catalog: served
answers (``sharded_serving``, four of conftest's eight virtual devices)
against the plain reference (benchmark/reference_ecommerce.py, NumPy, knows
nothing of shards) AND against the one-chip storefront, for every query kind
on the masked sharded two-stage program and on the masked sharded exact one;
a category that lies on one shard, lists that span shards, a live
availability change rebuilt shard by shard, a model file that spans files,
and the programs a query without rules keeps."""

from __future__ import annotations

import inspect

import jax
import numpy as np
import pytest

from predictionio_tpu.models import ecommerce as ec
from predictionio_tpu.models import modelfile
from predictionio_tpu.obs import device as obs_device
from predictionio_tpu.obs import trace as obs_trace
from predictionio_tpu.ops import retrieval
from predictionio_tpu.ops.topk import Rules
from predictionio_tpu.parallel import shard_topk
from predictionio_tpu.parallel.mesh import make_mesh
from predictionio_tpu.parallel.shard_topk import ShardedCatalog
from test_ecommerce_rules import APP, KINDS, C, I, World, _unavailable, _view

SHARDS = 4
ROWS = I // SHARDS  # rows a shard holds


@pytest.fixture()
def mesh(monkeypatch):
    monkeypatch.setenv("PIO_MESH", f"data={SHARDS}")


@pytest.fixture()
def two_stage(monkeypatch):
    monkeypatch.setenv("PIO_RETRIEVAL_THRESHOLD", "500")
    monkeypatch.setenv("PIO_RETRIEVAL_TILE", "256")
    monkeypatch.setenv("PIO_RETRIEVAL_PROBE_EVERY", "2")


class ShardedWorld(World):
    """``World`` served twice: ``algo`` over the catalog split on the
    mesh, ``one_chip`` over the same model on one device."""

    def __init__(self, storage, storage_dtype, **params):
        super().__init__(storage, storage_dtype)
        self.one_chip = ec.ECommAlgorithm(
            ec.ECommAlgorithmParams(app_name=APP, **params))
        self.algo = ec.ECommAlgorithm(
            ec.ECommAlgorithmParams(app_name=APP, sharded_serving=True, **params))


@pytest.fixture(params=["float32", "int8"])
def world(request, storage, mesh):
    return ShardedWorld(storage, request.param)


@pytest.fixture()
def f32_world(storage, mesh):
    return ShardedWorld(storage, "float32")


@pytest.mark.parametrize("batch", [1, 3, 16])
@pytest.mark.parametrize("kind", KINDS)
def test_sharded_two_stage_answers_agree_with_the_reference(
        world, two_stage, kind, batch):
    queries = [(n, world.query(kind, n)) for n in range(batch)]
    before = retrieval.stats_block()
    out = dict(world.algo.batch_predict(world.model, queries))
    after = retrieval.stats_block()
    assert after["exact_queries"] == before["exact_queries"]
    assert after["two_stage_queries"] == before["two_stage_queries"]  # no chip alone
    padded = retrieval._pow2(batch)  # the counters take a batch as it was sent
    assert after["sharded_queries"] - before["sharded_queries"] == padded
    assert after["sharded_masked_queries"] - before["sharded_masked_queries"] == padded
    for n, q in queries:
        world.check(q, out[n], exact=(kind == "whiteList"))


@pytest.mark.parametrize("kind", KINDS)
def test_sharded_exact_answers_equal_the_reference(world, kind):
    """Under the retrieval threshold the masked exact program serves, on
    every shard."""
    queries = [(n, world.query(kind, n)) for n in range(3)]
    before = retrieval.stats_block()
    out = dict(world.algo.batch_predict(world.model, queries))
    after = retrieval.stats_block()
    assert after["sharded_masked_queries"] - before["sharded_masked_queries"] == 4
    if kind != "whiteList":  # a whiteList is the sharded rescore of its list
        assert after["sharded_queries"] == before["sharded_queries"]
    for n, q in queries:
        world.check(q, out[n], exact=True)


@pytest.mark.parametrize("engaged", [True, False], ids=["two_stage", "exact"])
@pytest.mark.parametrize("batch", [1, 3, 16])
@pytest.mark.parametrize("shards", [1, 2, 4])
def test_the_masked_programs_on_meshes_of_one_two_and_four(
        storage, monkeypatch, shards, batch, engaged):
    """Every query kind in one batch (a ``whiteList`` among them: the
    listed program), on a mesh of one, two and four devices, through
    the masked two-stage program and the masked exact one: the plain
    reference's answers, and the one-chip storefront's items in its
    order."""
    monkeypatch.setenv("PIO_MESH", f"data={shards}")
    if engaged:
        monkeypatch.setenv("PIO_RETRIEVAL_THRESHOLD", "500")
        monkeypatch.setenv("PIO_RETRIEVAL_TILE", "256")
        monkeypatch.setenv("PIO_RETRIEVAL_PROBE_EVERY", "0")
    w = ShardedWorld(storage, "float32")
    queries = [(n, w.query(KINDS[n % len(KINDS)], n)) for n in range(batch)]
    out = dict(w.algo.batch_predict(w.model, queries))
    assert retrieval.stats_block()["shards"] == shards
    one = dict(w.one_chip.batch_predict(w.model, queries))
    for n, q in queries:
        w.check(q, out[n], exact=(not engaged or KINDS[n % len(KINDS)] == "whiteList"))
        assert [s.item for s in out[n].itemScores] == \
            [s.item for s in one[n].itemScores]


@pytest.mark.parametrize("shards", [1, 2, 4])
def test_a_dispatch_under_rules_is_one_copy_and_a_whitelist_two(
        storage, monkeypatch, two_stage, shards):
    """``shard_h2d_copies`` once a bucket's zero blocks are there: the
    packed buffer alone, and for a ``whiteList`` the candidate ids
    beside it — where a replicated upload wrote ``shards`` and twice
    ``shards`` device buffers. ``uploads`` as they were."""
    monkeypatch.setenv("PIO_MESH", f"data={shards}")
    w = ShardedWorld(storage, "float32")

    def dispatch(kind, n):
        before = retrieval.stats_block()
        w.algo.predict(w.model, w.query(kind, n))
        after = retrieval.stats_block()
        return (after["shard_h2d_copies"] - before["shard_h2d_copies"],
                after["uploads"] - before["uploads"])

    assert dispatch("home", 1) == (shards, 1)  # the copy + the zero blocks
    assert dispatch("home", 2) == (1, 1)
    assert dispatch("category", 3) == (1, 1)  # a layout is a shape: the same
    assert dispatch("whiteList", 4) == (1 + shards, 2)  # the ids' zeros
    assert dispatch("whiteList", 6) == (2, 2)
    catalog = w.algo._sharded_catalog(w.model)
    assert len(catalog._zeros) == 2


@pytest.mark.parametrize("engaged", [True, False], ids=["two_stage", "exact"])
@pytest.mark.parametrize("kind", KINDS)
def test_sharded_answers_are_the_one_chip_storefronts(
        f32_world, monkeypatch, kind, engaged):
    """The same items in the same order as the one-chip masked programs
    serve, scores to f32 rounding (the shards' dots are HIGHEST)."""
    w = f32_world
    if engaged:
        monkeypatch.setenv("PIO_RETRIEVAL_THRESHOLD", "500")
        monkeypatch.setenv("PIO_RETRIEVAL_TILE", "256")
        monkeypatch.setenv("PIO_RETRIEVAL_PROBE_EVERY", "0")
    queries = [(n, w.query(kind, n)) for n in range(5)]
    sharded = dict(w.algo.batch_predict(w.model, queries))
    one = dict(w.one_chip.batch_predict(w.model, queries))
    for n, _ in queries:
        assert [s.item for s in sharded[n].itemScores] == \
            [s.item for s in one[n].itemScores]
        np.testing.assert_allclose(
            [s.score for s in sharded[n].itemScores],
            [s.score for s in one[n].itemScores], rtol=0, atol=4e-6)


def test_a_category_on_one_shard_leaves_three_shards_nothing(f32_world, two_stage):
    """Category C-1's four items all lie on shard 0: three shards answer
    -1s only, and the merge still serves the whole (short) answer."""
    w = f32_world
    assert (np.flatnonzero(w.item_cat == C - 1) < ROWS).all()
    q = w.query("category", 0)
    got = w.algo.predict(w.model, q)
    assert 0 < len(got.itemScores) <= 4
    w.check(q, got, exact=True)
    # and one that only the LAST shard holds, unavailable rows and all
    last = ec.Query(user="u1", num=10, whiteList=[f"i{i}" for i in range(I - ROWS, I)])
    w.check(last, w.algo.predict(w.model, last), exact=True)
    unknown = ec.Query(user="u1", num=5, categories=["no-such-category"])
    assert w.algo.predict(w.model, unknown).itemScores == []


@pytest.mark.parametrize("length", [10, 300], ids=["bucket", "past_the_bucket"])
def test_an_exclusion_list_that_spans_the_shards(f32_world, two_stage, length):
    """A blackList of the user's own best items — rows of every shard —
    and one longer than ``_EXCLUDED_BUCKET``: every shard drops the rows
    it holds, none of another's."""
    w = f32_world
    home = ec.Query(user="u2", num=10)
    scores = w.U[2] @ w.V.T
    best = np.argsort(-scores)[:length]
    assert len(set(best // ROWS)) == SHARDS
    before = ec._m_overflow.value()
    q = ec.Query(user="u2", num=10, blackList=[f"i{i}" for i in best])
    got = w.algo.predict(w.model, q)
    assert not {int(s.item[1:]) for s in got.itemScores} & set(best.tolist())
    w.check(q, got, exact=False)
    assert ec._m_overflow.value() == before + (length > ec._EXCLUDED_BUCKET)
    w.check(home, w.algo.predict(w.model, home), exact=False)


@pytest.mark.parametrize("engaged", [True, False], ids=["two_stage", "exact"])
def test_an_availability_change_is_rebuilt_on_the_shards(
        f32_world, monkeypatch, engaged):
    w = f32_world
    if engaged:
        monkeypatch.setenv("PIO_RETRIEVAL_THRESHOLD", "500")
        monkeypatch.setenv("PIO_RETRIEVAL_TILE", "256")
    q = ec.Query(user="u3", num=10)
    first = w.algo.predict(w.model, q).itemScores
    refreshes = ec._m_refresh.value()
    rebuilds = ec._m_refresh_secs.summary()["count"]
    top, second = int(first[0].item[1:]), int(first[1].item[1:])
    unavailable = np.sort(np.append(w.unavailable, top))
    w.events.insert(_unavailable(unavailable), w.app_id)
    w.events.insert(_view("u3", second), w.app_id)
    again = w.algo.predict(w.model, q)
    served = {int(s.item[1:]) for s in again.itemScores}
    assert top not in served and second not in served
    w.check(q, again, exact=not engaged, unavailable=unavailable,
            seen=w.seen["u3"] | {second})
    assert ec._m_refresh.value() == refreshes + 1
    assert ec._m_refresh_secs.summary()["count"] == rebuilds + SHARDS  # one a shard
    # a write that leaves the constraint as it is rebuilds nothing
    w.events.insert(_view("u9", 1), w.app_id)
    w.algo.predict(w.model, q)
    assert ec._m_refresh.value() == refreshes + 1


def test_the_rule_vectors_lie_beside_the_rows_they_guard(f32_world, two_stage):
    w = f32_world
    w.algo.predict(w.model, ec.Query(user="u1", num=10))
    catalog = w.algo._sharded_catalog(w.model)
    avail, cats = w.algo._catalog_rules(w.model, catalog, None)
    stored = catalog.stored_rows
    assert avail.shape == (SHARDS * stored,) and avail.dtype == np.uint8
    assert avail.sharding.is_equivalent_to(catalog._ids.sharding, 1)
    host = np.asarray(avail).reshape(SHARDS, stored)
    want = np.ones(I, np.uint8)
    want[w.unavailable] = 0
    np.testing.assert_array_equal(host[:, :ROWS].reshape(-1), want)
    assert not host[:, ROWS:].any()  # padding rows are unavailable
    (cat,) = cats
    np.testing.assert_array_equal(
        np.asarray(cat).reshape(SHARDS, stored)[:, :ROWS].reshape(-1),
        w.model.item_categories[:, 0])
    assert retrieval.stats_block()["resident_bytes"]["rules"] == stored * 5


def test_weights_are_applied_to_a_shards_block(storage, mesh, two_stage):
    boosted = [f"i{i}" for i in range(0, I, 7)]
    w = ShardedWorld(storage, "float32",
                     weights=[{"items": boosted, "weight": 1.5}])
    queries = [(n, w.query("home", n)) for n in range(3)]
    sharded = dict(w.algo.batch_predict(w.model, queries))
    one = dict(w.one_chip.batch_predict(w.model, queries))
    for n, _ in queries:
        assert [s.item for s in sharded[n].itemScores] == \
            [s.item for s in one[n].itemScores]
        np.testing.assert_allclose(
            [s.score for s in sharded[n].itemScores],
            [s.score for s in one[n].itemScores], rtol=0, atol=4e-6)
    assert {s.item for n, _ in queries for s in sharded[n].itemScores} & set(boosted)


def test_a_model_that_spans_files_is_never_one_host_array(
        f32_world, two_stage, monkeypatch):
    """The item table as a model file's ``SpannedArray`` (parts that the
    shards' bounds cut through): staged a shard at a time, the whole never
    asked for."""
    w = f32_world
    parts = [w.V[:300], w.V[300:1100], w.V[1100:]]
    w.model.item_factors = modelfile.SpannedArray(parts, w.V.shape)

    def whole(self, *a, **kw):
        raise AssertionError("the spanned table was asked for whole")

    monkeypatch.setattr(modelfile.SpannedArray, "__array__", whole)
    for kind in ("home", "category", "blackList", "whiteList", "cold"):
        q = w.query(kind, 3)
        w.check(q, w.algo.predict(w.model, q), exact=(kind == "whiteList"))


def test_spans_and_counters_of_the_sharded_rules(f32_world, two_stage):
    w = f32_world
    trace = obs_trace.Trace("t")
    with obs_trace.use_trace(trace):
        w.algo.batch_predict(w.model, [
            (0, w.query("home", 1)), (1, w.query("category", 2)),
            (2, w.query("blackList", 3)), (3, w.query("whiteList", 4))])
    spans = {s[0]: s for s in trace.spans}
    assert {"rules.build", "rules.seen_read", "rules.refresh", "dispatch.shortlist",
            "dispatch.rescore", "dispatch.fetch"} <= set(spans)
    text = shard_topk._sharded_topk_masked.lower(
        *_masked_args(w), **_masked_static(w)).as_text(debug_info=True)
    for scope in ("retrieval.shard.broadcast", "retrieval.shard.rules",
                  "retrieval.shard.scan",
                  "retrieval.shard.rescore", "retrieval.shard.gather",
                  "retrieval.shard.merge", "retrieval.shortlist.mask",
                  "retrieval.rescore.mask"):
        assert scope in text, scope


def _masked_args(w):
    catalog = w.algo._sharded_catalog(w.model)
    avail, cats = w.algo._catalog_rules(w.model, catalog, None)
    layout = retrieval.Layout(catalog.dim, 1, ec._EXCLUDED_BUCKET)
    packed = jax.ShapeDtypeStruct((SHARDS, 1, sum(layout[:3]) + 1), np.int32)
    return packed, None, catalog._rows, catalog._tiles, catalog._ids, avail, cats


def _masked_static(w):
    catalog = w.algo._sharded_catalog(w.model)
    return dict(r=catalog.rows_per_shard, kp=128, k=16, mode="bf16",
                mesh=catalog.mesh, axis="data",
                layout=retrieval.Layout(catalog.dim, 1, ec._EXCLUDED_BUCKET))


class TestWithoutRules:
    """``rules=None`` keeps the programs it had: their signatures, their
    modules free of every rule's op, and no masked compile."""

    def test_the_unmasked_programs_keep_their_signatures(self):
        assert list(inspect.signature(shard_topk._sharded_topk).parameters) == [
            "q", "rows", "tiles", "ids", "r", "kp", "k", "mode", "mesh", "axis"]
        assert list(inspect.signature(shard_topk._sharded_exact).parameters) == [
            "q", "rows", "ids", "k", "mesh", "axis"]

    @pytest.mark.parametrize("batch", [1, 8])
    def test_a_query_without_rules_lowers_to_a_module_without_them(
            self, two_stage, batch):
        mesh4 = make_mesh([("data", SHARDS)])
        nt, t, d = 2, 256, 16
        args = (
            jax.ShapeDtypeStruct((SHARDS, batch, d), np.float32),
            jax.ShapeDtypeStruct((SHARDS * nt * t, d), np.float32),
            jax.ShapeDtypeStruct((SHARDS * nt, t, d), jax.numpy.bfloat16),
            jax.ShapeDtypeStruct((SHARDS * nt, *retrieval.side_shape(nt, t)[1:]),
                                 np.int32),
        )
        text = shard_topk._sharded_topk.lower(
            *args, r=500, kp=128, k=16, mode="bf16", mesh=mesh4, axis="data",
        ).as_text(debug_info=True)
        assert "retrieval.shard.broadcast" in text
        assert "retrieval.shard.scan" in text
        for scope in ("retrieval.shard.rules", "shortlist.mask", "rescore.mask"):
            assert scope not in text, scope

    def test_a_dispatch_without_rules_compiles_no_masked_program(self, two_stage):
        rng = np.random.default_rng(5)
        V = rng.standard_normal((I, 16)).astype(np.float32)
        U = rng.standard_normal((4, 16)).astype(np.float32)
        catalog = ShardedCatalog(V, make_mesh([("data", SHARDS)]))
        tracked = ("retrieval.sharded_topk_masked", "retrieval.sharded_exact_masked")
        before = {f: obs_device.compile_snapshot().get(f, {}).get("compiles", 0)
                  for f in tracked}
        masked = retrieval.stats_block()["sharded_masked_queries"]
        for query in (retrieval.Vectors(U), retrieval.UserRows(
                np.arange(4), None, lambda ix: U[ix])):
            s, ids = retrieval.top_k(query, catalog, I, None, 16)
            want = np.argsort(-(U @ V.T), axis=1, kind="stable")[:, :16]
            np.testing.assert_array_equal(ids, want)
        assert {f: obs_device.compile_snapshot().get(f, {}).get("compiles", 0)
                for f in tracked} == before
        assert retrieval.stats_block()["sharded_masked_queries"] == masked

    def test_a_sum_of_rows_under_rules_is_refused_by_name(self, two_stage):
        rng = np.random.default_rng(6)
        V = rng.standard_normal((I, 16)).astype(np.float32)
        catalog = ShardedCatalog(V, make_mesh([("data", SHARDS)]))
        rules = Rules(avail=None, cats=(), qcat=None, has_cat=None, ex=None)
        query = retrieval.SumRows(
            np.zeros((1, 8), np.int32), np.ones((1, 8), np.float32),
            lambda ix, w: V[ix[:, 0]], rules)
        with pytest.raises(ValueError, match="SumRows"):
            retrieval.top_k(query, catalog, I, None, 8)
