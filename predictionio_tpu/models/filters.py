"""Shared serve-time helpers for the cosine-scoring templates.

The self/whiteList/blackList exclusion semantics are common to the
similar-product, recommended-user, and e-commerce templates (reference
examples/scala-parallel-similarproduct/multi/src/main/scala/
ALSAlgorithm.scala:193-244 and the recommended-user variant): query
entities are never recommended back, a whitelist restricts candidates to
its members, a blacklist removes its members. The similar-product and
recommended-user templates are one scorer (``score_similar_batch``)
over one kind of model (``CosineCatalog``) with the entity names
swapped.
"""

from __future__ import annotations

import functools
import logging
from typing import Callable, Sequence

import numpy as np

from predictionio_tpu.data.bimap import BiMap
from predictionio_tpu.obs import metrics as obs_metrics
from predictionio_tpu.obs import trace as obs_trace

logger = logging.getLogger(__name__)


def normalized_device_factors(factors: np.ndarray, scales=None):
    """Row-normalize factors, place on device, and return
    ``(table, norms)`` (dot == cosine against ``table`` after this). The
    cosine-scoring models cache both per process.

    Dense storage: ``table`` is the dense f32 [I, D] row-normalized
    array, exactly as before. int8 storage (``scales`` is the per-row
    f32 scale vector): cosine is invariant to the positive per-row
    scale, so normalization folds INTO the scale — ``table`` stays the
    (int8 values, f32 1/||values||) pair, which dequantizes to unit
    rows while keeping the device catalog 4x smaller than dense
    (ops/topk.py scores the pair without densifying).

    ``norms`` is the device-resident [I] f32 vector of stored-row norms
    (what ``ops.topk.top_k_similar`` recomputes per call without its
    ``norms`` argument)."""
    import jax.numpy as jnp

    if scales is not None:
        vals = np.asarray(factors)
        n = np.linalg.norm(vals.astype(np.float32), axis=1)
        inv = (1.0 / np.maximum(n, 1e-12)).astype(np.float32)
        return (jnp.asarray(vals), jnp.asarray(inv)), jnp.asarray(
            n.astype(np.float32)
        )
    norms = np.linalg.norm(factors, axis=1, keepdims=True)
    table = jnp.asarray(factors / np.maximum(norms, 1e-12))
    return table, jnp.asarray(norms[:, 0].astype(np.float32))


def normalized_query_vectors(
    factors: np.ndarray, scales, row_ixs: np.ndarray, row_weights: np.ndarray
) -> np.ndarray:
    """Host-side [B, D] weighted sums of row-normalized catalog rows —
    the cosine templates' query vectors for the coarse shortlist pass
    (the gathers are [B, L], so host math is cheaper than a device
    round-trip; the exact rescore rebuilds them on device regardless,
    so this copy never touches final scores)."""
    rows = np.asarray(factors)[row_ixs].astype(np.float32)  # [B, L, D]
    del scales  # cosine drops the positive per-row scale
    n = np.linalg.norm(rows, axis=2, keepdims=True)
    rows = rows / np.maximum(n, 1e-12)
    return (rows * np.asarray(row_weights, np.float32)[..., None]).sum(axis=1)


class ItemCategories:
    """The items' categories of a model dataclass with the fields
    ``item_index``, ``categories`` (construction-time input: ``{item id:
    [category, ...]}``, what training and model files written before
    the array block hold), ``category_index`` (category name -> dense
    id) and ``item_categories`` ([I, W] int32 category ids, -1 past an
    item's last; W is the most categories any item has): the dictionary
    is indexed into the other two and dropped, so the model file holds
    an array block, not a 4 M-entry JSON header, and
    ``category_vectors`` has a table to put on the device."""

    def _index_categories(self) -> None:
        if self.item_categories is None:
            cats = self.categories or {}
            self.category_index = BiMap.from_dense(
                sorted({c for cs in cats.values() for c in cs})
            )
            width = max([1] + [len(cs) for cs in cats.values()])
            table = np.full((len(self.item_index), width), -1, np.int32)
            rows = self.item_index.index_of(list(cats)).tolist()  # ONE look-up
            for ix, cs in zip(rows, cats.values()):
                if ix >= 0:
                    table[ix, : len(cs)] = [self.category_index[c] for c in cs]
            self.item_categories = table
        self.categories = None

    def __setstate__(self, state):
        # a pickle from before the array block holds ``categories`` only
        self.__dict__.update(
            {"category_index": None, "item_categories": None, **state}
        )
        self._index_categories()

    def category_ids(self, names) -> list[int] | None:
        """The known ids of the categories a query names (None where it
        names none: unrestricted; a query that names only unknown
        categories is restricted to nothing)."""
        if names is None:
            return None
        return [c for c in map(self.category_index.get, names) if c is not None]


def sharded_catalog(item_table, row_weights=None):
    """The item rows of a model served with ``sharded_serving`` — an
    algorithm parameter of the templates that take it (Recommendation,
    E-Commerce Recommendation): serve with the item rows split over the
    device mesh (``pio deploy --mesh data=N``), each device scanning and
    rescoring the rows it holds and one small all-gather merging the
    answers (parallel/shard_topk.py), for a catalog one chip cannot
    hold. Shard ``i`` is read from ``item_table`` (an ndarray, a model
    file's ``SpannedArray``, the int8 pair) onto device ``i``, times its
    rows' ``row_weights`` where given: the table is never one host array
    nor one device array, and the user table stays on the host (a query
    reads one row of it). The caller caches what this returns."""
    from predictionio_tpu.parallel.mesh import serving_mesh
    from predictionio_tpu.parallel.shard_topk import ShardedCatalog

    with obs_trace.region("model.load_segments"):
        return ShardedCatalog(item_table, serving_mesh(), row_weights=row_weights)


def sharded_category_vectors(catalog, item_categories) -> tuple:
    """``category_vectors`` over a ``ShardedCatalog``'s stored rows: each
    vector sharded like the rows it describes and put up a shard at a
    time (``ShardedCatalog.row_vector``; -1 past a shard's rows)."""
    if item_categories is None:
        return ()
    return tuple(
        catalog.row_vector(
            lambda lo, hi, w=w: item_categories[lo:hi, w], np.int32, -1
        )
        for w in range(item_categories.shape[1])
    )


def sharded_availability_vector(catalog, unavailable, each):
    """``availability_vector`` over a ``ShardedCatalog``'s stored rows,
    built and put up a shard at a time beside the rows it guards
    (padding rows unavailable). ``unavailable``: sorted distinct catalog
    rows; ``each()`` wraps a shard's rebuild (the caller's span)."""

    def flags(lo: int, hi: int):
        a, b = np.searchsorted(unavailable, (lo, hi))
        out = np.ones(hi - lo, np.uint8)
        out[unavailable[a:b] - lo] = 0
        return out

    return catalog.row_vector(flags, np.uint8, 0, each)


# -- ``ops.topk.Rules`` from host lists ---------------------------------------
#
# The catalog-wide rules are resident device vectors over the ``rows``
# stored rows the programs slice (the coarse catalog's, padding
# included; the catalog's own below the retrieval threshold); a query's
# own rules are short index lists padded to power-of-two widths, so the
# compiled shapes do not move with the traffic, and stay HOST arrays
# here: ``ops.retrieval.top_k`` sends them up, in the one upload of a
# dispatch under rules. No request builds a dense [num_items] host
# array.


def category_vectors(item_categories, rows: int) -> tuple:
    """One device [rows] int32 vector per category column of the
    [I, W] table (-1 = none, and past the catalog); () for a catalog
    without categories."""
    import jax.numpy as jnp

    if item_categories is None:
        return ()
    cols = np.full((item_categories.shape[1], rows), -1, np.int32)
    cols[:, : len(item_categories)] = np.asarray(item_categories).T
    return tuple(jnp.asarray(c) for c in cols)


def availability_vector(num_items: int, rows: int, unavailable=None):
    """Device [rows] uint8: 1 = the row may be served; 0 for the
    ``unavailable`` rows and the padding past the catalog."""
    import jax.numpy as jnp

    avail = np.zeros(rows, np.uint8)
    avail[:num_items] = 1
    if unavailable is not None:
        avail[unavailable] = 0
    return jnp.asarray(avail)


def held_rows(index: BiMap, keys) -> np.ndarray:
    """The rows of those of ``keys`` that ``index`` holds, in the keys'
    order ([n] int32): ONE look-up a list, which a map over a model
    file's encoded dictionary answers without decoding it."""
    if not keys:  # most queries list nothing
        return np.zeros(0, np.int32)
    rows = index.index_of(list(keys)).tolist()
    return np.asarray([ix for ix in rows if ix >= 0], np.int32)


def padded_rows(rows: list[int]) -> list[int]:
    """``rows`` filled to a power of two by copies of the first (the
    batch shapes the programs compile for)."""
    from predictionio_tpu.ops.retrieval import _pow2

    return rows + rows[:1] * (_pow2(len(rows)) - len(rows))


def query_rules(avail, cats, excluded: Sequence, qcats: Sequence,
                bucket: int):
    """The ``Rules`` of a padded batch, their per-query parts on the
    host (no runtime call here): ``excluded[j]`` the rows
    query j alone may not be served, ``qcats[j]`` the category ids it is
    restricted to (None = unrestricted; an empty list allows nothing).
    ``ex`` is ``bucket`` wide unless a list outgrows it (the next power
    of two)."""
    from predictionio_tpu.ops.retrieval import _pow2
    from predictionio_tpu.ops.topk import Rules

    n = len(excluded)
    width = _pow2(max([bucket] + [len(e) for e in excluded]))
    ex = np.full((n, width), -1, np.int32)
    qcat = np.full(
        (n, _pow2(max([1] + [len(c or ()) for c in qcats]))), -2, np.int32,
    )
    for j, (e, c) in enumerate(zip(excluded, qcats)):
        ex[j, : len(e)] = e
        if c:
            qcat[j, : len(c)] = c
    has_cat = np.asarray([c is not None for c in qcats])
    return Rules(avail, cats, qcat, has_cat, ex)


def candidate_lists(lists: Sequence, rows: int, k: int) -> np.ndarray:
    """[rows, width] int32 candidate ids of a ``whiteList`` batch: the
    lists side by side, -1 past each one's end, ``width`` the power of
    two at or above the longest (and k), the rows past the last list
    copies of the first (the batch's padding)."""
    from predictionio_tpu.ops.retrieval import _pow2

    cand = np.full((rows, _pow2(max([k] + [len(c) for c in lists]))), -1, np.int32)
    for j, c in enumerate(lists):
        cand[j, : len(c)] = c
    cand[len(lists):] = cand[0]
    return cand


class CosineCatalog:
    """The serving caches of a model whose catalog is scored by cosine.
    The model (a dataclass) names its host factor array and its int8
    scale vector in ``_catalog_fields``."""

    _catalog_fields: tuple[str, str]

    # [rows, W] int32 category ids of the catalog's rows (``ItemCategories``),
    # or None for a catalog without categories
    item_categories = None

    def __post_init__(self):
        self._device = None
        self._norms = None
        self._coarse = None

    def host_catalog(self):
        """(factors, scales or None): the model's host arrays."""
        factors, scales = self._catalog_fields
        return getattr(self, factors), getattr(self, scales)

    def device_factors(self):
        """Row-normalized catalog on device (dot == cosine). int8
        storage stays the quantized (values, 1/||values||) pair — cosine
        drops the positive per-row scale, so normalization folds into
        the scale and the device table keeps the 4x size win."""
        if self._device is None:
            self._device, self._norms = normalized_device_factors(
                *self.host_catalog()
            )
        return self._device

    def device_norms(self):
        """Device-resident [rows] stored-row norms, computed once at
        load (``ops.topk.top_k_similar``'s ``norms`` argument)."""
        if self._norms is None:
            self.device_factors()
        return self._norms

    def coarse_catalog(self):
        """Tiled coarse copy of the normalized catalog for the
        two-stage shortlist pass (ops/retrieval.py), cached."""
        if self._coarse is None:
            from predictionio_tpu.ops.retrieval import CoarseCatalog

            self._coarse = CoarseCatalog(self.device_factors())
        return self._coarse

    def rule_vectors(self, rows: int):
        """The resident catalog-wide ``Rules`` vectors over ``rows``
        stored rows, built once per ``rows``: (availability — only the
        padding past the catalog is unavailable — and one category
        vector per category column)."""
        cache = self.__dict__.setdefault("_rule_vectors", {})
        vectors = cache.get(rows)
        if vectors is None:
            n = len(self.host_catalog()[0])
            vectors = cache[rows] = (
                availability_vector(n, rows),
                category_vectors(self.item_categories, rows),
            )
        return vectors

    def __getstate__(self):
        state = self.__dict__.copy()
        state["_device"] = None
        state["_norms"] = None
        state["_coarse"] = None
        state.pop("_rule_vectors", None)
        return state


# one pow2 bucket each for the rows summed into a query vector and for a
# query's own exclusion list (its entities + blackList), so the compiled
# shapes do not move with the traffic: a session's 8 items and 5
# black-listed ones fit; a longer list takes the next power of two
_ROWS_BUCKET = 8
_EXCLUDED_BUCKET = 16

_m_build = obs_metrics.histogram(
    "pio_similar_build_seconds",
    "host work turning a dispatch's queries into summed rows and rules",
)
_m_queries = {
    kind: obs_metrics.counter(
        "pio_similar_queries_total", "similar-to-these queries by kind",
        kind=kind,
    )
    for kind in ("plain", "category", "blacklist", "whitelist")
}
_m_query_rows = obs_metrics.histogram(
    "pio_similar_query_rows", "catalog rows summed into one query vector",
    bounds=tuple(float(1 << p) for p in range(0, 8)),
)


def _query_kind(q, categories) -> str:
    if q.whiteList is not None:
        return "whitelist"
    if categories is not None:
        return "category"
    return "blacklist" if q.blackList else "plain"


def score_similar_batch(
    model: CosineCatalog,
    index: BiMap,
    queries: Sequence,
    entities: Callable,
    result: Callable,
    categories: Callable = lambda q: None,
) -> list:
    """Score a micro-batch of "similar to these" queries against a
    ``CosineCatalog``: ``entities(q)`` are a query's own ids in
    ``index``, ``categories(q)`` the category ids it is restricted to
    (None = unrestricted), ``result(pairs)`` builds the template's
    answer from ``[(id, score), ...]``.

    One regime for every query, filtered or not: its exclusions — its
    own entities and its ``blackList`` — and its categories travel to
    the device as ``ops.topk.Rules`` (``similar.build``: short index
    lists on the host, never a dense [rows] mask, and no upload — they
    go up with the summed rows, once) and are applied where the scores
    are produced — inside the coarse scan and again in the rescore at
    retrieval scale, in the masked exact program below it
    (``ops.retrieval.top_k`` decides) — so k = pow2(num) carries no
    headroom, nothing is dropped on the host, and every such query of
    the batch shares one program per stage. A ``whiteList`` IS the
    candidate list: its members are scored exactly by the same rescore
    program, whatever the catalog's size. A query with fewer than
    ``num`` allowed rows gets a short, exact answer.

    Single-query ``predict`` delegates here with a batch of one, so a
    query is answered by the same programs alone and coalesced: the same
    entities in the same order, scores equal to the last bits of f32 (a
    dot's summation order can move with the batch size; tests hold them
    to 2e-6)."""
    from predictionio_tpu.ops import retrieval

    results: list = [None] * len(queries)
    n_rows = len(index)
    V = model.device_factors()  # row-normalized: dot == cosine
    k = retrieval._pow2(max([1] + [int(q.num) for q in queries]))
    two_stage = retrieval.two_stage_k(k, n_rows)
    with obs_trace.region("similar.build", hist=_m_build):
        # the rules are vectors over the rows the scan will slice: the
        # coarse catalog's (padding included) where one is used
        coarse = model.coarse_catalog() if two_stage else None
        avail, cats = model.rule_vectors(
            coarse.stored_rows if two_stage else n_rows
        )
        scored: list[int] = []
        knowns, excluded, qcats, whites = [], [], [], []
        for qi, q in enumerate(queries):
            qc = categories(q)
            _m_queries[_query_kind(q, qc)].inc()
            known = held_rows(index, entities(q)).tolist()
            if not known:
                logger.info(
                    "no query entities with factors; returning empty result"
                )
                results[qi] = result([])
                continue
            _m_query_rows.observe(float(len(known)))
            black = held_rows(index, q.blackList or ()).tolist()
            scored.append(qi)
            knowns.append(known)
            excluded.append(np.unique(np.asarray(known + black, np.int32)))
            qcats.append(qc)
            whites.append(
                None if q.whiteList is None
                else np.unique(held_rows(index, q.whiteList))
            )

        def batch_for(rows: list[int]):
            """(SumRows' ixs and weights, the rules) of ``scored[r] for r
            in rows``, padded to a power of two: the id lists to the
            bucket's width with weight-0 rows (index 0 gathered, then
            zeroed — exact)."""
            rows = padded_rows(rows)
            width = retrieval._pow2(
                max([_ROWS_BUCKET] + [len(knowns[r]) for r in rows])
            )
            ixs = np.zeros((len(rows), width), np.int32)
            weights = np.zeros(ixs.shape, np.float32)
            for j, r in enumerate(rows):
                ixs[j, : len(knowns[r])] = knowns[r]
                weights[j, : len(knowns[r])] = 1.0
            return ixs, weights, query_rules(
                avail, cats, [excluded[r] for r in rows],
                [qcats[r] for r in rows], _EXCLUDED_BUCKET,
            )

        open_ = [r for r in range(len(scored)) if whites[r] is None]
        listed = [r for r in range(len(scored)) if whites[r] is not None]
        open_batch = batch_for(open_) if open_ else None
        listed_batch = batch_for(listed) if listed else None

    def publish(rows, scores, ids):
        inv = index.inverse
        for j, r in enumerate(rows):
            num = int(queries[scored[r]].num)
            results[scored[r]] = result([
                (inv[int(i)], float(s))
                for s, i in zip(scores[j, :num], ids[j, :num])
                if int(i) >= 0
            ])

    if open_:
        ixs, weights, rules = open_batch
        scores, ids = retrieval.top_k(
            retrieval.SumRows(
                ixs, weights,
                functools.partial(
                    normalized_query_vectors, *model.host_catalog()
                ),
                rules,
            ),
            V, n_rows, coarse, k, probe_n=int(queries[scored[open_[0]]].num),
        )
        publish(open_, scores, ids)
    if listed:
        ixs, weights, rules = listed_batch
        cand = candidate_lists([whites[r] for r in listed], len(ixs), k)
        scores, ids = retrieval.rescore_sum_rows_top_k_batch(
            ixs, weights, V, cand, k=k, rules=retrieval.device_rules(rules)
        )
        publish(listed, scores, ids)
    return results
