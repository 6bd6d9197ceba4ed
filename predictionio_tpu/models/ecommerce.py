"""E-commerce recommendation template: weighted implicit ALS + live
serve-time business rules.

Capability parity with the reference template
``examples/scala-parallel-ecommercerecommendation/weighted-items``:

- DataSource reads user/item ``$set`` entities and ``view``/``buy``
  events,
- ALSAlgorithm trains ``ALS.trainImplicit`` on view counts
  (ALSAlgorithm.scala:136),
- predict applies, per request: unseen-item filtering from a **live**
  event-store read of the user's seen events, the unavailable-items
  constraint read live from the latest ``$set`` of constraint entity
  ``unavailableItems`` (:234-265), category/white/black-list filters,
  and per-group item weight multipliers (:295, WeightsGroup),
- cold-start users are scored from their recently viewed items' factor
  vectors (predictNewUser, :332-410).

TPU note: the rules reach the device as ``ops.topk.Rules`` and are
applied where the scores are produced — inside the coarse scan before a
tile's top-k, again in the exact rescore, or in the masked exact
program below the retrieval threshold — so exclusions cost no headroom
in k and every query kind shares the same batched programs:

- catalog-wide rules are RESIDENT device vectors over the stored rows:
  an availability byte (from the ``unavailableItems`` constraint) and
  the items' category ids (from the model file's array block), rebuilt
  only when the event store's change token moves AND the constraint's
  content has changed;
- per-query rules (seen items, ``blackList``) are short index lists
  padded to one pow2 bucket (``_EXCLUDED_BUCKET``; longer lists take the
  next power of two, counted), so the compiled shapes do not move with
  the traffic; a ``whiteList`` is scored as a candidate list;
- no request builds a dense [num_items] host array.

With ``sharded_serving`` (``pio deploy --mesh data=N``: a catalog one
chip cannot hold) the item rows stand split over the mesh and the same
rules are applied on every shard: the catalog-wide vectors sharded like
the rows they guard, built a shard at a time; the per-query lists
handed to every shard, in global rows, each shard resolving the ones it
holds (parallel/shard_topk.py). The item table is then never one host array
nor one device array, and the user table stays on the host.
"""

from __future__ import annotations

import functools
import json
import logging
import threading
from dataclasses import dataclass, field
from typing import Sequence

import numpy as np

from predictionio_tpu.core import (
    Algorithm,
    DataSource,
    Engine,
    FirstServing,
    IdentityPreparator,
    Params,
    SanityCheck,
    WorkflowContext,
)
from predictionio_tpu.data import store
from predictionio_tpu.data.storage.base import RatingsBatch
from predictionio_tpu.models.columnar import aggregate_counts
from predictionio_tpu.models.filters import (
    ItemCategories,
    availability_vector,
    candidate_lists,
    category_vectors,
    held_rows,
    padded_rows,
    query_rules,
    sharded_availability_vector,
    sharded_catalog,
    sharded_category_vectors,
)
from predictionio_tpu.data.bimap import BiMap
from predictionio_tpu.obs import metrics as obs_metrics
from predictionio_tpu.obs import trace as obs_trace
from predictionio_tpu.ops import als as als_ops
from predictionio_tpu.ops.retrieval import _pow2

logger = logging.getLogger(__name__)


@dataclass
class Query:
    user: str = ""
    num: int = 4
    categories: list[str] | None = None
    whiteList: list[str] | None = None
    blackList: list[str] | None = None


@dataclass
class ItemScore:
    item: str
    score: float


@dataclass
class PredictedResult:
    itemScores: list[ItemScore] = field(default_factory=list)


@dataclass
class DataSourceParams(Params):
    app_name: str = ""


@dataclass
class TrainingData(SanityCheck):
    users: list[str] = field(default_factory=list)
    items: dict[str, list[str]] = field(default_factory=dict)
    # bulk signals, columnar (no per-event Python objects at 10^7 scale)
    view_events: RatingsBatch = field(default_factory=RatingsBatch.empty)
    buy_events: RatingsBatch = field(default_factory=RatingsBatch.empty)

    def sanity_check(self) -> None:
        if not len(self.view_events):
            raise ValueError(
                "viewEvents in TrainingData cannot be empty. Please check if "
                "DataSource generates TrainingData correctly."
            )


class ECommerceDataSource(DataSource):
    params_class = DataSourceParams

    def read_training(self, ctx: WorkflowContext) -> TrainingData:
        app = self.params.app_name
        users = list(store.aggregate_properties(app, entity_type="user"))
        items = {
            iid: pm.get_opt("categories", default=[]) or []
            for iid, pm in store.aggregate_properties(app, entity_type="item").items()
        }
        views = store.find_ratings(
            app, entity_type="user", event_names=["view"],
            target_entity_type="item", rating_key=None,
            default_ratings={"view": 1.0},
        )
        buys = store.find_ratings(
            app, entity_type="user", event_names=["buy"],
            target_entity_type="item", rating_key=None,
            default_ratings={"buy": 1.0},
        )
        return TrainingData(
            users=users, items=items, view_events=views, buy_events=buys
        )


@dataclass
class WeightsGroup:
    items: list[str] = field(default_factory=list)
    weight: float = 1.0


@dataclass
class ECommAlgorithmParams(Params):
    app_name: str = ""  # for live serve-time event reads
    unseen_only: bool = True
    seen_events: tuple[str, ...] = ("view", "buy")
    rank: int = 10
    num_iterations: int = 20
    lambda_: float = 0.01
    alpha: float = 1.0
    seed: int = 3
    # bf16 halves HBM gather / ICI all_gather bytes at parity
    # (f32 accumulation; ops/als.py ALSParams.storage_dtype)
    compute_dtype: str = "float32"
    storage_dtype: str = "float32"
    weights: list[dict] = field(default_factory=list)  # [{items, weight}]
    # serve with the item rows split over the device mesh, the rules
    # applied on every shard (models/filters.py sharded_catalog)
    sharded_serving: bool = False
    sharded_train: bool = False  # train over the WorkflowContext mesh
    # per-chip budget for the sharded trainer's gathered opposite
    # factors; past it training auto-switches to the ppermute ring
    # half-step (parallel/als_sharded.py). None = library default (8 GiB)
    sharded_gather_budget_bytes: int | None = None


@dataclass
class ECommModel(ItemCategories):
    user_index: BiMap
    item_index: BiMap
    user_factors: np.ndarray  # int8 values when user_scales set
    item_factors: np.ndarray  # int8 values when item_scales set
    # construction-time input ({item id: [category, ...]}, what training
    # and model files written before the array block hold); indexed into
    # ``category_index`` / ``item_categories`` and dropped
    categories: dict[str, list[str]] | None = None
    user_scales: np.ndarray | None = None  # [U] f32, int8 storage only
    item_scales: np.ndarray | None = None  # [I] f32, int8 storage only
    category_index: BiMap | None = None  # category name -> dense id
    # [I, W] int32: the items' category ids, -1 past an item's last (W is
    # the most categories any item has; an array block in the model
    # file, not a 4 M-entry JSON header)
    item_categories: np.ndarray | None = None

    def __post_init__(self):
        self._device = None
        self._index_categories()

    def user_rows(self, ixs):
        """Dense f32 user vectors (dequantizes int8 storage)."""
        rows = self.user_factors[ixs]
        if self.user_scales is not None:
            return rows.astype(np.float32) * self.user_scales[ixs][..., None]
        return np.asarray(rows, dtype=np.float32)

    def item_rows(self, ixs):
        """Dense f32 item vectors (dequantizes int8 storage)."""
        rows = self.item_factors[ixs]
        if self.item_scales is not None:
            return rows.astype(np.float32) * self.item_scales[ixs][..., None]
        return np.asarray(rows, dtype=np.float32)

    def item_table(self):
        """The item factor table in scorer form: the (int8 values, f32
        scales) pair for quantized models, else the dense array."""
        if self.item_scales is not None:
            return (self.item_factors, self.item_scales)
        return self.item_factors

    def device_factors(self):
        """(U_dev, V_dev); quantized tables stay (values, scales) pairs
        on device — ops.topk scores them without densifying."""
        if self._device is None:
            import jax.numpy as jnp

            def put(values, scales):
                if scales is not None:
                    return (jnp.asarray(values), jnp.asarray(scales))
                return jnp.asarray(values)

            self._device = (
                put(self.user_factors, self.user_scales),
                put(self.item_factors, self.item_scales),
            )
        return self._device

    def __getstate__(self):
        state = self.__dict__.copy()
        state["_device"] = None
        # derived serving caches (device arrays) rebuild lazily after
        # unpickle
        state.pop("_weighted_V", None)
        state.pop("_coarse_V", None)
        state.pop("_sharded_V", None)
        state.pop("_rules", None)
        return state


# one pow2 bucket for a query's own exclusion list (seen + blackList):
# it covers a storefront's seen sets (~100 events a user), so the
# compiled shapes do not move with the traffic; a longer list takes the
# next power of two and is counted
_EXCLUDED_BUCKET = 128
_SEEN_CACHE_USERS = 1 << 16

_m_seen_read = obs_metrics.histogram(
    "pio_ecomm_seen_read_seconds",
    "one user's seen items read from the event store (cache misses only)",
)
_m_rules = obs_metrics.histogram(
    "pio_ecomm_rules_seconds",
    "host work turning a dispatch's queries into rule arguments",
)
_m_queries = {
    kind: obs_metrics.counter(
        "pio_ecomm_queries_total", "e-commerce queries by kind", kind=kind,
    )
    for kind in ("home", "category", "list")
}
_m_excluded = obs_metrics.histogram(
    "pio_ecomm_excluded_items",
    "items on one query's own exclusion list (seen + blackList)",
    bounds=tuple(float(1 << p) for p in range(0, 14)),
)
_m_overflow = obs_metrics.counter(
    "pio_ecomm_headroom_overflow_total",
    "queries whose exclusion list outgrew the compiled bucket",
)
_m_refresh = obs_metrics.counter(
    "pio_ecomm_rules_refresh_total",
    "rebuilds of the resident availability vector",
)
_m_refresh_secs = obs_metrics.histogram(
    "pio_ecomm_rules_refresh_seconds",
    "one rebuild of the resident availability vector",
)


def _query_kind(q: Query) -> str:
    if q.whiteList is not None or q.blackList:
        return "list"
    return "category" if q.categories is not None else "home"


class ECommAlgorithm(Algorithm):
    params_class = ECommAlgorithmParams
    query_class = Query

    def __init__(self, params=None):
        super().__init__(params)
        # serving caches are read and rebuilt from concurrent HTTP
        # handler threads; one lock (double-checked before each costly
        # rebuild) keeps a write spike from fanning out N duplicate
        # full-store scans / [I, D] multiplies whose results all but one
        # thread would discard
        self._serve_lock = threading.Lock()

    def train(self, ctx: WorkflowContext, td: TrainingData) -> ECommModel:
        if not len(td.view_events):
            raise ValueError("cannot train on zero view events")
        r = aggregate_counts(td.view_events, extra_items=td.items)
        user_index, item_index = r.user_index, r.item_index
        data = als_ops.build_ratings_data(
            r.rows, r.cols, r.vals, len(user_index), len(item_index)
        )
        from predictionio_tpu.parallel.als_sharded import train_for_context

        U, V = train_for_context(
            data,
            als_ops.ALSParams(
                rank=self.params.rank,
                iterations=self.params.num_iterations,
                reg=self.params.lambda_,
                implicit=True,
                alpha=self.params.alpha,
                seed=self.params.seed,
                compute_dtype=self.params.compute_dtype,
                storage_dtype=self.params.storage_dtype,
                **als_ops.sharded_budget_kwarg(
                    self.params.sharded_gather_budget_bytes
                ),
            ),
            ctx,
            sharded=self.params.sharded_train,
        )
        uf, us = als_ops.host_factors(U)
        vf, vs = als_ops.host_factors(V)
        return ECommModel(
            user_index=user_index,
            item_index=item_index,
            user_factors=uf,
            item_factors=vf,
            categories=dict(td.items),
            user_scales=us,
            item_scales=vs,
        )

    # -- live business rules ----------------------------------------------
    #
    # Live semantics with cached cost: every rule read goes through a
    # per-algorithm cache keyed by the event store's change_token — a
    # static store serves seen/unavailable sets from memory, while ANY
    # write to the store changes the token and drops the cache, so a
    # just-ingested ``$set unavailableItems`` or view event takes effect
    # on the next query. Every shipped backend produces a token (the
    # http client proxies it to the storage service, so cross-host
    # writes invalidate too); a custom Events DAO without a change_token
    # override returns None, which disables caching and keeps the
    # reference's read-per-request behavior.

    def _filter_cache(self) -> tuple[dict | None, object]:
        """(cache dict or None if caching disabled, current token).

        Read ONCE per dispatch (batch_predict passes the cache down): on
        remote backends the token read is a network roundtrip. The
        (app_id, channel_id) resolution is memoized — it is immutable
        for the life of a deployed engine."""
        try:
            from predictionio_tpu.data.storage import get_storage

            ids = getattr(self, "_app_ids", None)
            if ids is None:
                ids = store.app_name_to_id(self.params.app_name)
                self._app_ids = ids
            token = get_storage().get_events().change_token(*ids)
        except Exception:
            token = None
        if token is None:
            return None, None
        cache = getattr(self, "_filters", None)
        if cache is None or cache["token"] != token:
            with self._serve_lock:
                cache = getattr(self, "_filters", None)  # double-check
                if cache is None or cache["token"] != token:
                    cache = {"token": token, "seen": {}, "unavail": None}
                    self._filters = cache
        return cache, token

    def _seen_items(self, user: str, cache: dict | None) -> set[str]:
        """Live read of the user's seen events (reference :234-249).

        On replay-style backends (jsonl, partitioned, memory — where a
        filtered read costs a full scan anyway) the first miss builds the
        seen sets of EVERY user in one scan, so 40 distinct users cost
        one replay, not 40. Indexed backends (sqlite, http) answer a
        per-user point read that builds no Event objects."""
        if cache is not None and cache.get("seen_all") is not None:
            return cache["seen_all"].get(user, frozenset())
        try:
            from predictionio_tpu.data.storage import get_storage

            indexed = get_storage().get_events().entity_indexed
        except Exception:
            indexed = True
        if cache is not None and not indexed:
            with self._serve_lock:
                if cache.get("seen_all") is not None:  # double-check
                    return cache["seen_all"].get(user, frozenset())
                try:
                    with obs_trace.region("rules.seen_read", hist=_m_seen_read):
                        events = store.find(
                            app_name=self.params.app_name,
                            entity_type="user",
                            event_names=list(self.params.seen_events),
                            target_entity_type="item",
                            limit=None,
                        )
                except Exception:
                    logger.exception(
                        "seen-items scan failed; serving without filter"
                    )
                    return set()
                seen_all: dict[str, set[str]] = {}
                for e in events:
                    if e.target_entity_id:
                        seen_all.setdefault(e.entity_id, set()).add(
                            e.target_entity_id
                        )
                cache["seen_all"] = seen_all
                return seen_all.get(user, frozenset())
        try:
            with obs_trace.region("rules.seen_read", hist=_m_seen_read):
                return store.find_target_ids(
                    app_name=self.params.app_name,
                    entity_type="user",
                    entity_id=user,
                    event_names=list(self.params.seen_events),
                    target_entity_type="item",
                )
        except Exception:
            logger.exception("seen-items read failed; serving without filter")
            return set()

    def _seen_rows(self, model: ECommModel, user: str,
                   cache: dict | None) -> np.ndarray:
        """The catalog rows of the user's seen items, cached until the
        event store changes."""
        if cache is not None:
            rows = cache["seen"].get(user)
            if rows is not None:
                return rows
        rows = held_rows(model.item_index, self._seen_items(user, cache))
        if cache is not None:
            if len(cache["seen"]) >= _SEEN_CACHE_USERS:
                cache["seen"].clear()
            cache["seen"][user] = rows
        return rows

    def _unavailable_rows(self, model: ECommModel,
                          cache: dict | None) -> np.ndarray:
        """Live read of the latest unavailableItems constraint
        (reference :250-265) as sorted catalog rows, cached until the
        event store changes."""
        if cache is not None and cache["unavail"] is not None:
            return cache["unavail"]
        try:
            events = store.find_by_entity(
                app_name=self.params.app_name,
                entity_type="constraint",
                entity_id="unavailableItems",
                event_names=["$set"],
                limit=1,
                latest=True,
            )
        except Exception:
            logger.exception("constraint read failed; serving without filter")
            return np.zeros(0, np.int32)
        items = (
            events[0].properties.get_opt("items", default=[]) or []
            if events
            else []
        )
        rows = np.unique(held_rows(model.item_index, items))
        if cache is not None:
            cache["unavail"] = rows
        return rows

    def _recent_item_vector(self, model: ECommModel, user: str):
        """Cold-start: mean factor vector of recently viewed items
        (reference predictNewUser :332-410)."""
        try:
            events = store.find_by_entity(
                app_name=self.params.app_name,
                entity_type="user",
                entity_id=user,
                event_names=["view"],
                target_entity_type="item",
                limit=10,
                latest=True,
            )
        except Exception:
            return None
        ixs = held_rows(
            model.item_index, [e.target_entity_id for e in events]
        ).tolist()
        if not ixs:
            return None
        return model.item_rows(ixs).mean(axis=0)

    def _catalog_rules(self, model: ECommModel, rows, cache: dict | None):
        """The resident catalog-wide rules over ``rows`` stored rows
        (the coarse catalog's, padding included; the catalog's own below
        the retrieval threshold) or — ``rows`` a ``ShardedCatalog`` —
        over every shard's stored rows, sharded like them and built a
        shard at a time: (availability uint8, one int32 category vector
        per category column), on the device. The category vectors are
        built once; the availability vector again only when the
        constraint's CONTENT has changed — a view event moves the token
        and costs one point read here."""
        from predictionio_tpu.ops import retrieval

        unavail = self._unavailable_rows(model, cache)
        states = model.__dict__.setdefault("_rules", {})
        state = states.get(rows)
        if state is not None and (
            state["unavail"] is unavail
            or np.array_equal(state["unavail"], unavail)
        ):
            return state["avail"], state["cats"]
        refresh = functools.partial(
            obs_trace.region, "rules.refresh", hist=_m_refresh_secs
        )
        with self._serve_lock:
            cats = None if state is None else state["cats"]
            if isinstance(rows, int):
                if cats is None:
                    cats = category_vectors(model.item_categories, rows)
                with refresh():
                    avail = availability_vector(
                        len(model.item_index), rows, unavail
                    )
                held = (avail, *cats)
            else:  # one observation a shard's rebuild
                if cats is None:
                    cats = sharded_category_vectors(
                        rows, model.item_categories
                    )
                avail = sharded_availability_vector(rows, unavail, refresh)
                held = tuple(
                    a.addressable_shards[0].data for a in (avail, *cats)
                )
            retrieval.set_resident(rules=held)
            _m_refresh.inc()
            states[rows] = {"unavail": unavail, "avail": avail, "cats": cats}
        return avail, cats

    def _item_weights(self, model: ECommModel) -> np.ndarray | None:
        """[I] f32 row weights of the ``weights`` groups (1.0 outside
        every group), or None where the deployment has none."""
        if not self.params.weights:
            return None
        weights = np.ones(len(model.item_index), dtype=np.float32)
        for group in self.params.weights:
            w = float(group.get("weight", 1.0))
            weights[held_rows(model.item_index, group.get("items", []))] = w
        return weights

    def _by_weights(self, model: ECommModel, attr: str, build):
        """``build()``'s result cached on ``model`` under ``attr``,
        keyed by the weight CONTENT: two algorithms with different
        weight groups may serve the same model object, and an
        instance-identity key would both defeat that sharing and go
        stale when ids are recycled. Lock-free on a hit: predicts must
        not stall behind the lock while another thread holds it across
        a full-store seen scan."""
        key = json.dumps(self.params.weights, sort_keys=True)
        cache = getattr(model, attr, None)
        if cache is not None and key in cache:
            return cache[key]
        with self._serve_lock:
            cache = getattr(model, attr, None)  # double-check
            if cache is None:
                cache = {}
                setattr(model, attr, cache)
            if key not in cache:
                cache[key] = build()
            return cache[key]

    def _sharded_catalog(self, model: ECommModel):
        """The WEIGHTED item rows staged over the serving mesh
        (``sharded_serving``): exact rows, coarse copy and ids a shard,
        the weights applied to a shard's block before it goes up."""
        return self._by_weights(
            model, "_sharded_V", lambda: sharded_catalog(
                model.item_table(), self._item_weights(model)
            ),
        )

    def _weighted_item_factors(self, model: ECommModel):
        """Device-resident ``V * weights`` — weights are static per
        deployment (params), so the [I, D] multiply runs once, not per
        query (``_by_weights``)."""

        def build():
            import jax.numpy as jnp

            _, V = model.device_factors()
            weights = self._item_weights(model)
            if weights is None:
                return V
            if isinstance(V, tuple):
                # per-row weight folds into the per-row scale: the
                # weighted catalog stays int8
                return (V[0], V[1] * jnp.asarray(weights))
            return V * jnp.asarray(weights)[:, None]

        return self._by_weights(model, "_weighted_V", build)

    def _coarse_catalog(self, model: ECommModel):
        """Tiled coarse copy of the WEIGHTED item table for the
        two-stage shortlist pass (ops/retrieval.py) — the business-rule
        weights bake into the coarse scores exactly like the exact
        path's, so the shortlist ranks what serving ranks. Cached by
        weight content, like ``_weighted_item_factors`` (which a
        dispatch asks for first: the lock is not re-entrant)."""
        from predictionio_tpu.ops.retrieval import CoarseCatalog

        return self._by_weights(
            model, "_coarse_V",
            lambda: CoarseCatalog(self._weighted_item_factors(model)),
        )

    def cacheable_query(self, query: Query) -> bool:
        """Never cacheable: predictions depend on LIVE event-store state
        the epoch fence can't see — the user's seen events, the latest
        ``$set`` of the ``unavailableItems`` constraint entity, and
        cold-start users' recent views all change with ingest, not with
        model swaps. A cached result would keep recommending an item the
        store just marked unavailable until the next retrain."""
        return False

    def predict(self, model: ECommModel, query: Query) -> PredictedResult:
        # batch of one through the batched scorer: the same programs as
        # the same query inside a coalesced micro-batch (same items in
        # the same order, scores to the last bits of f32)
        return self.batch_predict(model, [(0, query)])[0][1]

    def _query_rows(self, model: ECommModel, q: Query, cache: dict | None):
        """One query's own rules as index lists: (excluded catalog rows,
        category ids or None, whiteList rows or None)."""
        index = model.item_index
        excluded = held_rows(index, q.blackList or ())
        if self.params.unseen_only:
            excluded = np.union1d(
                self._seen_rows(model, q.user, cache), excluded
            )
        else:
            excluded = np.unique(excluded)
        cats = model.category_ids(q.categories)
        white = None
        if q.whiteList is not None:
            white = np.unique(held_rows(index, q.whiteList))
        return excluded, cats, white

    def batch_predict(
        self, model: ECommModel, queries: Sequence[tuple[int, Query]]
    ) -> list[tuple[int, PredictedResult]]:
        """Batched scoring with the live business rules intact. The host
        turns each query into index lists (``rules.build``: no upload),
        the rules travel to the device as ``ops.topk.Rules`` — with the
        query vectors, in one upload a dispatch — and every home,
        category and blackList query of the micro-batch shares ONE
        masked program per stage (``ops.retrieval.top_k``: the coarse
        scan and the exact rescore at retrieval scale, the masked exact
        top-k below it). k is pow2(num) — exclusions are applied before
        each top-k and need no headroom. ``whiteList`` queries score
        their own candidate lists through the same rescore program.
        ``sharded_serving`` hands ``top_k`` the catalog split over the
        mesh in the table's place, and the rules' vectors split like it:
        the same decision and the same rules, on every shard."""
        from predictionio_tpu.ops import retrieval
        from predictionio_tpu.ops.topk import Rules

        results: list[PredictedResult | None] = [None] * len(queries)
        n_items = len(model.item_index)
        sharded = self.params.sharded_serving
        V = (self._sharded_catalog(model) if sharded
             else self._weighted_item_factors(model))
        k = _pow2(max(int(q.num) for _, q in queries)) if queries else 1
        two_stage = not sharded and retrieval.two_stage_k(k, n_items)
        with obs_trace.region("rules.build", hist=_m_rules):
            cache, _ = self._filter_cache()  # one token read per dispatch
            # the rules are vectors over the rows the scan will slice:
            # the coarse catalog's (padding included) where one is used,
            # every shard's stored rows on a sharded catalog (which is
            # its own coarse copy)
            coarse = self._coarse_catalog(model) if two_stage else None
            avail, cats = self._catalog_rules(
                model,
                V if sharded else coarse.stored_rows if two_stage else n_items,
                cache,
            )
            scored: list[int] = []
            vecs, excluded, qcats, whites = [], [], [], []
            for qi, (_, q) in enumerate(queries):
                _m_queries[_query_kind(q)].inc()
                uix = model.user_index.get(q.user)
                if uix is not None:
                    vec = np.asarray(model.user_rows(uix))
                else:
                    recent = self._recent_item_vector(model, q.user)
                    if recent is None:
                        logger.info(
                            "user %s has no factors and no recent views;"
                            " empty result",
                            q.user,
                        )
                        results[qi] = PredictedResult(itemScores=[])
                        continue
                    vec = np.asarray(recent)
                ex, qc, white = self._query_rows(model, q, cache)
                _m_excluded.observe(float(len(ex)))
                if len(ex) > _EXCLUDED_BUCKET:
                    _m_overflow.inc()
                scored.append(qi)
                vecs.append(vec.astype(np.float32))
                excluded.append(ex)
                qcats.append(qc)
                whites.append(white)

            def rules_for(rows: list[int]) -> Rules:
                """The rules of ``scored[r] for r in rows``, padded."""
                rows = padded_rows(rows)
                return query_rules(
                    avail, cats, [excluded[r] for r in rows],
                    [qcats[r] for r in rows], _EXCLUDED_BUCKET,
                )

            def batch_for(rows: list[int]) -> np.ndarray:
                return np.stack([vecs[r] for r in padded_rows(rows)])

            open_ = [r for r in range(len(scored)) if whites[r] is None]
            listed = [r for r in range(len(scored)) if whites[r] is not None]
            open_rules = rules_for(open_) if open_ else None
            listed_rules = rules_for(listed) if listed else None

        def publish(rows, scores, ids):
            inv = model.item_index.inverse
            scores, ids = np.asarray(scores), np.asarray(ids)
            for j, r in enumerate(rows):
                qi = scored[r]
                num = int(queries[qi][1].num)
                results[qi] = PredictedResult(itemScores=[
                    ItemScore(item=inv[int(i)], score=float(s))
                    for s, i in zip(scores[j, :num], ids[j, :num])
                    if int(i) >= 0
                ])

        if open_:
            # over the weighted catalog, every stage under the rules
            scores, ids = retrieval.top_k(
                retrieval.Vectors(batch_for(open_), open_rules), V, n_items,
                coarse, k, probe_n=int(queries[scored[open_[0]]][1].num),
            )
            publish(open_, scores, ids)
        if listed:
            # a whiteList IS the candidate list: every allowed member is
            # scored exactly, whatever the catalog's size
            cand = candidate_lists(
                [whites[r] for r in listed], len(listed_rules.ex), k
            )
            # (the shards take the host's rules, packed with the vectors)
            scores, ids = retrieval.rescore_top_k_batch(
                batch_for(listed), V, cand, k=k,
                rules=listed_rules if sharded
                else retrieval.device_rules(listed_rules),
            )
            publish(listed, scores, ids)
        return [(ix, r) for (ix, _), r in zip(queries, results)]


def engine() -> Engine:
    """Reference ECommerceRecommendationEngine factory."""
    return Engine(
        datasource_classes=ECommerceDataSource,
        preparator_classes=IdentityPreparator,
        algorithm_classes={"als": ECommAlgorithm},
        serving_classes=FirstServing,
    )
