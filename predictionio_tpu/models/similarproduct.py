"""Similar-product engine template: implicit ALS + item-item cosine.

Capability parity with the reference template
``examples/scala-parallel-similarproduct/multi``:

- DataSource reads ``$set`` user/item entities (items carry
  ``categories``) plus ``view`` and ``like``/``dislike`` events,
- ALSAlgorithm trains MLlib ``ALS.trainImplicit`` on view counts and
  scores candidate items by summed cosine similarity against the query
  items' factor vectors (ALSAlgorithm.scala:147,193,244),
- LikeAlgorithm (the "multi" variant's second algorithm) trains on
  like=1 / dislike=-1 signals (LikeAlgorithm.scala),
- CosineAlgorithm covers the experimental DIMSUM variant
  (examples/experimental/scala-parallel-similarproduct-dimsum):
  exact top-N item-item cosine from raw view counts — the MXU matmul
  replaces ``RowMatrix.columnSimilarities`` sampling,
- Serving sums per-item scores across algorithms and re-ranks (the
  multi variant's Serving.scala).

Query: ``{"items": [...], "num": N, "categories": [...]?,
"whiteList": [...]?, "blackList": [...]?}`` ->
``{"itemScores": [{"item": ..., "score": ...}]}``.
"""

from __future__ import annotations

from collections import defaultdict
from dataclasses import dataclass, field
from typing import Sequence

import numpy as np

from predictionio_tpu.core import (
    Algorithm,
    DataSource,
    Engine,
    IdentityPreparator,
    Params,
    SanityCheck,
    Serving,
    WorkflowContext,
)
from predictionio_tpu.data import store
from predictionio_tpu.data.bimap import BiMap
from predictionio_tpu.data.storage.base import RatingsBatch
from predictionio_tpu.models.columnar import (
    IndexedRatings,
    aggregate_counts,
    from_triples,
)
from predictionio_tpu.models.filters import (
    CosineCatalog,
    ItemCategories,
    held_rows,
    score_similar_batch,
)
from predictionio_tpu.ops import als as als_ops


@dataclass
class Query:
    items: list[str] = field(default_factory=list)
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
    items: dict[str, list[str]] = field(default_factory=dict)  # id -> categories
    # bulk signal, columnar (no per-event Python objects at 10^7 scale)
    view_events: RatingsBatch = field(default_factory=RatingsBatch.empty)
    # order-sensitive small signal (latest like/dislike wins) stays a list
    like_events: list[tuple[str, str, bool]] = field(default_factory=list)

    def sanity_check(self) -> None:
        if not len(self.view_events) and not self.like_events:
            raise ValueError("TrainingData has no view/like events")


class SimilarProductDataSource(DataSource):
    params_class = DataSourceParams

    def read_training(self, ctx: WorkflowContext) -> TrainingData:
        app = self.params.app_name
        users = list(store.aggregate_properties(app, entity_type="user"))
        item_props = store.aggregate_properties(app, entity_type="item")
        items = {
            iid: pm.get_opt("categories", default=[]) or []
            for iid, pm in item_props.items()
        }
        # columnar bulk read: every view carries implicit weight 1.0
        views = store.find_ratings(
            app, entity_type="user", event_names=["view"],
            target_entity_type="item", rating_key=None,
            default_ratings={"view": 1.0},
        )
        likes = [
            (e.entity_id, e.target_entity_id, e.event == "like")
            for e in store.find(
                app, entity_type="user", event_names=["like", "dislike"],
                target_entity_type="item",
            )
        ]
        return TrainingData(
            users=users, items=items, view_events=views, like_events=likes
        )


@dataclass
class ALSAlgorithmParams(Params):
    rank: int = 10
    num_iterations: int = 20
    lambda_: float = 0.01
    alpha: float = 1.0
    seed: int = 3
    # bf16 halves HBM gather / ICI all_gather bytes at parity
    # (f32 accumulation; ops/als.py ALSParams.storage_dtype)
    compute_dtype: str = "float32"
    storage_dtype: str = "float32"
    sharded_train: bool = False  # train over the WorkflowContext mesh
    # per-chip budget for the sharded trainer's gathered opposite
    # factors; past it training auto-switches to the ppermute ring
    # half-step (parallel/als_sharded.py). None = library default (8 GiB)
    sharded_gather_budget_bytes: int | None = None


@dataclass
class SimilarProductModel(ItemCategories, CosineCatalog):
    item_index: BiMap
    item_factors: np.ndarray  # [I, D]; int8 values when item_scales set
    # ``filters.ItemCategories``: the dictionary is construction-time
    # input, the model keeps the index and the [I, W] int32 array block
    categories: dict[str, list[str]] | None = None
    item_scales: np.ndarray | None = None  # [I] f32, int8 storage only
    category_index: BiMap | None = None
    item_categories: np.ndarray | None = None

    _catalog_fields = ("item_factors", "item_scales")

    def __post_init__(self):
        super().__post_init__()
        self._index_categories()


def _view_counts(td: TrainingData) -> IndexedRatings:
    """Aggregate view events into per-(user, item) counts, vectorized
    (items known only from ``$set`` entities still get index slots)."""
    return aggregate_counts(td.view_events, extra_items=td.items)


class ALSAlgorithm(Algorithm):
    """Implicit ALS on view counts; cosine item-item scoring."""

    params_class = ALSAlgorithmParams
    query_class = Query

    def _ratings(self, td: TrainingData) -> IndexedRatings:
        return _view_counts(td)

    def train(self, ctx: WorkflowContext, td: TrainingData) -> SimilarProductModel:
        r = self._ratings(td)
        user_index, item_index = r.user_index, r.item_index
        data = als_ops.build_ratings_data(
            r.rows, r.cols, r.vals, len(user_index), len(item_index)
        )
        params = als_ops.ALSParams(
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
        )
        from predictionio_tpu.parallel.als_sharded import train_for_context

        _, V = train_for_context(data, params, ctx, sharded=self.params.sharded_train)
        vf, vs = als_ops.host_factors(V)
        return SimilarProductModel(
            item_index=item_index,
            item_factors=vf,
            categories=dict(td.items),
            item_scales=vs,
        )

    def predict(self, model: SimilarProductModel, query: Query) -> PredictedResult:
        # batch of one through the batched scorer: the same programs as
        # the same query inside a coalesced micro-batch (same items in
        # the same order, scores to the last bits of f32)
        return self.batch_predict(model, [(0, query)])[0][1]

    def batch_predict(
        self, model: SimilarProductModel,
        queries: Sequence[tuple[int, Query]],
    ) -> list[tuple[int, PredictedResult]]:
        """``filters.score_similar_batch`` over items: the query's own
        items, its ``blackList`` and its ``categories`` are rules
        applied where the scores are produced, a ``whiteList`` is a
        candidate list (reference ALSAlgorithm.scala:193-244)."""
        results = score_similar_batch(
            model, model.item_index, [q for _, q in queries],
            entities=lambda q: q.items,
            categories=lambda q: model.category_ids(q.categories),
            result=lambda pairs: PredictedResult(
                itemScores=[ItemScore(item=i, score=s) for i, s in pairs]
            ),
        )
        return [(ix, r) for (ix, _), r in zip(queries, results)]


class LikeAlgorithm(ALSAlgorithm):
    """like=1 / dislike=-1 signal instead of view counts
    (reference multi/LikeAlgorithm.scala: latest like/dislike wins)."""

    def _ratings(self, td: TrainingData) -> IndexedRatings:
        latest: dict[tuple[str, str], float] = {}
        for u, i, is_like in td.like_events:  # events are time-ordered
            latest[(u, i)] = 1.0 if is_like else -1.0
        return from_triples(
            [(u, i, v) for (u, i), v in latest.items()], extra_items=td.items
        )


@dataclass
class CosineAlgorithmParams(Params):
    top_n: int = 20  # neighbors kept per item (dimsum threshold analog)


@dataclass
class CosineModel:
    item_index: BiMap
    sim_scores: np.ndarray  # [I, N] cosine of the N nearest items
    sim_ids: np.ndarray  # [I, N] their item indices
    categories: dict[str, list[str]]


class CosineAlgorithm(Algorithm):
    """Precomputed exact item-item cosine neighbors from view counts
    (DIMSUM-variant parity; see ops/cosine_sim.py)."""

    params_class = CosineAlgorithmParams
    query_class = Query

    def train(self, ctx: WorkflowContext, td: TrainingData) -> CosineModel:
        from predictionio_tpu.ops.cosine_sim import item_similarity_topn

        r = _view_counts(td)
        scores, ids = item_similarity_topn(
            r.rows, r.cols, r.vals, len(r.user_index), len(r.item_index),
            top_n=self.params.top_n,
        )
        item_index = r.item_index
        return CosineModel(
            item_index=item_index,
            sim_scores=scores,
            sim_ids=ids,
            categories=dict(td.items),
        )

    def predict(self, model: CosineModel, query: Query) -> PredictedResult:
        index, inv = model.item_index, model.item_index.inverse
        known = held_rows(index, query.items).tolist()
        if not known:
            return PredictedResult(itemScores=[])
        combined: dict[int, float] = defaultdict(float)
        for ix in known:
            for score, jx in zip(model.sim_scores[ix], model.sim_ids[ix]):
                if np.isfinite(score):
                    combined[int(jx)] += float(score)
        # the neighbour lists live on the host: set look-ups are enough
        dropped = {*known, *held_rows(index, query.blackList or ()).tolist()}
        white = (
            None if query.whiteList is None
            else set(held_rows(index, query.whiteList).tolist())
        )
        wanted = None if query.categories is None else set(query.categories)

        def allowed(jx: int) -> bool:
            return (
                jx not in dropped
                and (white is None or jx in white)
                and (wanted is None
                     or not wanted.isdisjoint(model.categories.get(inv[jx], ())))
            )

        ranked = sorted(
            ((jx, s) for jx, s in combined.items() if allowed(jx)),
            key=lambda kv: -kv[1],
        )[: int(query.num)]
        return PredictedResult(
            itemScores=[ItemScore(item=inv[jx], score=s) for jx, s in ranked]
        )


class SumScoreServing(Serving):
    """Combines algorithms by summing per-item scores and re-ranking
    (reference multi/Serving.scala)."""

    def serve(self, query: Query, predictions: Sequence[PredictedResult]) -> PredictedResult:
        combined: dict[str, float] = defaultdict(float)
        for p in predictions:
            for item_score in p.itemScores:
                combined[item_score.item] += item_score.score
        ranked = sorted(combined.items(), key=lambda kv: -kv[1])[: query.num]
        return PredictedResult(
            itemScores=[ItemScore(item=i, score=s) for i, s in ranked]
        )


def engine() -> Engine:
    """Reference SimilarProductEngine factory (multi/Engine.scala:
    Map("als" -> ALSAlgorithm, "likealgo" -> LikeAlgorithm))."""
    return Engine(
        datasource_classes=SimilarProductDataSource,
        preparator_classes=IdentityPreparator,
        algorithm_classes={
            "als": ALSAlgorithm,
            "likealgo": LikeAlgorithm,
            "cosine": CosineAlgorithm,
        },
        serving_classes=SumScoreServing,
    )
