"""Recommended-user engine template: similar users via implicit ALS.

Capability parity with the reference template variant
``examples/scala-parallel-similarproduct/recommended-user``: the
similar-product pipeline retargeted at users — DataSource reads ``$set``
user entities and user→user ``follow`` events, ALS trains implicitly on
the follow matrix, and a query for one or more users returns the users
most cosine-similar to the *followed-user* factor vectors, with
white/black-list filters.

Query: ``{"users": [...], "num": N, "whiteList": [...]?,
"blackList": [...]?}`` -> ``{"userScores": [{"user": ..., "score": ...}]}``.
"""

from __future__ import annotations

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
    CosineCatalog,
    score_similar_batch,
)
from predictionio_tpu.data.bimap import BiMap
from predictionio_tpu.ops import als as als_ops


@dataclass
class Query:
    users: list[str] = field(default_factory=list)
    num: int = 4
    whiteList: list[str] | None = None
    blackList: list[str] | None = None


@dataclass
class UserScore:
    user: str
    score: float


@dataclass
class PredictedResult:
    userScores: list[UserScore] = field(default_factory=list)


@dataclass
class DataSourceParams(Params):
    app_name: str = ""


@dataclass
class TrainingData(SanityCheck):
    users: list[str] = field(default_factory=list)
    # bulk signal, columnar (no per-event Python objects at 10^7 scale)
    follow_events: RatingsBatch = field(default_factory=RatingsBatch.empty)

    def sanity_check(self) -> None:
        if not len(self.follow_events):
            raise ValueError("TrainingData has no follow events")


class RecommendedUserDataSource(DataSource):
    params_class = DataSourceParams

    def read_training(self, ctx: WorkflowContext) -> TrainingData:
        app = self.params.app_name
        users = list(store.aggregate_properties(app, entity_type="user"))
        follows = store.find_ratings(
            app, entity_type="user", event_names=["follow"],
            target_entity_type="user", rating_key=None,
            default_ratings={"follow": 1.0},
        )
        return TrainingData(users=users, follow_events=follows)


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
class RecommendedUserModel(CosineCatalog):
    followed_index: BiMap  # followed-user id <-> column index
    followed_factors: np.ndarray  # [F, D] row-normalized at device load
    followed_scales: np.ndarray | None = None  # [F] f32, int8 storage only

    _catalog_fields = ("followed_factors", "followed_scales")


class ALSAlgorithm(Algorithm):
    """Implicit ALS on follow counts; cosine user-user scoring over the
    followed-side factors (reference recommended-user ALSAlgorithm.scala)."""

    params_class = ALSAlgorithmParams
    query_class = Query

    def train(self, ctx: WorkflowContext, td: TrainingData) -> RecommendedUserModel:
        if not len(td.follow_events):
            raise ValueError("cannot train on zero follow events")
        r = aggregate_counts(td.follow_events, extra_items=td.users)
        followed_index = r.item_index
        data = als_ops.build_ratings_data(
            r.rows, r.cols, r.vals, len(r.user_index), len(followed_index)
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
        return RecommendedUserModel(
            followed_index=followed_index,
            followed_factors=vf,
            followed_scales=vs,
        )

    def predict(self, model: RecommendedUserModel, query: Query) -> PredictedResult:
        # batch of one through the batched scorer: the same programs as
        # the same query inside a coalesced micro-batch (same users in
        # the same order, scores to the last bits of f32)
        return self.batch_predict(model, [(0, query)])[0][1]

    def batch_predict(
        self, model: RecommendedUserModel,
        queries: Sequence[tuple[int, Query]],
    ) -> list[tuple[int, PredictedResult]]:
        """``filters.score_similar_batch`` over followed users: the
        query's own users and its ``blackList`` are rules applied where
        the scores are produced, a ``whiteList`` is a candidate list."""
        results = score_similar_batch(
            model, model.followed_index, [q for _, q in queries],
            entities=lambda q: q.users,
            result=lambda pairs: PredictedResult(
                userScores=[UserScore(user=u, score=s) for u, s in pairs]
            ),
        )
        return [(ix, r) for (ix, _), r in zip(queries, results)]


def engine() -> Engine:
    """Reference RecommendedUserEngine factory (recommended-user
    Engine.scala: Map("als" -> ALSAlgorithm))."""
    return Engine(
        datasource_classes=RecommendedUserDataSource,
        preparator_classes=IdentityPreparator,
        algorithm_classes={"als": ALSAlgorithm},
        serving_classes=FirstServing,
    )
