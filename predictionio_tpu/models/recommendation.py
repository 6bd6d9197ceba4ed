"""Recommendation engine template: explicit-feedback ALS.

Capability parity with the reference's quickstart template
``examples/scala-parallel-recommendation/custom-prepartor``:

- DataSource reads ``rate`` and ``buy`` events from the event store and
  maps ``buy`` to an implicit 4.0 rating (DataSource.scala:35-60),
- ALSAlgorithm trains MLlib ALS at the configured rank/iterations/lambda
  (ALSAlgorithm.scala:44-86, ``ALS.train`` at :72) — here the TPU batched
  ALS from ``predictionio_tpu.ops.als``,
- ``BiMap.stringInt`` maps entity ids to dense factor-row indices
  (ALSAlgorithm.scala:50-56),
- predict scores ``user . item^T`` and returns the top ``num`` items
  (ALSAlgorithm.scala:88; MatrixFactorizationModel.recommendProducts) —
  here one fused device op (``ops.topk``).

Queries/results use the same JSON shape as the reference template:
``{"user": "1", "num": 4}`` -> ``{"itemScores": [{"item": ..., "score": ...}]}``.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from dataclasses import dataclass, field
from typing import Sequence

import numpy as np

from predictionio_tpu.core import (
    Algorithm,
    DataSource,
    Engine,
    EvalTopK,
    FirstServing,
    Params,
    Preparator,
    SanityCheck,
    WorkflowContext,
)
from predictionio_tpu.data import store
from predictionio_tpu.data.bimap import BiMap
from predictionio_tpu.obs import metrics as obs_metrics
from predictionio_tpu.ops import als as als_ops

logger = logging.getLogger(__name__)


# -- query / result wire shapes --------------------------------------------


@dataclass
class Query:
    user: str
    num: int = 4


@dataclass
class ItemScore:
    item: str
    score: float


@dataclass
class PredictedResult:
    itemScores: list[ItemScore] = field(default_factory=list)


# -- DASE components --------------------------------------------------------


@dataclass
class DataSourceParams(Params):
    app_name: str = ""
    event_names: tuple[str, ...] = ("rate", "buy")
    buy_rating: float = 4.0
    # evaluation split knobs (read_eval): fold count and the PRNG seed
    # for the shuffled fold assignment. The seed makes repeated
    # `pio eval` runs bit-reproducible — same folds, same metric values
    # (docs/evaluation.md "Reproducibility")
    eval_folds: int = 3
    eval_seed: int = 42


@dataclass
class TrainingData(SanityCheck):
    """Columnar ratings: dense-indexed COO triples plus id lists.

    ``user_ids[rows[i]]`` rated ``item_ids[cols[i]]`` with ``ratings[i]``.
    Columnar (not one Python object per event) so a 20M-event training
    read stays a few hundred MB of arrays instead of gigabytes of
    objects — the RDD-to-array boundary done streaming.
    """

    user_ids: list[str] = field(default_factory=list)
    item_ids: list[str] = field(default_factory=list)
    rows: np.ndarray = field(default_factory=lambda: np.empty(0, np.int32))
    cols: np.ndarray = field(default_factory=lambda: np.empty(0, np.int32))
    ratings: np.ndarray = field(default_factory=lambda: np.empty(0, np.float32))
    # packed-prep cache handle riding alongside the data (core/prep_cache
    # PrepHandle): lets Algorithm.train reuse/splice the cached bucketed
    # pack and publish the fresh one after training. None for synthetic
    # TrainingData (eval folds, tests) — everything downstream must
    # getattr-gate on it.
    prep: object = field(default=None, repr=False, compare=False)

    def sanity_check(self) -> None:
        if len(self.ratings) == 0:
            raise ValueError(
                "TrainingData has no ratings; check event store contents "
                "and the datasource appName"
            )


class RecommendationDataSource(DataSource):
    params_class = DataSourceParams

    def read_training(self, ctx: WorkflowContext) -> TrainingData:
        # buy is FORCED to buy_rating, beating any rating property — the
        # reference ignores properties for buy events (DataSource.scala:55
        # `case "buy" => 4.0`). On the file backends this read is served
        # from the columnar segment cache when warm (mmap'ed column
        # blocks, no per-event parse; storage/columnar_cache.py) — the
        # timing log below is the input-pipeline number to watch when a
        # train looks slow.
        t0 = time.perf_counter()
        from predictionio_tpu.core import prep_cache

        handle = prep_cache.probe(
            self.params.app_name,
            entity_type="user",
            event_names=list(self.params.event_names),
            target_entity_type="item",
            rating_key="rating",
            default_ratings=None,
            override_ratings={"buy": self.params.buy_rating},
        )
        if handle.status in ("hit", "splice"):
            # warm retrain: the full scan is skipped — an exact hit is an
            # mmap of the previous packed prep, a splice decoded only the
            # appended tail bytes (docs/storage.md "Packed-prep cache")
            batch = handle.batch
        else:
            batch = store.find_ratings(
                app_name=self.params.app_name,
                entity_type="user",
                event_names=list(self.params.event_names),
                target_entity_type="item",
                rating_key="rating",
                override_ratings={"buy": self.params.buy_rating},
            )
        logger.info(
            "read_training: %d rating rows in %.3fs (prep cache: %s)",
            len(batch.vals), time.perf_counter() - t0, handle.status,
        )
        return TrainingData(
            user_ids=batch.entity_ids,
            item_ids=batch.target_ids,
            rows=batch.rows,
            cols=batch.cols,
            ratings=batch.vals,
            prep=handle,
        )

    def read_eval(self, ctx: WorkflowContext):
        """Seeded k-fold split for evaluation (reference evaluation
        DataSource pattern). Fold assignment is a seeded shuffled
        balanced partition — deterministic in (event data, eval_folds,
        eval_seed), so repeated `pio eval` runs see identical
        train/test splits and produce identical metric values; raising
        index-correlated ingest order (e.g. time-sorted imports) no
        longer biases folds the way the old index-modulo split did."""
        td = self.read_training(ctx)
        k = max(1, int(self.params.eval_folds))
        folds = []
        n = len(td.ratings)
        rng = np.random.default_rng(int(self.params.eval_seed))
        fold_of = np.empty(n, dtype=np.int64)
        fold_of[rng.permutation(n)] = np.arange(n) % k
        for fold in range(k):
            mask = fold_of == fold
            # compact the train fold's id space to entities that actually
            # appear in it: a user whose only ratings fell in the test
            # fold must be ABSENT from the model (unseen-user -> empty
            # prediction), not scored from untrained random-init factors
            rows_tr, cols_tr = td.rows[~mask], td.cols[~mask]
            used_u = np.unique(rows_tr)
            used_i = np.unique(cols_tr)
            train = TrainingData(
                user_ids=[td.user_ids[u] for u in used_u],
                item_ids=[td.item_ids[i] for i in used_i],
                rows=np.searchsorted(used_u, rows_tr).astype(np.int32),
                cols=np.searchsorted(used_i, cols_tr).astype(np.int32),
                ratings=td.ratings[~mask],
            )
            qa = [
                (
                    Query(user=td.user_ids[td.rows[i]], num=1),
                    {
                        "item": td.item_ids[td.cols[i]],
                        "rating": float(td.ratings[i]),
                    },
                )
                for i in np.flatnonzero(mask)
            ]
            folds.append((train, {"fold": fold}, qa))
        return folds


class RecommendationPreparator(Preparator):
    """Passthrough (the reference custom-prepartor variant's Preparator
    simply wraps TrainingData)."""

    def prepare(self, ctx: WorkflowContext, td: TrainingData) -> TrainingData:
        return td


@dataclass
class ALSAlgorithmParams(Params):
    rank: int = 10
    num_iterations: int = 10
    lambda_: float = 0.01
    seed: int = 3
    compute_dtype: str = "float32"
    # dtype the factors are stored in between solves — "bfloat16" halves
    # the HBM gather / ICI all_gather traffic of this HBM-bound op at
    # parity RMSE, "int8" quarters it (values + per-row f32 scale,
    # dequantized at gather; solves still accumulate float32; ops/als.py)
    storage_dtype: str = "float32"
    # serve with the item rows split over the device mesh
    # (models/filters.py sharded_catalog) — the TPU answer to the
    # reference's PAlgorithm "model bigger than one host" case, which
    # issues a Spark job per query instead
    # (examples/.../ALSAlgorithm.scala:88)
    sharded_serving: bool = False
    # train over the WorkflowContext device mesh (factors sharded row-wise,
    # all_gather over ICI each half-iteration) — the production multi-chip
    # train path replacing MLlib ALS's Spark-cluster execution
    sharded_train: bool = False
    # half-step variant for the sharded trainer: "auto" picks gather
    # while the gathered opposite side fits the per-chip budget and the
    # scan-fused ppermute ring past it; "gather"/"ring" force one
    # (parallel/als_sharded.py "Two half-step variants")
    sharded_mode: str = "auto"
    # degree-bucket widths for the padded ALS layout (ops/als.py); rows
    # hotter than the largest width segment exactly across table rows
    bucket_widths: tuple[int, ...] = als_ops.DEFAULT_BUCKETS
    # per-chip budget for the sharded trainer's gathered opposite factors;
    # catalogs past it auto-switch to the ppermute ring half-step whose
    # working set shrinks with mesh size (parallel/als_sharded.py
    # "Memory model"). None = library default (8 GiB)
    sharded_gather_budget_bytes: int | None = None


# one staging at a time: a table goes up once, whichever thread asks first
_STAGING = threading.RLock()
_m_capacity = obs_metrics.gauge(
    "pio_model_user_capacity_rows",
    "Rows the resident user table has room for (the rows held, or the "
    "power of two above them that reserve_user_rows set)",
)


@dataclass
class ALSModel:
    """Host-persistable factor model; device arrays materialized lazily.

    With ``storage_dtype="int8"`` the factor arrays hold the quantized
    values and ``user_scales``/``item_scales`` the per-row f32 scales
    (``row = values * scale``, ops/als.py quantize_rows) — the persisted
    MODELDATA blob stays 4x smaller than f32, and scoring dequantizes
    inside the jitted top-k programs. Dense models keep scales None.
    """

    user_index: BiMap
    item_index: BiMap
    user_factors: np.ndarray  # [U, D] float32/bf16, or int8 values
    item_factors: np.ndarray  # [I, D] float32/bf16, or int8 values
    user_scales: np.ndarray | None = None  # [U] float32 when int8
    item_scales: np.ndarray | None = None  # [I] float32 when int8

    _user_capacity = 0  # (a model pickled before PR 45 has no such entry)
    # bytes a fold-in sent up to make this model of the one before it
    # (``patched``); None for a model that was loaded or built whole
    patch_h2d_bytes = None

    def __post_init__(self):
        self._device = None
        self._sharded = None
        self._coarse = None
        # rows the resident user table has room for: 0 = as many as are
        # held (``reserve_user_rows``: a deployment with --realtime)
        self._user_capacity = 0

    def user_rows(self, ixs):
        """Dense f32 user vectors for the given indices (dequantizes
        int8 storage) — the per-query [*, D] gather, done host-side."""
        rows = self.user_factors[ixs]
        if self.user_scales is not None:
            return rows.astype(np.float32) * self.user_scales[ixs][..., None]
        return np.asarray(rows, dtype=np.float32)

    def item_table(self):
        """The item factor table in scorer form: the (int8 values, f32
        scales) pair for quantized models, else the dense array."""
        if self.item_scales is not None:
            return (self.item_factors, self.item_scales)
        return self.item_factors

    def device_factors(self):
        """(U_dev, V_dev) cached on current default device; quantized
        tables stay (values, scales) pairs on device. A table goes up
        as it is stored — int8 values stay int8 on the host and on the
        chip — and one that spans model files a part at a time
        (``retrieval.put_rows``): never one host array. Staged once,
        whichever thread asks first (the batch worker, the fold)."""
        if self._device is None:
            with _STAGING:
                if self._device is None:
                    self._device = self._stage_factors()
        return self._device

    def _stage_factors(self):
        import jax

        from predictionio_tpu.obs import trace as obs_trace
        from predictionio_tpu.ops import retrieval

        with obs_trace.region(
            "model.stage_table",
            hist=retrieval._m_load["stage_to_device"],
        ):
            users, items = jax.block_until_ready((
                self._stage_users(),
                retrieval.put_padded(self.item_factors, self.item_scales, 0),
            ))
        values, scales = items if isinstance(items, tuple) else (items, None)
        retrieval.set_resident(users=users, table=values, table_scales=scales)
        return users, items

    def _stage_users(self):
        from predictionio_tpu.ops import retrieval

        return retrieval.put_padded(
            self.user_factors, self.user_scales, self._user_capacity
        )

    def user_capacity(self) -> int:
        """Rows the resident user table has room for."""
        return max(self._user_capacity, int(self.user_factors.shape[0]))

    def reserve_user_rows(self, rows: int = 0) -> int:
        """Give the resident user table room to grow: the next power of
        two ABOVE the rows held (or above ``rows``), so that a user
        appended by a fold-in changes no shape and compiles nothing —
        doubled again by the fold that finds it full. Where the table is
        on the device already its users go up again at the new size; the
        item side is not touched. Returns the capacity."""
        want = max(int(self.user_factors.shape[0]), int(rows))
        capacity = 1 << want.bit_length()  # 2^k > want
        if capacity <= self._user_capacity:
            return self._user_capacity
        with _STAGING:
            self._user_capacity = capacity
            if self._device is not None:
                from predictionio_tpu.ops import retrieval

                users = self._stage_users()
                retrieval.set_resident(users=users)
                self._device = (users, self._device[1])
        _m_capacity.set(float(capacity))
        return capacity

    def resident_parts(self) -> dict:
        """What of the ITEM side is on the device, by part (None: not
        yet): the server compares a patched model's with the served
        model's — the same objects, or the patch will stage them again."""
        return {
            "table": None if self._device is None else self._device[1],
            "coarse": self._coarse,
            "sharded": self._sharded,
        }

    def patched(self, user_index, user_factors, user_scales, users_device):
        """A model with another user side and THIS model's item side —
        the host arrays and whatever of them is on the device (the exact
        table and its scales, the coarse catalog, a sharded catalog), by
        reference: nothing item-side is staged, built or copied again.
        ``users_device`` is the new resident user table (or None where
        this model has none yet)."""
        m = ALSModel(
            user_index=user_index, item_index=self.item_index,
            user_factors=user_factors, item_factors=self.item_factors,
            user_scales=user_scales, item_scales=self.item_scales,
        )
        m._user_capacity = self._user_capacity
        m._sharded, m._coarse = self._sharded, self._coarse
        if self._device is not None and users_device is not None:
            m._device = (users_device, self._device[1])
        return m

    def sharded_catalog(self):
        """The item rows staged over the serving mesh, shard ``i`` read
        from the model file's segments onto device ``i``, cached — the
        deployed-server resident layout for a catalog bigger than one
        chip: exact rows, coarse copy and ids per device, the user table
        left on the host (a query reads one row of it)."""
        if self._sharded is None:
            from predictionio_tpu.models.filters import sharded_catalog

            self._sharded = sharded_catalog(self.item_table())
        return self._sharded

    def coarse_catalog(self):
        """Tiled coarse copy of the item table for the two-stage
        shortlist pass (ops/retrieval.py), cached — only built once a
        catalog crosses ``PIO_RETRIEVAL_THRESHOLD``."""
        if self._coarse is None:
            from predictionio_tpu.obs import trace as obs_trace
            from predictionio_tpu.ops import retrieval

            # an int8 pair is tiled from the resident copy, on the device
            table = (
                self.item_table() if self.item_scales is None
                else self.device_factors()[1]
            )
            with obs_trace.region(
                "model.coarse_build", hist=retrieval._m_load["coarse_build"]
            ):
                self._coarse = retrieval.CoarseCatalog(table)
        return self._coarse

    def __getstate__(self):
        state = self.__dict__.copy()
        state["_device"] = None
        state["_sharded"] = None
        state["_coarse"] = None
        state["_user_capacity"] = 0  # a deployment's, not the model's
        state.pop("patch_h2d_bytes", None)
        return state


class ALSAlgorithm(Algorithm):
    params_class = ALSAlgorithmParams
    query_class = Query

    def train(self, ctx: WorkflowContext, td: TrainingData) -> ALSModel:
        if len(td.ratings) == 0:
            raise ValueError("cannot train ALS on zero ratings")
        # ids arrive pre-dense-indexed from the columnar read; the BiMap
        # is a view over the id lists, not a per-event rebuild
        user_index = BiMap.from_dense(td.user_ids)
        item_index = BiMap.from_dense(td.item_ids)
        rows, cols = td.rows, td.cols
        vals = np.asarray(td.ratings, dtype=np.float32)
        prep = getattr(td, "prep", None)
        widths = tuple(self.params.bucket_widths)
        packed = (
            prep.packed_buckets(widths)
            if prep is not None and prep.active else None
        )
        if packed is not None:
            # hot retrain: buckets come out of the prep cache (mmap'd on
            # an exact hit, surgically spliced on an appended tail) —
            # bit-identical to a fresh build_padded_buckets by contract
            data = als_ops.RatingsData(
                rows=np.asarray(rows, np.int32),
                cols=np.asarray(cols, np.int32),
                vals=vals,
                num_rows=len(user_index),
                num_cols=len(item_index),
                row_buckets=packed[0],
                col_buckets=packed[1],
            )
        else:
            data = als_ops.build_ratings_data(
                rows,
                cols,
                vals,
                len(user_index),
                len(item_index),
                bucket_widths=widths,
            )
        params = als_ops.ALSParams(
            rank=self.params.rank,
            iterations=self.params.num_iterations,
            reg=self.params.lambda_,
            seed=self.params.seed,
            compute_dtype=self.params.compute_dtype,
            storage_dtype=self.params.storage_dtype,
            **als_ops.sharded_budget_kwarg(self.params.sharded_gather_budget_bytes),
        )
        from predictionio_tpu.parallel.als_sharded import train_for_context

        warm = self._resolve_warm_start(ctx, td)
        try:
            tol = float(os.environ.get("PIO_TOL", "") or (
                ctx.runtime_conf.get("tol", 0.0) if ctx is not None else 0.0
            ) or 0.0)
        except ValueError:
            tol = 0.0
        prepacked = None
        pub_sharded = None
        if self.params.sharded_train and ctx is not None:
            prepacked, pub_sharded = self._sharded_prepack(ctx, prep, data, params)
        U, V = train_for_context(
            data,
            params,
            ctx,
            sharded=self.params.sharded_train,
            mode=self.params.sharded_mode,
            warm_start=warm,
            tol=tol,
            prepacked=prepacked,
            progress_extra=(
                {"prep_cache": prep.status} if prep is not None else None
            ),
        )
        if prep is not None and prep.active and prep.status != "hit":
            from predictionio_tpu.data.storage import base as storage_base

            prep.publish(
                storage_base.RatingsBatch(
                    entity_ids=td.user_ids, target_ids=td.item_ids,
                    rows=data.rows, cols=data.cols, vals=data.vals,
                ),
                data=data,
                bucket_widths=widths,
                sharded=pub_sharded,
                params=params,
                sharded_requested=self.params.sharded_mode,
            )
        logger.info(
            "ALS trained: %d users x %d items, rank %d, train RMSE %.4f",
            len(user_index),
            len(item_index),
            self.params.rank,
            als_ops.rmse(U, V, rows, cols, vals),
        )
        uf, us = als_ops.host_factors(U)
        vf, vs = als_ops.host_factors(V)
        return ALSModel(
            user_index=user_index,
            item_index=item_index,
            user_factors=uf,
            item_factors=vf,
            user_scales=us,
            item_scales=vs,
        )

    def _resolve_warm_start(self, ctx, td):
        """Previous model -> iteration-0 factor carry, or None for cold.

        The model arrives via ``ctx.runtime_conf["warm_start_model"]``
        (core/workflow.py resolves ``--warm-start`` to the latest
        COMPLETED instance's persisted model). Incompatible models —
        wrong type, changed rank, changed storage dtype — fall back to
        cold start with a named warning, never a crash: factor shapes are
        baked into the compiled trainers, so feeding them mismatched
        carries would be a silent re-trace at best. Rows are re-aligned
        id-by-id; entities unknown to the previous model keep NaN, which
        the trainer's warm-init merge replaces with the cold random draw.
        """
        prev = ctx.runtime_conf.get("warm_start_model") if ctx is not None else None
        if prev is None:
            return None
        if not isinstance(prev, ALSModel):
            logger.warning(
                "warm-start: previous model is %s, not ALSModel; cold start",
                type(prev).__name__,
            )
            return None
        prev_rank = int(prev.user_factors.shape[1])
        if prev_rank != int(self.params.rank):
            logger.warning(
                "warm-start: rank mismatch (previous model %d, params %d); "
                "cold start", prev_rank, self.params.rank,
            )
            return None
        prev_dtype = (
            "int8" if prev.user_scales is not None
            else str(prev.user_factors.dtype)
        )
        if prev_dtype != self.params.storage_dtype:
            logger.warning(
                "warm-start: storage dtype mismatch (previous model %s, "
                "params %s); cold start", prev_dtype, self.params.storage_dtype,
            )
            return None

        def align(ids, index, take):
            out = np.full((len(ids), prev_rank), np.nan, np.float32)
            ix = index.index_of(ids)
            m = ix >= 0
            if m.any():
                out[np.flatnonzero(m)] = take(ix[m])
            return out

        U0 = align(td.user_ids, prev.user_index, prev.user_rows)
        V0 = align(
            td.item_ids, prev.item_index,
            lambda ixs: (
                prev.item_factors[ixs].astype(np.float32)
                * prev.item_scales[ixs][:, None]
                if prev.item_scales is not None
                else np.asarray(prev.item_factors[ixs], np.float32)
            ),
        )
        logger.info(
            "warm-start: carrying %d/%d user and %d/%d item factor rows "
            "from previous model",
            int(np.isfinite(U0[:, 0]).sum()), len(td.user_ids),
            int(np.isfinite(V0[:, 0]).sum()), len(td.item_ids),
        )
        return U0, V0

    def _sharded_prepack(self, ctx, prep, data, params):
        """(prepacked, publishable) for the sharded trainer: the cached
        layouts+superstructures on an exact prep-cache hit, else a fresh
        ``prepare_sharded_pack`` built here so it can be published after
        training. Returns (None, None) when the mesh axis can't be
        resolved — train_for_context then packs internally and raises its
        own (better) error."""
        from predictionio_tpu.parallel import als_sharded

        mesh = ctx.mesh
        if "data" in mesh.shape:
            axis = "data"
        elif len(mesh.axis_names) == 1:
            axis = mesh.axis_names[0]
        else:
            return None, None
        shards = int(mesh.shape[axis])
        if prep is not None and prep.active:
            cached = prep.sharded_pack(params, shards, self.params.sharded_mode)
            if cached is not None:
                if prep.status == "hit":
                    return cached, None
                # splice-grade layout reuse: republish the extended pack
                # so the next probe is an exact hit
                return cached, cached
        # shape-stable (pow2-envelope) packing whenever the prep cache is
        # live, so a later small splice keeps these compiled shapes
        fresh = als_sharded.prepare_sharded_pack(
            data, params, shards, self.params.sharded_mode,
            stable_shapes=prep is not None and prep.active,
        )
        return fresh, fresh

    def train_sweep(
        self, ctx: WorkflowContext, td: TrainingData, params_list
    ) -> list[ALSModel] | None:
        """Stacked candidate trainings for evaluation sweeps: ONE bucket
        layout build and ONE vmapped device program train every
        reg/seed/RANK candidate (ops.als.als_train_sweep — differing
        ranks ride the candidate axis via exact zero-padding). Falls
        back (None) when candidates differ in program shape
        (iterations, dtype, bucket widths) or in non-ALS knobs."""
        if len(td.ratings) == 0 or len(params_list) < 2:
            return None
        base = params_list[0]
        ranks_differ = len({p.rank for p in params_list}) > 1
        for p in params_list:
            if (
                p.num_iterations != base.num_iterations
                or p.compute_dtype != base.compute_dtype
                or p.storage_dtype != base.storage_dtype
                or tuple(p.bucket_widths) != tuple(base.bucket_widths)
                or p.sharded_train
                or (ranks_differ and p.lambda_ <= 0)
            ):
                return None
        user_index = BiMap.from_dense(td.user_ids)
        item_index = BiMap.from_dense(td.item_ids)
        data = als_ops.build_ratings_data(
            td.rows,
            td.cols,
            np.asarray(td.ratings, dtype=np.float32),
            len(user_index),
            len(item_index),
            bucket_widths=tuple(base.bucket_widths),
        )
        candidates = [
            als_ops.ALSParams(
                rank=p.rank,
                iterations=p.num_iterations,
                reg=p.lambda_,
                seed=p.seed,
                compute_dtype=p.compute_dtype,
                storage_dtype=p.storage_dtype,
            )
            for p in params_list
        ]
        results = als_ops.als_train_sweep(data, candidates)
        logger.info(
            "ALS sweep: %d candidates trained in one vmapped program "
            "(%d users x %d items, rank %d)",
            len(candidates), len(user_index), len(item_index), base.rank,
        )
        out = []
        for U, V in results:
            uf, us = als_ops.host_factors(U)
            vf, vs = als_ops.host_factors(V)
            out.append(
                ALSModel(
                    user_index=user_index,
                    item_index=item_index,
                    user_factors=uf,
                    item_factors=vf,
                    user_scales=us,
                    item_scales=vs,
                )
            )
        return out

    def warmup_query(self, model: ALSModel) -> Query | None:
        """Deploy-time jit warmup hits the REAL device path: a known
        user (the zero-arg default would take the unseen-user early
        return and compile nothing)."""
        if not len(model.user_index):
            return None
        return Query(user=model.user_index.inverse[0], num=4)

    def predict(self, model: ALSModel, query: Query) -> PredictedResult:
        # delegate to the batch path with a batch of one: a query is
        # answered by the same programs alone and coalesced — the same
        # items in the same order, scores equal to the last bits of f32
        # (a dot's summation order can move with the batch size; a
        # matvec here would differ more)
        return self.batch_predict(model, [(0, query)])[0][1]

    def batch_predict(
        self, model: ALSModel, queries: Sequence[tuple[int, Query]]
    ) -> list[tuple[int, PredictedResult]]:
        """THE scoring path (serving single, serving micro-batched, and
        eval): ONE fused gather+score+top-k device call for all known
        users. The user table is device-resident (``device_factors``),
        so a serving dispatch ships B int32 row indices up, not B
        dequantized f32 vectors — `gather_top_k_batch` dequantizes
        f32/bf16/int8 storage on device.

        Exact or two-stage (a coarse shortlist over the
        storage-precision catalog, then exact f32 rescoring of the
        [B, S] shortlist) is ``ops.retrieval.top_k``'s decision; below
        ``PIO_RETRIEVAL_THRESHOLD`` rows nothing changes, bit for bit.
        ``sharded_serving`` hands ``top_k`` the catalog split over the
        mesh in the table's place: the same decision, on the shards."""
        from predictionio_tpu.ops import retrieval

        # a dispatch's users one by one: a map over a model file's encoded
        # dictionary answers `get` in Python ints, decoding nothing — and
        # with no NumPy call, which inside a server costs a dispatch more
        # than the search (PR 46: `index_of` here read +0.24 ms a batch of 8)
        get = model.user_index.get
        rows = [get(q.user, -1) for _, q in queries]
        known = [(ix, q) for (ix, q), row in zip(queries, rows) if row >= 0]
        out: list[tuple[int, PredictedResult]] = [
            (ix, PredictedResult(itemScores=[]))
            for (ix, _), row in zip(queries, rows)
            if row < 0
        ]
        if known:
            uixs = np.asarray([row for row in rows if row >= 0], dtype=np.int32)
            # power-of-two k: the jitted batch top-k specializes on k,
            # and micro-batched serving would otherwise recompile per
            # distinct max(num) in a batch (results slice to q.num;
            # lax.top_k's prefix is k-invariant, so the slice equals
            # the smaller-k result exactly)
            k = retrieval._pow2(max(int(q.num) for _, q in known))
            num_items = len(model.item_index)
            n0 = int(known[0][1].num)  # the recall probe's row
            if self.params.sharded_serving:  # the catalog is table and
                # coarse copy both; a query's user row is gathered on the host
                users, table, coarse = None, model.sharded_catalog(), None
            else:
                users, table = model.device_factors()
                coarse = model.coarse_catalog
            scores, ids = retrieval.top_k(
                retrieval.UserRows(uixs, users, model.user_rows), table,
                num_items, coarse, k, probe_n=n0,
            )
            inv = model.item_index.inverse
            for row, (ix, q) in enumerate(known):
                out.append(
                    (
                        ix,
                        PredictedResult(
                            itemScores=[
                                ItemScore(item=inv[int(i)], score=float(s))
                                for s, i in zip(
                                    scores[row, : q.num], ids[row, : q.num]
                                )
                                if int(i) >= 0
                            ]
                        ),
                    )
                )
        return out

    def eval_topk(
        self, model: ALSModel, queries: Sequence[Query], k: int
    ) -> EvalTopK | None:
        """Device-resident eval scoring (core/fast_eval.py eval_device):
        ONE batched top-k over every known user in the eval split; the
        padded [Q, K] id matrix never becomes Python result objects.

        Parity with the per-query path is structural: the same scorer
        ranks the same user rows (lax.top_k's prefix is k-invariant, so
        a smaller k here equals the sliced pow2-k `batch_predict` rows),
        unknown users keep all -1 (empty-prediction) rows, and each row
        is capped to its query's ``num`` exactly like ``predict``
        truncates its result list.
        """
        from predictionio_tpu.ops.topk import top_k_items_batch

        num_items = len(model.item_index)
        if num_items == 0:
            return None
        kr = max(1, min(int(k), num_items))
        qn = len(queries)
        ids = np.full((qn, kr), -1, dtype=np.int32)
        scores = np.zeros((qn, kr), dtype=np.float32)
        rows = model.user_index.index_of([q.user for q in queries])
        known = np.flatnonzero(rows >= 0)
        if len(known):
            uixs = rows[known].astype(np.int32)
            if self.params.sharded_serving:
                s, i = model.sharded_catalog().exact_top_k(
                    model.user_rows(uixs), kr
                )
            else:
                _, V = model.device_factors()
                s, i = top_k_items_batch(model.user_rows(uixs), V, k=kr)
            ids[known] = np.asarray(i, dtype=np.int32)
            scores[known] = np.asarray(s, dtype=np.float32)
        # cap each row to the query's requested result count, mirroring
        # the per-query path's slice to q.num before metrics see it
        nums = np.fromiter((int(q.num) for q in queries), dtype=np.int64, count=qn)
        over = np.arange(kr)[None, :] >= nums[:, None]
        ids[over] = -1
        scores[over] = 0.0
        return EvalTopK(ids=ids, scores=scores, index=model.item_index)


def engine() -> Engine:
    """EngineFactory (reference RecommendationEngine object,
    examples/scala-parallel-recommendation/custom-prepartor/src/main/scala/
    Engine.scala)."""
    return Engine(
        datasource_classes=RecommendationDataSource,
        preparator_classes=RecommendationPreparator,
        algorithm_classes={"als": ALSAlgorithm},
        serving_classes=FirstServing,
    )
