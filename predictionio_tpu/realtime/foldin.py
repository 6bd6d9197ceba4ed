"""Incremental ALS fold-in: solve touched user rows against fixed items.

The ALX observation (arxiv 2112.02194): one ALS half-step already solves
every user row in closed form against the current item factors, and that
per-row least-squares is exactly the "fold a new/changed user in without
retraining" primitive. This module reuses the same jitted Gramian +
batched Cholesky path (:func:`ops.als.solve_bucket_explicit`, f32 solve
regardless of storage dtype) on the batch of users touched by tailed
rating events:

- each touched user's FULL rating history is re-read from the event
  store (the new events are already ingested there), so the solve is
  the exact half-step the next retrain would take for that row;
- item factors stay fixed — int8 tables are dequantized at gather time
  on device, exactly like training;
- solved rows are written back in the model's storage dtype: f32/bf16
  cast, or int8 requantized with a fresh per-row scale
  (:func:`ops.als.quantize_rows` semantics);
- brand-new users are appended to the factor table and the id index
  (``BiMap.appended``: the new ids' cost, not the index's);
- events naming items unseen at train time can't be solved against (no
  factor row) — they accumulate in ``cold_items`` (count + rating sum)
  as cold-start stats for the next retrain to pick up.

The patched model SHARES the item side with the old model — the host
arrays AND what is resident of them on the device (the exact table, the
coarse catalog, a sharded catalog: ``ALSModel.patched``) — and the fold
gathers its rows from that same resident table: a patch stages, builds
and copies nothing item-side. The solved rows go up as [B, D] (+ [B]
scales) and are written into the resident user table by one small jitted
update (``retrieval.patch_rows``) that leaves the old table whole for a
query in flight; the table has room to grow
(``ALSModel.reserve_user_rows``), so an appended user changes no shape.
Served state is never mutated — the server swap is a pointer flip under
its lock.
"""

from __future__ import annotations

import dataclasses
import functools
import logging

import jax
import jax.numpy as jnp
import numpy as np

from predictionio_tpu.data.event import Event
from predictionio_tpu.models.recommendation import ALSModel
from predictionio_tpu.obs import device as obs_device
from predictionio_tpu.obs import metrics as obs_metrics
from predictionio_tpu.obs import trace as obs_trace
from predictionio_tpu.ops import als as als_ops
from predictionio_tpu.ops import retrieval

logger = logging.getLogger(__name__)

_m_rows = obs_metrics.counter(
    "pio_foldin_rows_patched_total",
    "User rows a fold-in solved and wrote into the resident user table",
)
_m_added = obs_metrics.counter(
    "pio_foldin_users_added_total",
    "Users a fold-in appended (an id the model did not hold)",
)
_m_h2d = obs_metrics.counter(
    "pio_foldin_patch_h2d_bytes_total",
    "Bytes a fold-in sent up to patch the resident user table: the "
    "solved rows, their scales and their indices",
)


@obs_device.track_jit("foldin.solve")
@functools.partial(jax.jit, static_argnames=("weighted_reg",))
def _solve_rows(table, col_ids, ratings, mask, reg, weighted_reg: bool):
    """The fold's one solve program, under the fold's own name so that a
    device trace tells it from a training half-step: the rows of
    ``col_ids`` [B, K] gathered from the resident ``table`` (an int8 pair
    dequantized there), then per user the explicit-feedback half-step
    ``x = (V^T V + lam I)^-1 V^T r``, lam = reg * n under ``weighted_reg``.

    A user with fewer ratings than the rank (K < D: nearly every fold) is
    solved in the K x K form of the same equations,
    ``x = V^T (V V^T + lam I)^-1 r``: V^T V then has D - n eigenvalues of
    nothing but lam, and an f32 Cholesky of it loses a digit for every
    factor of ten between lam and |v|^2, which a row stored int8 shows at
    once (the scale is the row's largest value); V V^T + lam I has no
    such eigenvalue. On a TPU v5e, 16 users of 1 to 32 ratings at rank 64
    and lam = 0.05 n, against float64: 4.2e-7 of the row's largest value
    so, 9.0e-6 in the D x D form (PERF.md section 6, PR 45). K >= D is
    solved D x D, ``ops/als.py solve_bucket_explicit``'s arithmetic.

    Precision: every product of f32 operands here states HIGHEST, as
    ``_gramian_rhs`` does for its own and XLA's Cholesky and
    triangular-solve expanders for theirs. Left to the default a TPU runs
    them in bf16 passes: the same 16 rows then read 0.13 of their largest
    value off, and 596 of their 1,024 int8 codes differ (same run)."""
    with jax.named_scope("foldin.solve"):
        f32, hi = jnp.float32, jax.lax.Precision.HIGHEST
        # the rescore's gather: in place through the [rows, D/32, 32]
        # view, where a plain gather has the whole table re-laid a call
        # (6.2 GB of temporaries for the int8 catalog, compiled for a v5e)
        v = retrieval._table_rows(table, col_ids) * mask[:, :, None]
        r = (ratings * mask).astype(f32)
        n = mask.sum(axis=1)
        lam = reg * (n if weighted_reg else jnp.ones_like(n))
        lam = jnp.where(n > 0, lam, 1.0)  # a padded user: x = 0
        k, d = col_ids.shape[1], v.shape[2]
        if k >= d:  # the D x D form, as ``solve_bucket_explicit``
            a, b = als_ops._gramian_rhs(v, mask.astype(f32), r)
            a = a + lam[:, None, None] * jnp.eye(d, dtype=f32)
            return als_ops._psd_solve(a, b)
        g = jnp.einsum("bkd,bjd->bkj", v, v, precision=hi,
                       preferred_element_type=f32)
        g = g + lam[:, None, None] * jnp.eye(k, dtype=f32)
        alpha = als_ops._psd_solve(g, r)
        return jnp.einsum("bk,bkd->bd", alpha, v, precision=hi,
                          preferred_element_type=f32)


@dataclasses.dataclass(frozen=True)
class FoldInConfig:
    """Rating-extraction + solve parameters; must match the deployed
    engine's datasource/algorithm params so the fold-in solves the same
    problem the batch trainer does (SpeedLayer derives one from the
    server's EngineParams)."""

    event_names: tuple[str, ...] = ("rate", "buy")
    rating_key: str | None = "rating"
    default_ratings: dict | None = None
    override_ratings: dict | None = None
    entity_type: str = "user"
    target_entity_type: str = "item"
    reg: float = 0.01
    weighted_reg: bool = True


@dataclasses.dataclass
class FoldInStats:
    """What one fold() call did."""

    events: int = 0
    rating_events: int = 0
    users_touched: int = 0
    users_added: int = 0
    users_skipped: int = 0  # touched but no trainable pairs
    cold_item_events: int = 0


def _pow2(n: int, floor: int = 1) -> int:
    out = floor
    while out < n:
        out *= 2
    return out


class ALSFoldIn:
    """Folds batches of rating events into an ALSModel's user table."""

    def __init__(
        self,
        events,
        app_id: int,
        channel_id: int | None = None,
        config: FoldInConfig | None = None,
    ):
        self._events = events
        self._app_id = app_id
        self._channel_id = channel_id
        self.config = config or FoldInConfig()
        # item id -> [event count, rating sum]; unseen-at-train items
        self.cold_items: dict[str, list] = {}

    # -- rating extraction (mirrors base.Events.scan_ratings) ---------------

    def _rating_of(self, e: Event) -> float | None:
        cfg = self.config
        if e.event not in cfg.event_names:
            return None
        if e.entity_type != cfg.entity_type:
            return None
        if e.target_entity_type != cfg.target_entity_type:
            return None
        if e.target_entity_id is None:
            return None
        v = (cfg.override_ratings or {}).get(e.event)
        if v is None:
            v = (
                e.properties.to_dict().get(cfg.rating_key)
                if cfg.rating_key is not None
                else None
            )
            if not isinstance(v, (int, float)) or isinstance(v, bool):
                v = (cfg.default_ratings or {}).get(e.event)
        if v is None:
            return None
        return float(v)

    # -- history reads ------------------------------------------------------

    def _histories(self, touched: list[str]) -> dict[str, list[Event]]:
        """Full rating-event history per touched user, including the
        events that triggered this fold (they are already ingested)."""
        cfg = self.config
        out: dict[str, list[Event]] = {u: [] for u in touched}
        if getattr(self._events, "entity_indexed", False):
            for uid in touched:
                out[uid] = self._events.find(
                    self._app_id,
                    self._channel_id,
                    entity_type=cfg.entity_type,
                    entity_id=uid,
                    event_names=list(cfg.event_names),
                    target_entity_type=cfg.target_entity_type,
                )
            return out
        # replay backends: one bulk scan amortizes across the batch
        touched_set = set(touched)
        for e in self._events.find(
            self._app_id,
            self._channel_id,
            entity_type=cfg.entity_type,
            event_names=list(cfg.event_names),
            target_entity_type=cfg.target_entity_type,
        ):
            if e.entity_id in touched_set:
                out[e.entity_id].append(e)
        return out

    # -- the fold -----------------------------------------------------------

    def fold(
        self, model: ALSModel, events: list[Event]
    ) -> tuple[ALSModel | None, FoldInStats]:
        """Fold a batch of tailed events into ``model``.

        Returns ``(patched_model, stats)`` — patched_model is ``None``
        when the batch contained nothing foldable (stats says why). The
        input model is never mutated."""
        stats = FoldInStats(events=len(events))
        touched: list[str] = []
        touched_set: set[str] = set()
        self._collect_events(model, events, stats, touched, touched_set)
        if not touched:
            return None, stats
        return self._fold_touched(model, touched, stats)

    def fold_in_columnar(
        self, model: ALSModel, batch
    ) -> tuple[ALSModel | None, FoldInStats]:
        """Fold one :class:`~realtime.tailer.TailedBatch` — columnar
        array segments and object-path Event segments, in delivery
        order — without constructing an Event for any columnar row.

        The columnar rows were already shape-classified by the decoder
        (``colspans.decode_tail`` keeps exactly what :meth:`_rating_of`
        would accept), so collection reduces to touched-user and
        cold-item accumulation over arrays; the solve and patch are the
        same jitted path :meth:`fold` takes, hence bit-identical
        results across f32/bf16/int8 storage."""
        stats = FoldInStats(events=batch.n_events)
        touched: list[str] = []
        touched_set: set[str] = set()
        for seg in batch.segments:
            if isinstance(seg, list):
                self._collect_events(model, seg, stats, touched, touched_set)
            else:
                self._collect_columnar(model, seg, stats, touched, touched_set)
        if not touched:
            return None, stats
        return self._fold_touched(model, touched, stats)

    def _collect_events(
        self, model, events, stats, touched, touched_set
    ) -> None:
        rated = [(e, v) for e in events if (v := self._rating_of(e)) is not None]
        # one look-up for the batch: ``index_of`` never decodes a
        # catalog's id dictionary for a handful of ids
        known = model.item_index.index_of(
            [e.target_entity_id for e, _ in rated]
        ) >= 0
        for (e, v), held in zip(rated, known.tolist()):
            stats.rating_events += 1
            if not held:
                acc = self.cold_items.setdefault(e.target_entity_id, [0, 0.0])
                acc[0] += 1
                acc[1] += v
                stats.cold_item_events += 1
            if e.entity_id not in touched_set:
                touched_set.add(e.entity_id)
                touched.append(e.entity_id)

    def _collect_columnar(
        self, model, tail, stats, touched, touched_set
    ) -> None:
        n = tail.n_rows
        if n == 0:
            return
        stats.rating_events += n
        # cold-item accumulation, vectorized per distinct item (the
        # per-event loop's counts/sums, bincount-shaped)
        counts = np.bincount(tail.item_idx, minlength=len(tail.item_ids))
        sums = np.bincount(
            tail.item_idx, weights=tail.ratings,
            minlength=len(tail.item_ids),
        )
        known = model.item_index.index_of(tail.item_ids) >= 0
        for j, iid in enumerate(tail.item_ids):
            if known[j]:
                continue
            acc = self.cold_items.setdefault(iid, [0, 0.0])
            acc[0] += int(counts[j])
            acc[1] += float(sums[j])
            stats.cold_item_events += int(counts[j])
        for uid in tail.user_ids:  # first-appearance order, like events
            if uid not in touched_set:
                touched_set.add(uid)
                touched.append(uid)

    def _fold_touched(
        self, model: ALSModel, touched: list[str], stats: FoldInStats
    ) -> tuple[ALSModel | None, FoldInStats]:
        """History re-read + solve + patch for the touched users (the
        shared tail of :meth:`fold` and :meth:`fold_in_columnar` — the
        solve is exact against full histories, so results can't depend
        on which decode path delivered the triggering events)."""
        with obs_trace.region("foldin.history_read"):
            histories = self._histories(touched)
        item_ids = list({
            e.target_entity_id for evs in histories.values() for e in evs
        })
        row_of = dict(zip(item_ids, model.item_index.index_of(item_ids).tolist()))
        users: list[str] = []
        pairs: list[list[tuple[int, float]]] = []
        for uid in touched:
            seen: dict[int, float] = {}
            for e in histories.get(uid, ()):
                v = self._rating_of(e)
                if v is None:
                    continue
                ix = row_of[e.target_entity_id]
                if ix < 0:
                    continue  # cold item: no factor row to solve against
                seen[ix] = v  # replay order: last write wins
            if not seen:
                stats.users_skipped += 1
                continue
            users.append(uid)
            pairs.append(list(seen.items()))
        stats.users_touched = len(users)
        if not users:
            return None, stats

        with obs_trace.region("foldin.solve"):  # launch to read
            solved = self._solve(model, pairs)
        with obs_trace.region("foldin.patch_rows"):
            patched = self._patch(model, users, solved, stats)
        return patched, stats

    def _solve(self, model: ALSModel, pairs) -> np.ndarray:
        """Closed-form f32 solve of the touched rows against the item
        table the server serves (``model.device_factors()``: no copy of
        the fold's own), padded to stable (B, K) program shapes — B a
        power of two from 8, K from 8 — so repeat folds reuse the jit
        cache."""
        B = _pow2(len(pairs), floor=8)
        K = _pow2(max(len(p) for p in pairs), floor=8)
        col_ids = np.zeros((B, K), dtype=np.int32)
        ratings = np.zeros((B, K), dtype=np.float32)
        mask = np.zeros((B, K), dtype=np.float32)
        for i, p in enumerate(pairs):
            ixs, vals = zip(*p)
            col_ids[i, : len(p)] = ixs
            ratings[i, : len(p)] = vals
            mask[i, : len(p)] = 1.0
        x = _solve_rows(
            model.device_factors()[1], col_ids, ratings, mask,
            reg=self.config.reg, weighted_reg=self.config.weighted_reg,
        )
        return np.asarray(x)[: len(pairs)]

    def _patch(
        self,
        model: ALSModel,
        users: list[str],
        solved: np.ndarray,
        stats: FoldInStats,
    ) -> ALSModel:
        """New ALSModel with the solved rows written back (appending
        brand-new users): the host tables copied with the rows in, the
        resident user table patched where it lies, the item side shared
        — nothing of ``model`` is mutated."""
        held = model.user_index.index_of(users) >= 0
        new_ids = [u for u, h in zip(users, held.tolist()) if not h]
        stats.users_added = len(new_ids)
        user_index = (
            model.user_index.appended(new_ids) if new_ids else model.user_index
        )
        ixs = user_index.index_of(users).astype(np.int32)
        uf = model.user_factors
        scales = rows_s = None
        if model.user_scales is not None:
            # int8 storage: requantize each solved row with a fresh
            # per-row scale (quantize_rows semantics, host-side)
            rows_s = (np.max(np.abs(solved), axis=1) / 127.0).astype(np.float32)
            rows_s[rows_s <= 0] = 1.0
            rows = np.round(solved / rows_s[:, None]).astype(np.int8)
            scales = np.concatenate([
                model.user_scales,
                np.ones(len(new_ids), dtype=model.user_scales.dtype),
            ])
            scales[ixs] = rows_s
        else:
            rows = solved.astype(uf.dtype)
        values = np.concatenate(
            [uf, np.zeros((len(new_ids), uf.shape[1]), dtype=uf.dtype)]
        )
        values[ixs] = rows

        # the resident table: the rows and their indices go up, padded to
        # the solve's B with copies of the first (the same row written
        # twice), and one small program writes them in
        if len(user_index) > model.user_capacity():
            model.reserve_user_rows(len(user_index))
            obs_device.count_restage("users")
        pad = _pow2(len(users), floor=8) - len(users)

        def up(a):
            return np.concatenate([a, np.repeat(a[:1], pad, axis=0)])

        sent = (up(ixs), up(rows)) + (() if rows_s is None else (up(rows_s),))
        nbytes = sum(a.nbytes for a in sent)
        with obs_device.transfer("h2d", "serve.model_patch", nbytes):
            on_device = jax.device_put(sent)
        users_dev = retrieval.patch_rows(
            model.device_factors()[0], on_device[0],
            on_device[1] if rows_s is None else on_device[1:],
        )
        retrieval.set_resident(users=users_dev)
        patched = model.patched(user_index, values, scales, users_dev)
        patched.patch_h2d_bytes = nbytes
        _m_rows.inc(len(users))
        _m_added.inc(len(new_ids))
        _m_h2d.inc(patched.patch_h2d_bytes)
        return patched

    def cold_start_stats(self) -> dict[str, dict]:
        """Accumulated unseen-item stats: id -> {events, mean_rating}."""
        return {
            iid: {"events": c, "mean_rating": s / c if c else 0.0}
            for iid, (c, s) in self.cold_items.items()
        }
