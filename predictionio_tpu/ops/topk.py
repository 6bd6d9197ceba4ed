"""On-device scoring + top-k for serving.

The deployed engine scores ``user_vector @ V^T`` on-device and takes the
top-k (reference predict path: MatrixFactorizationModel.recommendProducts
invoked from examples/.../ALSAlgorithm.scala:88 — an RDD job per query in
the reference; a single fused device op here). Supports exclusion of
already-seen / blacklisted items via score masking (the e-commerce
template's business rules, examples/scala-parallel-ecommercerecommendation/
weighted-items/src/main/scala/ALSAlgorithm.scala:234-265).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from predictionio_tpu.obs import device as obs_device

NEG_INF = -1e30


def catalog_rows(item_factors) -> int:
    """Row count of a factor table in either representation: a dense
    [I, D] array, or the int8 (values [I, D], per-row f32 scales [I])
    pair of ``storage_dtype="int8"`` (ops/als.py quantize_rows)."""
    table = item_factors[0] if isinstance(item_factors, tuple) else item_factors
    return table.shape[0]


@obs_device.track_jit("topk.top_k_items")
@functools.partial(jax.jit, static_argnames=("k",))
def top_k_items(user_vector, item_factors, k: int, exclude_mask=None):
    """Scores one user vector against all items; returns (scores, ids).

    ``item_factors`` is a dense [I, D] array or the int8 (values,
    scales) pair — quantized catalogs score inside this jitted program
    (the deployed blob stays 4x smaller than f32 end to end; the per-row
    scale factors out of the dot product, so the dense f32 catalog is
    never materialized).

    ``exclude_mask``: optional [num_items] bool/0-1 array; masked items
    can never appear in the result.
    """
    # f32 scores regardless of factor storage dtype (bf16/int8-stored
    # factors still rank and report at full accumulation precision)
    if isinstance(item_factors, tuple):
        q, s = item_factors
        scores = (
            jnp.matmul(
                q, user_vector.astype(jnp.float32),
                preferred_element_type=jnp.float32,
            )
            * s
        )  # [I]
    else:
        scores = jnp.matmul(
            item_factors, user_vector, preferred_element_type=jnp.float32
        )  # [I]
    if exclude_mask is not None:
        scores = jnp.where(exclude_mask.astype(bool), NEG_INF, scores)
    k = min(k, catalog_rows(item_factors))
    return jax.lax.top_k(scores, k)


@obs_device.track_jit("topk.top_k_items_batch")
@functools.partial(jax.jit, static_argnames=("k",))
def top_k_items_batch(user_vectors, item_factors, k: int, exclude_mask=None):
    """Batched variant: [B, D] user vectors -> ([B, k] scores, [B, k] ids)."""
    if isinstance(item_factors, tuple):
        q, s = item_factors
        scores = (
            jnp.matmul(
                user_vectors.astype(jnp.float32), q.T,
                preferred_element_type=jnp.float32,
            )
            * s[None, :]
        )  # [B, I]
    else:
        scores = jnp.matmul(
            user_vectors, item_factors.T, preferred_element_type=jnp.float32
        )  # [B, I]
    if exclude_mask is not None:
        scores = jnp.where(exclude_mask.astype(bool)[None, :], NEG_INF, scores)
    k = min(k, catalog_rows(item_factors))
    return jax.lax.top_k(scores, k)


class Rules(NamedTuple):
    """Serve-time business rules as device arguments (the e-commerce
    template builds them, models/ecommerce.py): the catalog-wide rules
    as resident vectors over the P >= I stored rows, the per-query rules
    as short padded index lists — never a dense [I] mask per query.

    ``avail`` [P] uint8: 0 = the row may not be served (unavailable, or
    padding past the catalog); ``cats``: W vectors [P] int32, the row's
    w-th category id (-1 = none); ``qcat`` [B, C] int32: the categories
    a query is restricted to (-2 pads) and ``has_cat`` [B] bool whether
    it is restricted at all; ``ex`` [B, E] int32: rows excluded for this
    query alone (seen, blackList; -1 pads)."""

    avail: jax.Array
    cats: tuple
    qcat: jax.Array
    has_cat: jax.Array
    ex: jax.Array


def rows_allowed(av, cs, hit, qcat, has_cat):
    """[B, S] bool — may each of S rows be served to each of B queries?
    ``av`` / ``cs`` are the rows' ``Rules.avail`` / ``Rules.cats``
    entries ([S], or [B, S] where every query has rows of its own),
    ``hit`` [B, S] marks the rows on the query's own exclusion list."""
    if av.ndim == 1:
        av, cs = av[None], tuple(c[None] for c in cs)
    in_cat = jnp.zeros(hit.shape, bool)
    for c in cs:
        in_cat = in_cat | (c[..., None] == qcat[:, None, :]).any(-1)
    return (av != 0) & ~hit & (in_cat | ~has_cat[:, None])


def _f32_scores(query_vectors, item_factors):
    """[B, I] f32 products of [B, D] query vectors with every catalog
    row (dense, or the int8 pair), f32 on every backend
    (``precision=HIGHEST``, as ops/als.py's solves: a TPU's default
    rounds f32 operands to bf16, 1.4e-2 off on unit-variance scores)."""
    hi = jax.lax.Precision.HIGHEST
    if isinstance(item_factors, tuple):
        q, s = item_factors
        return (
            jnp.matmul(
                query_vectors.astype(jnp.float32), q.T.astype(jnp.float32),
                precision=hi, preferred_element_type=jnp.float32,
            )
            * s[None, :]
        )
    return jnp.matmul(
        query_vectors.astype(jnp.float32),
        item_factors.astype(jnp.float32).T,
        precision=hi, preferred_element_type=jnp.float32,
    )


def _top_k_allowed(scores, rules: Rules, k: int):
    """Top-k of [B, I] ``scores`` over the rows ``rules`` allow each
    query: the mask is built on the device from the resident vectors
    and the queries' index lists and applied before the top-k, so k
    carries no headroom for exclusions. A query with fewer than k
    allowed rows reports id -1 in the slots it cannot fill."""
    B, n = scores.shape
    ex = jnp.where(rules.ex >= 0, rules.ex, n)  # pads fall off the end
    hit = jnp.zeros((B, n), bool).at[
        jnp.arange(B)[:, None], ex
    ].set(True, mode="drop")
    ok = rows_allowed(
        rules.avail[:n], tuple(c[:n] for c in rules.cats), hit,
        rules.qcat, rules.has_cat,
    )
    s, ids = jax.lax.top_k(jnp.where(ok, scores, NEG_INF), min(k, n))
    return s, jnp.where(s > NEG_INF / 2, ids, -1)


@obs_device.track_jit("topk.top_k_items_batch_masked")
@functools.partial(jax.jit, static_argnames=("k",))
def top_k_items_batch_masked(user_vectors, item_factors, rules: Rules, k: int):
    """``top_k_items_batch`` under ``Rules`` (``_top_k_allowed``), its
    products f32 on every backend (``_f32_scores``)."""
    return _top_k_allowed(_f32_scores(user_vectors, item_factors), rules, k)


@obs_device.track_jit("topk.gather_top_k_batch")
@functools.partial(jax.jit, static_argnames=("k",))
def gather_top_k_batch(user_ixs, user_factors, item_factors, k: int,
                       exclude_mask=None):
    """Fused gather + batched top-k: the serving batch fast path.

    ``user_ixs`` ([B] int32) select rows from the device-RESIDENT user
    table ``user_factors`` (dense [U, D] array or int8 (values, scales)
    pair); the gathered vectors are dequantized on device and scored
    like ``top_k_items_batch``. Host-to-device traffic per dispatch is
    B int32s instead of B*D floats — the user table went up once at
    deploy.

    Dequantization (``values.astype(f32) * scales[:, None]``) is
    elementwise-exact, i.e. bitwise-identical to the host-side
    ``ALSModel.user_rows`` dequant, and the matmul rows of a batched
    score are invariant to the batch size — so a batch-of-1 through
    this op byte-matches any batchmate's row in a larger batch (the
    property the batched/unbatched response-parity tests pin down)."""
    ixs = user_ixs.astype(jnp.int32)
    if isinstance(user_factors, tuple):
        uq, us = user_factors
        user_vectors = uq[ixs].astype(jnp.float32) * us[ixs][:, None]
    else:
        user_vectors = user_factors[ixs].astype(jnp.float32)
    if isinstance(item_factors, tuple):
        q, s = item_factors
        scores = (
            jnp.matmul(
                user_vectors, q.T.astype(jnp.float32),
                preferred_element_type=jnp.float32,
            )
            * s[None, :]
        )  # [B, I]
    else:
        scores = jnp.matmul(
            user_vectors, item_factors.astype(jnp.float32).T,
            preferred_element_type=jnp.float32,
        )  # [B, I]
    if exclude_mask is not None:
        scores = jnp.where(exclude_mask.astype(bool)[None, :], NEG_INF, scores)
    k = min(k, catalog_rows(item_factors))
    return jax.lax.top_k(scores, k)


@obs_device.track_jit("topk.sum_rows_top_k_batch_masked")
@functools.partial(jax.jit, static_argnames=("k",))
def sum_rows_top_k_batch_masked(row_ixs, row_weights, item_factors,
                                rules: Rules, k: int):
    """Fused multi-row gather-sum + batched top-k under ``Rules`` for
    the cosine-family templates (similarproduct, recommendeduser), whose
    query vector is the SUM of several catalog rows and whose every
    query excludes at least its own rows. What their recall probe and
    their catalogs under the retrieval threshold are scored by.

    ``row_ixs``: [B, L] int32 rows of ``item_factors`` (dense [I, D]
    row-normalized array, or the int8 (values, scales) pair whose
    dequantized rows are the normalized catalog — models/filters.py
    ``normalized_device_factors``; quantized cosine catalogs stay int8
    on device, 4x smaller than the dense form) to sum per query,
    right-padded to a shared static L;
    ``row_weights``: [B, L] f32, 1.0 for real rows and 0.0 for padding
    (adding an exactly-zero vector never perturbs the f32 sum, so rows
    are bitwise-invariant across padded widths).
    The products are f32 on every backend (``_f32_scores``), the rules
    applied before the top-k (``_top_k_allowed``). Returns ([B, k]
    scores, [B, k] ids, -1 where fewer than k rows are allowed)."""
    ixs = row_ixs.astype(jnp.int32)
    if isinstance(item_factors, tuple):
        vq, vs = item_factors
        rows = vq[ixs].astype(jnp.float32) * vs[ixs][..., None]  # [B, L, D]
    else:
        rows = item_factors[ixs].astype(jnp.float32)
    qvecs = jnp.sum(rows * row_weights[..., None], axis=1)  # [B, D]
    return _top_k_allowed(_f32_scores(qvecs, item_factors), rules, k)


@obs_device.track_jit("topk.ranking_metrics_batch")
@functools.partial(jax.jit, static_argnames=("k",))
def ranking_metrics_batch(pred_ids, actual_sorted, actual_counts, k: int):
    """Vectorized P@K / AP@K / NDCG@K over a padded top-k id matrix.

    The evaluation fast path's metric kernel (core/fast_eval.py
    eval_device): one call scores EVERY eval query of a candidate,
    replacing the per-query Python set-membership loops in
    core/ranking.py. Membership is a sorted lookup per rank position
    (searchsorted), hit prefix sums give the precision-at-hit terms.

    ``pred_ids``: [Q, P] int32 ranked predicted ids, P <= k; -1 marks an
    empty slot (shorter result rows, unseen users).
    ``actual_sorted``: [Q, A] int32 relevant ids per query, sorted
    ascending and padded with int32-max; relevant ids that are OUTSIDE
    the prediction id space are encoded as distinct codes <= -2 so they
    count toward |actual| (AP normalization, IDCG) but can never match.
    ``actual_counts``: [Q] int32 true |actual| per query.
    ``k``: static metric cutoff — denominators use it even when P < k.

    Returns ``(precision, ap, ndcg, valid)`` with shape [Q]; ``valid`` is
    False where the actual set is empty (the Option-skip rows — metric
    semantics in core/ranking.py say those queries score None).
    """
    pred = jnp.asarray(pred_ids, dtype=jnp.int32)
    actual = jnp.asarray(actual_sorted, dtype=jnp.int32)
    counts = jnp.asarray(actual_counts, dtype=jnp.int32)
    pn = pred.shape[1]

    def row_hits(p_row, a_row, count):
        pos = jnp.searchsorted(a_row, p_row)
        clipped = jnp.clip(pos, 0, a_row.shape[0] - 1)
        return (pos < count) & (a_row[clipped] == p_row) & (p_row >= 0)

    hits = jax.vmap(row_hits)(pred, actual, counts).astype(jnp.float32)

    precision = hits.sum(axis=1) / float(k)

    ranks = jnp.arange(1, pn + 1, dtype=jnp.float32)
    ap_terms = jnp.where(hits > 0, jnp.cumsum(hits, axis=1) / ranks, 0.0)
    ap_norm = jnp.maximum(jnp.minimum(float(k), counts.astype(jnp.float32)), 1.0)
    ap = ap_terms.sum(axis=1) / ap_norm

    discounts = 1.0 / jnp.log2(jnp.arange(2, pn + 2, dtype=jnp.float32))
    dcg = (hits * discounts).sum(axis=1)
    # IDCG over min(k, |actual|) ideal hits; |actual| may exceed P, so
    # the prefix table spans the full k, not just the prediction width
    idcg_prefix = jnp.cumsum(1.0 / jnp.log2(jnp.arange(2, k + 2, dtype=jnp.float32)))
    ideal_n = jnp.clip(jnp.minimum(counts, k), 1, k)
    ndcg = dcg / idcg_prefix[ideal_n - 1]

    return precision, ap, ndcg, counts > 0


@obs_device.track_jit("topk.catalog_norms")
@jax.jit
def catalog_norms(item_factors):
    """Per-row L2 norms of a catalog's STORED values ([I] f32) — the
    quantity ``top_k_similar`` needs per call. Compute once at model
    build/load, keep device-resident, and pass as its ``norms`` argument
    (the cosine-family models cache this next to their factor tables)."""
    if isinstance(item_factors, tuple):
        f32 = item_factors[0].astype(jnp.float32)
    else:
        f32 = item_factors.astype(jnp.float32)
    return jnp.linalg.norm(f32, axis=1)


@obs_device.track_jit("topk.top_k_similar")
@functools.partial(jax.jit, static_argnames=("k",))
def top_k_similar(item_vector, item_factors, k: int, exclude_mask=None,
                  norms=None):
    """Cosine item-item similarity top-k (similarproduct template's scoring,
    examples/scala-parallel-similarproduct/multi/src/main/scala/
    ALSAlgorithm.scala:147,193,244).

    ``norms``: optional precomputed ``catalog_norms(item_factors)`` —
    without it every call re-reduces the whole [I, D] catalog just to
    normalize scores."""
    if isinstance(item_factors, tuple):
        # cosine is scale-invariant per row, so the per-row scale drops
        # out entirely: normalize the int8 values directly
        f32 = item_factors[0].astype(jnp.float32)
    else:
        f32 = item_factors.astype(jnp.float32)
    v32 = item_vector.astype(jnp.float32)
    if norms is None:
        norms = jnp.linalg.norm(f32, axis=1)
    denom = norms * jnp.linalg.norm(v32)
    scores = (f32 @ v32) / jnp.maximum(denom, 1e-12)
    if exclude_mask is not None:
        scores = jnp.where(exclude_mask.astype(bool), NEG_INF, scores)
    k = min(k, catalog_rows(item_factors))
    return jax.lax.top_k(scores, k)
