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
from typing import Callable, Iterable, Sequence

import numpy as np

from predictionio_tpu.data.bimap import BiMap

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


def entity_exclusion_mask(
    index: BiMap,
    self_entities: Iterable[str],
    white_list: Sequence[str] | None,
    black_list: Sequence[str] | None,
) -> np.ndarray:
    """[len(index)] bool mask; True = candidate may never be returned."""
    n = len(index)
    mask = np.zeros(n, dtype=bool)
    for ent in self_entities:
        if ent in index:
            mask[index[ent]] = True
    if white_list is not None:
        allowed = {index[e] for e in white_list if e in index}
        mask |= ~np.isin(np.arange(n), list(allowed))
    if black_list:
        for ent in black_list:
            if ent in index:
                mask[index[ent]] = True
    return mask


class CosineCatalog:
    """The serving caches of a model whose catalog is scored by cosine.
    The model (a dataclass) names its host factor array and its int8
    scale vector in ``_catalog_fields``."""

    _catalog_fields: tuple[str, str]

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

    def __getstate__(self):
        state = self.__dict__.copy()
        state["_device"] = None
        state["_norms"] = None
        state["_coarse"] = None
        return state


def score_similar_batch(
    model: CosineCatalog,
    index: BiMap,
    queries: Sequence,
    entities: Callable,
    dense_mask: Callable,
    result: Callable,
) -> list:
    """Score a micro-batch of "similar to these" queries against a
    ``CosineCatalog``: ``entities(q)`` are a query's own ids in
    ``index``, ``result(pairs)`` builds the template's answer from
    ``[(id, score), ...]``.

    Two filter regimes:

    - SIMPLE (``dense_mask(q)`` is None): the excluded set is small and
      enumerable host-side (the query's own entities plus any
      ``blackList`` hits), so instead of shipping a [rows] mask per
      query the batch requests top-(num + |excluded|) with NO mask and
      drops excluded ids from the returned prefix — identical results
      (masking sinks excluded entries without perturbing the others,
      and ``lax.top_k`` prefixes are k-invariant), zero mask traffic,
      one shared device call for every simple query in the batch.
    - DENSE (``dense_mask(q)`` is the query's [rows] bool exclusion
      mask: a ``whiteList`` or a category filter can cover most of the
      catalog, so headroom-k is unbounded): masked exact scoring, one
      call each, through the same fused op.

    How a call is scored — exact or shortlist + rescore — is
    ``ops.retrieval.top_k``'s decision. Single-query ``predict``
    delegates here with a batch of one, so a query is answered by the
    same programs alone and coalesced: the same entities in the same
    order, scores equal to the last bits of f32 (a dot's summation
    order can move with the batch size; tests hold them to 2e-6)."""
    import jax.numpy as jnp

    from predictionio_tpu.ops import retrieval

    inv = index.inverse
    results: list = [None] * len(queries)
    simple: list[tuple[int, list[int], set[int], int]] = []
    dense: list[tuple[int, list[int], np.ndarray, int]] = []
    for qi, q in enumerate(queries):
        known = [index[e] for e in entities(q) if e in index]
        if not known:
            logger.info("no query entities with factors; returning empty result")
            results[qi] = result([])
            continue
        mask = dense_mask(q)
        if mask is not None:
            dense.append((qi, known, mask, int(q.num)))
        else:
            excluded = set(known)
            if q.blackList is not None:
                excluded.update(index[e] for e in q.blackList if e in index)
            simple.append((qi, known, excluded, int(q.num)))
    V = model.device_factors()  # row-normalized: dot == cosine
    vectors = functools.partial(normalized_query_vectors, *model.host_catalog())

    def top(knowns: list[list[int]], k: int, probe_n=None, mask=None):
        # pad the per-query id lists to a shared pow2 width with
        # weight-0 rows (index 0 gathered, then zeroed — exact); k is
        # pow2 as well, so the jitted programs specialize on a bounded
        # shape set
        ixs = np.zeros(
            (len(knowns), retrieval._pow2(max(map(len, knowns)))), np.int32
        )
        weights = np.zeros(ixs.shape, np.float32)
        for row, known in enumerate(knowns):
            ixs[row, : len(known)] = known
            weights[row, : len(known)] = 1.0
        return retrieval.top_k(
            retrieval.SumRows(ixs, weights, vectors, mask), V, len(index),
            model.coarse_catalog, retrieval._pow2(k), probe_n,
        )

    if simple:
        # k for the worst headroom in the batch; the probe compares the
        # ids the first query's answer is cut from
        scores, ids = top(
            [known for _, known, _, _ in simple],
            max(num + len(excl) for _, _, excl, num in simple),
            probe_n=simple[0][3] + len(simple[0][2]),
        )
        for row, (qi, _, excluded, num) in enumerate(simple):
            pairs: list[tuple[str, float]] = []
            for s, i in zip(scores[row], ids[row]):
                ii = int(i)
                if ii < 0 or ii in excluded:
                    continue
                pairs.append((inv[ii], float(s)))
                if len(pairs) == num:
                    break
            results[qi] = result(pairs)
    for qi, known, mask, num in dense:
        scores, ids = top([known], num, mask=jnp.asarray(mask))
        results[qi] = result([
            (inv[int(i)], float(s))
            for s, i in zip(scores[0][:num], ids[0][:num])
            if s > -1e29  # drop fully-masked placeholders
        ])
    return results
