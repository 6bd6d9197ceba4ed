"""Ring-sharded batch scoring: serve catalogs too big for one chip.

The reference's answer to "model bigger than one host" is a PAlgorithm
whose RDD-backed model issues a Spark job per query
(MatrixFactorizationModel.recommendProducts, invoked from
examples/scala-parallel-recommendation/custom-prepartor/src/main/scala/
ALSAlgorithm.scala:88) — per-query cluster scatter/gather over TCP. The
TPU-native design keeps item factors **resident and sharded** across the
mesh and moves them over ICI instead:

- item factors are sharded row-wise over the mesh axis; each device holds
  one shard plus that shard's global item ids (and exclusion mask),
- the query batch is sharded over the same axis; queries never move,
- n ring steps: each device scores its local queries against the item
  shard it currently holds, merges a running per-query top-k, then
  ``ppermute``s the item shard (+ ids + mask) to its ring neighbour.

This is the ring-attention communication pattern (stationary Q, rotating
KV — PAPERS.md) applied to retrieval: compute on the current shard fully
overlaps the ICI transfer of the next, so HBM never holds more than
``items/n`` of the catalog and no all_gather materialises the full score
matrix. Per-query top-k merge keeps the working set at [b, k + i_shard].
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from predictionio_tpu.ops.als import dequantize_rows
from predictionio_tpu.ops.topk import NEG_INF


@functools.partial(
    jax.jit, static_argnames=("k", "mesh", "axis", "normalize", "coarse")
)
def _ring_topk_device(
    queries,  # [B', D] sharded P(axis) on dim 0
    item_factors,  # [I', D] sharded P(axis) on dim 0
    item_ids,  # [I'] int32 sharded P(axis); -1 marks padding
    keep_mask,  # [I'] float32 sharded P(axis); 0 = excluded or padding
    k: int,
    *,
    mesh: Mesh,
    axis: str,
    normalize: bool,
    coarse: bool = False,
):
    n = mesh.shape[axis]
    perm = [(j, (j + 1) % n) for j in range(n)]

    quantized = isinstance(item_factors, tuple)

    def local(q_blk, v_blk, ids_blk, mask_blk):
        if normalize:
            # normalize once before the ring: ppermute only relocates
            # rows, so normalized shards stay normalized as they rotate
            q_blk = q_blk / jnp.maximum(
                jnp.linalg.norm(q_blk, axis=1, keepdims=True), 1e-12
            )
            if quantized:
                # cosine is per-row scale-invariant, so normalization
                # folds INTO the scale (1/||q||): the slab keeps rotating
                # as (int8, f32 scale) and dequantizes to unit rows
                vq, _ = v_blk
                nrm = jnp.linalg.norm(vq.astype(jnp.float32), axis=1)
                v_blk = (vq, 1.0 / jnp.maximum(nrm, 1e-12))
            else:
                v_blk = v_blk / jnp.maximum(
                    jnp.linalg.norm(v_blk, axis=1, keepdims=True), 1e-12
                )

        def step(carry, _):
            v, ids, keep, best_s, best_i = carry
            if quantized and coarse:
                # coarse shortlist pass (ops/retrieval.py): score the
                # int8 slab WITHOUT materializing its dequantized f32
                # copy — the per-row scale factors out of the dot and
                # multiplies back per column. Ranking-equivalent to the
                # dequantized score up to f32 rounding; two-stage
                # serving rescores the shortlist exactly anyway.
                vq, vs = v
                s = (
                    jnp.matmul(
                        q_blk, vq.T.astype(q_blk.dtype),
                        preferred_element_type=jnp.float32,
                    )
                    * vs[None, :]
                )
            else:
                # int8 slabs dequantize per step, right before the
                # matmul: ICI hops stay quantized, scores stay f32
                vd = dequantize_rows(*v) if quantized else v
                s = q_blk @ vd.T  # [b, i] — MXU matmul per ring step
            s = jnp.where(keep[None, :] > 0, s, NEG_INF)
            cand_s = jnp.concatenate([best_s, s], axis=1)
            cand_i = jnp.concatenate(
                [best_i, jnp.broadcast_to(ids[None, :], s.shape)], axis=1
            )
            best_s, idx = jax.lax.top_k(cand_s, k)
            best_i = jnp.take_along_axis(cand_i, idx, axis=1)
            # rotate the shard to the next device; XLA overlaps this
            # ppermute with the next step's matmul
            v = jax.tree_util.tree_map(
                lambda x: jax.lax.ppermute(x, axis, perm), v
            )
            ids = jax.lax.ppermute(ids, axis, perm)
            keep = jax.lax.ppermute(keep, axis, perm)
            return (v, ids, keep, best_s, best_i), None

        b = q_blk.shape[0]
        # constants must be marked device-varying to sit in a shard_map
        # scan carry alongside the ppermute'd (varying) shard arrays
        varying = lambda x: jax.lax.pcast(x, (axis,), to="varying")
        init = (
            v_blk,
            ids_blk,
            mask_blk,
            varying(jnp.full((b, k), NEG_INF, q_blk.dtype)),
            varying(jnp.full((b, k), -1, jnp.int32)),
        )
        (_, _, _, best_s, best_i), _ = jax.lax.scan(step, init, None, length=n)
        return best_s, best_i

    v_spec = (P(axis), P(axis)) if quantized else P(axis)
    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axis), v_spec, P(axis), P(axis)),
        out_specs=(P(axis), P(axis)),
    )(queries, item_factors, item_ids, keep_mask)


@functools.partial(jax.jit, static_argnames=("sharding",))
def _exclude_on_device(keep_all, exclude_ids, sharding):
    """Scatter excluded ids into the resident keep vector ON DEVICE:
    per-query exclusion without a full-catalog host->device copy.
    Out-of-range padding ids are dropped by the scatter."""
    keep = keep_all.at[exclude_ids].set(0.0, mode="drop")
    return jax.lax.with_sharding_constraint(keep, sharding)


class RingCatalog:
    """An item catalog staged sharded on the mesh, reusable across queries.

    The [I, D] factor matrix (the big, query-independent array; dense
    f32/bf16 or the int8 (values, scales) pair of storage_dtype="int8")
    is padded, sharded, and transferred to the mesh ONCE at construction; per-query
    work only ships the [B, D] query batch and (with ``exclude_ids``) a
    small padded id list over PCIe — the exclusion mask is built ON
    DEVICE by scattering those ids into the resident keep vector, so a
    10^7-item catalog never moves a [I] vector per query. This is what
    "factors resident and sharded" means for a deployed server — without
    it every request would re-stage catalog-sized data host-to-device.
    (``exclude_mask`` remains for callers that already hold a full mask;
    it pays the full [I] transfer.)
    """

    def __init__(self, item_factors, mesh: Mesh, axis: str = "data"):
        quantized = isinstance(item_factors, tuple)
        if quantized:
            # int8 catalog: (values [I, D], per-row f32 scales [I]) from
            # storage_dtype="int8" training — staged AND rotated in
            # quantized form, 4x less HBM and ICI than f32
            vq = np.asarray(item_factors[0], dtype=np.int8)
            vs = np.asarray(item_factors[1], dtype=np.float32)
        else:
            vq = np.asarray(item_factors, dtype=np.float32)
            vs = None
        self.mesh = mesh
        self.axis = axis
        self.num_items = vq.shape[0]
        self.dim = vq.shape[1]
        n = mesh.shape[axis]
        pad_i = (-self.num_items) % n
        self._sharding = NamedSharding(mesh, P(axis))
        vq_pad = np.concatenate([vq, np.zeros((pad_i, self.dim), vq.dtype)])
        if quantized:
            # padding rows dequantize to zero (0 * scale); scale 1 keeps
            # them harmless
            self._v = (
                jax.device_put(vq_pad, self._sharding),
                jax.device_put(
                    np.concatenate([vs, np.ones(pad_i, np.float32)]),
                    self._sharding,
                ),
            )
        else:
            self._v = jax.device_put(vq_pad, self._sharding)
        self._ids = jax.device_put(
            np.concatenate(
                [
                    np.arange(self.num_items, dtype=np.int32),
                    np.full(pad_i, -1, np.int32),
                ]
            ),
            self._sharding,
        )
        base_keep = np.ones(self.num_items + pad_i, np.float32)
        base_keep[self.num_items :] = 0.0
        self._base_keep = base_keep
        self._keep_all = jax.device_put(base_keep, self._sharding)

    def top_k(
        self,
        user_vectors,
        k: int,
        exclude_mask=None,
        exclude_ids=None,
        normalize=False,
        coarse=False,
    ):
        """Top-k over the staged catalog. See :func:`ring_top_k`.

        ``coarse=True`` is the mesh shortlist pass of two-stage
        retrieval (ops/retrieval.py): int8 slabs are scored without
        dequantization (ranking-equivalent, not bitwise-equal, to the
        exact scores) — callers rescore the returned ids exactly.
        Dense catalogs score identically either way.

        ``B`` and ``k`` are compile-time shapes in the device program, and
        serving traffic varies both per request (``query.num`` drives k).
        Both are padded up to power-of-two buckets so arbitrary traffic
        reuses a handful of compiled programs instead of accumulating one
        per distinct (B, k); results are sliced back before returning.

        ``exclude_ids`` (preferred for serving): a SMALL int array of
        item indices to exclude — scattered into the device-resident keep
        vector inside the jitted program (padded to power-of-two length
        for compile reuse), shipping O(len) bytes instead of the O(I)
        full-mask copy ``exclude_mask`` costs.
        """
        user_vectors = np.asarray(user_vectors, dtype=np.float32)
        B = user_vectors.shape[0]
        k = min(k, self.num_items)
        k_pad = min(1 << max(0, k - 1).bit_length(), self.num_items)
        n = self.mesh.shape[self.axis]
        # pad B to n * 2^j: divisible by the mesh axis AND bucketed
        per_dev = max(1, -(-B // n))
        pad_b = n * (1 << (per_dev - 1).bit_length()) - B
        q = np.concatenate(
            [user_vectors, np.zeros((pad_b, self.dim), np.float32)]
        )
        if exclude_ids is not None:
            if exclude_mask is not None:
                raise ValueError(
                    "pass exclude_ids or exclude_mask, not both"
                )
            eids = np.asarray(exclude_ids, dtype=np.int32).ravel()
            total = self._keep_all.shape[0]
            # power-of-two padding with an out-of-range index the
            # scatter drops (mode="drop")
            cap = 1 << max(0, len(eids) - 1).bit_length() if len(eids) else 1
            padded = np.full(cap, total, np.int32)
            padded[: len(eids)] = eids
            keep = _exclude_on_device(
                self._keep_all, jnp.asarray(padded), self._sharding
            )
        elif exclude_mask is None:
            keep = self._keep_all
        else:
            host_keep = self._base_keep.copy()
            host_keep[: self.num_items] = np.where(
                np.asarray(exclude_mask).astype(bool),
                0.0,
                host_keep[: self.num_items],
            )
            keep = jax.device_put(host_keep, self._sharding)
        scores, out_ids = _ring_topk_device(
            jax.device_put(q, self._sharding),
            self._v,
            self._ids,
            keep,
            k_pad,
            mesh=self.mesh,
            axis=self.axis,
            normalize=normalize,
            coarse=coarse,
        )
        return np.asarray(scores)[:B, :k], np.asarray(out_ids)[:B, :k]


def ring_top_k(
    user_vectors,
    item_factors,
    k: int,
    mesh: Mesh,
    axis: str = "data",
    exclude_mask=None,
    exclude_ids=None,
    normalize: bool = False,
):
    """Top-k items for a query batch with mesh-sharded item factors.

    One-shot convenience over :class:`RingCatalog` (which long-lived
    servers should hold instead, to amortize the catalog transfer).

    Args:
      user_vectors: [B, D] query vectors (host or device).
      item_factors: [I, D] full catalog factors, dense or as the int8
        (values, scales) pair (host or device; laid out sharded over
        ``axis``).
      k: results per query.
      mesh: the device mesh; ``axis`` names the ring dimension.
      exclude_mask: optional [I] bool/0-1 array; 1/True = never return
        this item (seen/unavailable filters of the e-commerce template).
      exclude_ids: optional small int array of item indices to exclude —
        the cheap path (on-device scatter; see RingCatalog.top_k).
      normalize: score by cosine similarity instead of dot product
        (similar-product template).

    Returns:
      (scores [B, k], ids [B, k]) numpy arrays, per-query descending.
      Ids are global item indices; -1 marks slots beyond the number of
      eligible items.
    """
    catalog = RingCatalog(item_factors, mesh, axis)
    return catalog.top_k(
        user_vectors,
        k,
        exclude_mask=exclude_mask,
        exclude_ids=exclude_ids,
        normalize=normalize,
    )
