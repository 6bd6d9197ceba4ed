"""Alternating Least Squares on TPU: explicit and implicit feedback.

Replaces ``org.apache.spark.mllib.recommendation.ALS.train`` /
``trainImplicit`` (invoked by the reference templates at
examples/scala-parallel-recommendation/custom-prepartor/src/main/scala/
ALSAlgorithm.scala:72 and examples/scala-parallel-similarproduct/multi/
src/main/scala/ALSAlgorithm.scala) with a TPU-first formulation:

- MLlib blocks users/items across executors and exchanges factors via
  shuffle; here each half-iteration is a **batched dense solve**: for every
  user u, accumulate the normal equations
  ``A_u = sum_i v_i v_i^T (+reg)``, ``b_u = sum_i r_ui v_i`` over padded
  per-user item lists and Cholesky-solve all users at once. The Gramian
  accumulation is a ``[K,D]^T @ [K,D]`` batched matmul — exactly MXU shape.
- Ragged degrees are handled by **degree bucketing** (the ALX approach,
  PAPERS.md "ALX: Large Scale Matrix Factorization on TPUs"): users are
  grouped into power-of-two-padded buckets so XLA sees a few static shapes
  instead of dynamic ones.
- Gathers and matmuls run in a configurable ``compute_dtype`` (bfloat16 by
  default on TPU) with float32 accumulation (``preferred_element_type``)
  for RMSE parity with the float32 MLlib baseline.
- Regularization matches MLlib's weighted-lambda ("ALS-WR"): the reference
  template's RMSE target assumes ``reg * n_u`` scaling (flag-controlled).

Multi-chip: see ``predictionio_tpu.parallel.als_sharded`` — the batched
solves shard row-wise over the mesh with the opposite factor matrix
replicated/all-gathered over ICI each half-iteration.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
from dataclasses import dataclass, field
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from predictionio_tpu.obs import device as obs_device

logger = logging.getLogger(__name__)

DEFAULT_BUCKETS = (8, 32, 128, 512, 2048)


# ---------------------------------------------------------------------------
# Host-side layout: COO ratings -> degree-bucketed padded neighbor lists
# ---------------------------------------------------------------------------


@dataclass
class PaddedBucket:
    """One degree bucket of padded per-row neighbor lists (static shapes).

    When ``seg_row`` is None each table row solves one matrix row
    (``B == len(row_ids)``). Otherwise the bucket is **segmented**: rows
    whose degree exceeds the bucket width are split across several table
    rows, ``seg_row[i]`` maps table row i to its index in ``row_ids``,
    and the solver scatter-adds per-segment Gramians before solving — so
    arbitrarily hot rows (a blockbuster item with 10^5 ratings) train
    exactly with bounded memory instead of being truncated.
    """

    row_ids: np.ndarray  # [R] int32 — which row (user/item) each entry solves
    col_ids: np.ndarray  # [B, K] int32 — rated column indices, 0-padded
    ratings: np.ndarray  # [B, K] float32 — rating values, 0-padded
    mask: np.ndarray  # [B, K] float32 — 1 for real entries, 0 for padding
    seg_row: np.ndarray | None = None  # [B] int32 into row_ids, or None

    @property
    def width(self) -> int:
        return self.col_ids.shape[1]


@dataclass
class RatingsData:
    """COO ratings plus both row-major layouts, ready for ALS."""

    rows: np.ndarray  # [N] int32 user indices
    cols: np.ndarray  # [N] int32 item indices
    vals: np.ndarray  # [N] float32 ratings
    num_rows: int
    num_cols: int
    row_buckets: list[PaddedBucket] = field(default_factory=list)
    col_buckets: list[PaddedBucket] = field(default_factory=list)


def build_padded_buckets(
    rows: np.ndarray,
    cols: np.ndarray,
    vals: np.ndarray,
    bucket_widths: Sequence[int] = DEFAULT_BUCKETS,
    segment: bool = True,
) -> list[PaddedBucket]:
    """Group rows by degree into padded buckets (fully vectorized).

    Rows whose degree exceeds the largest width are **segmented** across
    multiple table rows of the largest bucket (exact training; the solver
    scatter-adds segment Gramians). Every production path — single-chip
    ``als_train`` AND the mesh-sharded trainer, which colocates all of a
    row's segments on one shard (parallel/als_sharded.py shard_bucket) —
    trains segmented rows exactly. ``segment=False`` is an opt-in lossy
    cap: such rows instead keep their ``width`` highest-|rating| entries
    (bounds the table size when blockbuster rows may be approximated).
    Buckets are ordered by width, rows by id.
    """
    if len(rows) == 0:
        return []
    order = np.argsort(rows, kind="stable")
    rows_s, cols_s, vals_s = rows[order], cols[order], vals[order]
    uniq, starts, counts = np.unique(rows_s, return_index=True, return_counts=True)
    # within-row rank of every entry (vectorized: entry index - row start)
    rank = np.arange(len(rows_s)) - np.repeat(starts, counts)
    inv = np.repeat(np.arange(len(uniq)), counts)  # entry -> uniq row index

    max_width = int(max(bucket_widths))
    n_over = int((counts > max_width).sum())
    if n_over and not segment:
        logger.warning(
            "ALS bucketing: %d rows exceed max degree %d; keeping the "
            "%d highest-|rating| entries for those rows (segment=False)",
            n_over,
            max_width,
            max_width,
        )
        # per-row descending-|rating| order, vectorized: sort by
        # (row, -|val|) then recompute ranks; entries ranked past the
        # width are dropped
        order2 = np.lexsort((-np.abs(vals_s), rows_s))
        rows_s, cols_s, vals_s = rows_s[order2], cols_s[order2], vals_s[order2]
        rank = np.arange(len(rows_s)) - np.repeat(starts, counts)
        inv = np.repeat(np.arange(len(uniq)), counts)
        keep = rank < max_width
        rows_s, cols_s, vals_s = rows_s[keep], cols_s[keep], vals_s[keep]
        rank, inv = rank[keep], inv[keep]
        counts = np.minimum(counts, max_width)

    buckets: list[PaddedBucket] = []
    widths = sorted(set(int(w) for w in bucket_widths))
    for wi, width in enumerate(widths):
        lo = widths[wi - 1] if wi > 0 else 0
        last = wi == len(widths) - 1
        sel = (counts > lo) if last else (counts > lo) & (counts <= width)
        idx = np.nonzero(sel)[0]
        if len(idx) == 0:
            continue
        buckets.append(
            _fill_bucket_class(
                width, last, counts, uniq, idx, rank, inv, cols_s, vals_s
            )
        )
    return buckets


def _fill_bucket_class(
    width: int,
    last: bool,
    counts: np.ndarray,
    uniq: np.ndarray,
    idx: np.ndarray,
    rank: np.ndarray,
    inv: np.ndarray,
    cols_s: np.ndarray,
    vals_s: np.ndarray,
) -> PaddedBucket:
    """Materialize ONE width class from row-sorted entry arrays. Shared
    by the full build and :func:`splice_padded_buckets` — the splice
    rebuilds affected classes through this exact fill, which is what
    makes spliced buckets bit-identical to a fresh pack by construction.

    ``counts``/``uniq`` describe the distinct rows of the entry set;
    ``idx`` selects this class's rows within ``uniq``; ``rank`` is each
    entry's within-row rank and ``inv`` its ``uniq`` index; ``cols_s``/
    ``vals_s`` are the entries sorted stably by row.
    """
    R = len(idx)
    # per selected row: number of width-sized segments (1 unless hot)
    nseg = (
        np.maximum(1, -(-counts[idx] // width)) if last else np.ones(R, np.int64)
    )
    seg_base = np.concatenate([[0], np.cumsum(nseg)])
    B = int(seg_base[-1])

    # entry -> (segment table row, within-segment position)
    rowpos = np.full(len(uniq), -1, np.int64)
    rowpos[idx] = np.arange(R)
    pos = rowpos[inv]
    m = pos >= 0
    seg_of_entry = seg_base[pos[m]] + rank[m] // width
    within = rank[m] % width

    col_ids = np.zeros((B, width), dtype=np.int32)
    ratings = np.zeros((B, width), dtype=np.float32)
    mask = np.zeros((B, width), dtype=np.float32)
    col_ids[seg_of_entry, within] = cols_s[m]
    ratings[seg_of_entry, within] = vals_s[m]
    mask[seg_of_entry, within] = 1.0

    seg_row = None
    if last and B > R:
        seg_row = np.repeat(np.arange(R, dtype=np.int32), nseg)
    return PaddedBucket(
        row_ids=uniq[idx].astype(np.int32),
        col_ids=col_ids,
        ratings=ratings,
        mask=mask,
        seg_row=seg_row,
    )


def splice_padded_buckets(
    old_buckets: Sequence[PaddedBucket],
    rows: np.ndarray,
    cols: np.ndarray,
    vals: np.ndarray,
    delta_rows: np.ndarray,
    bucket_widths: Sequence[int] = DEFAULT_BUCKETS,
) -> list[PaddedBucket]:
    """Incrementally rebuild padded buckets after a splice.

    ``rows``/``cols``/``vals`` are the FULL post-splice COO arrays (old
    entries in their original stream order with the delta entries
    spliced in); ``delta_rows`` are the row indices of just the delta
    entries; ``old_buckets`` is the pack of the pre-splice arrays built
    with the same ``bucket_widths``.

    Only width classes whose membership or contents could have changed —
    the current and previous classes of every delta-touched row — are
    rebuilt (from the full arrays, restricted to member rows, through
    the same :func:`_fill_bucket_class` fill as a fresh build); untouched
    classes reuse the old bucket arrays verbatim. Correct because a
    class's arrays depend only on its member rows' entry sequences, and
    an untouched row's entries (and their relative order under the
    stable row sort) are unchanged by the splice. Requires a stable id
    space: delta entries may only reference existing row indices or new
    indices past the old maximum (the appended-ids invariant of the
    prep cache's splice path). ``segment=True`` semantics only.
    """
    if len(rows) == 0:
        return []
    if len(delta_rows) == 0 and old_buckets:
        return list(old_buckets)
    widths = sorted(set(int(w) for w in bucket_widths))
    n_w = len(widths)
    warr = np.asarray(widths)
    bc = np.bincount(rows)
    uniq_all = np.flatnonzero(bc)
    counts_all = bc[uniq_all]
    # width class of every present row: first width >= count, clamped to
    # the (segmenting) last class — matches the (lo, width] selection of
    # the full build exactly
    cls = np.minimum(
        np.searchsorted(warr, counts_all, side="left"), n_w - 1
    )

    touched = np.unique(delta_rows)
    pos_t = np.searchsorted(uniq_all, touched)
    affected = set(int(c) for c in cls[pos_t])
    old_counts_t = counts_all[pos_t] - np.bincount(
        delta_rows, minlength=int(bc.shape[0])
    )[touched]
    existed = old_counts_t > 0
    if existed.any():
        affected |= set(
            int(c)
            for c in np.minimum(
                np.searchsorted(warr, old_counts_t[existed], side="left"),
                n_w - 1,
            )
        )

    old_by_width = {b.width: b for b in old_buckets}
    out: list[PaddedBucket] = []
    for wi, width in enumerate(widths):
        sel = cls == wi
        if not sel.any():
            continue
        if wi not in affected and width in old_by_width:
            out.append(old_by_width[width])
            continue
        member = np.zeros(bc.shape[0], dtype=bool)
        member[uniq_all[sel]] = True
        m_ent = member[rows]
        sub_rows = rows[m_ent]
        order = np.argsort(sub_rows, kind="stable")
        rows_s = sub_rows[order]
        cols_s = cols[m_ent][order]
        vals_s = vals[m_ent][order]
        uniq, starts, counts = np.unique(
            rows_s, return_index=True, return_counts=True
        )
        rank = np.arange(len(rows_s)) - np.repeat(starts, counts)
        inv = np.repeat(np.arange(len(uniq)), counts)
        out.append(
            _fill_bucket_class(
                width,
                wi == n_w - 1,
                counts,
                uniq,
                np.arange(len(uniq)),
                rank,
                inv,
                cols_s,
                vals_s,
            )
        )
    return out


def build_ratings_data(
    rows: np.ndarray,
    cols: np.ndarray,
    vals: np.ndarray,
    num_rows: int | None = None,
    num_cols: int | None = None,
    bucket_widths: Sequence[int] = DEFAULT_BUCKETS,
    segment: bool = True,
) -> RatingsData:
    rows = np.asarray(rows, dtype=np.int32)
    cols = np.asarray(cols, dtype=np.int32)
    vals = np.asarray(vals, dtype=np.float32)
    num_rows = int(num_rows if num_rows is not None else rows.max() + 1)
    num_cols = int(num_cols if num_cols is not None else cols.max() + 1)
    return RatingsData(
        rows=rows,
        cols=cols,
        vals=vals,
        num_rows=num_rows,
        num_cols=num_cols,
        row_buckets=build_padded_buckets(rows, cols, vals, bucket_widths, segment),
        col_buckets=build_padded_buckets(cols, rows, vals, bucket_widths, segment),
    )


# ---------------------------------------------------------------------------
# Host-side layout: entry packing (shared with the sharded trainer)
# ---------------------------------------------------------------------------


PACK_WIDTH_CANDIDATES = (4, 8, 16, 32, 64, 128, 256, 512, 1024)

# Extra slot-equivalents one packed ROW costs beyond its slots: each row
# materializes a [D, D] partial Gramian and scatter-adds it into the
# per-solved-row accumulators, work that scales like a handful of slots'
# worth of outer products. Measured on the bench workload (rank 16,
# 250k entries): pure slot-minimization picks K=4 / 64k rows and runs
# ~2x slower than K=32 / 9k rows; overhead 8 lands each mode at its
# empirical optimum (gather ~32-64, ring ~8-16).
PACK_ROW_OVERHEAD_SLOTS = 8


def choose_pack_width(
    counts,
    candidates=PACK_WIDTH_CANDIDATES,
    row_overhead=PACK_ROW_OVERHEAD_SLOTS,
) -> int:
    """Pick one packed-row width for a set of entry groups.

    ``counts`` are per-group entry counts (e.g. per-row degrees). The
    width minimizing ``sum(ceil(c/K)) * (K + row_overhead)`` — total
    padded slots plus the per-row accumulate/scatter overhead — wins;
    ties go to the LARGER width (fewer, wider rows batch better on the
    MXU). This replaces the per-bucket width ladder for the sharded
    trainer: one uniform width means one table, one program — the ALX
    trade of a little padding for zero per-bucket dispatch.
    """
    counts = np.asarray(counts, dtype=np.int64)
    if counts.size == 0:
        return int(candidates[0])
    best_k, best_cost = None, None
    for k in candidates:
        rows = (-(-counts // k)).sum()
        cost = int(rows * (k + row_overhead))
        if best_cost is None or cost <= best_cost:
            best_k, best_cost = int(k), cost
    return best_k


def pack_entries(keys: np.ndarray, width: int):
    """Pack entries into ``width``-wide rows, one group per run of rows.

    ``keys`` is one int64 group key per entry (arbitrary values; entries
    sharing a key form one group). Each group fills ``ceil(count/width)``
    consecutive packed rows, groups ordered by ascending key, entries
    within a group keeping their input order (stable sort — this is what
    preserves the single-chip reduction order for parity). Returns
    ``(entry_row, entry_slot, row_key, n_rows)``: the packed (row, slot)
    of every entry, the group key each packed row serves, and the total
    packed row count.
    """
    keys = np.asarray(keys, dtype=np.int64)
    n = len(keys)
    if n == 0:
        z = np.zeros(0, np.int64)
        return z, z, z, 0
    order = np.argsort(keys, kind="stable")
    ks = keys[order]
    starts = np.concatenate([[0], np.nonzero(np.diff(ks))[0] + 1])
    counts = np.diff(np.concatenate([starts, [n]]))
    rank = np.arange(n) - np.repeat(starts, counts)
    nseg = -(-counts // width)
    seg_base = np.concatenate([[0], np.cumsum(nseg)[:-1]])
    row_sorted = np.repeat(seg_base, counts) + rank // width
    entry_row = np.empty(n, np.int64)
    entry_row[order] = row_sorted
    entry_slot = np.empty(n, np.int64)
    entry_slot[order] = rank % width
    row_key = np.repeat(ks[starts], nseg)
    return entry_row, entry_slot, row_key, int(nseg.sum())


# ---------------------------------------------------------------------------
# Device-side solves
# ---------------------------------------------------------------------------


@obs_device.track_jit("als.solve_bucket_explicit")
@functools.partial(
    jax.jit, static_argnames=("weighted_reg", "compute_dtype")
)
def solve_bucket_explicit(
    factors_other,
    col_ids,
    ratings,
    mask,
    reg: float,
    weighted_reg: bool = True,
    compute_dtype: str = "float32",
):
    """Solve one padded bucket's normal equations for explicit feedback.

    ``A_u = sum v v^T + reg * (n_u if weighted_reg else 1) * I``,
    ``b_u = sum r v``; returns x [B, D] in float32.
    """
    D = table_dim(factors_other)
    dt = jnp.dtype(compute_dtype)
    vg = _read_rows(factors_other, col_ids, dt)  # [B, K, D]
    w = mask.astype(dt)
    r = (ratings * mask).astype(dt)
    A, b = _gramian_rhs(vg, w, r)

    n = mask.sum(axis=1)
    lam = reg * (n if weighted_reg else jnp.ones_like(n))
    # rows with no ratings (shard padding) get an identity system -> x = 0
    lam = jnp.where(n > 0, lam, 1.0)
    A = A + lam[:, None, None] * jnp.eye(D, dtype=jnp.float32)
    return _psd_solve(A, b)


@obs_device.track_jit("als.solve_bucket_implicit")
@functools.partial(
    jax.jit, static_argnames=("weighted_reg", "compute_dtype")
)
def solve_bucket_implicit(
    factors_other,
    gram,  # [D, D] precomputed Y^T Y over *all* other factors
    col_ids,
    ratings,
    mask,
    reg: float,
    alpha: float,
    weighted_reg: bool = False,
    compute_dtype: str = "float32",
):
    """Implicit-feedback bucket solve (Hu-Koren-Volinsky; MLlib
    trainImplicit semantics): confidence ``c = 1 + alpha*r``,
    ``A_u = Y^T Y + sum alpha*r * v v^T + reg I``,
    ``b_u = sum (1 + alpha*r) v``.
    """
    D = table_dim(factors_other)
    dt = jnp.dtype(compute_dtype)
    vg = _read_rows(factors_other, col_ids, dt)  # [B, K, D]
    conf_minus_1 = (alpha * ratings * mask).astype(dt)
    rhs_w = ((1.0 + alpha * ratings) * mask).astype(dt)
    A_c, b = _gramian_rhs(vg, conf_minus_1, rhs_w)
    n = mask.sum(axis=1)
    lam = reg * (n if weighted_reg else jnp.ones_like(n))
    lam = jnp.where(n > 0, lam, 1.0)  # padded rows -> identity system
    A = gram[None, :, :] + A_c + lam[:, None, None] * jnp.eye(D, dtype=jnp.float32)
    return _psd_solve(A, b)


def _gramian_rhs_gathered(factors_other, col_ids, w, r, dt, budget_bytes):
    """Gather ``factors_other[col_ids]`` and reduce it to (A, b) per
    batch row, bounding the [B, K, D] gather temp to ``budget_bytes``.

    Under the budget this is exactly gather + ``_gramian_rhs`` (the XLA
    fusion the module relies on). Over it — wide buckets at high rank,
    where B*K*D would blow HBM (measured: ML-20M rank 128 needs a 21.7G
    program unchunked on a 16G v5e) — the batch dim is processed in
    ``lax.map`` chunks: each chunk's gather+gramian lives only for that
    scan step, so the resident temp is one chunk. Shapes are static, so
    the choice costs nothing at runtime.
    """
    B, K = col_ids.shape
    D = table_dim(factors_other)
    if B * K * D * jnp.dtype(dt).itemsize <= budget_bytes or B <= 1:
        vg = _read_rows(factors_other, col_ids, dt)
        return _gramian_rhs(vg, w, r)
    rows_per_chunk = max(1, budget_bytes // (K * D * jnp.dtype(dt).itemsize))
    n_chunks = -(-B // rows_per_chunk)
    pad = n_chunks * rows_per_chunk - B
    # padded rows gather factor row 0 with zero weight -> A = 0, b = 0;
    # sliced off below before regularization sees them
    ci = jnp.pad(col_ids, ((0, pad), (0, 0)))
    wp = jnp.pad(w, ((0, pad), (0, 0)))
    rp = jnp.pad(r, ((0, pad), (0, 0)))

    def one_chunk(chunk):
        c_ids, c_w, c_r = chunk
        return _gramian_rhs(_read_rows(factors_other, c_ids, dt), c_w, c_r)

    A, b = jax.lax.map(
        one_chunk,
        (
            ci.reshape(n_chunks, rows_per_chunk, K),
            wp.reshape(n_chunks, rows_per_chunk, K),
            rp.reshape(n_chunks, rows_per_chunk, K),
        ),
    )
    return (
        A.reshape(n_chunks * rows_per_chunk, D, D)[:B],
        b.reshape(n_chunks * rows_per_chunk, D)[:B],
    )


def _gramian_rhs(vg, w, r):
    """Fused ``A = vg^T diag(w) vg`` and ``b = vg^T r`` per batch row.

    vg: [B, K, D]; w, r: [B, K]. Returns (A [B,D,D] f32, b [B,D] f32).
    The batched dot_general is the MXU hot loop; float32 accumulation via
    preferred_element_type regardless of compute dtype.

    Deliberately XLA, not Pallas. A hand-written Pallas kernel for this op
    (batch-tiled, both matmuls fused over a VMEM-resident Vg tile) was
    built and measured on a v5e chip in round 3: op-level it was parity
    with this path (geomean 1.01x over B/K bucket shapes at rank 20/64/
    128), but end-to-end ALS training was 27x SLOWER (265ms vs 9.8ms,
    ML-100K rank 20) because the opaque custom call forces the
    ``factors_other[col_ids]`` gather to materialize [B,K,D] in HBM,
    breaking XLA's fusion of gather+gramian+solve+scatter inside the
    fused training program. The kernel was deleted (git history:
    ops/als_pallas.py).
    """
    # f32 inputs get HIGHEST precision so TPU hardware doesn't silently
    # decompose the matmul to bf16 passes (RMSE-parity requirement);
    # bf16 compute keeps the fast default path.
    prec = "highest" if vg.dtype == jnp.float32 else "default"
    vw = vg * w[:, :, None]
    A = jax.lax.dot_general(
        vw,
        vg,
        dimension_numbers=(((1,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
        precision=prec,
    )
    b = jax.lax.dot_general(
        r[:, None, :],
        vg,
        dimension_numbers=(((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
        precision=prec,
    )[:, 0, :]
    return A, b


def _psd_solve(A, b):
    """Batched SPD solve via Cholesky (the per-block executor-side Cholesky
    of MLlib ALS, done as one batched device op)."""
    chol = jax.scipy.linalg.cho_factor(A, lower=True)
    return jax.scipy.linalg.cho_solve(chol, b)


# ---------------------------------------------------------------------------
# int8 factor storage: per-row symmetric quantization
# ---------------------------------------------------------------------------
#
# ``storage_dtype="int8"`` stores a factor table as the pair
# ``(values int8 [N, D], scales float32 [N])`` with
# ``row_f32 = values * scales[:, None]`` — per-row max-abs/127 symmetric
# quantization (the Tensor Casting trade, PAPERS.md: compressed factor
# traffic, full-precision accumulation). Every function below that takes
# a factor table accepts either a plain array (f32/bf16 path, unchanged)
# or this pair; the choice is static at trace time, so f32/bf16 programs
# are byte-identical to before.


def quantize_rows(x):
    """f32 factors ``[..., N, D]`` -> ``(int8 [..., N, D], f32 [..., N])``
    per-row scales. All-zero rows get scale 1 (quantize to exact zeros)."""
    x = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(x), axis=-1) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    q = jnp.round(x / scale[..., None]).astype(jnp.int8)
    return q, scale


def dequantize_rows(q, scale, dt=jnp.float32):
    """Inverse of :func:`quantize_rows` in dtype ``dt``."""
    return q.astype(dt) * scale[..., None].astype(dt)


def to_storage(x, storage_dtype: str):
    """f32 factors -> their storage representation (array or int8 pair)."""
    if storage_dtype == "int8":
        return quantize_rows(x)
    return x.astype(jnp.dtype(storage_dtype))


def dense_factors(table, dt=jnp.float32):
    """A whole factor table as a dense array of dtype ``dt``."""
    if isinstance(table, tuple):
        return dequantize_rows(table[0], table[1], dt)
    return table.astype(dt)


def host_factors(table):
    """Factor table -> host arrays ``(values, scales)``: scales is the
    [N] f32 per-row array for the int8 pair representation, None for
    dense dtypes. The model classes persist exactly this split, keeping
    quantized MODELDATA blobs 4x smaller than f32."""
    if isinstance(table, tuple):
        return np.asarray(table[0]), np.asarray(table[1])
    return np.asarray(table), None


def table_rows(table) -> int:
    """Row count of a factor table in either representation."""
    return (table[0] if isinstance(table, tuple) else table).shape[0]


def table_dim(table) -> int:
    """Factor dimension (rank) of a table in either representation."""
    return (table[0] if isinstance(table, tuple) else table).shape[1]


def slice_rows(table, n: int):
    """First ``n`` rows of a factor table, preserving representation."""
    if isinstance(table, tuple):
        return (table[0][:n], table[1][:n])
    return table[:n]


def _read_rows(table, ids, dt):
    """Gather ``table[ids]`` as dtype ``dt``, dequantizing int8 tables
    (the quant->f32 transition happens at gather time, so only int8
    bytes move out of HBM/over ICI)."""
    if isinstance(table, tuple):
        q, s = table
        return dequantize_rows(q[ids], s[ids], dt)
    return table[ids].astype(dt)


def _scatter_rows(target, row_ids, x):
    """Write freshly solved f32 rows ``x`` back into the storage-format
    table (requantizing each half-iteration for int8 storage)."""
    if isinstance(target, tuple):
        tq, ts = target
        q, s = quantize_rows(x)
        return (tq.at[row_ids].set(q), ts.at[row_ids].set(s))
    return target.at[row_ids].set(x.astype(target.dtype))


def compute_gram(factors, compute_dtype: str = "float32"):
    """Y^T Y for the implicit-feedback term (float32 accumulate)."""
    y = dense_factors(factors, jnp.dtype(compute_dtype))
    prec = "highest" if y.dtype == jnp.float32 else "default"
    return jax.lax.dot_general(
        y,
        y,
        (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=prec,
    )


# ---------------------------------------------------------------------------
# Training loop (host orchestration; each step is one jitted device call)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)  # hashable: used as a static jit argument
class ALSParams:
    rank: int = 10
    iterations: int = 10
    reg: float = 0.01
    implicit: bool = False
    alpha: float = 1.0
    weighted_reg: bool = True  # explicit path: ALS-WR reg * n_u scaling
    implicit_weighted_reg: bool = False  # implicit path default: plain reg*I
    seed: int = 7
    compute_dtype: str = "float32"
    # dtype the factor matrices are STORED in between solves. The
    # rank-20 north star is HBM-bound (the per-bucket factor gathers and
    # the sharded trainer's all_gathers dominate, not the MXU), so
    # bfloat16 storage halves the dominant traffic; every solve still
    # accumulates its normal equations in float32
    # (preferred_element_type) and the Cholesky solves run in float32,
    # so the quantization acts as per-iteration noise on the factors —
    # the ALX trade (PAPERS.md), measured at parity RMSE.
    # "int8" halves it AGAIN: tables become (int8 values, f32 per-row
    # scale) pairs (see quantize_rows), dequantized at gather time and
    # requantized on each half-iteration's write-back; solves stay f32.
    storage_dtype: str = "float32"
    bucket_widths: tuple[int, ...] = DEFAULT_BUCKETS
    # HBM budget for one bucket's [B, K, D] factor-gather temp: buckets
    # whose gather would exceed it are solved in lax.map chunks over the
    # batch dim instead of one materialization (static shapes, so this is
    # a trace-time decision; programs under the budget are unchanged).
    # 2 GiB keeps every ML-20M rank-20 bucket on the unchunked path
    # (largest gather there: 1.74 GiB — the measured-good north-star
    # program is untouched) while rank-64/128 buckets (2.6-11.2 GiB
    # unchunked, which OOM a 16-GiB v5e) get chunked.
    gather_chunk_bytes: int = 2 << 30
    # Per-chip budget for the mesh-sharded trainer's gathered opposite
    # factor matrix (parallel/als_sharded.py). When the all_gather of one
    # side would exceed it, the trainer auto-selects the ppermute RING
    # half-step (opposite-factor slabs rotate around the mesh; per-chip
    # memory then SHRINKS with mesh size) instead of the latency-optimal
    # full all_gather. 8 GiB = half of a 16-GiB v5e: every catalog the
    # all_gather design ceiling admits stays on the fused-gather path.
    sharded_gather_budget_bytes: int = 8 << 30


def sharded_budget_kwarg(value: int | None) -> dict:
    """ALSParams kwargs fragment used by the templates: include
    ``sharded_gather_budget_bytes`` only when the engine params override
    it (None keeps the library default above)."""
    return {} if value is None else {"sharded_gather_budget_bytes": int(value)}


def init_factors(num: int, rank: int, key, scale: float | None = None):
    scale = scale if scale is not None else 1.0 / np.sqrt(rank)
    return scale * jax.random.normal(key, (num, rank), dtype="float32")


def _solve_bucket_inline(
    factors_other,
    gram,
    bucket_arrays,
    params: ALSParams,
    seg_row=None,
    num_solved_rows: int | None = None,
    reg=None,
    alpha=None,
):
    """One bucket's solve, for use inside a larger jitted computation
    (same math as the standalone solve_bucket_* entry points).

    ``seg_row`` (segmented bucket): [B] table-row -> solved-row mapping
    with ``num_solved_rows`` distinct rows; per-segment Gramians/rhs are
    scatter-added into the solved rows before regularization, so hot rows
    train on ALL their ratings with bounded memory.

    ``reg``/``alpha`` override the static ``params`` values with TRACED
    scalars — the hook the vmapped parameter sweep (als_train_sweep) uses
    to train many regularization candidates in one program.
    """
    col_ids, ratings, mask = bucket_arrays
    reg = params.reg if reg is None else reg
    alpha = params.alpha if alpha is None else alpha
    dt = jnp.dtype(params.compute_dtype)
    w, r = _bucket_weights(ratings, mask, params, alpha)
    A, b = _gramian_rhs_gathered(
        factors_other, col_ids, w, r, dt, params.gather_chunk_bytes
    )
    n = mask.sum(axis=1)
    return _finish_bucket_solve(
        A, b, n, gram, params, seg_row, num_solved_rows, reg
    )


def _bucket_weights(ratings, mask, params: ALSParams, alpha):
    """Per-entry Gramian weight ``w`` and rhs weight ``r`` for one bucket
    (explicit: w=mask, r=rating; implicit: Hu-Koren-Volinsky confidence).
    Shared by the gather path and the ring trainer, which further masks
    these by slab ownership per rotation."""
    dt = jnp.dtype(params.compute_dtype)
    if params.implicit:
        w = (alpha * ratings * mask).astype(dt)
        r = ((1.0 + alpha * ratings) * mask).astype(dt)
    else:
        w = mask.astype(dt)
        r = (ratings * mask).astype(dt)
    return w, r


def _finish_bucket_solve(
    A, b, n, gram, params: ALSParams, seg_row, num_solved_rows, reg
):
    """Tail of a bucket solve given accumulated normal equations:
    scatter-add row segments, regularize, add the implicit Gramian, and
    batched-Cholesky solve. Shared by `_solve_bucket_inline` (which
    accumulates (A, b) in one gather) and the ring sharded trainer
    (which accumulates them over ppermute rotations)."""
    D = b.shape[1]
    if seg_row is not None:
        R = num_solved_rows
        A = jnp.zeros((R, D, D), A.dtype).at[seg_row].add(A)
        b = jnp.zeros((R, D), b.dtype).at[seg_row].add(b)
        n = jnp.zeros((R,), n.dtype).at[seg_row].add(n)
    weighted = params.implicit_weighted_reg if params.implicit else params.weighted_reg
    lam = reg * (n if weighted else jnp.ones_like(n))
    lam = jnp.where(n > 0, lam, 1.0)
    A = A + lam[:, None, None] * jnp.eye(D, dtype=jnp.float32)
    if params.implicit:
        A = A + gram[None, :, :]
    return _psd_solve(A, b)


@obs_device.track_jit("als.train_fused")
@functools.partial(jax.jit, static_argnames=("params",), donate_argnums=(0, 1))
def _train_fused(U, V, row_arrays, col_arrays, params: ALSParams, iterations):
    """The whole training run as ONE device program: lax.fori_loop over
    iterations (dynamic trip count — one compile serves any iteration
    count), bucket loop unrolled inside (static shapes per bucket).

    Removes per-bucket dispatch + host round-trips of the step-by-step
    path: factors stay resident, XLA fuses the scatter of one bucket's
    solutions with the next bucket's gather, and buffers are donated so
    U/V update in place across the loop.
    """

    def half(target, other, bucket_arrays_list):
        gram = (
            compute_gram(other, params.compute_dtype) if params.implicit else None
        )
        for row_ids, col_ids, ratings, mask, seg_row in bucket_arrays_list:
            x = _solve_bucket_inline(
                other,
                gram,
                (col_ids, ratings, mask),
                params,
                seg_row=seg_row,
                num_solved_rows=row_ids.shape[0],
            )
            # solves come back float32; factors persist in storage_dtype
            # (int8 storage requantizes here, computing fresh per-row
            # scales from the f32 solutions each half-iteration)
            target = _scatter_rows(target, row_ids, x)
        return target

    def step(_, carry):
        U, V = carry
        U = half(U, V, row_arrays)
        V = half(V, U, col_arrays)
        return (U, V)

    return jax.lax.fori_loop(0, iterations, step, (U, V))


def _device_bucket_arrays(buckets: Sequence[PaddedBucket]):
    """Upload bucket arrays once; returned as a tuple usable as a jit arg."""
    nbytes = sum(
        b.row_ids.nbytes + b.col_ids.nbytes + b.ratings.nbytes
        + b.mask.nbytes
        + (b.seg_row.nbytes if b.seg_row is not None else 0)
        for b in buckets
    )
    with obs_device.transfer("h2d", "train.buckets", nbytes):
        return tuple(
            (
                jnp.asarray(b.row_ids),
                jnp.asarray(b.col_ids),
                jnp.asarray(b.ratings),
                jnp.asarray(b.mask),
                jnp.asarray(b.seg_row) if b.seg_row is not None else None,
            )
            for b in buckets
        )


# Diagnostics of the most recent als_train / sharded_als_train run in
# this process: {"iterations_run", "early_stopped", "final_rmse",
# "warm_start"}. A test/bench hook, not an API — read it right after the
# call that produced it.
LAST_TRAIN_INFO: dict = {}


def _warm_init(cold, warm) -> jnp.ndarray:
    """Merge a warm-start factor table into the cold init: ``warm`` is a
    full-size float32 array with NaN rows marking "no prior factors —
    keep the cold draw", so rows absent from the previous model train
    from exactly the factors a cold run would have given them."""
    if warm is None:
        return cold
    warm = jnp.asarray(np.asarray(warm, dtype=np.float32))
    return jnp.where(jnp.isnan(warm), cold, warm)


def als_train(
    data: RatingsData,
    params: ALSParams,
    checkpoint_cfg=None,
    warm_start=None,
    tol: float = 0.0,
    progress_extra: dict | None = None,
):
    """Run ALS; returns (user_factors, item_factors) as jax arrays.

    The full iteration loop runs as a single fused device program (one
    compile per unique set of bucket shapes; see _train_fused).

    Checkpointing (``checkpoint_cfg`` or the PIO_CHECKPOINT_* env vars;
    see core/checkpoint.py): the dynamic trip count lets the run be
    dispatched as segments of ``every`` iterations feeding the donated
    (U, V) carry back through the SAME compiled program — bit-identical
    to one full-length dispatch, zero recompiles — with an atomic
    snapshot of the carry persisted at each segment boundary. ``resume``
    restores the latest fingerprint-matched snapshot and continues.

    ``warm_start`` feeds a previous model in as the iteration-0 carry:
    an optional ``(U0, V0)`` pair of full-size float32 arrays (NaN rows
    fall back to the cold init — see :func:`_warm_init`) that rides the
    same donated-carry dispatch as a checkpoint resume. ``tol`` > 0
    enables RMSE-plateau early stop: the run is dispatched in segments
    (of the checkpoint cadence, else one iteration) and stops when the
    per-segment RMSE improvement drops below ``tol`` — what converts a
    warm start into fewer iterations instead of just a better curve.
    """
    from predictionio_tpu import faults
    from predictionio_tpu.core import checkpoint as ckpt

    key_u, key_v = jax.random.split(jax.random.PRNGKey(params.seed))
    U0 = _warm_init(init_factors(data.num_rows, params.rank, key_u),
                    warm_start[0] if warm_start is not None else None)
    V0 = _warm_init(init_factors(data.num_cols, params.rank, key_v),
                    warm_start[1] if warm_start is not None else None)
    U = to_storage(U0, params.storage_dtype)
    V = to_storage(V0, params.storage_dtype)
    # iterations rides as a dynamic loop bound; normalize it out of the
    # static params key so runs differing only in iteration count share
    # one compiled program
    static_params = dataclasses.replace(params, iterations=0)
    row_arrays = _device_bucket_arrays(data.row_buckets)
    col_arrays = _device_bucket_arrays(data.col_buckets)

    cfg = checkpoint_cfg if checkpoint_cfg is not None else ckpt.from_env()
    start_iter = 0
    fingerprint = None
    if cfg is not None and cfg.active:
        fingerprint = ckpt.data_fingerprint(
            data.rows, data.cols, data.vals, static_params, mesh="single"
        )
        if cfg.resume:
            snap = ckpt.load_checkpoint(cfg, fingerprint)
            if snap is not None and snap.iteration <= params.iterations:
                U = jax.device_put(snap.U)
                V = jax.device_put(snap.V)
                start_iter = snap.iteration
    import time as _time

    from predictionio_tpu.obs import progress as obs_progress

    nnz = len(data.vals)
    prog = obs_progress.ProgressPublisher(
        params.iterations, tol=tol, mesh="single", trainer="single",
        warm_start=warm_start is not None, **obs_device.where(),
        **(progress_extra or {}),
    )
    t0 = _time.perf_counter()
    final_rmse = None
    it = params.iterations
    if tol <= 0.0 and (cfg is None or cfg.every <= 0):
        prog.publish(start_iter)
        faults.fault_point("device.dispatch")
        out = _train_fused(
            U, V, row_arrays, col_arrays, static_params,
            params.iterations - start_iter,
        )
    else:
        # segmented dispatch: the checkpoint cadence, or — when only the
        # tol early stop asks for segments — every iteration, so the
        # plateau check rides the same per-segment RMSE trajectory the
        # progress file publishes
        ckpt_every = cfg.every if (cfg is not None and cfg.every > 0) else 0
        every = ckpt_every if ckpt_every > 0 else 1
        prog.publish(start_iter)
        out = (U, V)
        it = start_iter
        epochs = 0
        prev_rmse = None
        while it < params.iterations:
            seg = min(every, params.iterations - it)
            faults.fault_point("device.dispatch")
            t_seg = _time.perf_counter()
            out = _train_fused(
                out[0], out[1], row_arrays, col_arrays, static_params, seg
            )
            it += seg
            if ckpt_every > 0 and it < params.iterations:
                jax.block_until_ready(out)
                ckpt.save_checkpoint(
                    cfg, fingerprint, out[0], out[1], it, params.seed,
                    mesh="single",
                )
                epochs += 1
            seg_wall = _time.perf_counter() - t_seg
            seg_rmse = (
                rmse(out[0], out[1], data.rows, data.cols, data.vals)
                if (tol > 0.0 or prog.enabled)
                else None
            )
            if seg_rmse is not None:
                final_rmse = float(seg_rmse)
            prog.publish(
                it,
                rmse=seg_rmse,
                events_per_s=nnz * seg / seg_wall if seg_wall > 0 else None,
                segment_wall_s=seg_wall,
                checkpoint_epoch=epochs,
            )
            if tol > 0.0 and final_rmse is not None:
                if prev_rmse is not None and abs(prev_rmse - final_rmse) < tol:
                    logger.info(
                        "ALS early stop at iteration %d/%d: RMSE plateau "
                        "|%.6f - %.6f| < tol=%g",
                        it, params.iterations, prev_rmse, final_rmse, tol,
                    )
                    break
                prev_rmse = final_rmse
    jax.block_until_ready(out)
    prog.done(it, early_stopped=it < params.iterations)
    LAST_TRAIN_INFO.clear()
    LAST_TRAIN_INFO.update(
        iterations_run=it - start_iter,
        early_stopped=it < params.iterations,
        final_rmse=final_rmse,
        warm_start=warm_start is not None,
    )
    total = _time.perf_counter() - t0
    from predictionio_tpu.obs import metrics as obs_metrics

    obs_metrics.histogram(
        "pio_als_train_seconds",
        "Whole-run ALS training time",
        path="single",
    ).observe(total)
    if it > start_iter:
        # one fused fori_loop program — per-half-step is derived
        obs_metrics.histogram(
            "pio_als_halfstep_seconds",
            "Derived per-half-step time of the fused sharded ALS loop",
            mode="single",
        ).observe(total / (2 * (it - start_iter)))
    return out


@obs_device.track_jit("als.train_fused_sweep")
@functools.partial(jax.jit, static_argnames=("params",), donate_argnums=(0, 1))
def _train_fused_sweep(
    U0, V0, regs, alphas, row_arrays, col_arrays, params: ALSParams, iterations
):
    """C candidate trainings as ONE vmapped device program.

    U0/V0: [C, rows, D] / [C, cols, D] per-candidate inits; regs/alphas:
    [C] traced hyperparameters. The bucket tables are shared across the
    batch (in_axes=None) — XLA sees one batched program whose matmuls
    carry an extra candidate dimension, keeping the MXU fed where C
    sequential small trainings would each underfill it.
    """

    def one(U, V, reg, alpha):
        def half(target, other, bucket_arrays_list):
            gram = (
                compute_gram(other, params.compute_dtype)
                if params.implicit
                else None
            )
            for row_ids, col_ids, ratings, mask, seg_row in bucket_arrays_list:
                x = _solve_bucket_inline(
                    other,
                    gram,
                    (col_ids, ratings, mask),
                    params,
                    seg_row=seg_row,
                    num_solved_rows=row_ids.shape[0],
                    reg=reg,
                    alpha=alpha,
                )
                target = _scatter_rows(target, row_ids, x)
            return target

        def step(_, carry):
            U, V = carry
            U = half(U, V, row_arrays)
            V = half(V, U, col_arrays)
            return (U, V)

        return jax.lax.fori_loop(0, iterations, step, (U, V))

    return jax.vmap(one, in_axes=(0, 0, 0, 0))(U0, V0, regs, alphas)


def als_train_sweep(
    data: RatingsData, params_list: Sequence[ALSParams]
) -> list[tuple[jax.Array, jax.Array]]:
    """Train every candidate in ``params_list`` in ONE device program.

    The TPU answer to SURVEY §7's evaluation-sweep hard part: the
    reference runs sweep candidates serially on one SparkContext; here
    independent small trainings stack on the candidate axis (vmap), so a
    lambda/seed sweep costs roughly one training's dispatch overhead.

    Candidates must share the static program shape — iterations, bucket
    layout, compute dtype, implicit flag, and reg-weighting flags;
    ``reg``, ``alpha``, ``seed`` AND ``rank`` may vary per candidate.
    Raises ValueError otherwise.

    **Rank rides the candidate axis via zero-padding.** A candidate of
    rank r trains inside the max-rank program with its factor columns
    >= r initialized to exactly zero — and they STAY exactly zero: the
    Gramian of zero-padded factors is block-diagonal ``[[A_rr, 0], [0,
    0]]``, regularization lifts the dead block to ``lam*I``, and the
    solve returns exact zeros for the padded columns (0*x and sums of
    zeros are exact in floating point, any dtype). So each candidate's
    trajectory equals its standalone rank-r training for the same seed
    — the common rank-tuning sweep (MetricEvaluator.scala:185-260 runs
    those serially on Spark) compiles and dispatches ONCE.

    Returns a list of per-candidate (U, V) at each candidate's own rank
    (padded columns sliced off), matching ``als_train`` per candidate
    (same bucket math; tiny float differences can arise from batched-op
    scheduling).
    """
    if not params_list:
        raise ValueError("params_list must not be empty")
    base = params_list[0]
    static_fields = (
        "iterations", "implicit", "weighted_reg",
        "implicit_weighted_reg", "compute_dtype", "storage_dtype",
        "bucket_widths", "gather_chunk_bytes",
    )
    for p in params_list[1:]:
        diffs = [f for f in static_fields if getattr(p, f) != getattr(base, f)]
        if diffs:
            raise ValueError(
                "als_train_sweep candidates must share the static program "
                f"shape; differing fields: {diffs} (sweep reg/alpha/seed/"
                "rank instead, or run separate trainings)"
            )
    rank_max = max(p.rank for p in params_list)
    ranks = [p.rank for p in params_list]
    if len(set(ranks)) > 1 and any(p.reg <= 0 for p in params_list):
        # the padded columns' dead block is lifted to lam*I by the
        # regularizer; reg == 0 would leave it singular
        raise ValueError(
            "rank-sweep candidates need reg > 0 (the zero-padded factor "
            "block is kept solvable by the regularizer)"
        )
    # cost model: padding every candidate to rank_max multiplies the
    # dominant Gramian term by (rank_max/r)^2. When the pad waste beats
    # ~1.5x the exact work, split into per-rank groups instead — each
    # group still vmaps its lambda/seed candidates; the price is one
    # compile per distinct rank (a rank x lambda grid keeps full
    # batching within each rank)
    exact = sum(r * r for r in ranks)
    if len(set(ranks)) > 1 and len(ranks) * rank_max**2 > 1.5 * exact:
        out: list = [None] * len(params_list)
        for r in sorted(set(ranks)):
            idx = [i for i, p in enumerate(params_list) if p.rank == r]
            for i, res in zip(
                idx, als_train_sweep(data, [params_list[i] for i in idx])
            ):
                out[i] = res
        return out
    U0 = []
    V0 = []
    for p in params_list:
        key_u, key_v = jax.random.split(jax.random.PRNGKey(p.seed))
        pad = ((0, 0), (0, rank_max - p.rank))
        U0.append(jnp.pad(init_factors(data.num_rows, p.rank, key_u), pad))
        V0.append(jnp.pad(init_factors(data.num_cols, p.rank, key_v), pad))
    regs = jnp.asarray([p.reg for p in params_list], jnp.float32)
    alphas = jnp.asarray([p.alpha for p in params_list], jnp.float32)
    static_params = dataclasses.replace(
        base, iterations=0, reg=0.0, alpha=0.0, rank=rank_max
    )
    U, V = _train_fused_sweep(
        to_storage(jnp.stack(U0), base.storage_dtype),
        to_storage(jnp.stack(V0), base.storage_dtype),
        regs,
        alphas,
        _device_bucket_arrays(data.row_buckets),
        _device_bucket_arrays(data.col_buckets),
        static_params,
        base.iterations,
    )

    def cand(table, c, r):
        # per-candidate slice at its own rank, keeping the representation
        if isinstance(table, tuple):
            return (table[0][c, :, :r], table[1][c])
        return table[c, :, :r]

    return [
        (cand(U, c, p.rank), cand(V, c, p.rank))
        for c, p in enumerate(params_list)
    ]


def predict_pairs(U, V, rows: np.ndarray, cols: np.ndarray):
    """Scores for explicit (row, col) pairs: sum(U[r] * V[c], -1).
    Gathers cast (or dequantize, for int8 storage) to float32 so
    reduced-precision factors score/evaluate at full accumulation
    precision."""
    u = _read_rows(U, jnp.asarray(rows), jnp.float32)
    v = _read_rows(V, jnp.asarray(cols), jnp.float32)
    return jnp.sum(u * v, axis=-1)


def rmse(U, V, rows, cols, vals, chunk: int = 4_000_000) -> float:
    """Chunked over the pair dim: the [N, D] gathers of ``predict_pairs``
    at N=2*10^7, D=128 would alone exceed a v5e's HBM."""
    n = len(vals)
    total = 0.0
    for lo in range(0, n, chunk):
        hi = min(lo + chunk, n)
        pred = predict_pairs(U, V, rows[lo:hi], cols[lo:hi])
        total += float(jnp.sum((pred - jnp.asarray(vals[lo:hi])) ** 2))
    return float(np.sqrt(total / n))
