"""Mesh-sharded ALS: the multi-chip training path.

MLlib ALS distributes by blocking users x items across executors and
shuffling factor blocks each half-iteration (external Spark dep; SURVEY
§2.7). The TPU-native design here is the ALX execution model
(PAPERS.md, arxiv 2112.02194):

- both factor matrices live **uniformly row-sharded** over the mesh's
  ``data`` axis as ``NamedSharding(mesh, P("data"))``, moved through
  ``jax.jit`` with explicit ``in_shardings``/``out_shardings`` and
  donated buffers — no hand-rolled shard bookkeeping,
- ALL of a half-step's work — every degree bucket, every ring hop — is
  ONE ``shard_map`` region inside one compiled program; the entire
  training run is a single ``lax.fori_loop`` with a dynamic trip count,
- the implicit-feedback Gramian Y^T Y is computed shard-locally and
  ``psum``-reduced — a [D, D] allreduce instead of MLlib's shuffle.

**Packed bucket superstructure.** Instead of one sub-table per degree
bucket (whose count multiplies dispatch and shard_map regions), the
trainer packs the ENTIRE rating set into one padded table per side at a
single width ``K`` chosen to minimize padding (ops/als.py
``choose_pack_width`` / ``pack_entries``). Rows hotter than ``K`` span
several packed rows; ``seg`` maps each packed row to its shard-local
solved row and the per-segment normal equations are scatter-added
before the solve — the exact-hot-row guarantee of the bucketed layout
(all segments of a solved row on ONE shard; serpentine
descending-degree assignment balances load) at uniform shape.

**Two half-step variants, auto-selected** (``choose_sharded_mode``):

- ``gather``: tables are ``[S, B, K]`` (shard-major, global column
  ids). One fused ``all_gather`` of the opposite factors per half-step;
  each shard solves its packed rows against the full gathered matrix.
  Latency-optimal while the gathered side fits
  ``ALSParams.sharded_gather_budget_bytes`` per chip (ALX makes the
  same trade).
- ``ring``: the opposite factor TABLE never materializes whole on any
  chip. The value tables are the same gather-shaped ``[S, B, K]``; a
  routing table ``[S, T, E]`` with ``T = S`` rotation steps lists, per
  step, the slab-local ids of the entries whose opposite factor row is
  owned by shard ``(s - t) mod S`` — a device-side owner layout
  replacing the old host-side ``ring_partition_bucket`` repartition.
  The half-step is a single ``lax.scan`` over ``ppermute`` slab
  rotations: each step reads the slab rows its entries need (slab-local
  ids baked in at pack time), the stacked reads are permuted into
  ``[B, K, D]`` working-set order by one gather off a host-precomputed
  inverse map, and the last slot is peeled so a half-step costs S-1
  collective hops — after which the working set is bit-identical to
  gather's and the IDENTICAL packed solve runs.
  Per-chip memory — slab + the shard's ``~nnz/S``-slot working set —
  SHRINKS with mesh size, like MLlib's block ALS (reference
  examples/scala-parallel-recommendation/custom-prepartor/src/main/
  scala/ALSAlgorithm.scala:72 delegates to that substrate).

Both variants share the single-chip bucket math (ops/als.py
``_bucket_weights`` / ``_gramian_rhs_gathered`` /
``_finish_bucket_solve``), are exact on segmented hot rows, and thread
int8 ``(values, scales)`` / bf16 storage pairs through every collective
(quantized bytes on the wire). Per-row gather temps stay bounded by
``ALSParams.gather_chunk_bytes`` in both.

The legacy host-side layout (``shard_bucket`` / ``ring_partition_bucket``
/ ``resegment_skewed_rows``) is kept below as the REFERENCE
implementation: the property tests check the packed device layout
preserves every (row, col, rating) triple against it, and the skew
analysis in its docstrings documents why the packed layout replaced it.
"""

from __future__ import annotations

import dataclasses
import functools
import heapq
import logging
from dataclasses import dataclass
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from predictionio_tpu.obs import device as obs_device
from predictionio_tpu.obs import metrics as obs_metrics
from predictionio_tpu.obs import progress as obs_progress
from predictionio_tpu.ops import als as als_ops
from predictionio_tpu.parallel.mesh import factor_sharding, replicated_sharding

logger = logging.getLogger(__name__)


# ---------------------------------------------------------------------------
# Host-side: packed per-shard layout (the device-side owner layout)
# ---------------------------------------------------------------------------


@dataclass
class SideLayout:
    """Degree-balanced placement of ONE side's factor rows on the mesh.

    Factor tables are stored PERMUTED: row ``u`` lives at table position
    ``assign[u] * rows_per_shard + loc[u]``, with shards assigned
    serpentine over descending degree. This is the uniform-shard layout
    that makes ring mode work under popularity skew: slab ownership
    becomes ``assign[col]`` (balanced entry load per owner — a pareto
    catalog no longer concentrates every hot column on slab 0 the way
    contiguous ``col // slab_rows`` ownership does), and the solved
    side's table position doubles as its accumulator/scatter slot.
    ``rows_per_shard`` includes one guaranteed-free trailing slot per
    shard — the scatter target for padding rows. The final factors are
    un-permuted once per training run (``positions`` gather).
    """

    assign: np.ndarray  # [N] shard of each factor row
    loc: np.ndarray  # [N] shard-local table slot
    rows_per_shard: int  # R: max rows on any shard, +1 dummy slot
    shards: int

    @property
    def positions(self) -> np.ndarray:
        """[N] global (permuted) table position of every factor row."""
        return self.assign * self.rows_per_shard + self.loc

    @property
    def table_len(self) -> int:
        return self.shards * self.rows_per_shard

    def dummy_position(self, shard: int) -> int:
        """The guaranteed-free last slot of ``shard``."""
        return shard * self.rows_per_shard + self.rows_per_shard - 1


def _envelope(n: int) -> int:
    """Smallest power of two >= ``n`` that still leaves ~12.5% free
    headroom past it. The layout-stable warm-retrain path (prep cache)
    sizes every packed dimension to this envelope so a small delta
    splices into the FREE slots without changing any array shape — the
    shape stability that lets a warm solve re-enter the already-compiled
    fused trainer."""
    n = max(1, int(n))
    e = 1 << max(3, (n - 1).bit_length())
    if e - n < max(1, n // 8):
        e *= 2
    return e


def build_side_layout(
    ids: np.ndarray, num_rows: int, shards: int, stable_shapes: bool = False
) -> SideLayout:
    """Lay one side's ``num_rows`` factor rows out over ``shards``.

    ``ids`` are that side's COO ids (degree = occurrence count; rows
    absent from the data get degree 0 and fill the tail slots). The
    serpentine over descending degree balances per-shard entry load to
    within one row's degree; within a shard, slots follow ascending row
    id — a stable layout independent of degree ties.

    ``stable_shapes`` pads ``rows_per_shard`` to the pow2
    :func:`_envelope` so later small deltas can append rows per shard
    without resizing the factor tables (extra slots stay zero and never
    scatter — correctness-free headroom, like the per-shard dummy).
    """
    deg = np.bincount(np.asarray(ids, dtype=np.int64), minlength=num_rows)
    order = np.argsort(-deg, kind="stable")
    pos = np.arange(num_rows)
    blk, off = divmod(pos, shards)
    assign = np.empty(num_rows, np.int64)
    assign[order] = np.where(blk % 2 == 0, off, shards - 1 - off)
    loc = np.empty(num_rows, np.int64)
    max_count = 0
    for s in range(shards):
        js = np.nonzero(assign == s)[0]  # ascending row id
        loc[js] = np.arange(len(js))
        max_count = max(max_count, len(js))
    R = _envelope(max_count + 1) if stable_shapes else max_count + 1
    return SideLayout(
        assign=assign, loc=loc, rows_per_shard=R, shards=shards
    )


def extend_side_layout(
    layout: SideLayout,
    new_num_rows: int,
    delta_ids: np.ndarray,
    shard_loads=None,
) -> SideLayout | None:
    """Append ids ``[len(assign), new_num_rows)`` into an existing layout
    WITHOUT moving any placed row (the layout-reuse half of the warm
    sharded retrain: factor placement — and with it the compiled fused
    program — survives the delta).

    New ids are assigned least-loaded-first (``shard_loads`` seeds the
    heap with the cached pack's per-shard entry counts; each new id adds
    its delta degree), filling each shard's existing order at
    ``loc = current row count``. Returns ``None`` when any shard would
    lose its guaranteed-free trailing slot — the caller falls back to a
    fresh layout (counted as ``layout_drift``)."""
    old_n = len(layout.assign)
    if new_num_rows < old_n:
        return None
    if new_num_rows == old_n:
        return layout
    S, R = layout.shards, layout.rows_per_shard
    row_counts = np.bincount(layout.assign, minlength=S)
    deg = np.bincount(
        np.asarray(delta_ids, dtype=np.int64), minlength=new_num_rows
    )[old_n:]
    loads = (
        np.asarray(shard_loads, dtype=np.float64)
        if shard_loads is not None
        else row_counts.astype(np.float64)
    )
    assign = np.concatenate(
        [layout.assign, np.zeros(new_num_rows - old_n, np.int64)]
    )
    loc = np.concatenate(
        [layout.loc, np.zeros(new_num_rows - old_n, np.int64)]
    )
    heap = [
        (float(loads[s]), int(row_counts[s]), s)
        for s in range(S)
        if row_counts[s] < R - 1  # keep the dummy slot free
    ]
    heapq.heapify(heap)
    for j in np.argsort(-deg, kind="stable"):
        if not heap:
            return None
        load, cnt, s = heapq.heappop(heap)
        u = old_n + int(j)
        assign[u] = s
        loc[u] = cnt
        cnt += 1
        if cnt < R - 1:
            heapq.heappush(heap, (load + float(deg[j]), cnt, s))
    return SideLayout(assign=assign, loc=loc, rows_per_shard=R, shards=S)


@dataclass
class PackedSide:
    """One side's ratings packed for the fused half-step.

    ``ratings``/``mask`` are ``[S, B, K]`` (shard ``s`` owns ``[s]``)
    in BOTH modes — the packed solve is mode-independent. ``col_ids``
    differs: ``mode="gather"`` stores PERMUTED-GLOBAL table positions
    of the opposite layout ``[S, B, K]`` (one lookup against the
    all_gathered table); ``mode="ring"`` stores a routing table
    ``[S, T, E]`` with ``T = S`` rotation steps — step ``[s, t]`` lists
    the slab-local col ids of exactly the entries whose opposite factor
    row is owned by shard ``(s - t) mod S`` under the opposite
    :class:`SideLayout`, and ``seg[:, :, 1:]`` carries the inverse
    gather map (working-set slot ``(b, k)`` -> flat ``[T * E]`` scan
    output position; padding -> the appended zero row), so the scan can
    assemble the same ``[B, K, D]`` working set gather's lookup
    produces, one passing slab at a time, with a single final gather.

    ``seg`` (``[S, B]`` gather; ``[S, B, 1 + K]`` ring, slot ``0``)
    maps each packed row to its shard-local solved slot in
    ``[0, rows_per_shard)`` — the solved side's own table ``loc`` — and
    all packed rows of one solved row live on its ``assign`` shard
    (exact hot rows). ``row_ids`` is ``[S * rows_per_shard]`` permuted
    table positions for the global scatter of solutions (each shard's
    dummy slot absorbs the zero solutions of never-solved slots).
    ``packed_rows`` counts REAL packed rows before the per-shard /
    per-slot max padding — the sizing guard compares ``col_ids.size``
    against it to detect residual owner-skew blowup.
    """

    row_ids: np.ndarray
    col_ids: np.ndarray
    ratings: np.ndarray
    mask: np.ndarray
    seg: np.ndarray
    mode: str
    shards: int
    rows_per_shard: int
    pack_width: int
    packed_rows: int


def _group_positions(g: np.ndarray) -> np.ndarray:
    """Per-element rank within its group (stable order)."""
    order = np.argsort(g, kind="stable")
    gs = g[order]
    if len(gs) == 0:
        return np.zeros(0, np.int64)
    starts = np.concatenate([[0], np.nonzero(np.diff(gs))[0] + 1])
    cnts = np.diff(np.concatenate([starts, [len(gs)]]))
    r = np.arange(len(gs)) - np.repeat(starts, cnts)
    pos = np.empty(len(g), np.int64)
    pos[order] = r
    return pos


def pack_sharded_side(
    t_ids: np.ndarray,
    o_ids: np.ndarray,
    vals: np.ndarray,
    t_layout: SideLayout,
    o_layout: SideLayout,
    shards: int,
    mode: str,
    stable_shapes: bool = False,
) -> PackedSide:
    """Build one side's :class:`PackedSide` from raw COO entries.

    ``t_ids`` are this side's (solved) row ids under ``t_layout``,
    ``o_ids`` the opposite side's column ids under ``o_layout``. Rows
    absent from ``t_ids`` are never solved and keep their init factors —
    same as single-chip. Entries keep their input order within each
    packed group (stable packing), which keeps the accumulation order —
    and thus the float32 trajectory — aligned with single-chip
    ``als_train``.

    ``stable_shapes`` pads the packed-row count ``B`` (and ring's
    rotation-cell width ``E``) to the pow2 :func:`_envelope`, leaving
    free all-zero rows/slots a later :func:`splice_packed_side` fills in
    place. Padding rows carry ``mask=0`` and scatter zeros, so the
    float32 trajectory is unchanged (exact zeros add exactly).
    """
    t_ids = np.asarray(t_ids, dtype=np.int64)
    o_ids = np.asarray(o_ids, dtype=np.int64)
    vals = np.asarray(vals, dtype=np.float32)
    uniq, inv, counts = np.unique(t_ids, return_inverse=True, return_counts=True)
    n_uniq = max(1, len(uniq))
    R = t_layout.rows_per_shard

    # scatter map: solved slots -> their table position; everything else
    # (never-solved slots, the trailing dummy) -> the shard's dummy slot
    row_ids = np.empty((shards, R), np.int32)
    for s in range(shards):
        row_ids[s, :] = t_layout.dummy_position(s)
    if len(uniq):
        row_ids[t_layout.assign[uniq], t_layout.loc[uniq]] = t_layout.positions[
            uniq
        ].astype(np.int32)

    assign_e = t_layout.assign[t_ids]
    loc_u = t_layout.loc[uniq] if len(uniq) else np.zeros(0, np.int64)
    base_key = assign_e * n_uniq + inv

    if mode == "gather":
        K = als_ops.choose_pack_width(counts)
        e_row, e_slot, row_key, n_rows = als_ops.pack_entries(base_key, K)
        row_shard = row_key // n_uniq
        row_loc = loc_u[row_key % n_uniq] if len(uniq) else row_key
        B = (
            max(1, int(np.bincount(row_shard, minlength=shards).max()))
            if n_rows
            else 1
        )
        if stable_shapes:
            B = _envelope(B)
        row_pos = _group_positions(row_shard)
        col_ids = np.zeros((shards, B, K), np.int32)
        ratings = np.zeros((shards, B, K), np.float32)
        mask = np.zeros((shards, B, K), np.float32)
        seg = np.zeros((shards, B), np.int32)
        if n_rows:
            seg[row_shard, row_pos] = row_loc
            rs, rp = row_shard[e_row], row_pos[e_row]
            col_ids[rs, rp, e_slot] = o_layout.positions[o_ids]
            ratings[rs, rp, e_slot] = vals
            mask[rs, rp, e_slot] = 1.0
    elif mode == "ring":
        # SAME value tables as gather ([S, B, K] ratings/mask, one seg
        # per packed row) plus a per-rotation ROUTING table: the scan
        # assembles the gather variant's [B, K, D] working set
        # incrementally, each step writing the slab rows its entries
        # need into their exact (packed row, slot) positions. Both
        # variants then run the identical one-dot packed solve — ring's
        # extra cost is only the S-1 small per-step gathers, not a
        # second (owner-fragmented, padding-heavy) packing.
        K = als_ops.choose_pack_width(counts)
        e_row, e_slot, row_key, n_rows = als_ops.pack_entries(base_key, K)
        row_shard = row_key // n_uniq
        row_loc = loc_u[row_key % n_uniq] if len(uniq) else row_key
        B = (
            max(1, int(np.bincount(row_shard, minlength=shards).max()))
            if n_rows
            else 1
        )
        if stable_shapes:
            B = _envelope(B)
        row_pos = _group_positions(row_shard)
        ratings = np.zeros((shards, B, K), np.float32)
        mask = np.zeros((shards, B, K), np.float32)
        owner_e = o_layout.assign[o_ids]
        rs_e = row_shard[e_row]
        step_e = (rs_e - owner_e) % shards
        cell = rs_e * shards + step_e
        E = (
            max(1, int(np.bincount(cell, minlength=shards * shards).max()))
            if n_rows
            else 1
        )
        if stable_shapes:
            E = _envelope(E)
        e_pos = _group_positions(cell)
        # routing: [S, T, E] slab-local col ids read per rotation step
        # (padding rereads slab row 0 — discarded by the gather map).
        # seg grows an INVERSE gather map: seg[:, :, 1:] holds, per
        # working-set slot (b, k), the flat [T * E] position its row
        # lands at in the scan's stacked outputs (padding slots -> the
        # appended zero row T * E). Assembly is then a pure gather —
        # XLA:CPU lowers row scatters serially, ~10x slower than the
        # equivalent gather, and real-slot order is already known here.
        col_ids = np.zeros((shards, shards, E), np.int32)
        seg = np.full((shards, B, 1 + K), shards * E, np.int32)
        seg[:, :, 0] = 0
        if n_rows:
            seg[row_shard, row_pos, 0] = row_loc
            ratings[rs_e, row_pos[e_row], e_slot] = vals
            mask[rs_e, row_pos[e_row], e_slot] = 1.0
            col_ids[rs_e, step_e, e_pos] = o_layout.loc[o_ids]
            seg[rs_e, row_pos[e_row], 1 + e_slot] = step_e * E + e_pos
    else:
        raise ValueError(f"mode must be gather|ring, got {mode!r}")

    return PackedSide(
        row_ids=row_ids.reshape(-1),
        col_ids=col_ids,
        ratings=ratings,
        mask=mask,
        seg=seg,
        mode=mode,
        shards=shards,
        rows_per_shard=R,
        pack_width=K,
        packed_rows=n_rows,
    )


def splice_packed_side(
    ps: PackedSide,
    t_layout: SideLayout,
    o_layout: SideLayout,
    delta_t: np.ndarray,
    delta_o: np.ndarray,
    delta_vals: np.ndarray,
) -> PackedSide | None:
    """Append delta entries into a cached :class:`PackedSide` under the
    REUSED (possibly :func:`extend_side_layout`-extended) layouts,
    preserving every array shape — the packed half of the
    zero-recompile warm retrain.

    Each delta entry first tops up its solved row's one partial packed
    segment (``pack_entries`` fills slots as a prefix, so occupancy is
    recoverable from the mask), else claims the shard's next free
    envelope row; ring mode additionally claims the next free slot of
    its ``(shard, rotation-step)`` routing cell and extends the inverse
    gather map. Returns ``None`` when the delta outgrows the free
    slots of any dimension — the caller falls back to a fresh pack
    (``layout_drift``). Entry order within a packed row is append
    order, which a fresh repack would not reproduce exactly: the warm
    solve is float-equal to ~1e-6 of a fresh-layout solve, not
    bit-identical (the fallback path stays bit-identical).
    """
    S, R, K = ps.shards, ps.rows_per_shard, ps.pack_width
    mode = ps.mode
    # entry arrays may be read-only mmap views out of the prep cache
    row_ids = np.array(ps.row_ids, dtype=np.int32, copy=True)
    col_ids = np.array(ps.col_ids, copy=True)
    ratings = np.array(ps.ratings, copy=True)
    mask = np.array(ps.mask, copy=True)
    seg = np.array(ps.seg, copy=True)
    B = ratings.shape[1]
    used = (mask > 0).sum(axis=2).astype(np.int64)  # [S, B] filled slots
    n_real = (used > 0).sum(axis=1).astype(np.int64)  # real rows: [0, n_s)
    seg_slot = seg if mode == "gather" else seg[:, :, 0]
    # the at-most-one partial packed row per (shard, solved slot)
    partial = {
        (int(s), int(seg_slot[s, b])): int(b)
        for s, b in zip(*np.nonzero((used > 0) & (used < K)))
    }
    if mode == "ring":
        E = col_ids.shape[2]
        gmap = seg[:, :, 1:]
        cell_fill = np.zeros((S, S), np.int64)
        for s in range(S):
            v = gmap[s][mask[s] > 0]  # real slots: step * E + e_pos
            if v.size:
                cell_fill[s] = np.bincount(v // E, minlength=S)
    delta_t = np.asarray(delta_t, np.int64)
    delta_o = np.asarray(delta_o, np.int64)
    delta_vals = np.asarray(delta_vals, np.float32)
    o_pos = o_layout.positions
    packed_rows = int(ps.packed_rows)
    for t, o, v in zip(delta_t, delta_o, delta_vals):
        s, l = int(t_layout.assign[t]), int(t_layout.loc[t])
        b = partial.get((s, l))
        if b is None:
            b = int(n_real[s])
            if b >= B:
                return None  # envelope exhausted
            n_real[s] += 1
            packed_rows += 1
            seg_slot[s, b] = l
            partial[(s, l)] = b
            k = 0
        else:
            k = int(used[s, b])
        ratings[s, b, k] = v
        mask[s, b, k] = 1.0
        used[s, b] = k + 1
        if used[s, b] >= K:
            partial.pop((s, l), None)
        if mode == "gather":
            col_ids[s, b, k] = o_pos[o]
        else:
            step = (s - int(o_layout.assign[o])) % S
            e = int(cell_fill[s, step])
            if e >= E:
                return None  # routing cell exhausted
            col_ids[s, step, e] = o_layout.loc[o]
            seg[s, b, 1 + k] = step * E + e
            cell_fill[s, step] = e + 1
        row_ids[s * R + l] = s * R + l  # solved slots self-address
    return PackedSide(
        row_ids=row_ids,
        col_ids=col_ids,
        ratings=ratings,
        mask=mask,
        seg=seg,
        mode=mode,
        shards=S,
        rows_per_shard=R,
        pack_width=K,
        packed_rows=packed_rows,
    )


def upload_packed_side(ps: PackedSide, mesh: Mesh, axis: str) -> tuple:
    """Place one packed side on the mesh: tables sharded ``P(axis)`` on
    the shard-major dim, scatter row-ids replicated."""
    table = factor_sharding(mesh, axis)
    repl = replicated_sharding(mesh)
    nbytes = (
        ps.row_ids.nbytes + ps.col_ids.nbytes + ps.ratings.nbytes
        + ps.mask.nbytes + ps.seg.nbytes
    )
    with obs_device.transfer("h2d", "train.packed_side", nbytes):
        return (
            jax.device_put(ps.row_ids, repl),
            jax.device_put(ps.col_ids, table),
            jax.device_put(ps.ratings, table),
            jax.device_put(ps.mask, table),
            jax.device_put(ps.seg, table),
        )


def packed_table_bytes_per_chip(sides: Sequence[PackedSide], shards: int) -> int:
    """Per-chip bytes of the packed tables (4-byte col-id/routing,
    rating, mask, and seg/gather-map slots, including padding)."""
    return (
        sum(
            4 * (ps.col_ids.size + ps.ratings.size + ps.mask.size + ps.seg.size)
            for ps in sides
        )
        // max(1, shards)
    )


def _check_ring_layout(
    row_ps: PackedSide, col_ps: PackedSide, params: als_ops.ALSParams, shards: int
) -> None:
    """Sizing guard for the ring layout's one blowup mode: owner skew.

    The routing table pads every ``[s, t]`` rotation slot to the max
    per-step entry count ``E``. The degree-balanced :class:`SideLayout`
    keeps owner loads near-uniform, so residual skew is rare (it takes
    correlated row/owner structure the serpentine cannot split —
    e.g. every entry of every row pointing at ONE opposite row); when
    it does happen most steps sit empty and the routing costs up to
    ``S`` times the ideally-balanced packing. Past 2x AND past the
    gather budget the run refuses with the knob named.
    """
    packed = packed_table_bytes_per_chip([row_ps, col_ps], shards)
    # balanced cost: value+mask slots (8B) plus one 4B routing id and
    # one 4B gather-map entry per slot, with zero rotation-step padding
    ideal = (
        sum(ps.packed_rows * ps.pack_width * 16 for ps in (row_ps, col_ps))
        // max(1, shards)
    )
    budget = params.sharded_gather_budget_bytes
    if packed > 2 * max(1, ideal):
        if packed > budget:
            raise ValueError(
                f"ring-mode packed tables need {packed} bytes/chip under "
                f"owner skew (balanced packing: {ideal}), over "
                f"sharded_gather_budget_bytes={budget}; raise the budget, "
                "add chips, or use mode='gather'"
            )
        if packed > (1 << 20):
            # tiny problems are always padding-dominated; only flag skew
            # once the tables are big enough for the blowup to matter
            logger.warning(
                "ring-mode packed tables blow up under owner skew: %d "
                "bytes/chip vs %d ideally balanced (within budget %d; "
                "proceeding)",
                packed,
                ideal,
                budget,
            )


# ---------------------------------------------------------------------------
# Device-side: fused training program
# ---------------------------------------------------------------------------


@dataclass
class ShardedALSState:
    """Factors resident on the mesh in :class:`SideLayout` (permuted)
    order, one guaranteed-free dummy slot per shard."""

    mesh: Mesh
    axis: str
    U: jax.Array  # [S * row rows_per_shard, D] sharded P(axis)
    V: jax.Array  # [S * col rows_per_shard, D] sharded P(axis)
    num_rows: int
    num_cols: int


def _padded_len(n: int, shards: int) -> int:
    return n + 1 + ((-(n + 1)) % shards)  # +1 dummy row, then round up


def init_sharded_factors(
    data: als_ops.RatingsData,
    params: als_ops.ALSParams,
    mesh: Mesh,
    axis: str = "data",
    row_layout: SideLayout | None = None,
    col_layout: SideLayout | None = None,
    warm_start=None,
) -> ShardedALSState:
    shards = mesh.shape[axis]
    if row_layout is None:
        row_layout = build_side_layout(data.rows, data.num_rows, shards)
    if col_layout is None:
        col_layout = build_side_layout(data.cols, data.num_cols, shards)
    key_u, key_v = jax.random.split(jax.random.PRNGKey(params.seed))
    # draw the TRUE-size init (identical to single-chip als_train for the
    # same seed — the parity tests rely on trajectory equality), then
    # place each row at its layout position; unfilled slots (per-shard
    # dummies) stay zero and contribute nothing to the psum'd Gramian
    U = np.zeros((row_layout.table_len, params.rank), np.float32)
    V = np.zeros((col_layout.table_len, params.rank), np.float32)
    U_true = np.asarray(
        als_ops.init_factors(data.num_rows, params.rank, key_u)
    )
    V_true = np.asarray(
        als_ops.init_factors(data.num_cols, params.rank, key_v)
    )
    if warm_start is not None:
        # warm factors ride in true row order (NaN rows keep the cold
        # draw — same merge rule as single-chip als_train) and are
        # re-permuted through the SideLayout with everything else
        w_u = np.asarray(warm_start[0], dtype=np.float32)
        w_v = np.asarray(warm_start[1], dtype=np.float32)
        U_true = np.where(np.isnan(w_u), U_true, w_u)
        V_true = np.where(np.isnan(w_v), V_true, w_v)
    U[row_layout.positions] = U_true
    V[col_layout.positions] = V_true
    sharding = factor_sharding(mesh, axis)
    # factors persist (and all_gather/ppermute) in storage_dtype: bf16
    # halves the per-half-iteration ICI traffic and the gathered working
    # set while solves still accumulate float32 (ops/als.py
    # ALSParams.storage_dtype)
    U_dev = jax.device_put(U, sharding)
    V_dev = jax.device_put(V, sharding)
    if params.storage_dtype == "int8":
        # per-row quantization reduces over the (unsharded) rank dim
        # only, so the row sharding of both values and scales is
        # preserved; the all_gather/ppermute'd working set becomes the
        # (int8 values, f32 scales) pair — ~4x fewer ICI bytes than f32
        U_dev = als_ops.quantize_rows(U_dev)
        V_dev = als_ops.quantize_rows(V_dev)
    elif params.storage_dtype != "float32":
        sd = jnp.dtype(params.storage_dtype)
        U_dev = U_dev.astype(sd)  # elementwise: sharding preserved
        V_dev = V_dev.astype(sd)
    return ShardedALSState(
        mesh=mesh,
        axis=axis,
        U=U_dev,
        V=V_dev,
        num_rows=data.num_rows,
        num_cols=data.num_cols,
    )


def _gather_table_rows(table, positions: np.ndarray, sharding):
    """Un-permute a trained factor table: gather ``positions`` (original
    row order) out of the layout-ordered table, keeping the storage
    representation (int8 ``(values, scales)`` gathers both leaves).
    The gather runs at full table length (dummy-padded tail) so the
    result can be re-placed under the row ``sharding``, then is trimmed
    to the true row count."""
    n = len(positions)
    table_len = als_ops.table_rows(table)
    pos_full = np.full(table_len, table_len - 1, np.int32)
    pos_full[:n] = positions
    pos = jnp.asarray(pos_full)
    gathered = jax.tree_util.tree_map(
        lambda t: jax.device_put(t[pos], sharding), table
    )
    return als_ops.slice_rows(gathered, n)


@functools.lru_cache(maxsize=None)
def _fused_trainer(mesh: Mesh, axis: str, mode: str, params: als_ops.ALSParams):
    """Build (and cache) the jitted trainer for one (mesh, mode, params).

    The returned function runs the WHOLE training run as one XLA
    program: ``jax.jit`` with explicit ``in_shardings``/``out_shardings``
    (uniform ``NamedSharding(mesh, P(axis))`` on every factor/table
    leaf; a pytree-prefix covers the int8 ``(values, scales)`` pairs)
    and donated factor buffers, a ``lax.fori_loop`` over a DYNAMIC
    iteration count (one compile serves any count — the lru_cache key
    is the iteration-normalized params), and ONE ``shard_map`` region
    per half-step:

    - ``mode="gather"``: all_gather the opposite factors (tiled — one
      fused ICI collective), then a single packed-table solve via the
      single-chip bucket math.
    - ``mode="ring"``: a single ``lax.scan`` over ``ppermute`` slab
      rotations. Step ``t`` consumes rotation slot ``t`` of the routing
      table — whose slab-local column ids index the passing slab
      directly — and stacks the reads as scan outputs; one final gather
      off the host-precomputed inverse map lands them in gather's exact
      ``[B, K, D]`` working-set order, and the identical packed solve
      runs. The final slot is peeled out of the scan so a half-step
      costs S-1 hops, and only the slab rides the carry (donated, no
      per-hop re-materialization).
    """
    shards = mesh.shape[axis]
    factor = factor_sharding(mesh, axis)
    repl = replicated_sharding(mesh)
    dt = jnp.dtype(params.compute_dtype)
    perm = [(i, (i + 1) % shards) for i in range(shards)]

    def opposite_gram(other_shard):
        if not params.implicit:
            return None
        return jax.lax.psum(
            als_ops.compute_gram(other_shard, params.compute_dtype), axis
        )

    def gather_fn(R, other_shard, col_ids, ratings, mask, seg):
        # int8 storage: other_shard is the (values, scales) pair; gather
        # both leaves so the ICI collective moves quantized bytes
        other_full = jax.tree_util.tree_map(
            lambda t: jax.lax.all_gather(t, axis, tiled=True), other_shard
        )
        return als_ops._solve_bucket_inline(
            other_full,
            opposite_gram(other_shard),
            (col_ids[0], ratings[0], mask[0]),
            params,
            seg_row=seg[0],
            num_solved_rows=R,
        )

    def ring_fn(R, other_shard, col_ids, ratings, mask, seg):
        # col_ids is the ROUTING table [T, E] of slab-local col ids to
        # read per rotation step; ratings/mask are the exact
        # gather-shaped [B, K] tables and seg is [B, 1 + K] (solved
        # slot, then the inverse gather map). The scan ASSEMBLES the
        # gather variant's [B, K, D] working set: step t reads the rows
        # this shard's entries need from the slab passing by (their
        # owner's rotation); the stacked reads are then permuted into
        # (row, slot) order by ONE gather off the inverse map — padding
        # slots pull the appended zero row. After S-1 hops the working
        # set is bit-identical to what gather's all_gather + table
        # lookup produces, and the SAME packed solve runs — one dot,
        # one segment scatter, one batched Cholesky.
        lcol, rt, mt = col_ids[0], ratings[0], mask[0]
        sg, ginv = seg[0][:, 0], seg[0][:, 1:]
        D = als_ops.table_dim(other_shard)
        T, E = lcol.shape
        gram = opposite_gram(other_shard)

        def assemble(slab, lc):
            rows = als_ops._read_rows(slab, lc, dt)
            # int8 slabs rotate as (values, scales) — quantized ICI hops
            slab = jax.tree_util.tree_map(
                lambda x: jax.lax.ppermute(x, axis, perm), slab
            )
            return slab, rows

        # S-1 rotate-and-read steps in ONE scan, final slot peeled (the
        # last rotation's hop would be unused): S-1 hops per half-step,
        # all inside this program. Only the slab rides the carry; the
        # per-step reads stack as scan outputs.
        slab, rows_t = jax.lax.scan(assemble, other_shard, lcol[:-1])
        rows_all = jnp.concatenate(
            [rows_t, als_ops._read_rows(slab, lcol[-1], dt)[None]], axis=0
        )
        flat = jnp.concatenate(
            [rows_all.reshape(T * E, D), jnp.zeros((1, D), dt)], axis=0
        )
        vg = flat[ginv]
        w, rr = als_ops._bucket_weights(rt, mt, params, params.alpha)
        A, b = als_ops._gramian_rhs(vg, w, rr)
        return als_ops._finish_bucket_solve(
            A, b, mt.sum(axis=1), gram, params, sg, R, params.reg
        )

    shard_fn = {"gather": gather_fn, "ring": ring_fn}[mode]

    def half(target, other, pack):
        row_ids, col_ids, ratings, mask, seg = pack
        R = row_ids.shape[0] // shards
        # int8 factor tables are (values, scales) pairs: spell out the
        # matching spec structure (both leaves row-sharded over axis)
        other_spec = (P(axis), P(axis)) if isinstance(other, tuple) else P(axis)
        x = jax.shard_map(
            functools.partial(shard_fn, R),
            mesh=mesh,
            in_specs=(other_spec, P(axis), P(axis), P(axis), P(axis)),
            out_specs=P(axis),
        )(other, col_ids, ratings, mask, seg)
        # solves come back float32 [S*R, D]; factors persist in
        # storage_dtype (int8 requantizes here, fresh per-row scales)
        target = als_ops._scatter_rows(target, row_ids, x)
        return jax.tree_util.tree_map(
            lambda t: jax.lax.with_sharding_constraint(t, factor), target
        )

    def train(U, V, row_pack, col_pack, iterations):
        def step(_, carry):
            U, V = carry
            U = half(U, V, row_pack)
            V = half(V, U, col_pack)
            return (U, V)

        return jax.lax.fori_loop(0, iterations, step, (U, V))

    pack_s = (repl, factor, factor, factor, factor)
    return obs_device.track_jit(f"sharded.train.{mode}")(
        jax.jit(
            train,
            donate_argnums=(0, 1),
            in_shardings=(factor, factor, pack_s, pack_s, repl),
            out_shardings=(factor, factor),
        )
    )


def choose_sharded_mode(
    data: als_ops.RatingsData, params: als_ops.ALSParams, shards: int
) -> str:
    """Pick the half-step variant for a run: ``gather`` while the larger
    gathered side fits ``params.sharded_gather_budget_bytes`` per chip,
    ``ring`` past it (module docstring, "Two half-step variants")."""
    rows = max(
        _padded_len(data.num_rows, shards), _padded_len(data.num_cols, shards)
    )
    gathered = rows * _factor_row_bytes(params)
    return "ring" if gathered > params.sharded_gather_budget_bytes else "gather"


def _factor_row_bytes(params: als_ops.ALSParams) -> int:
    """Bytes one gathered factor row costs in storage form (int8 rows
    carry their f32 per-row scale alongside the quantized values)."""
    if params.storage_dtype == "int8":
        return params.rank + 4
    return params.rank * jnp.dtype(params.storage_dtype).itemsize


def halfstep_collective_bytes(
    num_rows: int, num_cols: int, shards: int, params: als_ops.ALSParams, mode: str
) -> dict:
    """Per-chip ICI traffic of ONE half-step (the larger, opposite-side
    gather; both halves of an iteration together move both sides).

    ``gather``: one fused all_gather — each chip receives the other
    S-1 slabs of the opposite table in a single collective. ``ring``:
    S-1 ``ppermute`` hops, each moving one opposite-factor slab. Total
    bytes match; the ring trades the fused collective for S-1 smaller
    hops (and never materializes the full table).
    """
    opp = max(_padded_len(num_rows, shards), _padded_len(num_cols, shards))
    row_bytes = _factor_row_bytes(params)
    slab_bytes = (opp // shards) * row_bytes
    hops = 1 if mode == "gather" else max(1, shards - 1)
    per_hop = slab_bytes * (shards - 1) if mode == "gather" else slab_bytes
    return {
        "mode": mode,
        "hops_per_halfstep": hops,
        "bytes_per_hop": int(per_hop),
        "total_bytes_per_halfstep": int(per_hop * hops),
    }


def sharded_memory_estimate(
    num_rows: int,
    num_cols: int,
    nnz: int,
    shards: int,
    params: als_ops.ALSParams,
    mode: str,
) -> dict:
    """Analytic peak-HBM estimate per chip for one training run (bytes).

    Counts the resident terms of the memory model: both factor shards,
    the packed tables (12 bytes/entry/side gather, 16 ring — routing
    ids and the inverse gather map ride along; padding ignored), and
    the mode's working set.
    ``gather`` holds the full gathered opposite table — it does NOT
    shrink with mesh size. ``ring`` holds one rotating slab plus the
    shard's assembled f32 ``[B, K, D]`` working set (~``nnz/S`` slots),
    both of which DO shrink with mesh size — that 1/S scaling, not the
    absolute size, is what lets ring outlive the gather budget.
    """
    row_bytes = _factor_row_bytes(params)
    u_len = _padded_len(num_rows, shards)
    v_len = _padded_len(num_cols, shards)
    D = params.rank
    factors = (u_len + v_len) * row_bytes // shards
    tables = 2 * nnz * (12 if mode == "gather" else 16) // shards
    opp = max(u_len, v_len)
    if mode == "gather":
        working = opp * row_bytes
    else:
        working = (opp // shards) * row_bytes + (nnz // shards) * D * 4
    return {
        "mode": mode,
        "factors_bytes": int(factors),
        "tables_bytes": int(tables),
        "working_set_bytes": int(working),
        "peak_bytes": int(factors + tables + working),
    }


def prepare_sharded_pack(
    data: als_ops.RatingsData,
    params: als_ops.ALSParams,
    shards: int,
    mode: str = "auto",
    stable_shapes: bool = False,
):
    """Build the host-side sharded prep — resolved mode, both
    :class:`SideLayout`\\ s, and both :class:`PackedSide`\\ s — WITHOUT
    training. This is the scan+pack work :func:`sharded_als_train`
    normally does inline; split out so the packed-prep cache
    (core/prep_cache.py) can persist and restore it, handing the result
    back via ``prepacked=``. ``stable_shapes`` (set by the prep cache)
    pads every packed dimension to its pow2 :func:`_envelope` so a
    cached pack can absorb small deltas shape-stably. Returns ``(mode,
    row_layout, col_layout, row_ps, col_ps)``."""
    if mode == "auto":
        mode = choose_sharded_mode(data, params, shards)
    elif mode not in ("gather", "ring"):
        raise ValueError(f"mode must be auto|gather|ring, got {mode!r}")
    row_layout = build_side_layout(
        data.rows, data.num_rows, shards, stable_shapes=stable_shapes
    )
    col_layout = build_side_layout(
        data.cols, data.num_cols, shards, stable_shapes=stable_shapes
    )
    row_ps = pack_sharded_side(
        data.rows, data.cols, data.vals, row_layout, col_layout, shards,
        mode, stable_shapes=stable_shapes,
    )
    col_ps = pack_sharded_side(
        data.cols, data.rows, data.vals, col_layout, row_layout, shards,
        mode, stable_shapes=stable_shapes,
    )
    return mode, row_layout, col_layout, row_ps, col_ps


def sharded_als_train(
    data: als_ops.RatingsData,
    params: als_ops.ALSParams,
    mesh: Mesh,
    axis: str = "data",
    mode: str = "auto",
    checkpoint_cfg=None,
    warm_start=None,
    tol: float = 0.0,
    prepacked=None,
    progress_extra: dict | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Full multi-chip ALS with mesh-resident factors.

    Exact on arbitrarily hot rows: packed segments of one solved row are
    colocated per shard and scatter-added before the solve, so results
    match single-chip ``als_train`` for the same seed. ``mode`` is
    ``"gather"``, ``"ring"``, or ``"auto"`` (default: pick by the
    per-chip budget — ``choose_sharded_mode``). Returns (U, V) trimmed
    to the true row counts (still sharded device arrays).

    Checkpointing (``checkpoint_cfg`` or PIO_CHECKPOINT_*; see
    core/checkpoint.py): like single-chip ``als_train``, the dynamic
    fori_loop bound lets the run dispatch in ``every``-iteration
    segments through the one cached trainer, persisting the
    layout-ordered sharded carry at each boundary. The fingerprint
    carries the mesh descriptor (axis size + half-step mode), so a
    snapshot can only resume onto the identical layout. Single-host
    only: multi-host runs skip checkpointing with a warning."""
    if axis not in mesh.shape:
        raise ValueError(
            f"mesh has axes {tuple(mesh.axis_names)} but the sharded ALS "
            f"trainer shards over {axis!r}; name one mesh axis {axis!r} "
            f"(e.g. --mesh {axis}=N) or pass axis="
        )
    shards = mesh.shape[axis]
    if tol > 0.0:
        logger.warning(
            "RMSE-plateau early stop (tol=%g) is unavailable on the "
            "sharded trainer: mid-run tables are in SideLayout order, so "
            "no per-segment RMSE exists to ride; running the configured "
            "%d iterations", tol, params.iterations,
        )
    if prepacked is not None:
        mode, row_layout, col_layout, row_ps, col_ps = prepacked
    else:
        mode, row_layout, col_layout, row_ps, col_ps = prepare_sharded_pack(
            data, params, shards, mode
        )
    state = init_sharded_factors(
        data, params, mesh, axis, row_layout, col_layout,
        warm_start=warm_start,
    )
    if mode == "ring":
        _check_ring_layout(row_ps, col_ps, params, shards)
    row_pack = upload_packed_side(row_ps, mesh, axis)
    col_pack = upload_packed_side(col_ps, mesh, axis)
    # iterations rides as a dynamic loop bound (shared compile across
    # iteration counts, like the single-chip _train_fused)
    static_params = dataclasses.replace(params, iterations=0)
    trainer = _fused_trainer(mesh, axis, mode, static_params)
    import time as _time

    from predictionio_tpu import faults
    from predictionio_tpu.core import checkpoint as ckpt

    cfg = checkpoint_cfg if checkpoint_cfg is not None else ckpt.from_env()
    if cfg is not None and cfg.active and jax.process_count() > 1:
        logger.warning(
            "checkpointing is single-host only; disabling for this "
            "multi-host run"
        )
        cfg = None
    factor = factor_sharding(mesh, axis)
    start_iter = 0
    fingerprint = None
    mesh_desc = f"sharded:{axis}={shards}:{mode}"
    if cfg is not None and cfg.active:
        fingerprint = ckpt.data_fingerprint(
            data.rows, data.cols, data.vals, static_params, mesh=mesh_desc
        )
        if cfg.resume:
            snap = ckpt.load_checkpoint(cfg, fingerprint)
            if snap is not None and snap.iteration <= params.iterations:
                # the snapshot holds the layout-ordered padded tables;
                # layouts derive deterministically from the (fingerprint-
                # matched) data, so positions line up exactly
                state.U = jax.device_put(snap.U, factor)
                state.V = jax.device_put(snap.V, factor)
                start_iter = snap.iteration

    # per-segment RMSE is skipped here on purpose: mid-run tables are in
    # SideLayout (degree-balanced) order, so scoring them against the
    # original-order (rows, cols) pairs would be wrong
    prog = obs_progress.ProgressPublisher(
        params.iterations, mesh=mesh_desc, trainer="sharded",
        warm_start=warm_start is not None, **obs_device.where(),
        **(progress_extra or {}),
    )
    # multi-host: every host runs this loop; one writer is enough
    prog.enabled = prog.enabled and jax.process_index() == 0
    nnz = len(data.vals)
    t0 = _time.perf_counter()
    if cfg is None or cfg.every <= 0:
        prog.publish(start_iter)
        faults.fault_point("device.dispatch")
        U, V = trainer(
            state.U, state.V, row_pack, col_pack,
            params.iterations - start_iter,
        )
    else:
        prog.publish(start_iter)
        U, V = state.U, state.V
        it = start_iter
        epochs = 0
        while it < params.iterations:
            seg = min(cfg.every, params.iterations - it)
            faults.fault_point("device.dispatch")
            t_seg = _time.perf_counter()
            U, V = trainer(U, V, row_pack, col_pack, seg)
            it += seg
            if it < params.iterations:
                # save_checkpoint host-copies the carry (np.asarray)
                # before the next dispatch donates its buffers
                jax.block_until_ready((U, V))
                ckpt.save_checkpoint(
                    cfg, fingerprint, U, V, it, params.seed, mesh=mesh_desc
                )
                epochs += 1
            seg_wall = _time.perf_counter() - t_seg
            prog.publish(
                it,
                events_per_s=nnz * seg / seg_wall if seg_wall > 0 else None,
                segment_wall_s=seg_wall,
                checkpoint_epoch=epochs,
            )
    jax.block_until_ready((U, V))
    prog.done(params.iterations)
    als_ops.LAST_TRAIN_INFO.clear()
    als_ops.LAST_TRAIN_INFO.update(
        iterations_run=params.iterations - start_iter,
        early_stopped=False,
        final_rmse=None,
        warm_start=warm_start is not None,
    )
    total = _time.perf_counter() - t0
    # the whole loop is ONE scan-fused jit program, so per-half-step
    # timing is derived: total / (2 * iterations). First-call totals
    # include the XLA compile — read p50, not max.
    if params.iterations > start_iter:
        obs_metrics.histogram(
            "pio_als_halfstep_seconds",
            "Derived per-half-step time of the fused sharded ALS loop",
            mode=mode,
        ).observe(total / (2 * (params.iterations - start_iter)))
    obs_metrics.histogram(
        "pio_als_train_seconds",
        "Whole-run ALS training time",
        path="sharded",
    ).observe(total)
    # tables are in SideLayout (degree-balanced) order: un-permute ONCE
    # per training run back to original row order
    factor = factor_sharding(mesh, axis)
    return (
        _gather_table_rows(U, row_layout.positions, factor),
        _gather_table_rows(V, col_layout.positions, factor),
    )


def train_for_context(
    data: als_ops.RatingsData,
    params: als_ops.ALSParams,
    ctx=None,
    sharded: bool = False,
    mode: str = "auto",
    warm_start=None,
    tol: float = 0.0,
    prepacked=None,
    progress_extra: dict | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Framework dispatch point: the engine-param ``shardedTrain`` knob.

    Templates call this from ``Algorithm.train``; with ``sharded`` the
    run executes on the WorkflowContext's device mesh (the production
    multi-chip path — the TPU replacement for MLlib ALS's Spark-cluster
    execution, reference examples/scala-parallel-recommendation/
    custom-prepartor/src/main/scala/ALSAlgorithm.scala:72), otherwise on
    the single default device. ``mode`` forwards to
    :func:`sharded_als_train` (the engine-param ``shardedMode`` knob:
    auto|gather|ring).
    """
    if not sharded or ctx is None:
        return als_ops.als_train(
            data, params, warm_start=warm_start, tol=tol,
            progress_extra=progress_extra,
        )
    mesh = ctx.mesh
    # shard over "data" when present; a 1-D mesh shards over its only axis
    if "data" in mesh.shape:
        axis = "data"
    elif len(mesh.axis_names) == 1:
        axis = mesh.axis_names[0]
    else:
        raise ValueError(
            f"shardedTrain needs a 'data' axis on the mesh; got axes "
            f"{tuple(mesh.axis_names)}"
        )
    U, V = sharded_als_train(
        data, params, mesh, axis, mode=mode, warm_start=warm_start,
        tol=tol, prepacked=prepacked, progress_extra=progress_extra,
    )
    if jax.process_count() > 1:
        # multi-host: shards live on other hosts' devices; templates
        # np.asarray the factors for persistence, so gather them to
        # host-replicated arrays (every host gets the full model)
        from jax.experimental import multihost_utils

        U = multihost_utils.process_allgather(U, tiled=True)
        V = multihost_utils.process_allgather(V, tiled=True)
    return U, V


# ---------------------------------------------------------------------------
# Legacy host-side layout (reference implementation)
# ---------------------------------------------------------------------------
#
# The pre-fusion layout: one sub-table per degree bucket, repartitioned
# host-side by slab owner for ring mode. Kept as the REFERENCE the
# property tests check the packed device layout against (every
# (row, col, rating) triple must survive both layouts identically), and
# because its docstrings document the owner-skew failure mode the packed
# layout absorbs. Not used by the training path.


@dataclass
class ShardedBucket:
    """One degree bucket laid out as per-shard sub-tables (flattened).

    ``col_ids/ratings/mask/seg_row`` are ``[S*B, ...]`` where shard ``s``
    owns rows ``[s*B, (s+1)*B)`` — exactly what ``P(axis)`` on dim 0
    yields. ``seg_row`` holds **shard-local** solved-row indices in
    ``[0, R)``; all segments of one solved row live on one shard.
    ``row_ids`` is ``[S*R]`` global factor-row ids (``dummy_row`` for
    padding slots), used for the global scatter of solutions.
    """

    row_ids: np.ndarray  # [S*R] int32 global row ids (dummy-padded)
    col_ids: np.ndarray  # [S*B, K] int32
    ratings: np.ndarray  # [S*B, K] float32
    mask: np.ndarray  # [S*B, K] float32
    seg_row: np.ndarray  # [S*B] int32, shard-local in [0, R)
    shards: int
    rows_per_shard: int  # R
    table_rows_per_shard: int  # B


def shard_bucket(
    bucket: als_ops.PaddedBucket, shards: int, dummy_row: int
) -> ShardedBucket:
    """Lay one PaddedBucket out over ``shards`` with row-segment
    colocation and balanced per-shard table sizes."""
    K = bucket.width
    R0 = len(bucket.row_ids)
    if bucket.seg_row is None:
        nseg = np.ones(R0, np.int64)
        seg_starts = np.arange(R0, dtype=np.int64)
    else:
        seg_of = bucket.seg_row.astype(np.int64)
        nseg = np.bincount(seg_of, minlength=R0)
        # segments of row j are contiguous table rows by construction
        # (ops/als.py build_padded_buckets seg_base layout)
        seg_starts = np.concatenate([[0], np.cumsum(nseg)[:-1]])

    if (nseg == 1).all():
        # fast path: one segment per row -> round-robin is perfectly even
        assign = np.arange(R0, dtype=np.int64) % shards
    else:
        # greedy LPT on segment counts so hot rows don't pile on one shard
        assign = np.empty(R0, np.int64)
        heap = [(0, 0, s) for s in range(shards)]
        heapq.heapify(heap)
        for j in np.argsort(-nseg, kind="stable"):
            load, cnt, s = heapq.heappop(heap)
            assign[j] = s
            heapq.heappush(heap, (load + int(nseg[j]), cnt + 1, s))

    per_shard_rows = np.bincount(assign, minlength=shards)
    per_shard_load = np.bincount(assign, weights=nseg, minlength=shards).astype(
        np.int64
    )
    R = max(1, int(per_shard_rows.max()))
    B = max(1, int(per_shard_load.max()))

    row_ids = np.full((shards, R), dummy_row, np.int32)
    col_ids = np.zeros((shards, B, K), np.int32)
    ratings = np.zeros((shards, B, K), np.float32)
    mask = np.zeros((shards, B, K), np.float32)
    seg_row = np.zeros((shards, B), np.int32)
    for s in range(shards):
        js = np.nonzero(assign == s)[0]  # ascending original order
        if len(js) == 0:
            continue
        ns = nseg[js]
        total = int(ns.sum())
        # source table rows: each row's contiguous segment run
        base = np.cumsum(ns) - ns
        within = np.arange(total) - np.repeat(base, ns)
        src = np.repeat(seg_starts[js], ns) + within
        row_ids[s, : len(js)] = bucket.row_ids[js]
        col_ids[s, :total] = bucket.col_ids[src]
        ratings[s, :total] = bucket.ratings[src]
        mask[s, :total] = bucket.mask[src]
        seg_row[s, :total] = np.repeat(np.arange(len(js), dtype=np.int32), ns)
    return ShardedBucket(
        row_ids=row_ids.reshape(-1),
        col_ids=col_ids.reshape(shards * B, K),
        ratings=ratings.reshape(shards * B, K),
        mask=mask.reshape(shards * B, K),
        seg_row=seg_row.reshape(-1),
        shards=shards,
        rows_per_shard=R,
        table_rows_per_shard=B,
    )


def resegment_skewed_rows(
    sb: ShardedBucket, opp_rows_loc: int, shards: int
) -> ShardedBucket:
    """Split table rows whose entries concentrate on one opposite-slab
    owner, BEFORE ring partitioning.

    ``ring_partition_bucket`` pads every (table row, owner) cell to the
    bucket-wide max ``K_sub``, so a single row with ~K entries on one
    owner drives ``K_sub -> K`` and the whole bucket to S x the flat
    bytes (its docstring's adversarial case). Splitting just the
    offending rows into sub-rows of at most ``ceil(K / S)`` entries per
    owner — more segments of the same solved row, scatter-added by
    ``seg_row`` exactly like hot-row segments — caps ``K_sub`` at the
    spread-case value, so only the skewed rows grow (by their segment
    count) instead of every row paying the padding. (The packed layout
    makes this moot: ``pack_entries`` pads per (row, owner) GROUP, so a
    skewed row only grows its own cell.)
    """
    S, B, K = sb.shards, sb.table_rows_per_shard, sb.col_ids.shape[1]
    T = max(1, -(-K // shards))
    col3 = sb.col_ids.reshape(S, B, K)
    rat3 = sb.ratings.reshape(S, B, K)
    msk3 = sb.mask.reshape(S, B, K)
    seg2 = sb.seg_row.reshape(S, B)
    per_shard: list[list[tuple]] = []
    for s in range(S):
        out_rows: list[tuple] = []
        for b in range(B):
            m = msk3[s, b] > 0
            n = int(m.sum())
            if n == 0:
                continue  # padding slot; re-padded below
            own = col3[s, b][m].astype(np.int64) // opp_rows_loc
            if np.bincount(own, minlength=shards).max() <= T:
                out_rows.append((col3[s, b], rat3[s, b], msk3[s, b], seg2[s, b]))
                continue
            # within-owner rank -> sub-row index; each sub-row holds at
            # most T entries of any one owner
            order = np.argsort(own, kind="stable")
            oo = own[order]
            starts = np.concatenate([[0], np.nonzero(np.diff(oo))[0] + 1])
            counts = np.diff(np.concatenate([starts, [len(oo)]]))
            rank = np.arange(len(oo)) - np.repeat(starts, counts)
            sub = rank // T
            cols_m = col3[s, b][m][order]
            rats_m = rat3[s, b][m][order]
            for i in range(int(sub.max()) + 1):
                pick = sub == i
                c = np.zeros(K, np.int32)
                r = np.zeros(K, np.float32)
                mk = np.zeros(K, np.float32)
                c[: pick.sum()] = cols_m[pick]
                r[: pick.sum()] = rats_m[pick]
                mk[: pick.sum()] = 1.0
                out_rows.append((c, r, mk, seg2[s, b]))
        per_shard.append(out_rows)
    B2 = max(1, max(len(rows) for rows in per_shard))
    col_ids = np.zeros((S, B2, K), np.int32)
    ratings = np.zeros((S, B2, K), np.float32)
    mask = np.zeros((S, B2, K), np.float32)
    seg_row = np.zeros((S, B2), np.int32)
    for s, rows in enumerate(per_shard):
        for j, (c, r, mk, sg) in enumerate(rows):
            col_ids[s, j] = c
            ratings[s, j] = r
            mask[s, j] = mk
            seg_row[s, j] = sg
    return ShardedBucket(
        row_ids=sb.row_ids,
        col_ids=col_ids.reshape(S * B2, K),
        ratings=ratings.reshape(S * B2, K),
        mask=mask.reshape(S * B2, K),
        seg_row=seg_row.reshape(-1),
        shards=S,
        rows_per_shard=sb.rows_per_shard,
        table_rows_per_shard=B2,
    )


def ring_partition_bucket(
    sb: ShardedBucket, opp_rows_loc: int, shards: int
) -> ShardedBucket:
    """Repartition one sharded bucket's tables by opposite-slab OWNER for
    the ring half-step: ``col_ids/ratings/mask`` become ``[S*B, S_owner,
    K_sub]`` where slot ``[b, s, :]`` packs exactly the entries of table
    row ``b`` whose opposite factor row lives on shard ``s`` (owner =
    ``col_id // opp_rows_loc``; factors are row-contiguous over shards).

    Reference semantics for the packed ring layout (the property tests
    compare the two): the per-(row, owner) triples must be identical.
    Its weakness — and why the packed layout replaced it — is the shared
    ``K_sub``: one row with ~K entries on one owner drives ``K_sub -> K``
    and the WHOLE bucket to S x the flat bytes, whereas ``pack_entries``
    pads per group.
    """
    SB, K = sb.col_ids.shape
    m_flat = sb.mask.reshape(-1) > 0
    rows_idx = np.repeat(np.arange(SB, dtype=np.int64), K)[m_flat]
    own = (sb.col_ids.reshape(-1)[m_flat].astype(np.int64)) // opp_rows_loc
    cnt = np.zeros((SB, shards), np.int64)
    np.add.at(cnt, (rows_idx, own), 1)
    K_sub = max(1, int(cnt.max()))
    # within-(row, owner) rank: stable sort by the group key, then
    # position minus group start
    key = rows_idx * shards + own
    order = np.argsort(key, kind="stable")
    ks = key[order]
    starts = np.concatenate([[0], np.nonzero(np.diff(ks))[0] + 1])
    counts = np.diff(np.concatenate([starts, [len(ks)]]))
    rank = np.arange(len(ks)) - np.repeat(starts, counts)
    rr, oo = rows_idx[order], own[order]
    col_ids = np.zeros((SB, shards, K_sub), np.int32)
    ratings = np.zeros((SB, shards, K_sub), np.float32)
    mask = np.zeros((SB, shards, K_sub), np.float32)
    col_ids[rr, oo, rank] = sb.col_ids.reshape(-1)[m_flat][order]
    ratings[rr, oo, rank] = sb.ratings.reshape(-1)[m_flat][order]
    mask[rr, oo, rank] = 1.0
    return ShardedBucket(
        row_ids=sb.row_ids,
        col_ids=col_ids,
        ratings=ratings,
        mask=mask,
        seg_row=sb.seg_row,
        shards=sb.shards,
        rows_per_shard=sb.rows_per_shard,
        table_rows_per_shard=sb.table_rows_per_shard,
    )


def upload_sharded_buckets(
    sharded: Sequence[ShardedBucket], mesh: Mesh, axis: str
) -> tuple:
    """Place the legacy layout on the mesh: tables sharded ``P(axis)``,
    scatter row-ids replicated."""
    table = NamedSharding(mesh, P(axis))
    repl = NamedSharding(mesh, P())
    return tuple(
        (
            jax.device_put(sb.row_ids, repl),
            jax.device_put(sb.col_ids, table),
            jax.device_put(sb.ratings, table),
            jax.device_put(sb.mask, table),
            jax.device_put(sb.seg_row, table),
        )
        for sb in sharded
    )


def _table_bytes_per_chip(sbs: Sequence[ShardedBucket], shards: int) -> int:
    """Per-chip bytes of a legacy bucket-table set (col_ids/ratings/mask
    at 12 bytes per slot) — same formula for the flat ``[S*B, K]`` layout
    and the ring-partitioned ``[S*B, S, K_sub]`` one, so the two layouts
    are directly comparable."""
    return sum(sb.col_ids.size * 12 for sb in sbs) // max(1, shards)
