"""Two-stage catalog retrieval: coarse shortlist + exact f32 rescore.

Every exact serving op in ops/topk.py scores the FULL catalog per batch
— a dense ``[B, I]`` matmul plus a full-catalog ``lax.top_k``. Exact and
fast at MovieLens scale, O(I) per query at the catalog sizes the
ROADMAP north star implies (the [B, I] score matrix alone is 320 MB at
B=8, I=10M). This module is the retrieval-tier / scoring-tier split the
ads serving stack runs at scale (PAPERS.md, arxiv 2501.10546):

1. **Coarse shortlist** — score the catalog in its low-precision
   storage form *without materializing a dequantized f32 copy*, tiled
   so neither the [B, I] score matrix nor a full-catalog top-k ever
   is selected from whole: a ``lax.scan`` over ``[NT, T, D]`` tiles
   scores a tile a step and keeps the scores and their group maxima,
   and one selection after the loop picks the k' best. int8 catalogs
   score as ``(q @ values^T) * scale`` (the per-row scale factors out
   of the within-row dot and multiplies back scalar-per-column);
   ``int8_dot`` additionally quantizes the queries and accumulates in
   int32 (the MXU-native form; never picked by rule — on a v5e it is no
   faster for a batch and 2.5x slower for one query: PERF.md section 6,
   PR 41); dense catalogs carry a bf16 coarse copy. On a mesh each
   device runs this same scan over the rows it holds
   (parallel/shard_topk.py: stationary shards, the query handed round).
   A step selects nothing: it scores the tile, scales, guards, masks
   and writes the [B, T] scores and the maximum of each group of G of
   them to the scan's stacked outputs, and the k' best come once, after
   the loop, in two exact levels over ALL tiles' groups (``_select``):
   the k' best groups by maximum, then the k' best of those groups'
   scores — exactly the catalog's top-k', from selections over
   NT x T/G + k'G elements instead of NT x T (``select_group`` decides
   G from NT, T and k' alone; a catalog too small to split is one
   ``lax.top_k`` of the stored row). The stored scores are a call's
   one large temporary, so a pass takes at most as many queries as
   store half the coarse tiles' bytes (``scan_chunk``: 16 at rank 64 in
   bf16) or 604 MB, whichever is more, and a larger batch is scanned a
   chunk at a time inside the one program. A step scores its tile in
   one of two forms (``score_form`` decides from B and D alone): the
   batch's f32 rows against the tile cast to f32 ("rows": two or more
   queries, or D >= 128), or — ONE query
   whose rank leaves the 128 lanes unfilled — the query split into
   three bf16 rows whose sum it is, one dot against the tile as stored,
   the three partial scores added back ("dot": the same f32 product to
   summation order, through the form of it that the chip reads at the
   memory's speed). Everything after the score is one row either way,
   and a single's scaled and guarded scores and their group maxima are
   the two results of ONE pass over it (``_kept_once``).
   Beside the tiles lie one value a row in two side arrays, the row ids
   (-1: padding, guarded to a score that cannot win) and an int8 pair's
   row scales, stored [NT, T/128, 128] (``side_shape``) so that the one
   tile's worth a step reads of each is a dense block of the chip's
   memory and not a sublane of every memory tile of an [NT, T] array.
2. **Exact rescore** — gather the [B, S] shortlisted rows and rescore
   them in f32 through shortlist-gather variants of the fused ops
   (``rescore_*_top_k_batch`` below). The rescore builds its query
   vectors exactly like the exact path (same gathers, same dequant), so
   the two-stage ranking equals the exact ranking restricted to the
   shortlist — recall is purely a question of shortlist coverage, which
   the oversampling factor buys (k' = oversample * pow2(num+|excluded|),
   pow2-bucketed like every serving shape so jit compile count stays
   flat).

One function chains the two stages, ``top_k`` (the end of this file):
the four ALS templates hand it a query batch in one of three forms
(``UserRows``, ``Vectors``, ``SumRows`` — each the argument list that an
exact op of ops/topk.py and its rescore variant share; the last two
under ``ops.topk.Rules`` where the template has filters: no filter
keeps a query from the shortlist, and such a dispatch goes up to the
device in ONE buffer, ``pack``), the resident
exact table, the catalog's row count, the coarse copy and ``k``, and it
alone decides exact or two-stage, runs shortlist -> rescore, and every
Nth two-stage dispatch re-scores row 0 exactly (the live recall probe).
Where the table is a ``parallel.shard_topk.ShardedCatalog`` — item rows
split over a mesh, for a catalog one chip cannot hold — the same
decision runs both stages and a merge as one program on the shards,
``Vectors`` under rules included (the rules' vectors sharded like the
rows, a query's own list resolved on every shard; a sum of rows under
rules is refused there by name).
Engagement is catalog-size gated: a catalog under
``PIO_RETRIEVAL_THRESHOLD`` rows (default 100_000) — every small test
fixture — is served by the form's exact op, bit for bit what it was
before this module existed. The oversampling factor is 8 (recall@num
>= 0.999 holds with margin); the coarse representation follows the
table (int8 catalogs stay int8 and are scanned in mode ``int8`` on every
backend; dense catalogs get a bf16 copy). Knobs (read per call, so tests
and operators can flip them live):

- ``PIO_RETRIEVAL_THRESHOLD``: catalog rows below which serving stays
  exact (default 100000; <= 0 disables two-stage entirely).
- ``PIO_RETRIEVAL_TILE``: coarse tile width (default 2^18 rows).
- ``PIO_RETRIEVAL_PROBE_EVERY``: every Nth two-stage dispatch re-scores
  one query exactly and publishes recall (default 256; 0 disables).

Where the tables' layout is decided, and who reads them: nowhere in
this repo. A template's ``device_factors()`` puts each [rows, D] factor
array up with ``jnp.asarray``, once, in the device's default layout,
and every reader takes it as it lies: the rescore programs here,
``ops/topk.py``'s exact programs (the recall probe, catalogs below the
threshold) and the templates' own row math;
``CoarseCatalog`` reads the model's host arrays. On
a TPU that default keeps the long axis minor wherever D is no multiple
of 128, and XLA answers a gather of 64-column rows from it by first
copying the entire table row-major — 2.4 GB read and 4.8 GB written per
call for 128 rows of a 9.39 M x 64 f32 table. So the rescore gathers
through a view of the same buffer that XLA reads in place
(``_gather_rows``), and no program here has an instruction of a table's
shape. (A non-default resident layout would do it too — leave the
gather's operand layout to the compiler, ``Layout.AUTO``, and place the
table in what it picks — but it doubles the table, and an array that a
program loaded from the persistent compile cache writes in a
non-default layout reports the default one (jax 0.9.0, v5e), so the
next program is compiled for a layout the buffer does not have.)

Observability: ``pio_retrieval_*`` metrics (docs/observability.md);
``pio_retrieval_score_form_total{form}`` says which score a shortlist
call's program holds (``dot`` / ``rows``); each
rescore program publishes the temporary bytes its compiled form needs
(``pio_retrieval_rescore_temp_bytes``: a table-sized number means a
re-layout came back); the
two stages record themselves as ``dispatch.shortlist`` /
``dispatch.rescore`` regions (``obs.trace.region``: a span on the
current trace — every batchmate's, under the batch worker — around a
stage's conversions, uploads and launch: what it costs to enqueue),
the one blocking read that ends the chain as ``dispatch.fetch``
(``pio_retrieval_fetch_seconds``: the device time of both programs and
the copy back; ``pio_retrieval_host_reads_total`` counts such reads,
one a dispatch through ``top_k``). Below the stages, the crossings of
the host <-> device boundary are regions of their own: every upload is
an ``xfer.h2d[serve.dispatch]`` (``_up``; ``obs.device.transfer`` —
``pio_device_transfer_seconds{direction,op}`` with its bytes and its
count: one a dispatch under rules — ``pack``'s buffer — two for
``UserRows``), every launch a ``launch[<fn>]`` (``obs.device.track_jit``,
``pio_jit_call_seconds{fn}``), and on one dispatch in
``obs.trace.CPU_EVERY`` the read is told apart into ``fetch.wait`` (the
device still working: ``pio_retrieval_fetch_wait_seconds``) and
``xfer.d2h[serve.answers]`` (the copy back) — so a stage's self time is
its Python: convert, pad, ``pack``. The two serving programs
carry ``jax.named_scope`` s (``retrieval.shortlist.*`` — a step is
``score`` / ``mask`` / ``group_max`` and ``select`` follows the loop —
and ``retrieval.rescore.*``) that name their ops in a trace viewer.
"""

from __future__ import annotations

import functools
import inspect
import itertools
import os
import threading
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from predictionio_tpu.obs import device as obs_device
from predictionio_tpu.obs import metrics as obs_metrics
from predictionio_tpu.obs import trace as obs_trace
from predictionio_tpu.ops.topk import (
    Rules,
    gather_top_k_batch,
    rows_allowed,
    sum_rows_top_k_batch_masked,
    top_k_items_batch,
    top_k_items_batch_masked,
)

NEG_INF = -1e30
_LANES = 128  # the minor dimension of a TPU's vector registers and tiles

# -- knobs (env-read per call: operators flip them on a live server) --------

_DEFAULT_THRESHOLD = 100_000
_OVERSAMPLE = 8  # k' over the pow2 headroom-k: a power of two itself
_DEFAULT_TILE = 1 << 18
_DEFAULT_PROBE_EVERY = 256


def retrieval_threshold() -> int:
    return int(os.environ.get("PIO_RETRIEVAL_THRESHOLD", _DEFAULT_THRESHOLD))


def tile_size() -> int:
    return int(os.environ.get("PIO_RETRIEVAL_TILE", _DEFAULT_TILE))


def probe_every() -> int:
    return int(os.environ.get("PIO_RETRIEVAL_PROBE_EVERY", _DEFAULT_PROBE_EVERY))


def engaged(num_rows: int) -> bool:
    """Should serving route this catalog through two-stage retrieval?"""
    t = retrieval_threshold()
    return t > 0 and num_rows >= t


def _pow2(n: int) -> int:
    return 1 << max(0, n - 1).bit_length()


def side_shape(nt: int, t: int) -> tuple[int, int, int]:
    """The shape in which ``nt`` tiles of ``t`` rows store a per-row
    side array (the row ids; an int8 pair's row scales): [nt, t/L, L],
    L the 128 lanes wherever they divide the tile (every catalog at
    retrieval scale: a tile is a power of two) and the whole tile
    otherwise (the small tiles of the CPU tests). Decided from the
    tile's shape alone. Why not [nt, t]: a scan step reads ONE tile's
    row of each, and a TPU lays a 2-D array out in memory tiles of 8
    rows x 128 columns — row i of [nt, t] is one sublane of every tile,
    512 B out of every 4 KB spread over 8 x t x 4 bytes, and on a v5e a
    2^18-row step's 1 MB of ids cost a batch 18.2 us and its 1 MB of
    scales 11–12 us at every batch size, where the memory moves 1 MB in
    1.3. In [nt, t/128, 128] the step's slice is one dense block of
    whole memory tiles — 2.0 us a pass — in the shape the step's scores
    end in (PERF.md section 6, PR 42)."""
    lanes = _LANES if t % _LANES == 0 else t
    return nt, t // lanes, lanes


def shortlist_k(k: int, num_rows: int) -> int:
    """Shortlist size k' for a headroom-k request against ``num_rows``
    catalog rows: oversample * k, pow2-bucketed (compile-count flat),
    capped at the tile width and the catalog's pow2 envelope."""
    kp = _OVERSAMPLE * _pow2(max(1, k))
    return max(1, min(kp, tile_size(), _pow2(num_rows)))


def two_stage_k(k: int, num_rows: int) -> int:
    """The shortlist size k' with which a headroom-k request against
    ``num_rows`` catalog rows is served in two stages, or 0 where the
    exact path serves it: a catalog under the threshold, or a k' that
    would not sit between k and the catalog (nothing to shortlist)."""
    if not engaged(num_rows):
        return 0
    kp = shortlist_k(k, num_rows)
    return kp if k <= kp < num_rows else 0


# -- metrics -----------------------------------------------------------------

_SIZE_BOUNDS = tuple(float(1 << p) for p in range(4, 20, 2))  # 16 .. 262144

_QUERIES_HELP = (
    "serving queries of a catalog at retrieval scale, by path: two_stage = "
    "shortlist then rescore; exact = the exact program, because k leaves a "
    "shortlist no room (no filter takes a query there since PR 30); sharded "
    "= both stages and the merge in one program over stationary shards"
)
_m_two_stage = obs_metrics.counter(
    "pio_retrieval_queries_total", _QUERIES_HELP, path="two_stage",
)
_m_exact = obs_metrics.counter(
    "pio_retrieval_queries_total", _QUERIES_HELP, path="exact",
)
_m_sharded = obs_metrics.counter(
    "pio_retrieval_queries_total", _QUERIES_HELP, path="sharded",
)
_m_sharded_masked = obs_metrics.counter(
    "pio_retrieval_sharded_masked_total",
    "queries served by the masked sharded programs: business rules applied "
    "on every shard, in its scan and its rescore (a whiteList: in the "
    "rescore of the listed rows it holds)",
)
_m_gather_bytes = obs_metrics.counter(
    "pio_retrieval_shard_gather_bytes_total",
    "bytes the sharded chain's all-gather moved: shards x B x k x 8 a "
    "dispatch (every shard's [B, k] f32 scores and int32 ids)",
)
_m_shards = obs_metrics.gauge(
    "pio_retrieval_shards",
    "devices the served catalog's rows are split over (0: one chip)",
)
_m_load = {
    stage: obs_metrics.histogram(
        "pio_model_load_seconds",
        "staging a served catalog (one observation a shard of a sharded "
        "one), by stage: read = a shard's rows out of the model's segments "
        "into one host block; stage_to_device = the upload (a shard's "
        "block; on one chip the user and item tables as stored, a spanned "
        "one a part at a time); coarse_build = the coarse tiles made",
        stage=stage,
    )
    for stage in ("read", "stage_to_device", "coarse_build")
}
_m_shortlist_size = obs_metrics.histogram(
    "pio_retrieval_shortlist_size",
    "shortlist candidates per query (k')", bounds=_SIZE_BOUNDS,
)
_m_shortlist_secs = obs_metrics.histogram(
    "pio_retrieval_shortlist_seconds", "coarse shortlist pass wall time",
)
_m_rescore_secs = obs_metrics.histogram(
    "pio_retrieval_rescore_seconds", "exact rescore pass wall time",
)
# the two stages that only enqueue, on their thread's CPU clock: wall
# minus CPU is time the thread wanted to run and did not
_m_shortlist_cpu = obs_metrics.histogram(
    "pio_retrieval_shortlist_cpu_seconds",
    "coarse shortlist pass thread_time (its enqueue, on the CPU)",
)
_m_rescore_cpu = obs_metrics.histogram(
    "pio_retrieval_rescore_cpu_seconds",
    "exact rescore pass thread_time (its enqueue, on the CPU)",
)
_m_fetch_secs = obs_metrics.histogram(
    "pio_retrieval_fetch_seconds",
    "the two-stage chain's blocking read of its result: what is left of "
    "both device programs, and the copy to the host",
)
_m_host_reads = obs_metrics.counter(
    "pio_retrieval_host_reads_total",
    "blocking device-to-host reads made by two-stage retrieval",
)
_m_fetch_wait = obs_metrics.histogram(
    "pio_retrieval_fetch_wait_seconds",
    "the wait in front of the chain's one read: what the device still had "
    "to do when the host had nothing left to enqueue",
)
_m_probe_recall = obs_metrics.gauge(
    "pio_retrieval_probe_recall",
    "recall@num of the most recent exact-rescored probe query",
)
_m_probes = obs_metrics.counter(
    "pio_retrieval_probes_total", "live recall probes run",
)

_m_score_form = {
    form: obs_metrics.counter(
        "pio_retrieval_score_form_total",
        "shortlist calls by how a scan step scores its tile: dot = a "
        "single query under 128 columns, as three bf16 rows through one "
        "dot; rows = the batch's rows as they are",
        form=form,
    )
    for form in ("dot", "rows")
}

_m_coarse_mode = {
    mode: obs_metrics.counter(
        "pio_retrieval_coarse_mode_total",
        "shortlist calls by the coarse catalog's storage mode: bf16 = a "
        "bf16 copy of a dense table; int8 = stored int8 values against the "
        "f32 query, the row's scale multiplied back; int8_dot = the query "
        "quantized too, int8 x int8 accumulated in int32",
        mode=mode,
    )
    for mode in ("bf16", "int8", "int8_dot")
}

# what a served factor model keeps on the device, by part: the exact
# table's values (and the f32 scales of an int8 pair), the coarse tiles
# with their scales and row ids, the user table, the business rules'
# catalog-wide vectors. Set where each is put up (``CoarseCatalog``; a
# template's ``device_factors``; the E-Commerce template's rules).
RESIDENT_PARTS = (
    "table", "table_scales", "coarse", "coarse_scales", "coarse_ids", "users",
    "rules",
)
_m_resident = {
    part: obs_metrics.gauge(
        "pio_model_resident_bytes",
        "device bytes the served model keeps resident, by part: table / "
        "table_scales = the exact item table (int8 values and their f32 "
        "scales, or the dense rows and 0); coarse / coarse_scales / "
        "coarse_ids = the tiled coarse catalog; users = the user table; "
        "rules = the catalog-wide business rules' availability and category "
        "vectors (on a sharded catalog: what ONE shard holds of them)",
        part=part,
    )
    for part in RESIDENT_PARTS
}


def set_resident(**parts) -> None:
    """Publish device-resident bytes by part (``RESIDENT_PARTS``): each
    value a device array, a tuple of them, or None (0 bytes)."""
    for part, held in parts.items():
        arrays = held if isinstance(held, tuple) else (held,)
        _m_resident[part].set(float(sum(
            a.size * a.dtype.itemsize for a in arrays if a is not None
        )))


_probe_clock = itertools.count(1)


def _count_scan(b: int, k: int, d: int, mode: str, rows: int) -> None:
    """One shortlist call, by how its program scores (``score_form`` of
    the queries a pass over its ``rows`` stored rows takes) and by the
    catalog's coarse mode."""
    _m_shortlist_size.observe(float(k))
    _m_score_form[score_form(scan_chunk(b, d, mode, rows), d, mode)].inc()
    _m_coarse_mode[mode].inc()


def probe_recall(two_stage_ids, exact_ids) -> float:
    """Measure + publish id-set recall of a two-stage result row
    against its exact-path counterpart (the live recall probe)."""
    want = {int(i) for i in np.asarray(exact_ids).ravel() if int(i) >= 0}
    got = {int(i) for i in np.asarray(two_stage_ids).ravel() if int(i) >= 0}
    recall = len(got & want) / len(want) if want else 1.0
    _m_probes.inc()
    _m_probe_recall.set(recall)
    return recall


def probe(two_stage_ids, exact_ids: Callable) -> None:
    """The live recall probe, called once per two-stage dispatch with
    the served ids of its first query: on every
    ``PIO_RETRIEVAL_PROBE_EVERY``-th call ``exact_ids()`` scores that
    query on the exact path and the overlap is published."""
    n = probe_every()
    if n > 0 and next(_probe_clock) % n == 0:
        probe_recall(two_stage_ids, exact_ids())


def stats_block() -> dict:
    """Compact ``retrieval`` object for the servers' ``/stats.json``."""
    return {
        "threshold": retrieval_threshold(),
        "oversample": _OVERSAMPLE,
        "two_stage_queries": _m_two_stage.value(),
        "exact_queries": _m_exact.value(),
        "sharded_queries": _m_sharded.value(),
        "sharded_masked_queries": _m_sharded_masked.value(),
        "shards": int(_m_shards.value()),
        "shard_gather_bytes": _m_gather_bytes.value(),
        # device buffers a sharded chain wrote from the host: ONE a host
        # array of a dispatch, and shards - 1 zero blocks once a shape
        "shard_h2d_copies": obs_device.transfer_count(
            "h2d", "serve.dispatch", "serve.zero_blocks"
        ) if _m_shards.value() else 0,
        "load_seconds": {st: m.summary() for st, m in _m_load.items()},
        "shortlist_size": _m_shortlist_size.summary(),
        "shortlist_seconds": _m_shortlist_secs.summary(),
        "rescore_seconds": _m_rescore_secs.summary(),
        "fetch_seconds": _m_fetch_secs.summary(),
        "host_reads": _m_host_reads.value(),
        # every host array a stage converted and put up, each per-query
        # part of ``device_rules``, the packed buffer of a dispatch under rules
        "uploads": obs_device.transfer_count(
            "h2d", "serve.dispatch", "serve.rules"
        ),
        "score_form": {f: m.value() for f, m in _m_score_form.items()},
        "coarse_mode": {c: m.value() for c, m in _m_coarse_mode.items()},
        "resident_bytes": {p: m.value() for p, m in _m_resident.items()},
        "rescore_temp_bytes": {
            p.name: p.temp_bytes() for p in _RESCORE_PROGRAMS
            if p._cache_size()
        },
        "probes": _m_probes.value(),
        "probe_recall": _m_probe_recall.value(),
    }


# -- coarse shortlist kernel -------------------------------------------------


# The k' best of a row of N scores in two exact levels: the row in N/G
# groups of G, each group's maximum (the one pass that touches all N
# values), ``top_k`` of the [B, N/G] maxima, the chosen groups'
# [B, k', G] scores read out of the same array, and the k' best of those
# [B, k'G] candidates. An element among the k' largest has fewer than k'
# elements above it, and every group whose maximum exceeds its own
# group's holds one of them: its group is among the k' best by maximum.
# On a TPU v5e a ``top_k`` of 262,144 scores with k' = 128 is 115 us at
# B = 1 (two sorts) and 1,620 us at B = 16 (the ``TopK`` custom call)
# against 92 / 46 us to score as many rows; a sort of [B, 2048] is
# 6 / 14 us and one of 1,024 costs hardly less (PERF.md section 6,
# PR 27). So nothing under _MIN_SPLIT is split, a group is a row of 128
# lanes where that pays (the reshape then follows the array's own
# layout), and the candidates go through the same helper again: 2^18
# scores at k' = 128 sort [B, 2048] maxima, then their [B, 16384]
# candidates as [B, 1024] maxima and [B, 2048] candidates.
_MIN_SPLIT = 1 << 13


def select_group(t: int, k: int, nt: int = 1) -> int:
    """Group width G of the two-level selection of the k' = ``k`` best
    of a row of N = ``nt`` x ``t`` scores that lies in ``nt`` pieces of
    ``t`` (a scan's tiles; one piece: any [B, T] array), or 0 where one
    ``lax.top_k`` of the row stays: N under _MIN_SPLIT (the catalogs of
    the CPU tests), no whole number of groups a piece, fewer groups
    than k', or the two selections' N/G + k'G elements more than a
    quarter of N (a k' that nears the row). G is a row of 128 lanes
    where that passes, else the power of two at or above sqrt(N/k'),
    which balances the two. Decided from the shapes alone, at trace
    time."""
    n = nt * t
    if n < _MIN_SPLIT:
        return 0
    for g in (_LANES, _pow2(int(np.ceil(np.sqrt(n / k))))):
        if t % g == 0 and n // g >= k and 4 * (n // g + k * g) <= n:
            return g
    return 0


def _pick(table, ix):
    """``jnp.take_along_axis(table, ix, axis=1)`` for [B, k] ``table``
    and ``ix`` as a compare and a sum over k: a TPU gathers scalars one
    at a time (16 us for [16, 128] of them, the cost of a sort of
    [16, 2048]), and the [B, k, k] compare fuses into the sum."""
    hit = ix[:, :, None] == jnp.arange(table.shape[1], dtype=ix.dtype)
    return jnp.sum(jnp.where(hit, table[:, None, :], 0), axis=2)


def _two_level_top_k(sc, k: int, g: int):
    """``jax.lax.top_k(sc, k)`` of a [B, T] array through groups of
    ``g`` (T a multiple of g, T/g >= k). The candidates are read, not
    recomputed: the values are bit-equal to ``lax.top_k``'s, and the
    positions can differ from its only among exactly equal scores."""
    b, t = sc.shape
    groups = sc.reshape(b, t // g, g)
    _, gix = jax.lax.top_k(groups.max(axis=2), k)
    cand = jnp.take_along_axis(groups, gix[:, :, None], axis=1)
    return _best_of_groups(cand, gix)


def _best_of_groups(cand, gix):
    """The second level: the k best of the [B, k, G] scores of the
    chosen groups ``gix`` (through ``_tile_top_k`` again), and their
    positions ``group * G + lane`` in the array the groups number."""
    b, k, g = cand.shape
    ts, cix = _tile_top_k(cand.reshape(b, k * g), k)
    return ts, _pick(gix, cix // g) * g + cix % g


def _tile_top_k(sc, k: int):
    """The k best of each row of a [B, T] array of scores, and their
    positions in it."""
    g = select_group(sc.shape[1], k)
    if not g:
        return jax.lax.top_k(sc, k)
    return _two_level_top_k(sc, k, g)


# A scan step scores, scales, guards, masks and KEEPS: its tile's [B, T]
# scores and their [B, T/G] group maxima go to the scan's stacked
# outputs, and the k' best come from ONE selection after the loop
# (``_select``). A selection in the step is thrown away almost whole —
# of the 36 x 128 candidates a query that a 36-tile scan would sort,
# merge and look up ids for, 128 survive — and cost a fifth to two
# fifths of the loop (PERF.md section 6, PR 33 and PR 36). The argument
# above ``_MIN_SPLIT`` asks nothing of a tile, so G is ``select_group``
# of the CATALOG's NT x T; where that splits nothing a step keeps the
# scores alone and the selection is one ``lax.top_k`` of the stored row.
#
# Keeping costs memory: B x NT x T x 4 bytes a call (38 MB for one query
# over 36 tiles of 2^18 rows, 604 MB for 16), on top of everything
# resident. So a pass stores at most half the bytes of the coarse tiles
# it scores, B x 4 <= D x itemsize / 2 — a temporary that scales with
# what the deployment keeps resident for this scan — or ``_UNCUT``
# bytes, whichever is more: a low rank makes the tiles small, not the
# scores large (at the ALS templates' default rank 10 half the tiles is
# two queries' scores, one over int8 values), and the 604 MB that every
# run of yambda's cells has stored is a temporary no catalog is cut
# under. A batch beyond that (``_MicroBatcher`` collects up to 64) is
# scanned a chunk at a time inside the one program: slower than one
# pass that stored everything (9.8 against 6.2 ms for 32 queries at
# rank 64, with 1.2 GB) and faster than a step that selects and stores
# nothing (12.4: PERF.md section 6, PR 44). A loop a chunk, not a loop
# of loops: under an outer ``while`` XLA:TPU copies the resident tiles
# into the loop's state (3.0 GB of temporaries where the unrolled
# chunks reuse one chunk's 605 MB).
_UNCUT = 16 * 36 * 4 << 18  # 16 queries' scores of 36 tiles of 2^18 rows


def scan_chunk(b: int, d: int, mode: str, rows: int) -> int:
    """How many of ``b`` queries of rank ``d`` one pass over ``rows``
    stored rows scores: all of them where their f32 scores weigh at
    most half the rows' stored coarse values (bf16, or int8 in both int8
    modes: 16 queries at rank 64 in bf16, 32 at rank 128, 8 over
    rank-64 int8) or at most ``_UNCUT`` bytes (64 queries of any rank
    over 2.3 M rows, 16 over 9.4 M), else the largest power of two
    within the larger of the two bounds — at least one query; a batch
    that is no multiple of it (no served caller sends one) ends in a
    pass of what is left. Decided from the shapes alone: at trace time,
    and on the host for the counters."""
    bound = max(d * (2 if mode == "bf16" else 1) // 8, _UNCUT // (4 * rows), 1)
    return b if b <= bound else 1 << (bound.bit_length() - 1)


def _select(scores, maxima, ids, k: int):
    """The k best of each query over ALL tiles, from what the steps
    kept: [NT, B, T/G, G] scores and their [NT, B, T/G] group maxima —
    ``_two_level_top_k`` with the catalog in the tile's place, the
    values read, not recomputed — or, ``maxima`` None, [NT, B, T]
    scores and one ``lax.top_k`` of the row; and the row ids as stored
    (``side_shape``) -> ([B, k] scores, [B, k] ids)."""
    nt, b = scores.shape[:2]
    if maxima is None:
        t = scores.shape[2]
        best_s, pos = jax.lax.top_k(
            scores.transpose(1, 0, 2).reshape(b, nt * t), k
        )
    else:
        per, g = scores.shape[2:]
        t = per * g
        mx = maxima.transpose(1, 0, 2).reshape(b, nt * per)
        _, gix = _tile_top_k(mx, k)  # groups, numbered tile-major
        cand = scores[gix // per, jnp.arange(b)[:, None], gix % per]
        best_s, pos = _best_of_groups(cand, gix)  # rows of the stored catalog
    row = jnp.unravel_index(pos % t, ids.shape[1:])
    return best_s, ids[(pos // t, *row)]


# One query against a [T, D] tile: XLA:TPU turns the one-row product
# into a multiply-and-reduce fusion that costs 92 us a 2^18-row tile at
# rank 64 and at rank 128 alike (a resident tile lies feature-major, T in
# the lanes, nothing padded: the fusion is bound by its own arithmetic),
# which is 91 % of the memory's speed at rank 128 and 44 % at rank 64. A
# dot reads the rank-64 tile in 46 us, and XLA makes a dot of two rows or
# more (PERF.md section 6, PR 27 and PR 31). So a single's f32 query goes
# through a dot as three bf16 rows whose sum it is, and the three [T]
# partial scores are added back into the one row everything after the
# score works on. At D >= 128 the dot wins nothing (within 1 %), and a
# batch is a dot as it stands — of queries XLA rounds to ONE bf16 term
# at default precision, which the single's three terms do not copy.


def score_form(b: int, d: int, mode: str = "bf16") -> str:
    """How a scan step scores its tile for ``b`` f32 queries of rank
    ``d``: "dot" — the single query split into three bf16 rows
    (``_split_bf16``), one ``dot_general`` against the tile as stored,
    the three partial scores summed — where b == 1 and d < 128; "rows"
    — the batch's rows against the tile cast to f32, the program every
    other shape has always had (and mode ``int8_dot``, which has no f32
    query to split). Decided from the two shapes alone: at trace time,
    and on the host for the counter."""
    if mode == "int8_dot" or b > 1 or d >= _LANES:
        return "rows"
    return "dot"


def _split_bf16(q):
    """f32 [B, D] -> bf16 [3B, D]: the rows ``hi = bf16(q)``, ``mid =
    bf16(q - hi)``, ``lo = q - hi - mid``, smallest term LAST. Both
    differences are exact in f32 and a 24-bit significand is three of
    8, so ``lo`` is a bf16 number too and ``hi + mid + lo == q`` bit
    for bit (down to |q| ~ 2^-103, under which the last term would be
    subnormal and is worth under 2^-126): a bf16 x bf16 product is
    exact in f32, and the three partial dots sum to the f32 product up
    to the order of the additions. The rounding is ``reduce_precision``
    and not a cast to bf16 and back: XLA:TPU computes such a pair of
    casts inside one fusion in f32 (excess precision), ``q - hi`` comes
    out 0 and the query is silently ONE bf16 term (7.7e-2 off on scores
    of ~40: my chip run, PR 31)."""
    def rounded(x):  # to bf16's 8 significant bits, still f32
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

    hi = rounded(q)
    mid = rounded(q - hi)
    lo = q - hi - mid
    return jnp.concatenate([hi, mid, lo]).astype(jnp.bfloat16)


def _kept_once(kept):
    """A single's step's ``(scores, group maxima)`` as the two results
    of one computation. Both are functions of the step's scaled and
    guarded scores, and where nothing says otherwise XLA:TPU computes
    those twice — once inside the fusion that stores the scores and
    once inside the one that reduces them, each reading the summed row,
    the 1 MB of scales and the 1 MB of ids again (3.9 + 5.2 us a
    2^18-row tile beside a 23.4 us score: PERF.md section 6, PR 42).
    Behind one barrier the pair has one producer, which the compiler
    emits as a single two-result fusion: every value after the score
    is read once and written once. The values are untouched."""
    return jax.lax.optimization_barrier(kept)


def _coarse_scan(q, tiles, scales, ids, k: int, mode: str, rules=None):
    """Tiled coarse top-k' over a [NT, T, D] catalog: one scan step per
    tile scores [B, T] in the catalog's storage precision, multiplies
    an int8 pair's row scales back, guards the padding (id -1 ->
    NEG_INF) and keeps the tile's scores and their group maxima; the k'
    best come from ONE selection over all tiles after the loop
    (``_select``) — the [B, I] score matrix is never selected from
    whole, and a batch beyond ``scan_chunk`` is that loop and selection
    a chunk of the queries at a time, in this one program.

    ``ids`` and ``scales`` are [NT, ...] arrays of T values a tile, taken
    as they lie: a catalog stores them ``side_shape`` ([NT, T/128, 128]:
    a step's slice is one dense block of the chip's memory), and the
    same values [NT, T] give the same answer bit for bit (a row of
    [NT, T] is a sublane of every memory tile and costs a step 11–18 us
    a side array where a dense block costs 2).

    ``mode``: "int8" (values*scale columns, f32 GEMM on cast values),
    "int8_dot" (int8 x int8 -> int32 accumulation, quantized queries —
    the per-query quantization scale is positive so it drops out of the
    within-row ranking), or "bf16" (scales is None).

    ``rules`` (ops/topk.py ``Rules`` over the NT * T stored rows): rows
    a query may not be served score NEG_INF BEFORE the selection and
    come out as id -1, so exclusions cost no headroom in k. A chunk's
    own lists are scattered once into a [NT, B, T] mask that the scan
    slices like the tiles. Without ``rules`` the program is the one it
    was before rules existed, op for op."""
    B = q.shape[0]
    c = scan_chunk(B, q.shape[1], mode, ids.size)
    if c == B:
        return _scan_pass(q, tiles, scales, ids, k, mode, rules)
    chunks = [
        _scan_pass(q[lo: lo + c], tiles, scales, ids, k, mode,
                   _query_rows(rules, lo, lo + c))
        for lo in range(0, B, c)
    ]
    return tuple(jnp.concatenate(part) for part in zip(*chunks))


def _query_rows(rules, lo: int, hi: int):
    """``rules`` (or None) for the queries ``lo`` .. ``hi`` of its
    batch: the per-query parts cut, the catalog's vectors shared."""
    return rules and rules._replace(
        qcat=rules.qcat[lo:hi], has_cat=rules.has_cat[lo:hi],
        ex=rules.ex[lo:hi],
    )


def _scan_pass(q, tiles, scales, ids, k: int, mode: str, rules):
    """``_coarse_scan`` for queries within ``scan_chunk``: the tile loop
    and the selection after it."""
    B = q.shape[0]
    dot = score_form(B, q.shape[1], mode) == "dot"
    nt, t = ids.shape[0], ids.size // ids.shape[0]
    g = select_group(t, k, nt)  # a step keeps a maximum a group
    if rules is not None:
        with jax.named_scope("retrieval.shortlist.mask"):
            ex = jnp.where(rules.ex >= 0, rules.ex, nt * t)  # pads drop
            hit = jnp.zeros((nt, B, t), bool).at[
                ex // t, jnp.arange(B)[:, None], ex % t
            ].set(True, mode="drop")
        masks = (
            rules.avail.reshape(nt, t),
            tuple(c.reshape(nt, t) for c in rules.cats),
            hit,
        )
    if mode == "int8_dot":
        with jax.named_scope("retrieval.shortlist.quantize_query"):
            qs = jnp.max(jnp.abs(q), axis=1, keepdims=True) / 127.0
            qi = jnp.clip(
                jnp.round(q / jnp.maximum(qs, 1e-12)), -127, 127
            ).astype(jnp.int8)
    elif dot:
        q3 = _split_bf16(q)

    def step(_, xs):
        if rules is not None:
            xs, (av, cs, ht) = xs
        if scales is None:
            v, tid = xs
        else:
            v, s, tid = xs
        # named scopes are metadata only: they name these ops in a
        # trace viewer (docs/observability.md)
        with jax.named_scope("retrieval.shortlist.score"):
            if mode == "int8_dot":
                sc = jax.lax.dot_general(
                    qi, v, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.int32,
                ).astype(jnp.float32)
            elif dot:
                # int8 values are whole numbers under 2^7: exact in bf16
                p = jax.lax.dot_general(
                    q3, v.astype(jnp.bfloat16), (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                # one row: everything after it in the side arrays' own
                # shape, where a select costs a quarter of what it does
                # over [1, T] (the reshape follows the three rows' sum,
                # not a dot, whose operand XLA would re-lay for it)
                sc = ((p[2:3] + p[1:2]) + p[:1]).reshape(1, *tid.shape)
            else:
                sc = jnp.matmul(
                    q, v.T.astype(jnp.float32),
                    preferred_element_type=jnp.float32,
                )
            # scale and guard where ``sc`` lies: a single's summed row
            # [1, T/128, 128] against the step's slices as stored, a
            # batch's [B, T] against flat views of them (bitcasts)
            side = (1, *sc.shape[1:])
            if scales is not None:
                sc = sc * s.reshape(side)
            sc = jnp.where(tid.reshape(side) >= 0, sc, NEG_INF).reshape(B, t)
        if rules is not None:
            with jax.named_scope("retrieval.shortlist.mask"):
                ok = rows_allowed(av, cs, ht, rules.qcat, rules.has_cat)
                sc = jnp.where(ok, sc, NEG_INF)
        if not g:
            return None, (sc, None)
        with jax.named_scope("retrieval.shortlist.group_max"):
            groups = sc.reshape(B, t // g, g)
            kept = groups, groups.max(axis=2)
            # (a batch's maxima ride its dot: nothing to keep once)
            return None, (_kept_once(kept) if dot else kept)

    xs = (tiles, ids) if scales is None else (tiles, scales, ids)
    if rules is not None:
        xs = (xs, masks)
    _, (scores, maxima) = jax.lax.scan(step, None, xs)
    with jax.named_scope("retrieval.shortlist.select"):
        best_s, best_i = _select(scores, maxima, ids, k)
        if rules is not None:
            best_i = jnp.where(best_s > NEG_INF / 2, best_i, -1)
    return best_s, best_i


@obs_device.track_jit("retrieval.coarse_topk")
@functools.partial(jax.jit, static_argnames=("k", "mode"))
def _coarse_topk(q, tiles, scales, ids, k: int, mode: str):
    return _coarse_scan(q, tiles, scales, ids, k, mode)


# Under rules a scan cannot start before its queries' rules are on the
# device, and an upload costs the host ~0.25 ms to hand over and ~0.6 ms
# to land whatever its size (PERF.md section 6, PR 29 and PR 34): three
# rule arrays, the vectors and a sum-of-rows form's two more were four and
# six of them a dispatch, every one before the launch. So a dispatch under
# rules goes up ONCE: ``pack`` lays the form's per-query arrays and the
# rules' beside each other in one [bp, W] int32 buffer (f32 columns as
# their bits), ``Layout`` says where each lies — a static argument, so a
# layout is a shape like any other: one program per (bp, C, E, L), as the
# separate arrays had — and the three masked programs take the buffer
# apart themselves (``_unpack``: slices and bit-casts in front of the
# bodies they always had; no program of its own, no eager slice). A form
# without rules has nothing to wait for but its vectors, and whatever
# else its rescore wants goes up behind the running scan: it stays as it
# was.


class Layout(NamedTuple):
    """Column widths of a packed dispatch, in the buffer's order: the
    [dim] f32 query vectors, ``Rules.qcat`` [cats], ``Rules.has_cat``
    (one column), ``Rules.ex`` [excluded], and for a sum of rows its
    [rows] indices and [rows] f32 weights (0: a form without)."""

    dim: int
    cats: int
    excluded: int
    rows: int = 0

    def bounds(self):
        """The six parts' (start, stop) columns."""
        ends = np.cumsum([self.dim, self.cats, 1, self.excluded,
                          self.rows, self.rows]).tolist()
        return list(zip([0] + ends, ends))


def pack(vectors, rules: Rules, ixs=None, weights=None):
    """(the [bp, W] int32 buffer, its ``Layout``) of a dispatch under
    ``rules`` from the host (models/filters.py ``query_rules``): B rows
    of every part, padded to the power of two at or above B with copies
    of row 0 (discarded after the read). f32 parts travel as their
    bits: ``_unpack`` gives every value back exactly."""
    parts = [
        np.ascontiguousarray(vectors, np.float32).view(np.int32),
        np.asarray(rules.qcat, np.int32),
        np.asarray(rules.has_cat, np.int32)[:, None],
        np.asarray(rules.ex, np.int32),
    ]
    if ixs is not None:
        parts += [
            np.asarray(ixs, np.int32),
            np.ascontiguousarray(weights, np.float32).view(np.int32),
        ]
    if len({len(p) for p in parts}) != 1:
        raise ValueError(
            f"parts of {[len(p) for p in parts]} rows do not make one batch"
        )
    layout = Layout(
        parts[0].shape[1], parts[1].shape[1], parts[3].shape[1],
        0 if ixs is None else parts[4].shape[1],
    )
    return _pad_rows(np.concatenate(parts, axis=1), _pow2(len(parts[0]))), layout


def _unpack(packed, layout: Layout, rules: Rules):
    """On the device, inside the program that reads them: ``pack``'s
    buffer -> (vectors, ``rules`` with its per-query parts filled in,
    ixs, weights); the last two empty for a form without rows."""
    f32 = functools.partial(
        jax.lax.bitcast_convert_type, new_dtype=jnp.float32
    )
    vecs, qcat, has_cat, ex, ixs, weights = (
        packed[:, a:b] for a, b in layout.bounds()
    )
    rules = rules._replace(qcat=qcat, has_cat=has_cat[:, 0] != 0, ex=ex)
    return f32(vecs), rules, ixs, f32(weights)


@obs_device.track_jit("retrieval.coarse_topk_masked")
@functools.partial(jax.jit, static_argnames=("k", "mode", "layout"))
def _coarse_topk_masked(q, tiles, scales, ids, rules: Rules, k: int,
                        mode: str, layout: Layout | None = None):
    """The same scan under business rules: a program of its own, so the
    unmasked one stays what it is (and a device trace tells them apart:
    ``jit__coarse_topk_masked``). Under ``layout``, ``q`` is ``pack``'s
    buffer and ``rules`` holds the resident vectors alone."""
    if layout is not None:
        q, rules, _, _ = _unpack(q, layout, rules)
    return _coarse_scan(q, tiles, scales, ids, k, mode, rules)


def device_rules(rules: Rules) -> Rules:
    """``rules`` with its per-query parts put on the device, one array
    each: for the callers off ``top_k``'s packed chain (the exact
    programs, a ``whiteList``'s host-built candidates, the tests'
    references)."""
    def up(part, dtype):
        part = np.asarray(part, dtype)
        with obs_device.transfer("h2d", "serve.rules", part.nbytes):
            return jnp.asarray(part)

    return rules._replace(
        qcat=up(rules.qcat, np.int32), has_cat=up(rules.has_cat, bool),
        ex=up(rules.ex, np.int32),
    )


def _resident(rules: Rules) -> Rules:
    """``rules``' catalog-wide vectors alone: what a program is handed
    beside a packed buffer, which holds the rest."""
    return Rules(rules.avail, rules.cats, None, None, None)


def _pad_rows(a, rows: int):
    """Host ``a`` filled to ``rows`` rows with copies of row 0."""
    if len(a) < rows:
        a = np.concatenate([a, np.repeat(a[:1], rows - len(a), axis=0)])
    return a


def _up(a, dtype, rows: int = 0, put=None):
    """``a`` as a device array: one that is there already as it lies
    (the chain's arrays go up once, for both stages), a host array
    converted to ``dtype``, padded to ``rows`` rows with copies of row 0
    (discarded after the read) and uploaded — to the default device, or
    by ``put``, the way a sharded catalog's arrays reach its shards
    (``ShardedCatalog.put_replicated``). The copy alone is the
    ``xfer.h2d[serve.dispatch]`` region, to its return."""
    if isinstance(a, jax.Array):
        return a
    a = _pad_rows(np.ascontiguousarray(a, dtype=dtype), rows)
    with obs_device.transfer("h2d", "serve.dispatch", a.nbytes):
        return jnp.asarray(a) if put is None else put(a)


_shortlist_stage = functools.partial(
    obs_trace.region, "dispatch.shortlist", hist=_m_shortlist_secs,
    cpu_hist=_m_shortlist_cpu,
)


def _fetch(out, n: int):
    """The read that ends a chain of launches: device ``(scores, ids)``
    -> their first ``n`` rows on the host, in one ``device_get``, as a
    ``dispatch.fetch`` region. The host waits here, and only here, for
    whatever the programs behind ``out`` still have to do. On one call
    in ``obs.trace.CPU_EVERY`` the two halves are told apart: the wait
    for the device is ``fetch.wait``, the copy of the finished answer
    ``xfer.d2h[serve.answers]`` — on that call alone, because a read
    asked for once the device is done no longer overlaps it: on a TPU
    v5e the split costs a single's dispatch 0.23 ms (PERF.md section 6,
    PR 50). One read either way."""
    with obs_trace.region("dispatch.fetch", hist=_m_fetch_secs):
        if obs_trace.one_in_every(_m_fetch_wait):
            with obs_trace.region("fetch.wait", hist=_m_fetch_wait):
                jax.block_until_ready(out)
            with obs_device.transfer("d2h", "serve.answers") as read:
                s, ids = jax.device_get(out)
                read.nbytes = s.nbytes + ids.nbytes
        else:
            s, ids = jax.device_get(out)
    _m_host_reads.inc()
    return s[:n], ids[:n]


class Scan(NamedTuple):
    """What ``CoarseCatalog.launch`` left on the device."""

    queries: jax.Array  # as uploaded: [bp, D] f32, or ``pack``'s buffer
    scores: jax.Array  # [bp, k'] coarse scores
    ids: jax.Array  # [bp, k'] int32 candidate ids, -1 past the catalog
    layout: Layout | None = None  # of ``queries``, where they are packed


def put_rows(values):
    """A [rows, ...] factor array as ONE device array: what is there
    already as it lies, a host array as ``jnp.asarray`` puts it, and the
    ``SpannedArray`` of a model that spans files a part at a time,
    joined on the device — the table is never one host array."""
    parts = getattr(values, "parts", None)
    if parts is None or len(parts) == 1:
        return jnp.asarray(values if parts is None else parts[0])
    return jnp.concatenate([jnp.asarray(p) for p in parts])


def put_padded(values, scales, rows: int):
    """A host factor table as ``put_rows`` puts it, with room to grow:
    filled to ``rows`` rows (zero rows, of scale 1 in an int8 pair) where
    it holds fewer, so that a row appended later changes no shape."""
    short = rows - int(values.shape[0])
    table = put_rows(values)
    if short > 0:
        table = jnp.pad(table, ((0, short), (0, 0)))
    if scales is None:
        return table
    scales = jnp.asarray(scales)
    return table, (
        jnp.pad(scales, (0, short), constant_values=1.0) if short > 0
        else scales
    )


@obs_device.track_jit("retrieval.patch_rows")
@jax.jit
def patch_rows(table, ixs, rows):
    """The resident ``table`` (dense rows, or an int8 ``(values, scales)``
    pair) with ``rows`` written at ``ixs`` [B], as a NEW array: a copy on
    the device, so a query that holds the old table reads its old rows
    whole. ``rows`` is [B, D] in the table's dtype, or that with the [B]
    scales. An index may repeat with the same row (padding to a stable
    B). Donating the table instead would save the copy — for 8 rows of
    [1,048,576, 64] int8 with their scales on a TPU v5e, 1.77 ms against
    1.95 ms one patch at a time and read back, 0.77 ms either way back to
    back (PERF.md section 6, PR 45): a tenth of a patch's two milliseconds
    — and cost a query in flight its buffer."""
    if isinstance(table, tuple):
        return (table[0].at[ixs].set(rows[0]), table[1].at[ixs].set(rows[1]))
    return table.at[ixs].set(rows)


@functools.partial(jax.jit, static_argnames=("nt", "t"))
def _quantized_tiles(values, scales, nt: int, t: int):
    """The coarse form of a resident int8 pair, made where it lies: the
    [I, D] values and [I] scales padded to ``nt`` whole tiles (zero
    rows of scale 1; their ids say -1) -> ([nt, t, D], the scales in
    ``side_shape(nt, t)``)."""
    pad = nt * t - values.shape[0]
    return (
        jnp.pad(values, ((0, pad), (0, 0))).reshape(nt, t, values.shape[1]),
        jnp.pad(scales, (0, pad), constant_values=1.0).reshape(
            side_shape(nt, t)
        ),
    )


class CoarseCatalog:
    """A catalog staged in tiled coarse form for the shortlist pass.

    Built once per (model, weights) from the serving factor table —
    dense [I, D] f32/bf16 or the int8 (values, scales) pair, on the host
    or (the pair) resident on the device already — and cached
    by the templates next to their device tables. int8 catalogs keep
    their existing quantized values (no re-quantization error on top of
    storage; the pair goes up once and is tiled on the device,
    ``_quantized_tiles``: no padded or dequantized host copy); dense
    catalogs get an int8 or bf16 coarse COPY whose quantization error
    only ever costs shortlist coverage, never final score accuracy (the
    rescore reads the original table).

    Tiles are [NT, T, D], so one scan step's working set is a T-row slab
    regardless of I. The per-row side arrays — the row ids (-1 marks
    padding past the catalog) and an int8 pair's row scales — are
    stored [NT, T/128, 128] (``side_shape``): a step's slice of each is
    one dense block of the device's memory, where a row of [NT, T] is a
    sublane of every memory tile.
    """

    def __init__(self, item_table, tile: int | None = None,
                 mode: str | None = None):
        quantized = isinstance(item_table, tuple)
        vals = item_table[0] if quantized else item_table
        self.num_rows = int(vals.shape[0])
        self.dim = int(vals.shape[1])
        if mode is None:
            # an int8 pair is scanned as stored, against the f32 query: on
            # a TPU v5e a single's scan of 184 tiles is 9.2 ms so (its
            # three-row dot reads a tile in 23 us) and 22.6 ms with the
            # query quantized too ("int8_dot": a one-row s8 product has no
            # dot form and costs 96 us a tile), and from two queries on
            # the two are one speed within 2 % (PERF.md section 6, PR 41)
            mode = "int8" if quantized else "bf16"
        if mode not in ("int8", "int8_dot", "bf16"):
            raise ValueError(f"unknown coarse mode {mode!r}")
        self.mode = mode
        T = min(int(tile or tile_size()), _pow2(max(1, self.num_rows)))
        nt = -(-self.num_rows // T)
        pad = nt * T - self.num_rows
        self.tile = T

        if mode == "bf16":
            f = np.asarray(
                item_table[0], dtype=np.float32
            ) * np.asarray(item_table[1], np.float32)[:, None] if quantized \
                else np.asarray(item_table, dtype=np.float32)
            if pad:
                f = np.concatenate([f, np.zeros((pad, self.dim), np.float32)])
            self._tiles = jnp.asarray(f).astype(jnp.bfloat16).reshape(
                nt, T, self.dim
            )
            self._scales = None
        else:
            if quantized:  # the stored values are the tiles: as they lie
                vq, vs = item_table
            else:
                f = np.asarray(item_table, dtype=np.float32)
                s = np.max(np.abs(f), axis=1) / 127.0
                vs = np.where(s > 0, s, 1.0).astype(np.float32)
                vq = np.rint(f / vs[:, None]).astype(np.int8)
            self._tiles, self._scales = _quantized_tiles(
                put_rows(vq), jnp.asarray(vs), nt, T
            )
        ids = np.concatenate(
            [np.arange(self.num_rows, dtype=np.int32),
             np.full(pad, -1, np.int32)]
        )
        self._ids = jnp.asarray(ids.reshape(side_shape(nt, T)))
        set_resident(coarse=self._tiles, coarse_scales=self._scales,
                     coarse_ids=self._ids)

    def nbytes(self) -> int:
        """Device-resident coarse bytes (tiles + scales + ids)."""
        n = self._tiles.size * self._tiles.dtype.itemsize
        if self._scales is not None:
            n += self._scales.size * 4
        return n + self._ids.size * 4

    @property
    def stored_rows(self) -> int:
        """Rows the tiles hold, padding included: the length of a
        ``Rules`` vector that this catalog's scan can slice."""
        return int(self._ids.size)

    def launch(self, queries, k: int, rules: Rules | None = None,
               pack_with: tuple | None = None):
        """The coarse scan enqueued, nothing read: -> ``Scan``, device
        arrays of bp rows, bp the power of two at or above the B queries
        given (``shortlist`` below has the contract). ``top_k`` hands
        the ids to a rescore program as they are. ``pack_with``: None —
        the queries go up alone, the ``rules`` are ``device_rules`` —
        or what else of the form goes into ONE upload with the queries
        and the host ``rules``' rows for them (``pack``: nothing, or a
        sum of rows' indices and weights), which the rescore then reads
        out of ``Scan.queries`` too."""
        k = max(1, min(int(k), self.tile))
        layout = None
        with _shortlist_stage():
            if pack_with is None:
                q = _up(queries, np.float32, _pow2(len(queries)))
            else:
                queries, layout = pack(queries, rules, *pack_with)
                q, rules = _up(queries, np.int32), _resident(rules)
            if rules is None:
                s, ids = _coarse_topk(
                    q, self._tiles, self._scales, self._ids, k, self.mode,
                )
            else:
                if layout is None and len(rules.ex) != len(q):
                    raise ValueError(
                        f"rules for {len(rules.ex)} queries, "
                        f"batch of {len(q)}"
                    )
                s, ids = _coarse_topk_masked(
                    q, self._tiles, self._scales, self._ids, rules, k,
                    self.mode, layout,
                )
        _count_scan(len(q), k, self.dim, self.mode, self.stored_rows)
        return Scan(q, s, ids, layout)

    def shortlist(self, queries, k: int, rules: Rules | None = None):
        """Coarse top-k' candidate ids for a [B, D] f32 query batch ->
        host ([B, k'] coarse scores, [B, k'] int32 ids, -1 past the
        catalog). B pads to a pow2 bucket (copies of row 0, discarded)
        and k' clamps to the tile width, so arbitrary traffic reuses a
        bounded set of compiled programs. Under ``rules``
        (``device_rules``: vectors of ``stored_rows``, per-query rows
        for a batch the caller has padded to a power of two) only rows
        the query may be served are candidates; a query with fewer than
        k' of them gets id -1 in the rest."""
        scan = self.launch(queries, k, rules)
        return _fetch((scan.scores, scan.ids), len(queries))


# -- exact rescore kernels ---------------------------------------------------


# A TPU keeps a resident [rows, D] table row-major only where D fills
# the 128 lanes; otherwise the long axis is minor (no lane padding) and
# a logical row is D strided elements. XLA gathers rows out of that
# layout where they lie while the gathered minor dimension stays under
# 64 (56 passes; f32, bf16 and int8 alike); from 64 on it first copies
# the WHOLE table row-major, on every call. Seen as [rows, D/32, 32] the
# same buffer keeps the gather under that limit, and the reshape is a
# bitcast: 32 is a whole number of sublane tiles for all three dtypes
# (a group of 25 is not: that reshape is a real copy and a 145 s compile).
_VIEW_COLUMNS = 32


def _gather_rows(table, ixs):
    """f32 ``table[ixs]`` of a resident [rows, D] table, gathered
    through the [rows, D/32, 32] view where that spares the table a
    re-layout (above). Every other D takes the plain gather: free of
    one below 64 columns and at multiples of 128, not at the ranks in
    between that 32 does not divide (100: PERF.md section 7).
    ``pio_retrieval_rescore_temp_bytes`` says when a program re-lays."""
    rows, dim = table.shape
    if dim > _VIEW_COLUMNS and dim % _VIEW_COLUMNS == 0 and dim % _LANES:
        table = table.reshape(rows, dim // _VIEW_COLUMNS, _VIEW_COLUMNS)
    return table[ixs].reshape(*ixs.shape, dim).astype(jnp.float32)


def _table_rows(table, ixs):
    """Dequantized f32 rows ``ixs`` of a factor table: the dense array,
    or the int8 ``(values, scales)`` pair."""
    if isinstance(table, tuple):
        values, scales = table
        with jax.named_scope("retrieval.rescore.dequant"):
            return _gather_rows(values, ixs) * scales[ixs][..., None]
    return _gather_rows(table, ixs)


def _score_candidates(qvecs, item_factors, cand_ids, k: int, rules=None,
                      precision=None):
    """Shared exact-f32 candidate scorer: gather the [B, S] candidate
    rows (dequantizing int8 pairs on device), dot against the query
    vectors, top-k. -1 candidate slots can never win and report id -1;
    nor can a candidate that ``rules`` keeps from its query (the
    shortlist applied them already: a second, independent application
    where the served scores are produced). ``precision``: the dot's,
    where a caller states one (the sharded chain: HIGHEST); None leaves
    the programs of one chip what they were."""
    with jax.named_scope("retrieval.rescore.gather"):
        cand = jnp.maximum(cand_ids.astype(jnp.int32), 0)
        rows = _table_rows(item_factors, cand)
    with jax.named_scope("retrieval.rescore.score"):
        sc = jnp.einsum(
            "bd,bsd->bs", qvecs.astype(jnp.float32), rows,
            precision=precision, preferred_element_type=jnp.float32,
        )
        sc = jnp.where(cand_ids >= 0, sc, NEG_INF)
    if rules is not None:
        with jax.named_scope("retrieval.rescore.mask"):
            hit = (cand_ids[:, :, None] == rules.ex[:, None, :]).any(-1)
            ok = rows_allowed(
                rules.avail[cand], tuple(c[cand] for c in rules.cats), hit,
                rules.qcat, rules.has_cat,
            )
            sc = jnp.where(ok, sc, NEG_INF)
    with jax.named_scope("retrieval.rescore.topk"):
        k = min(k, int(cand_ids.shape[1]))
        s, ix = jax.lax.top_k(sc, k)
        ids = jnp.take_along_axis(cand_ids.astype(jnp.int32), ix, axis=1)
        return s, jnp.where(s > NEG_INF / 2, ids, -1)


class _RescoreProgram:
    """A rescore entry point as ``jax.jit`` would run it, compiled ahead
    of the first call of each signature (shapes, dtypes, the tables'
    layouts, ``k``) so that the compiled program's own memory analysis
    can be published (``k`` and, where ``fn`` has one, ``layout`` are
    static keywords): ``pio_retrieval_rescore_temp_bytes{fn}`` holds
    the most temporary bytes any of ``fn``'s programs needs. A program
    that re-lays a table before it gathers needs a table's worth.
    ``obs_device.track_jit`` counts these compiles through
    ``_cache_size``, as it counts a jit's."""

    def __init__(self, name: str, fn):
        self.name = name
        static = {"k", "layout"} & set(inspect.signature(fn).parameters)
        self._jit = jax.jit(fn, static_argnames=tuple(sorted(static)))
        self.lower = self._jit.lower
        self._compiled: dict = {}
        self._lock = threading.Lock()
        self._m_temp = obs_metrics.gauge(
            "pio_retrieval_rescore_temp_bytes",
            "most temporary device bytes a compiled rescore program needs",
            fn=name,
        )

    def _cache_size(self) -> int:
        return len(self._compiled)

    def temp_bytes(self) -> int:
        return int(self._m_temp.value())

    def __call__(self, *args, **static):
        key = (*sorted(static.items()), *(
            (a.shape, a.dtype, getattr(a, "format", None))
            for a in jax.tree.leaves(args)
        ))
        program = self._compiled.get(key)
        if program is None:
            with self._lock:
                program = self._compiled.get(key)
                if program is None:
                    program = self._jit.lower(*args, **static).compile()
                    stats = program.memory_analysis()
                    if stats is not None:  # a backend may not say
                        self._m_temp.set(max(
                            self._m_temp.value(), stats.temp_size_in_bytes
                        ))
                    self._compiled[key] = program
        return program(*args)


_RESCORE_PROGRAMS: list[_RescoreProgram] = []


def _rescore_program(name: str):
    def deco(fn):
        program = _RescoreProgram(name, fn)
        _RESCORE_PROGRAMS.append(program)
        return obs_device.track_jit(name)(program)

    return deco


@_rescore_program("retrieval.rescore_gather")
def _rescore_gather(user_ixs, user_factors, item_factors, cand_ids, k: int):
    qvecs = _table_rows(user_factors, user_ixs.astype(jnp.int32))
    return _score_candidates(qvecs, item_factors, cand_ids, k)


@_rescore_program("retrieval.rescore_vectors")
def _rescore_vectors(user_vectors, item_factors, cand_ids, k: int):
    return _score_candidates(user_vectors, item_factors, cand_ids, k)


# Under ``layout`` a masked program's first argument is the scan's
# packed buffer, ``rules`` the resident vectors alone, and a sum of
# rows' weights are in the buffer too (``row_weights`` None).


@_rescore_program("retrieval.rescore_vectors_masked")
def _rescore_vectors_masked(user_vectors, item_factors, cand_ids,
                            rules: Rules, k: int,
                            layout: Layout | None = None):
    if layout is not None:
        user_vectors, rules, _, _ = _unpack(user_vectors, layout, rules)
    return _score_candidates(user_vectors, item_factors, cand_ids, k, rules)


@_rescore_program("retrieval.rescore_sum_rows_masked")
def _rescore_sum_rows_masked(row_ixs, row_weights, item_factors, cand_ids,
                             rules: Rules, k: int,
                             layout: Layout | None = None):
    if layout is not None:
        _, rules, row_ixs, row_weights = _unpack(row_ixs, layout, rules)
    rows = _table_rows(item_factors, row_ixs.astype(jnp.int32))
    qvecs = jnp.sum(rows * row_weights[..., None], axis=1)
    return _score_candidates(qvecs, item_factors, cand_ids, k, rules)


# Each rescore entry point comes twice: ``_launch_*`` converts and uploads
# what is not on the device yet (per-query arrays pad to the candidates'
# rows) and enqueues the program, as one ``dispatch.rescore`` region that
# reads nothing; the host-facing ``rescore_*_top_k_batch`` is that plus
# the read, for callers whose candidate lists are built on the host.
# ``top_k`` calls the launchers with the scan's device ids.

_rescore_stage = functools.partial(
    obs_trace.region, "dispatch.rescore", hist=_m_rescore_secs,
    cpu_hist=_m_rescore_cpu,
)


def _read_rescore(out, n_queries: int):
    _m_two_stage.inc(n_queries)
    return _fetch(out, n_queries)


def _launch_gather(user_ixs, user_factors, item_factors, cand_ids, k: int):
    with _rescore_stage():
        cand = _up(cand_ids, np.int32)
        return _rescore_gather(
            _up(user_ixs, np.int32, len(cand)), user_factors, item_factors,
            cand, k=k,
        )


def _launch_vectors(user_vectors, item_factors, cand_ids, k: int,
                    rules: Rules | None = None, layout: Layout | None = None):
    with _rescore_stage():
        cand = _up(cand_ids, np.int32)
        vecs = _up(user_vectors, np.float32, len(cand))
        if rules is None:
            return _rescore_vectors(vecs, item_factors, cand, k=k)
        return _rescore_vectors_masked(
            vecs, item_factors, cand, rules, k=k, layout=layout
        )


def _launch_sum_rows(row_ixs, row_weights, item_factors, cand_ids, k: int,
                     rules: Rules):
    with _rescore_stage():
        cand = _up(cand_ids, np.int32)
        return _rescore_sum_rows_masked(
            _up(row_ixs, np.int32, len(cand)),
            _up(row_weights, np.float32, len(cand)),
            item_factors, cand, rules, k=k,
        )


def rescore_gather_top_k_batch(user_ixs, user_factors, item_factors,
                               cand_ids, k: int):
    """Shortlist-gather variant of ``gather_top_k_batch``: [B] user row
    indices + the device-resident tables + a [B, S] candidate-id matrix
    instead of scoring [B, I]. The query vectors are gathered and
    dequantized exactly like the exact path's, so the returned ranking
    equals the exact ranking restricted to the candidates."""
    return _read_rescore(_launch_gather(
        user_ixs, user_factors, item_factors, cand_ids, k
    ), len(cand_ids))


def rescore_top_k_batch(user_vectors, item_factors, cand_ids, k: int,
                        rules: Rules | None = None):
    """Shortlist-gather variant of ``top_k_items_batch``: [B, D] query
    vectors against a [B, S] candidate-id matrix, under ``rules`` where
    given (``device_rules``, their per-query rows for these B queries).
    Over a ``ShardedCatalog`` the ``rules`` are required and are the
    host's (``query_rules``, its ``row_vector`` s): they go up packed."""
    if getattr(item_factors, "shards", 0):
        return _listed_sharded(user_vectors, item_factors, cand_ids, k, rules)
    return _read_rescore(_launch_vectors(
        user_vectors, item_factors, cand_ids, k, rules
    ), len(cand_ids))


def rescore_sum_rows_top_k_batch(row_ixs, row_weights, item_factors,
                                 cand_ids, k: int, rules: Rules):
    """Shortlist-gather variant of ``sum_rows_top_k_batch_masked`` for
    the cosine-family templates: the query vector is the weighted sum of
    gathered catalog rows (built on device exactly like the exact op),
    scored against the [B, S] candidates only, under ``rules`` (as
    ``rescore_top_k_batch``: a query of these templates always excludes
    its own rows, so the form has no rule-less program)."""
    return _read_rescore(_launch_sum_rows(
        row_ixs, row_weights, item_factors, cand_ids, k, rules
    ), len(cand_ids))


# -- the serving chain ---------------------------------------------------------
#
# A query batch reaches ``top_k`` in one of three forms. A form is the
# argument list that an exact op of ops/topk.py and its rescore variant
# above share, and knows four things: the f32 vectors the coarse pass
# scores (``coarse_vectors``), what else of it the scan's one upload
# carries under rules (``pack_with``; None: the vectors go up alone), its
# exact program and its rescore program (``rescore``: enqueued behind
# the ``Scan`` on its device ids, at their power-of-two rows — and under
# rules on its packed buffer, so that nothing more goes up). Every form
# leads with its [B, ...] per-query array and carries ``rules`` (None
# where the form has none; ``SumRows`` always has; their per-query parts
# host arrays, models/filters.py ``query_rules``), and ``head()`` is its
# first query alone: what the recall probe scores, in the shapes that
# query would have arriving alone.


def _head_rules(r: Rules | None) -> Rules | None:
    """``r`` with the first query's rows only."""
    return r and r._replace(qcat=r.qcat[:1], has_cat=r.has_cat[:1], ex=r.ex[:1])


class UserRows(NamedTuple):
    """[B] row indices into a resident user table
    (``gather_top_k_batch``)."""

    ixs: np.ndarray
    users: object  # the device-resident user table
    vectors: Callable  # ixs -> their [B, D] f32 rows, on the host
    rules = None

    def coarse_vectors(self):
        return self.vectors(self.ixs)

    def pack_with(self):
        return None  # the indices go up behind the running scan

    def exact(self, table, k: int):
        return gather_top_k_batch(self.ixs, self.users, table, k=k)

    def rescore(self, table, scan: Scan, k: int):
        return _launch_gather(self.ixs, self.users, table, scan.ids, k)

    def head(self):
        return self._replace(ixs=self.ixs[:1])


class Vectors(NamedTuple):
    """[B, D] f32 query vectors (``top_k_items_batch``), under
    ``Rules`` where given (``top_k_items_batch_masked``): the caller
    has padded the batch to the power of two the rules hold."""

    vectors: np.ndarray
    rules: Rules | None = None

    def coarse_vectors(self):
        return self.vectors

    def pack_with(self):
        return None if self.rules is None else ()

    def exact(self, table, k: int):
        if self.rules is None:
            return top_k_items_batch(self.vectors, table, k=k)
        return top_k_items_batch_masked(
            self.vectors, table, device_rules(self.rules), k=k
        )

    def rescore(self, table, scan: Scan, k: int):
        # the scan's upload is the rescore's: the queries, or the buffer
        return _launch_vectors(
            scan.queries, table, scan.ids, k,
            self.rules and _resident(self.rules), scan.layout,
        )

    def head(self):
        return Vectors(self.vectors[:1], _head_rules(self.rules))


class SumRows(NamedTuple):
    """Weighted sums of catalog rows, [B, L] indices and weights, under
    ``Rules`` (``sum_rows_top_k_batch_masked``; the batch padded
    as for ``Vectors``): a query's own rows, its blackList and its
    categories are rules like any other, applied inside the scan and the
    rescore, and every query of these templates has the first."""

    ixs: np.ndarray
    weights: np.ndarray
    vectors: Callable  # (ixs, weights) -> the [B, D] f32 sums, on the host
    rules: Rules

    def coarse_vectors(self):
        return self.vectors(self.ixs, self.weights)

    def pack_with(self):
        return self.ixs, self.weights

    def exact(self, table, k: int):
        return sum_rows_top_k_batch_masked(
            self.ixs, self.weights, table, device_rules(self.rules), k=k
        )

    def rescore(self, table, scan: Scan, k: int):
        with _rescore_stage():  # all it reads went up with the scan
            return _rescore_sum_rows_masked(
                scan.queries, None, table, scan.ids, _resident(self.rules),
                k=k, layout=scan.layout,
            )

    def head(self):
        return self._replace(
            ixs=self.ixs[:1], weights=self.weights[:1],
            rules=_head_rules(self.rules),
        )


def top_k(query, table, num_rows: int, coarse, k: int,
          probe_n: int | None = None):
    """Host ([B, k] scores, [B, k] ids, -1 where a query has fewer
    answers) for ``query`` (a form above) against the resident exact
    ``table`` of a catalog of ``num_rows`` rows. The one place that
    decides how: the form's exact program, or — where ``two_stage_k``
    says so — a shortlist from ``coarse`` (the catalog's
    ``CoarseCatalog``, or a callable that returns it, called only then;
    a caller that asked ``two_stage_k`` itself and got 0 has none to
    give) rescored by the form's rescore program — the scan's ids stay
    on the device, the rescore is enqueued behind the scan, and one read
    brings the answer back; a form under rules, whose scan has to wait
    for them, goes up in ONE upload that both programs take apart
    (``pack``), a form without as it always did: its vectors, then what
    its rescore wants behind the running scan — and on every
    ``PIO_RETRIEVAL_PROBE_EVERY``-th such dispatch the exact program
    again on the first query, whose leading ``probe_n`` ids (the ones
    its answer is cut from; all k by default) are compared. No filter
    keeps a query from the shortlist: ``path="exact"`` counts the
    queries of a catalog at retrieval scale whose k leaves a shortlist
    no room."""
    kp = two_stage_k(k, num_rows)
    if getattr(table, "shards", 0):
        return _top_k_sharded(query, table, kp, k, probe_n)
    if not kp:
        if engaged(num_rows):
            _m_exact.inc(len(query[0]))
        s, ids = query.exact(table, k)
        return np.asarray(s), np.asarray(ids)
    if callable(coarse):
        coarse = coarse()
    scan = coarse.launch(
        query.coarse_vectors(), kp, query.rules, query.pack_with()
    )
    s, ids = _read_rescore(query.rescore(table, scan, k), len(query[0]))
    probe(
        ids[0, :probe_n],
        lambda: np.asarray(query.head().exact(table, k)[1])[0, :probe_n],
    )
    return s, ids


def _top_k_sharded(query, catalog, kp: int, k: int, probe_n: int | None):
    """``top_k`` over a ``parallel.shard_topk.ShardedCatalog`` (the
    exact rows AND the coarse copy, split row-wise over a mesh): the
    form gives its f32 vectors, which go up ONCE, to the mesh's first
    device (``ShardedCatalog.put_replicated``: the device batch is
    [shards, bp, D], shard 0's block the upload), and ONE program hands
    them round, scans, rescores and merges on the shards —
    ``dispatch.shortlist`` is the conversion, the upload and that
    launch, ``dispatch.fetch`` the one read; there is no second enqueue
    to call ``dispatch.rescore``. The decision is ``top_k``'s (kp = 0:
    the sharded exact program), the probe re-scores the first query with
    that program. ``Vectors`` under rules go up packed (``pack``: the
    vectors and the queries' own rules in one buffer, lists in global
    row ids, by the same route) and the masked programs run, the rules'
    vectors sharded like the rows (``ShardedCatalog.row_vector``); a sum
    of rows would have to gather its rows across the shards first, and
    is refused."""
    rules, layout = query.rules, None
    if isinstance(query, SumRows):
        raise ValueError(
            "a sharded catalog serves no SumRows query under rules yet: the "
            "rows a query sums lie on other shards than the rows it scores"
        )
    n = len(query[0])
    with _shortlist_stage():
        if rules is None:
            q = catalog.put_queries(query.coarse_vectors())
        else:
            packed, layout = pack(query.coarse_vectors(), rules)
            q, rules = catalog.put_replicated(packed, np.int32), _resident(rules)
        out = (catalog.launch(q, kp, k, rules, layout) if kp
               else catalog.launch_exact(q, k, rules, layout))
    s, ids = _fetch(out, n)
    bp = q.shape[1]  # the padded batch: q is [shards, bp, ...]
    _m_gather_bytes.inc(catalog.gather_bytes(bp, min(k, kp) if kp else k))
    if rules is not None:
        _m_sharded_masked.inc(n)
    if not kp:
        if engaged(catalog.num_rows):
            _m_exact.inc(n)
        return s, ids
    _m_sharded.inc(n)
    _count_scan(bp, min(kp, catalog.tile), catalog.dim, catalog.mode,
                catalog.stored_rows)
    probe(
        ids[0, :probe_n],
        lambda: _fetch(
            catalog.launch_exact(q[:, :1], k, rules, layout), 1
        )[1][0, :probe_n],
    )
    return s, ids


def _listed_sharded(vectors, catalog, cand_ids, k: int, rules: Rules):
    """``rescore_top_k_batch`` over a ``ShardedCatalog``: the masked
    sharded program with the [B, S] candidate lists (global row ids) in
    the scan's place — every shard scores the listed rows it holds under
    the host ``rules``, which go up packed with the vectors."""
    n = len(cand_ids)
    with _rescore_stage():
        packed, layout = pack(vectors, rules)
        q = catalog.put_replicated(packed, np.int32)
        out = catalog.launch(
            q, 1, k, _resident(rules), layout,
            catalog.put_replicated(cand_ids, np.int32, q.shape[1]),
        )
    _m_sharded.inc(n)
    _m_sharded_masked.inc(n)
    return _fetch(out, n)
