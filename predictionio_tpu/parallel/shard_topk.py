"""Stationary-shard retrieval: serve a catalog one chip cannot hold.

The reference's answer to "model bigger than one host" is a PAlgorithm
whose RDD-backed model issues a Spark job per query
(MatrixFactorizationModel.recommendProducts, invoked from
examples/scala-parallel-recommendation/custom-prepartor/src/main/scala/
ALSAlgorithm.scala:88) — per-query cluster scatter/gather over TCP. Here
the item rows stand still and the query travels:

- item rows are split row-wise over the mesh axis; device ``i`` holds
  rows ``[i*R, (i+1)*R)`` three ways — the exact f32 rows, their bf16
  coarse copy in tiles (what ``ops.retrieval.CoarseCatalog`` holds for
  one chip), and their GLOBAL ids (-1 marks padding: a catalog the mesh
  does not divide, a last tile not full);
- a dispatch copies the [B, D] query vectors (256 B a query at rank
  64) to ONE device, the mesh's first — the device batch is
  [shards, bp, D] split over the mesh axis: shard 0's block is that
  copy, the others' are resident zeros, stitched without a copy or a
  launch (``ShardedCatalog.put_replicated``) — and runs ONE program
  under ``shard_map``, whose first statement hands shard 0's block to
  every shard over the chips' links (``retrieval.shard.broadcast``:
  bits moved, never added); each device then scans its
  own tiles with the one-chip scan (``retrieval._coarse_scan``, not a
  copy — so a step only scores its tile and keeps the scores and their
  group maxima, each device selects its k' best once, after its own
  loop, and a batch whose stored scores would outweigh half a shard's
  tiles, and 604 MB, is scanned in chunks, ``retrieval.scan_chunk``, as
  on one chip), rescores its own shortlist
  against its own f32 rows (``retrieval._score_candidates``,
  ``precision=HIGHEST``), keeps its k best, and one ``all_gather`` of
  [B, k] scores and ids — ``shards x B x k x 8`` bytes — feeds the
  merge to the global top-k on every device;
- the host reads the answer once (``retrieval.top_k`` owns the chain and
  its one ``device_get``).

Under business rules (``ops.topk.Rules``: the E-Commerce template's
seen, unavailable, blackList and category filters) the catalog-wide
vectors are sharded like the rows they guard — device ``i`` holds the
availability byte and the category ids of its own stored rows, padding
unavailable — and a query's own list travels with the queries, in
GLOBAL row ids, inside the dispatch's one packed upload
(``retrieval.pack``), by the same route. Each
shard turns the list into its own rows (``retrieval.shard.rules``: id -
first, a row of another shard a pad) and runs the scan and the rescore
the one-chip storefront runs, under those rules; a shard with no
allowed row answers -1s, which the merge drops. A ``whiteList`` is the
same program with the list in the scan's place: a shard scores the
listed rows it holds.

The union of the local shortlists holds the one-chip shortlist (a row
among the catalog's k' best is among its shard's k' best), so recall is
no lower than the one-chip chain's, and every served score is the f32
dot of the stored rows.

Training moves the other way round (``als_sharded.py``'s ring half-step
rotates factor shards past stationary ratings): a solve reads every
opposite row, so the rows must visit every device; a query reads a
shortlist, so 4.6 GB of catalog stays put and a few KB move.
"""

from __future__ import annotations

import contextlib
import functools
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from predictionio_tpu.obs import device as obs_device
from predictionio_tpu.ops import retrieval
from predictionio_tpu.ops.topk import NEG_INF, Rules, _f32_scores, _top_k_allowed

_HIGHEST = jax.lax.Precision.HIGHEST


def _from_first(x, axis: str):
    """A shard's [1, ...] block of ``put_replicated``'s device batch ->
    shard 0's block, on every shard: the one copy a dispatch made,
    handed round in one collective. Bits are moved, not added (an f32
    sum would turn -0.0 into +0.0), so a shard holds what a copy of its
    own would have given it."""
    with jax.named_scope("retrieval.shard.broadcast"):
        return jax.lax.all_gather(x[0], axis)[0]


def _merge(all_s, all_i, k: int):
    """[n, B, k] local answers -> the global ([B, k] scores, [B, k]
    ids): shard-major candidates, so equal scores keep the lower id."""
    n, b, kk = all_s.shape
    cs = all_s.transpose(1, 0, 2).reshape(b, n * kk)
    ci = all_i.transpose(1, 0, 2).reshape(b, n * kk)
    s, ix = jax.lax.top_k(cs, k)
    ids = jnp.take_along_axis(ci, ix, axis=1)
    return s, jnp.where(s > NEG_INF / 2, ids, -1)


def _gather_merge(s, gid, k: int, axis: str):
    """Every shard's [B, k] scores and global ids to every shard in ONE
    all-gather (the scores ride as the int32 of their bits: integers are
    moved as they are, where a small id read as f32 is a denormal), then
    the merge."""
    with jax.named_scope("retrieval.shard.gather"):
        bits = jax.lax.bitcast_convert_type(s, jnp.int32)
        both = jax.lax.all_gather(jnp.stack([bits, gid]), axis)  # [n, 2, B, k]
        all_s = jax.lax.bitcast_convert_type(both[:, 0], jnp.float32)
    with jax.named_scope("retrieval.shard.merge"):
        return _merge(all_s, both[:, 1], k)


@obs_device.track_jit("retrieval.sharded_topk")
@functools.partial(
    jax.jit, static_argnames=("r", "kp", "k", "mode", "mesh", "axis")
)
def _sharded_topk(q, rows, tiles, ids, r: int, kp: int, k: int, mode: str,
                  mesh: Mesh, axis: str):
    """One dispatch of two-stage retrieval over stationary shards:
    ``put_replicated``'s [shards, B, D] queries -> replicated ([B, k]
    scores, [B, k] global ids). Shard ``i`` holds the catalog's rows
    from ``i * r`` on."""

    def local(q, rows, tiles, ids):
        q = _from_first(q, axis)
        first = jax.lax.axis_index(axis) * r  # the global id of local row 0
        with jax.named_scope("retrieval.shard.scan"):
            _, cand = retrieval._coarse_scan(q, tiles, None, ids, kp, mode)
        with jax.named_scope("retrieval.shard.rescore"):
            # global ids -> rows of this shard's table, and back
            s, lix = retrieval._score_candidates(
                q, rows, jnp.where(cand >= 0, cand - first, -1), k,
                precision=_HIGHEST,
            )
            gid = jnp.where(lix >= 0, lix + first, -1)
        return _gather_merge(s, gid, k, axis)

    return jax.shard_map(
        local, mesh=mesh, in_specs=(P(axis), P(axis), P(axis), P(axis)),
        out_specs=(P(), P()), check_vma=False,
    )(q, rows, tiles, ids)


@obs_device.track_jit("retrieval.sharded_exact")
@functools.partial(jax.jit, static_argnames=("k", "mesh", "axis"))
def _sharded_exact(q, rows, ids, k: int, mesh: Mesh, axis: str):
    """The exact program over the same shards: every row scored in f32
    (``ops.topk._f32_scores``), each shard's k best, the same gather and
    merge. What the recall probe runs, and a catalog under the
    retrieval threshold."""

    def local(q, rows, ids):
        q = _from_first(q, axis)
        with jax.named_scope("retrieval.shard.exact"):
            gids = ids.reshape(-1)
            sc = jnp.where(gids[None, :] >= 0, _f32_scores(q, rows), NEG_INF)
            s, ix = jax.lax.top_k(sc, min(k, sc.shape[1]))
            gid = jnp.where(s > NEG_INF / 2, gids[ix], -1)
        return _gather_merge(s, gid, min(k, mesh.shape[axis] * s.shape[1]), axis)

    return jax.shard_map(
        local, mesh=mesh, in_specs=(P(axis), P(axis), P(axis)),
        out_specs=(P(), P()), check_vma=False,
    )(q, rows, ids)


def _own_rows(gids, first, r: int):
    """Global row ids -> positions among the ``r`` rows of the shard that
    starts at ``first``; -1 for a row of another shard, and for a pad."""
    return jnp.where((gids >= first) & (gids < first + r), gids - first, -1)


def _shard_rules(packed, layout, avail, cats, first, r: int, axis: str):
    """A packed dispatch on one shard: (the f32 queries, their ``Rules``
    over THIS shard's stored rows). The buffer is handed round first
    (``_from_first``). The query's own list arrives in global row ids;
    a row this shard holds becomes its position here (``id - first``),
    any other a pad — as is a list's own padding."""
    packed = _from_first(packed, axis)
    with jax.named_scope("retrieval.shard.rules"):
        q, rules, _, _ = retrieval._unpack(
            packed, layout, Rules(avail, cats, None, None, None)
        )
        return q, rules._replace(ex=_own_rows(rules.ex, first, r))


@obs_device.track_jit("retrieval.sharded_topk_masked")
@functools.partial(
    jax.jit, static_argnames=("r", "kp", "k", "mode", "mesh", "axis", "layout")
)
def _sharded_topk_masked(packed, cand, rows, tiles, ids, avail, cats, r: int,
                         kp: int, k: int, mode: str, mesh: Mesh, axis: str,
                         layout):
    """``_sharded_topk`` under business rules: ``packed`` is
    ``retrieval.pack``'s buffer as ``put_replicated`` left it (the
    queries and their own rules, lists in global ids), ``avail`` /
    ``cats`` the catalog-wide rules sharded like the rows. Each shard
    runs the one-chip masked scan and masked rescore over the rows it
    holds. ``cand`` None: the scan shortlists; else ``put_replicated``'s
    [shards, B, S] global candidate ids (a ``whiteList``) stand in the
    scan's place, each shard scoring those it holds. A program of its
    own: the unmasked one stays what it is."""

    def local(packed, cand, rows, tiles, ids, avail, cats):
        first = jax.lax.axis_index(axis) * r
        q, rules = _shard_rules(packed, layout, avail, cats, first, r, axis)
        if cand is not None:
            cand = _from_first(cand, axis)
        else:
            with jax.named_scope("retrieval.shard.scan"):
                _, cand = retrieval._coarse_scan(
                    q, tiles, None, ids, kp, mode, rules
                )
        with jax.named_scope("retrieval.shard.rescore"):
            s, lix = retrieval._score_candidates(
                q, rows, _own_rows(cand, first, r), k, rules,
                precision=_HIGHEST,
            )
            gid = jnp.where(lix >= 0, lix + first, -1)
        return _gather_merge(s, gid, k, axis)

    sharded = P(axis)
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(sharded, None if cand is None else sharded, sharded, sharded,
                  sharded, sharded, tuple(sharded for _ in cats)),
        out_specs=(P(), P()), check_vma=False,
    )(packed, cand, rows, tiles, ids, avail, cats)


@obs_device.track_jit("retrieval.sharded_exact_masked")
@functools.partial(jax.jit, static_argnames=("r", "k", "mesh", "axis", "layout"))
def _sharded_exact_masked(packed, rows, ids, avail, cats, r: int, k: int,
                          mesh: Mesh, axis: str, layout):
    """``_sharded_exact`` under business rules: every row a shard holds
    scored in f32, the rules applied before its top-k
    (``ops.topk._top_k_allowed``, the one-chip masked exact program's)."""

    def local(packed, rows, ids, avail, cats):
        first = jax.lax.axis_index(axis) * r
        q, rules = _shard_rules(packed, layout, avail, cats, first, r, axis)
        with jax.named_scope("retrieval.shard.exact"):
            gids = ids.reshape(-1)
            sc = jnp.where(gids[None, :] >= 0, _f32_scores(q, rows), NEG_INF)
            s, ix = _top_k_allowed(sc, rules, k)
            gid = jnp.where(ix >= 0, gids[jnp.maximum(ix, 0)], -1)
        return _gather_merge(s, gid, min(k, mesh.shape[axis] * s.shape[1]), axis)

    sharded = P(axis)
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(sharded, sharded, sharded, sharded,
                  tuple(sharded for _ in cats)),
        out_specs=(P(), P()), check_vma=False,
    )(packed, rows, ids, avail, cats)


@functools.partial(jax.jit, static_argnames=("nt", "t"))
def _coarse_copy(rows, nt: int, t: int):
    return rows.astype(jnp.bfloat16).reshape(nt, t, rows.shape[1])


_COPY_ROWS = 1 << 20  # rows a thread copies at a time while a shard is read
_COPY_THREADS = 4  # a shard: four shards read side by side keep 16 cores busy


def _host_rows(table, lo: int, hi: int, out: np.ndarray) -> None:
    """Rows [lo, hi) of a factor table — an ndarray, a model file's
    ``SpannedArray`` or the int8 (values, scales) pair — as f32 into
    ``out[:hi - lo]``: the only host copy a shard ever has. A few
    threads copy a slice each: the rows come out of a mapped file page
    by page, and one thread reads them at a third of a GB a second."""
    values, scales = table if isinstance(table, tuple) else (table, None)
    take = getattr(values, "rows", None)

    def copy(a: int) -> None:
        b = min(a + _COPY_ROWS, hi)
        dst = out[a - lo: b - lo]
        dst[...] = take(a, b) if take is not None else values[a:b]
        if scales is not None:
            dst *= np.asarray(scales[a:b], np.float32)[:, None]

    with ThreadPoolExecutor(max_workers=_COPY_THREADS) as pool:
        list(pool.map(copy, range(lo, hi, _COPY_ROWS)))


class ShardedCatalog:
    """An item catalog staged row-wise over a 1-D mesh, for serving: the
    exact table and its coarse copy in one object, which
    ``ops.retrieval.top_k`` takes in the table's place.

    ``item_table``: a dense [I, D] table (ndarray, or the
    ``SpannedArray`` of a model file that spans files) or the int8
    (values, scales) pair, which is staged dequantized — the served
    score is the f32 dot of the rows the exact path would dequantize.
    Shard ``i`` is read from the table's rows ``[i*R, (i+1)*R)``, R =
    ceil(I / shards), into ONE host block of that shard's stored size
    and put on device ``i``, the shards side by side (a thread each): the
    whole table is never one host array nor one device array.
    ``row_weights`` ([I] f32, the E-Commerce template's weighted items)
    multiply a shard's block before it goes up.
    """

    def __init__(self, item_table, mesh: Mesh, axis: str = "data",
                 row_weights=None):
        if mesh.axis_names != (axis,):
            raise ValueError(
                f"a sharded catalog takes a 1-D mesh over {axis!r}, "
                f"not axes {mesh.axis_names}"
            )
        values = item_table[0] if isinstance(item_table, tuple) else item_table
        self.mesh, self.axis = mesh, axis
        self.shards = n = int(mesh.shape[axis])
        self.num_rows = int(values.shape[0])
        self.dim = int(values.shape[1])
        self.mode = "bf16"
        self.rows_per_shard = r = -(-self.num_rows // n)
        self.tile = t = min(retrieval.tile_size(), retrieval._pow2(max(1, r)))
        self.tiles_per_shard = nt = -(-r // t)
        stored = nt * t  # rows a device holds, padding included
        sides = retrieval.side_shape(nt, t)  # of a device's row ids
        devices = list(mesh.devices.flat)
        uploading = threading.Lock()

        def stage(i: int):
            lo, hi = min(i * r, self.num_rows), min((i + 1) * r, self.num_rows)
            t0 = time.perf_counter()
            block = np.zeros((stored, self.dim), np.float32)
            _host_rows(item_table, lo, hi, block)
            if row_weights is not None:
                block[: hi - lo] *= np.asarray(
                    row_weights[lo:hi], np.float32
                )[:, None]
            ids = np.full(stored, -1, np.int32)
            ids[: hi - lo] = np.arange(lo, hi, dtype=np.int32)
            # one upload at a time: four 3 GB uploads side by side took 14-21 s
            # EACH on a four-chip v5e host where one alone takes 0.65 s (my
            # chip runs, PR 32); the reads before them do run side by side
            with uploading:
                t1 = time.perf_counter()
                rows = jax.device_put(block, devices[i])
                dev_ids = jax.device_put(ids.reshape(sides), devices[i])
                rows.block_until_ready()
                t2 = time.perf_counter()
            tiles = _coarse_copy(rows, nt, t)
            tiles.block_until_ready()
            t3 = time.perf_counter()
            for stage_, dt in (("read", t1 - t0), ("stage_to_device", t2 - t1),
                               ("coarse_build", t3 - t2)):
                retrieval._m_load[stage_].observe(dt)
            return rows, tiles, dev_ids

        with ThreadPoolExecutor(max_workers=n) as pool:
            staged = list(pool.map(stage, range(n)))
        sharding = NamedSharding(mesh, P(axis))

        def whole(parts, shape):
            return jax.make_array_from_single_device_arrays(
                shape, sharding, list(parts)
            )

        self._rows = whole((s[0] for s in staged), (n * stored, self.dim))
        self._tiles = whole((s[1] for s in staged), (n * nt, t, self.dim))
        self._ids = whole((s[2] for s in staged), (n * nt, *sides[1:]))
        self._devices, self._split = devices, sharding
        self._zeros = {}  # (a block's shape, its dtype) -> the other shards' blocks
        retrieval._m_shards.set(float(n))

    @property
    def stored_rows(self) -> int:
        """Rows ONE shard's tiles hold, padding included: a shard's
        share of a ``row_vector``."""
        return self.tiles_per_shard * self.tile

    def row_vector(self, fill, dtype, pad, each=contextlib.nullcontext):
        """A per-row vector sharded like the rows it describes (a
        ``Rules.avail`` / ``Rules.cats`` entry): ``fill(lo, hi)`` gives
        the values of catalog rows [lo, hi), asked a shard at a time;
        shard ``i``'s ``stored_rows`` entries — ``pad`` past the rows it
        holds — go to device ``i``, as its ids did. ``each()`` wraps a
        shard's build and upload (a caller's span)."""
        r, stored = self.rows_per_shard, self.stored_rows
        parts = []
        for i, device in enumerate(self.mesh.devices.flat):
            lo, hi = min(i * r, self.num_rows), min((i + 1) * r, self.num_rows)
            with each():
                block = np.full(stored, pad, dtype)
                block[: hi - lo] = fill(lo, hi)
                parts.append(jax.device_put(block, device))
        return jax.make_array_from_single_device_arrays(
            (self.shards * stored,), NamedSharding(self.mesh, P(self.axis)), parts
        )

    def gather_bytes(self, b: int, k: int) -> int:
        """What one dispatch's all-gather moves: every shard's [b, k] f32
        scores and int32 ids."""
        return self.shards * b * k * 8

    def put_queries(self, vectors):
        """[B, D] host vectors -> the [shards, bp, D] device batch of
        ``put_replicated``, bp the power of two at or above B (copies of
        row 0, discarded)."""
        return self.put_replicated(
            vectors, np.float32, retrieval._pow2(len(vectors))
        )

    def put_replicated(self, a, dtype, rows: int = 0):
        """A host array for every shard — the queries, a packed
        dispatch, a ``whiteList`` batch's candidate ids — in ONE
        host-to-device copy (``retrieval._up``): [bp, W] goes to the
        mesh's first device and is stitched with the other shards'
        resident zero blocks (made once a shape) into one [shards, bp,
        W] array split over the mesh axis, no copy and no launch; the
        programs' first statement hands shard 0's block round
        (``_from_first``). A replicated ``device_put`` is a copy a
        device, one after another, in front of the launch."""
        return retrieval._up(a, dtype, rows, self._stitch)

    def _stitch(self, a: np.ndarray):
        """``_up``'s ``put``: inside its ``xfer.h2d[serve.dispatch]``
        region, which so times the ONE copy and the stitch; the other
        shards' zero blocks are copies of their own site, once a shape."""
        block = a[None]
        key = (block.shape, block.dtype)
        zeros = self._zeros.get(key)
        if zeros is None:
            zeros = []
            for d in self._devices[1:]:
                with obs_device.transfer(
                    "h2d", "serve.zero_blocks", block.nbytes
                ):
                    zeros.append(jax.device_put(np.zeros_like(block), d))
            self._zeros[key] = zeros
        return jax.make_array_from_single_device_arrays(
            (self.shards, *a.shape), self._split,
            [jax.device_put(block, self._devices[0]), *zeros],
        )

    def launch(self, q, kp: int, k: int, rules=None, layout=None, cand=None):
        """The two-stage program enqueued on ``put_queries``' batch,
        nothing read: replicated device ([bp, k] scores, [bp, k] ids).
        k' clamps to what a shard can shortlist (its tile width), k to
        k'. Under ``rules`` (their ``row_vector`` s alone) ``q`` is
        ``retrieval.pack``'s buffer of ``layout`` as ``put_replicated``
        left it, and the masked program runs; ``cand``
        (``put_replicated``'s [shards, bp, S] global ids) then stands in
        the scan's place."""
        kp = max(1, min(int(kp), self.tile))
        k = min(int(k), kp if cand is None else cand.shape[2])
        if rules is None:
            return _sharded_topk(
                q, self._rows, self._tiles, self._ids, r=self.rows_per_shard,
                kp=kp, k=k, mode=self.mode, mesh=self.mesh, axis=self.axis,
            )
        return _sharded_topk_masked(
            q, cand, self._rows, self._tiles, self._ids, rules.avail,
            rules.cats, r=self.rows_per_shard, kp=kp, k=k, mode=self.mode,
            mesh=self.mesh, axis=self.axis, layout=layout,
        )

    def launch_exact(self, q, k: int, rules=None, layout=None):
        """The exact program enqueued, nothing read (``rules`` and
        ``layout`` as ``launch``'s)."""
        k = max(1, min(int(k), self.num_rows))
        if rules is None:
            return _sharded_exact(
                q, self._rows, self._ids, k=k, mesh=self.mesh, axis=self.axis,
            )
        return _sharded_exact_masked(
            q, self._rows, self._ids, rules.avail, rules.cats,
            r=self.rows_per_shard, k=min(k, self.stored_rows), mesh=self.mesh,
            axis=self.axis, layout=layout,
        )

    def exact_top_k(self, vectors, k: int):
        """Host ([B, k] scores, [B, k] ids) of the exact program: the
        eval path's batched top-k over the shards."""
        s, ids = jax.device_get(self.launch_exact(self.put_queries(vectors), k))
        return s[: len(vectors)], ids[: len(vectors)]
