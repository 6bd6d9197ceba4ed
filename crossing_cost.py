#!/usr/bin/env python3
"""crossing_cost.py — what a crossing between the host and the chips
costs the thread that makes it: an upload to one device and to N, the
stitch, a launch of a no-op and of each sharded program, a read of one
array and of a pair, and a whole dispatch by either upload route.

    chiprun --chips 4 -- python3 crossing_cost.py          # the four-chip table
    chiprun -- python3 crossing_cost.py                    # a mesh of one
    chiprun --chips 4 -- python3 crossing_cost.py --devices 2 --reps 50
    python3 crossing_cost.py --dry-run-cpu                 # here: toy size, NO time

A ``parallel.shard_topk.ShardedCatalog`` of ``--rows`` x ``--rank`` seeded
f32 rows (the four-chip cells' 48.19 M x 64 by default, generated a block
at a time: never one host array) is staged over every device the process
has (or the first ``--devices``), with the storefront's rule vectors
beside it. Then, for each of the two buffers a dispatch of one query sends
— ``[1, rank]`` f32 and ``retrieval.pack``'s ``[1, W]`` int32 at the sharded
storefront's layout — and for ``--batch`` rows of each, every line is one
crossing repeated
``--reps`` times on an idle device, with two host clocks a repetition:
``return_ms`` (until the call hands the thread back) and ``ready_ms``
(until ``block_until_ready`` of what it made; a read's two are one).
Quartiles and the median of each are reported.

- ``put.one`` / ``put.each`` / ``put.stitched``: ``jax.device_put`` to
  the mesh's first device; to a replicated ``NamedSharding`` (one copy a
  device, one after another); ``ShardedCatalog.put_replicated`` (ONE
  copy and the stitch). ``stitch``: the stitch alone, on blocks that are
  there.
- ``launch.noop_one`` / ``.noop_all`` / ``.handed_round``: a one-op
  program on one device, under ``shard_map`` on all, and the served
  programs' first statement alone (``shard_topk._from_first``).
  ``launch.<program>``: each of the four sharded programs on a batch
  that is there.
- ``read.one`` / ``read.pair``: ``jax.device_get`` of a program's ready
  scores, and of its (scores, ids), a fresh answer each time.
- ``dispatch.<program>.stitched`` / ``.each``: upload + launch
  (``return_ms``: what ``dispatch.shortlist`` brackets; ``ready_ms``:
  until the answer is ready on the devices) and, in a loop of its own,
  the whole dispatch with its read (``read_ms``) by the served route and by a
  replicated upload in front of the SAME compiled program (the copies
  stitched as the devices' blocks: the route before PR 49 plus one
  ``stitch``), and the two routes' answers compared bit for bit
  (``answers_equal``).

Results go to ``chiprun_out/crossing_cost.json``. A platform other than
``tpu`` is refused: a CPU's time is nobody's number (a launch is 7 us
there). ``--dry-run-cpu`` runs every line once at toy size on the
devices there are, checks the answers, prints the lines WITHOUT a time
and exits 3: it is a rehearsal of the script, never a result.

This is the program behind the crossing tables of PERF.md section 6
(PR 29's on one chip, PR 49's on four). No benchmark cell runs it.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from predictionio_tpu.ops import retrieval
from predictionio_tpu.ops.topk import Rules
from predictionio_tpu.parallel import shard_topk

ROWS, RANK = 48_190_000, 64  # the four-chip cells' catalog
EXCLUDED = 128  # the storefront's layout (one category column): models/ecommerce.py
K, KP = 16, 128  # num 10 -> k 16, k' = 8 x 16
_BLOCK = 1 << 20


class SeededRows:
    """[rows, rank] f32 read a block at a time (``rows(a, b)``, as a
    model file's ``SpannedArray`` is): one seeded block, scaled by the
    block's number, so the table is never one host array."""

    def __init__(self, rows: int, rank: int, seed: int = 0):
        self.shape = (rows, rank)
        self._base = np.random.default_rng(seed).standard_normal(
            (min(rows, _BLOCK), rank), np.float32)

    def rows(self, a: int, b: int):
        return self._base[: b - a] * np.float32(1.0 + (a // _BLOCK) * 2.0 ** -7)


def stage(rows: int, rank: int, devices: int = 0):
    """(the catalog over the first ``devices`` devices — 0: all — its
    resident ``Rules``)."""
    mesh = Mesh(np.array(jax.devices()[: devices or None]), ("data",))
    catalog = shard_topk.ShardedCatalog(SeededRows(rows, rank), mesh)
    avail = catalog.row_vector(
        lambda lo, hi: (np.arange(lo, hi) % 1000 != 0), np.uint8, 0)
    cats = catalog.row_vector(
        lambda lo, hi: np.arange(lo, hi, dtype=np.int32) % 33, np.int32, -1)
    return catalog, Rules(avail, (cats,), None, None, None)


def buffers(catalog, b: int, seed: int = 1):
    """{name: (host array, dtype, layout)} of a dispatch of ``b``
    queries: the bare vectors (-0.0 among them) and the packed buffer
    (every other query asks for category 7, each excludes 100 rows)."""
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((b, catalog.dim)).astype(np.float32)
    q[0, 0] = -0.0
    ex = np.full((b, EXCLUDED), -1, np.int32)
    ex[:, :100] = rng.integers(0, catalog.num_rows, (b, 100))
    rules = Rules(None, (), np.where(np.arange(b)[:, None] % 2 == 0, 7, -2),
                  np.arange(b) % 2 == 0, ex)
    packed, layout = retrieval.pack(q, rules)
    return {"vectors": (q, np.float32, None), "packed": (packed, np.int32, layout)}


def programs(catalog, resident):
    """{name: (buffer it takes, batch -> its device (scores, ids))}."""
    return {
        "sharded_topk": ("vectors", lambda q, _: catalog.launch(q, KP, K)),
        "sharded_exact": ("vectors", lambda q, _: catalog.launch_exact(q, K)),
        "sharded_topk_masked": (
            "packed", lambda q, lay: catalog.launch(q, KP, K, resident, lay)),
        "sharded_exact_masked": (
            "packed", lambda q, lay: catalog.launch_exact(q, K, resident, lay)),
    }


def put_each(catalog, a, everywhere):
    """The batch by a REPLICATED upload — ``jax.device_put`` makes one
    copy a device, one after another — its copies stitched as the
    devices' blocks (no copy, no launch): what the programs took before
    PR 49, in the form they take now."""
    copies = jax.device_put(a[None], everywhere)
    return jax.make_array_from_single_device_arrays(
        (catalog.shards, *a.shape), catalog._split,
        [s.data for s in copies.addressable_shards])


_noop = jax.jit(lambda x: x + 1)


def clocked(make, reps: int, read=False):
    """``make()`` on an idle device, ``reps`` times: (its last result,
    {return_ms, ready_ms: [q1, median, q3]}). ``read``: ``make`` gives
    host arrays, and the two clocks are one."""
    out = make()  # warm: a compile, a zero block
    if not read:
        jax.block_until_ready(out)
    ret, ready = [], []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = make()
        t1 = time.perf_counter()
        if not read:
            jax.block_until_ready(out)
        ready.append(time.perf_counter() - t0)
        ret.append(t1 - t0)

    def three(xs):
        if len(xs) < 2:
            return [xs[0] * 1e3] * 3
        q = statistics.quantiles(xs, n=4)
        return [q[0] * 1e3, statistics.median(xs) * 1e3, q[2] * 1e3]

    return out, {"return_ms": three(ret), "ready_ms": three(ready)}


def lines(catalog, resident, b: int, reps: int):
    """Every line of the table for a dispatch of ``b`` queries, as
    (name, buffer, clocks-or-check) in the order of the docstring."""
    mesh, axis, devs = catalog.mesh, catalog.axis, catalog._devices
    everywhere = NamedSharding(mesh, P())
    bufs = buffers(catalog, b)

    def shard(f, ins):
        return jax.jit(jax.shard_map(
            f, mesh=mesh, in_specs=ins, out_specs=P(), check_vma=False))

    for name, (a, dtype, _) in bufs.items():
        yield "put.one", name, clocked(lambda: jax.device_put(a, devs[0]), reps)[1]
        yield "put.each", name, clocked(lambda: jax.device_put(a, everywhere), reps)[1]
        batch, took = clocked(lambda: catalog.put_replicated(a, dtype), reps)
        yield "put.stitched", name, took
        parts = [s.data for s in batch.addressable_shards]
        yield "stitch", name, clocked(
            lambda: jax.make_array_from_single_device_arrays(
                batch.shape, catalog._split, parts), reps)[1]
        one, each = jax.device_put(a, devs[0]), jax.device_put(a, everywhere)
        yield "launch.noop_one", name, clocked(lambda: _noop(one), reps)[1]
        noop_all = shard(lambda x: x + 1, P())
        yield "launch.noop_all", name, clocked(lambda: noop_all(each), reps)[1]
        handed = shard(lambda x: shard_topk._from_first(x, axis), P(axis))
        yield "launch.handed_round", name, clocked(lambda: handed(batch), reps)[1]
    for prog, (name, launch) in programs(catalog, resident).items():
        a, dtype, layout = bufs[name]
        slow = max(2, reps // 4) if "exact" in prog else reps
        batch = catalog.put_replicated(a, dtype)
        out, took = clocked(lambda: launch(batch, layout), slow)
        yield f"launch.{prog}", name, took
        if prog == "sharded_topk":
            # a fresh answer a read: an array keeps its host copy once read
            fresh = iter(jax.block_until_ready(
                [launch(batch, layout) for _ in range(2 * reps + 2)]))
            yield "read.one", name, clocked(
                lambda: jax.device_get(next(fresh)[0]), reps, read=True)[1]
            yield "read.pair", name, clocked(
                lambda: jax.device_get(next(fresh)), reps, read=True)[1]
        routes = {
            "stitched": lambda: launch(catalog.put_replicated(a, dtype), layout),
            "each": lambda: launch(put_each(catalog, a, everywhere), layout),
        }
        answers = {}
        for route, make in routes.items():
            answers[route], took = clocked(make, slow)
            took["read_ms"] = clocked(
                lambda: jax.device_get(make()), slow, read=True)[1]["ready_ms"]
            yield f"dispatch.{prog}.{route}", name, took
        (s0, i0), (s1, i1) = (jax.device_get(answers[r]) for r in routes)
        yield f"dispatch.{prog}", name, {"answers_equal": bool(
            (s0.view(np.uint32) == s1.view(np.uint32)).all() and (i0 == i1).all())}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=ROWS)
    ap.add_argument("--rank", type=int, default=RANK)
    ap.add_argument("--batch", default="1", help="queries a dispatch, comma-separated")
    ap.add_argument("--reps", type=int, default=200)
    ap.add_argument("--devices", type=int, default=0, help="0: every device")
    ap.add_argument("--dry-run-cpu", action="store_true")
    ap.add_argument("--out", default="chiprun_out/crossing_cost.json")
    a = ap.parse_args(argv)

    dev = jax.devices()[0]
    if a.dry_run_cpu:
        a.rows, a.reps = min(a.rows, 4096), 1
    elif dev.platform != "tpu":
        print(f"crossing_cost: platform {dev.platform!r}, not a TPU: "
              "no time is taken here (--dry-run-cpu rehearses the script)")
        return 2
    catalog, resident = stage(a.rows, a.rank, a.devices)
    head = {"device": dev.device_kind, "platform": dev.platform,
            "devices": catalog.shards, "rows": a.rows, "rank": a.rank,
            "reps": a.reps, "jax": jax.__version__}
    print(json.dumps(head), flush=True)
    table = []
    for b in (int(x) for x in a.batch.split(",")):
        for what, buffer, took in lines(catalog, resident, b, a.reps):
            if a.dry_run_cpu:  # the names and the checks: never a time
                took = {k: v for k, v in took.items() if k == "answers_equal"}
            table.append({"what": what, "buffer": buffer, "b": b, **took})
            print(json.dumps(table[-1]), flush=True)
    if a.dry_run_cpu:
        print("dry run on cpu: every line ran — this is NOT a chip result")
        return 3
    os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
    with open(a.out, "w") as fh:
        json.dump({**head, "lines": table}, fh)
    return 0


if __name__ == "__main__":
    sys.exit(main())
