#!/usr/bin/env python3
"""scan_alone.py — the coarse scan alone, on the chip: both forms its
per-row side arrays can lie in, and the forms of a single's step after
its score, on one input, at the benchmark's shapes and at five of a
lower rank that no cell sends.

    chiprun -- python3 scan_alone.py                     # every shape, B = 1 .. 64
    chiprun -- python3 scan_alone.py --shapes retrieval-yambda --batches 8,16
    chiprun -- python3 scan_alone.py --sides lanes     # the stored form alone
    chiprun -- python3 scan_alone.py --batches 1 --steps twice,after
    python3 scan_alone.py --compile-only                 # here: no chip needed

For each shape and batch size it jits ``ops.retrieval._coarse_scan``
once a side form — ``lanes`` (the row ids and an int8 pair's row scales
as a catalog stores them, ``retrieval.side_shape``: [NT, T/128, 128]) and
``flat`` ([NT, T], as they were stored before PR 42: the same values, the
scan takes either) — and reports, for each: ``temp_mb``
(``memory_analysis()`` temporaries of the compiled program),
``wall_ms`` (median host time of a call that ends in ``block_until_ready``),
``device_ms`` (the program's mean device time in a profiler trace) and
``step_us`` (the program's operations, device us a tile, largest first:
a chunk's loop has its own, and what runs once a chunk — the fill of its
stored scores, its selection — is among them, divided by NT). It
says how many chunks of the queries the program scans one after another
(``chunks``: ``retrieval.scan_chunk``), gives a checksum of the answer
(``scores_crc`` / ``ids_crc``: the same input on another commit gives the
same) and holds all answers of a row against the first: scores bit-equal,
ids equal. A row's ``scan`` entry is the ``lanes`` form's; the ``flat``
form's is under ``flat``. Where the scan is a ``dot``-form single (one
query under rank 128, no rules) the row also runs that step's other forms
(``--steps``, under ``steps``; ``_single`` says what each is): ``twice``
is the step of PR 42 — the served program less its one barrier, text for
text — and the served step is the row's ``scan``.

``--compile-only`` compiles for a DESCRIBED v5e and prints the temporaries
alone: nothing runs, so it gives no time. With a chip, a platform other
than ``tpu`` is refused: a CPU's time is nobody's number.

This is the program behind the tables of PERF.md section 6 (PR 33, PR 36,
PR 41, PR 42, PR 43, PR 44).
No benchmark cell runs it; results go to ``chiprun_out/scan_alone.json``.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import tempfile
import time
import zlib

import jax
import jax.numpy as jnp
import numpy as np

from predictionio_tpu.ops import retrieval
from predictionio_tpu.ops.topk import Rules

# the benchmark's own reading of a trace's names (not a package: by path)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "benchmark"))
from xplane import newest_xplane, op_label  # noqa: E402

TILE, KP = 1 << 18, 128  # the cells' tile and k' (num 10 -> 8 x 16)

# what ONE chip scans in each of the benchmark's five configurations, and
# below them what no cell sends: the ALS templates' default rank 10 and
# bench.py's rank 32 (its ``retrieval`` section, int8), over bench.py's
# 1 M rows — stored scores that ``retrieval.scan_chunk`` never cuts —
# over yambda's 9.39 M, where a pass takes 16 queries, and rank 10 over
# the int8 cell's 48.19 M, where it takes two
SHAPES = {
    "retrieval-yambda": dict(rows=9_390_000, rank=64, rules=False),
    "ecommerce-taobao": dict(rows=4_162_024, rank=128, rules=True),
    "similarproduct-taobao": dict(rows=4_162_024, rank=128, rules=True),
    "recommendation-amazon23": dict(rows=12_047_500, rank=64, rules=False),
    # the whole catalog stored int8 on ONE chip: both int8 coarse modes
    "recommendation-amazon23-int8": dict(rows=48_190_000, rank=64, rules=False,
                                         modes=("int8", "int8_dot")),
    "rank10-1m": dict(rows=1_000_000, rank=10, rules=False),
    "rank10-9m": dict(rows=9_390_000, rank=10, rules=False),
    "rank32-int8-1m": dict(rows=1_000_000, rank=32, rules=False,
                           modes=("int8",)),
    "rank32-int8-9m": dict(rows=9_390_000, rank=32, rules=False,
                           modes=("int8",)),
    "rank10-48m": dict(rows=48_190_000, rank=10, rules=False),
}
SIDES = ("lanes", "flat")
# what a single's step does after its score (``score_form`` "dot" rows
# alone), beside the served step, which is the row's ``scan``
STEPS = ("twice", "scores_once", "after")


def _scan(k, mode="bf16"):
    if mode == "bf16":
        def run(q, tiles, ids, rules=None):  # the trace names it jit_run
            return retrieval._coarse_scan(q, tiles, None, ids, k, mode, rules)
    else:
        def run(q, tiles, scales, ids):
            return retrieval._coarse_scan(q, tiles, scales, ids, k, mode)
    return jax.jit(run)


def _single(k, form, mode="bf16"):
    """A single's scan (``score_form`` "dot") with another step after
    the score than the served one, from the package's own pieces:
    the same three-row dot, sum, scale and guard, the same selection
    after the loop. ``form``: "twice" — the scores and their maxima as
    two consumers of the scaled and guarded row, which XLA:TPU computes
    twice (the step of PR 42, this PR's parent); "scores_once" — the
    scores behind a barrier of their own, stored by one pass and reduced
    by another; "after" — the step keeps the scores alone and the maxima
    are one reduce over the stored scores behind the loop. The served
    step (one barrier around the pair) is ``retrieval._coarse_scan``'s."""
    def scan(q, tiles, scales, ids):
        nt, t = ids.shape[0], ids.size // ids.shape[0]
        g = retrieval.select_group(t, k, nt)
        q3 = retrieval._split_bf16(q)

        def step(_, xs):
            v, s, tid = xs if scales is not None else (xs[0], None, xs[1])
            p = jax.lax.dot_general(
                q3, v.astype(jnp.bfloat16), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            side = (1, *tid.shape)
            sc = ((p[2:3] + p[1:2]) + p[:1]).reshape(side)
            if s is not None:
                sc = sc * s.reshape(side)
            sc = jnp.where(tid.reshape(side) >= 0, sc, retrieval.NEG_INF)
            groups = sc.reshape(1, t).reshape(1, t // g, g)
            if form == "after":
                return None, groups
            if form == "scores_once":
                groups = jax.lax.optimization_barrier(groups)
            return None, (groups, groups.max(axis=2))

        xs = (tiles, ids) if scales is None else (tiles, scales, ids)
        kept = jax.lax.scan(step, None, xs)[1]
        scores, maxima = (kept, kept.max(axis=3)) if form == "after" else kept
        return retrieval._select(scores, maxima, ids, k)

    if mode == "bf16":
        def run(q, tiles, ids):  # the trace names it jit_run
            return scan(q, tiles, None, ids)
    else:
        def run(q, tiles, scales, ids):
            return scan(q, tiles, scales, ids)
    return jax.jit(run)


def _arguments(shape, b, make, mode="bf16", sides="lanes"):
    """(q, tiles, ids[, rules]) through ``make(shape, dtype, fill)``; in an
    int8 mode (q, int8 tiles, f32 row scales, ids). ``sides``: the form
    of ids and scales — "lanes" as a catalog stores them, "flat" [NT, T];
    the values do not depend on it."""
    nt = -(-shape["rows"] // TILE)
    side = retrieval.side_shape(nt, TILE) if sides == "lanes" else (nt, TILE)
    args = [
        make((b, shape["rank"]), jnp.float32, "normal"),
        make((nt, TILE, shape["rank"]), jnp.bfloat16, "normal"),
        make(side, jnp.int32, ("ids", shape["rows"])),
    ]
    if mode != "bf16":
        args[1:2] = [make((nt, TILE, shape["rank"]), jnp.int8, "int8"),
                     make(side, jnp.float32, "scales")]
    if shape["rules"]:
        args.append(Rules(
            avail=make((nt * TILE,), jnp.uint8, "avail"),
            cats=(make((nt * TILE,), jnp.int32, "cats"),),
            qcat=make((b, 1), jnp.int32, "qcat"),
            has_cat=make((b,), jnp.bool_, "has_cat"),
            ex=make((b, 128), jnp.int32, "ex"),
        ))
    return args


def _device_array(shape, dtype, fill):
    n = int(np.prod(shape))
    # a side array is drawn [NT, T] in either form: the same values
    drawn = (shape[0], n // shape[0]) if fill == "scales" else shape
    key = jax.random.PRNGKey(zlib.crc32(repr((drawn, fill)).encode()))
    if fill == "normal":
        return jax.jit(
            lambda k: jax.random.normal(k, shape, jnp.float32).astype(dtype)
        )(key)
    if fill == "int8":  # stored values: whole numbers in [-127, 127]
        return jax.jit(
            lambda k: jax.random.randint(k, shape, -127, 128, jnp.int8)
        )(key)
    if fill == "scales":  # a Gaussian row's largest value over 127, about
        return jax.jit(
            lambda k: jax.random.uniform(
                k, drawn, jnp.float32, 0.006, 0.012
            ).reshape(shape)
        )(key)
    if isinstance(fill, tuple):  # row ids, -1 past the catalog
        ids = jnp.arange(n, dtype=jnp.int32)
        return jnp.where(ids < fill[1], ids, -1).reshape(shape)
    if fill == "avail":  # one row in a thousand may not be served
        return (jnp.arange(n) % 1000 != 0).astype(dtype)
    if fill == "cats":
        return (jnp.arange(n, dtype=jnp.int32) % 50).reshape(shape)
    if fill == "qcat":  # every other query asks for category 7
        return jnp.where(jnp.arange(n) % 2 == 0, 7, -2).astype(dtype).reshape(shape)
    if fill == "has_cat":
        return (jnp.arange(n) % 2 == 0).reshape(shape)
    if fill == "ex":  # 100 rows of its own a query, the rest padding
        own = jax.random.randint(key, shape, 0, 4_000_000, jnp.int32)
        return jnp.where(jnp.arange(shape[1])[None, :] < 100, own, -1)
    raise ValueError(fill)


def described_chip():
    """One chip of a DESCRIBED v5e 2x2, as a sharding: a program lowers
    and compiles for it with no chip here (and cannot run)."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    return SingleDeviceSharding(topo.devices[0])


def _loop_ops(path, program):
    """device seconds of every operation in the trace, by label, and the
    program's (seconds, runs) from the modules line."""
    from jax.profiler import ProfileData

    ops, secs, runs = {}, 0.0, 0
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if line.name == "XLA Modules" and e.name.startswith(program):
                    secs, runs = secs + e.duration_ns * 1e-9, runs + 1
                elif line.name == "XLA Ops":
                    label = op_label(e.name)
                    ops[label] = ops.get(label, 0.0) + e.duration_ns * 1e-9
    return ops, secs, runs


def measure(fn, args, calls, traced):
    """``fn``: the compiled executable (one compile serves the
    temporaries, the timing and the trace)."""
    out = jax.block_until_ready(fn(*args))  # warm
    wall = []
    for _ in range(calls):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        wall.append(time.perf_counter() - t0)
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        for _ in range(traced):
            jax.block_until_ready(fn(*args))
        jax.profiler.stop_trace()
        ops, secs, runs = _loop_ops(newest_xplane(d), "jit_run")
    nt = args[1].shape[0]
    steps = sorted(
        ((k, v) for k, v in ops.items() if not k.startswith("while")),
        key=lambda kv: -kv[1],
    )[:12]
    return out, {
        "wall_ms": statistics.median(wall) * 1e3,
        "device_ms": secs / runs * 1e3 if runs else None,
        "step_us": [[k, v / max(runs, 1) / nt * 1e6] for k, v in steps],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--batches", default="1,2,4,8,16,32,64")
    ap.add_argument("--calls", type=int, default=30)
    ap.add_argument("--traced", type=int, default=10)
    ap.add_argument("--sides", default=",".join(SIDES))
    ap.add_argument("--steps", default=",".join(STEPS))
    ap.add_argument("--compile-only", action="store_true")
    ap.add_argument("--out", default="chiprun_out/scan_alone.json")
    a = ap.parse_args(argv)

    if a.compile_only:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs to /tmp
        chip = described_chip()

        def make(shape, dtype, fill):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
    else:
        dev = jax.devices()[0]
        if dev.platform != "tpu":
            print(f"scan_alone: platform {dev.platform!r}, not a TPU: "
                  "no time is taken here (--compile-only needs no chip)")
            return 2
        make = _device_array

    done, rows = set(), []
    for name in a.shapes.split(","):
        shape = SHAPES[name]
        nt = -(-shape["rows"] // TILE)
        key = (nt, shape["rank"], shape["rules"], shape.get("modes"))
        if key in done:  # the two Taobao configurations scan one shape
            continue
        done.add(key)
        for mode, b in ((m, int(x)) for m in shape.get("modes", ("bf16",))
                        for x in a.batches.split(",")):
            chunk = retrieval.scan_chunk(b, shape["rank"], mode, nt * TILE)
            row = {
                "shape": name, "tiles": nt, "rank": shape["rank"],
                "rules": shape["rules"], "mode": mode, "b": b, "k": KP,
                "chunks": -(-b // chunk),
                "group": retrieval.select_group(TILE, KP, nt),
                "score_form": retrieval.score_form(chunk, shape["rank"], mode),
            }
            outs = []
            for sides in a.sides.split(","):
                args = _arguments(shape, b, make, mode, sides)
                into = row if sides == "lanes" else row.setdefault(sides, {})
                programs = [(into, "scan", _scan(KP, mode))]
                if (sides == "lanes" and row["score_form"] == "dot"
                        and not shape["rules"] and row["group"]):
                    programs += [
                        (row.setdefault("steps", {}), step, _single(KP, step, mode))
                        for step in a.steps.split(",") if step
                    ]
                for entries, program, jitted in programs:
                    fn = jitted.lower(*args).compile()
                    mem = fn.memory_analysis()
                    entries[program] = {"temp_mb": mem.temp_size_in_bytes / 1e6}
                    if not a.compile_only:
                        out, timed = measure(fn, args, a.calls, a.traced)
                        outs.append(jax.device_get(out))
                        entries[program].update(timed)
                del args  # one form's side arrays on the chip at a time
            if outs:
                s0, i0 = outs[0]
                row["scores_crc"] = zlib.crc32(np.ascontiguousarray(s0).tobytes())
                row["ids_crc"] = zlib.crc32(np.ascontiguousarray(i0).tobytes())
                row["scores_bit_equal"] = all(
                    (s0.view(np.uint32) == s.view(np.uint32)).all()
                    for s, _ in outs[1:]
                )
                row["ids_equal"] = all((i0 == i).all() for _, i in outs[1:])
            rows.append(row)
            print(json.dumps(row), flush=True)
    if not a.compile_only:
        os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
        with open(a.out, "w") as fh:
            json.dump({"device": jax.devices()[0].device_kind, "rows": rows}, fh)
    return 0


if __name__ == "__main__":
    sys.exit(main())
