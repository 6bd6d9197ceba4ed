#!/usr/bin/env python3
"""scan_alone.py — the coarse scan alone, on the chip: both places a
scan can select its k' best, on one input, at the benchmark's shapes.

    chiprun -- python3 scan_alone.py                     # every shape, B = 1 .. 64
    chiprun -- python3 scan_alone.py --shapes retrieval-yambda --batches 8,16
    python3 scan_alone.py --compile-only                 # here: no chip needed

For each shape and batch size it jits ``ops.retrieval._coarse_scan`` twice
— ``select="deferred"`` (one selection after the tile loop) and
``select="two_level"`` (every step its tile's, merged) — and reports, a body:
``temp_mb`` (``memory_analysis()`` temporaries of the compiled program),
``wall_ms`` (median host time of a call that ends in ``block_until_ready``),
``device_ms`` (the program's mean device time in a profiler trace) and
``step_us`` (the loop's operations, device us a tile, largest first). It
says which body the rule serves (``scan_select``) and holds the two bodies'
answers against each other: scores bit-equal, ids equal.

``--compile-only`` compiles for a DESCRIBED v5e and prints the temporaries
alone: nothing runs, so it gives no time. With a chip, a platform other
than ``tpu`` is refused: a CPU's time is nobody's number.

This is the program behind the tables of PERF.md section 6 (PR 33, PR 36).
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

# what ONE chip scans in each of the benchmark's five configurations
SHAPES = {
    "retrieval-yambda": dict(rows=9_390_000, rank=64, rules=False),
    "ecommerce-taobao": dict(rows=4_162_024, rank=128, rules=True),
    "similarproduct-taobao": dict(rows=4_162_024, rank=128, rules=True),
    "recommendation-amazon23": dict(rows=12_047_500, rank=64, rules=False),
    # the whole catalog stored int8 on ONE chip: both int8 coarse modes
    "recommendation-amazon23-int8": dict(rows=48_190_000, rank=64, rules=False,
                                         modes=("int8", "int8_dot")),
}
BODIES = ("two_level", "deferred")


def _scan(k, select, mode="bf16"):
    if mode == "bf16":
        def run(q, tiles, ids, rules=None):  # the trace names it jit_run
            return retrieval._coarse_scan(
                q, tiles, None, ids, k, mode, rules, select=select
            )
    else:
        def run(q, tiles, scales, ids):
            return retrieval._coarse_scan(
                q, tiles, scales, ids, k, mode, select=select
            )
    return jax.jit(run)


def _arguments(shape, b, make, mode="bf16"):
    """(q, tiles, ids[, rules]) through ``make(shape, dtype, fill)``; in an
    int8 mode (q, int8 tiles, f32 row scales, ids)."""
    nt = -(-shape["rows"] // TILE)
    args = [
        make((b, shape["rank"]), jnp.float32, "normal"),
        make((nt, TILE, shape["rank"]), jnp.bfloat16, "normal"),
        make((nt, TILE), jnp.int32, ("ids", shape["rows"])),
    ]
    if mode != "bf16":
        args[1:2] = [make((nt, TILE, shape["rank"]), jnp.int8, "int8"),
                     make((nt, TILE), jnp.float32, "scales")]
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
    key = jax.random.PRNGKey(zlib.crc32(repr((shape, fill)).encode()))
    n = int(np.prod(shape))
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
            lambda k: jax.random.uniform(k, shape, jnp.float32, 0.006, 0.012)
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
            args = _arguments(shape, b, make, mode)
            row = {
                "shape": name, "tiles": nt, "rank": shape["rank"],
                "rules": shape["rules"], "mode": mode, "b": b,
                "served": retrieval.scan_select(b, nt, TILE, KP, shape["rank"], mode),
                "score_form": retrieval.score_form(b, shape["rank"], mode),
            }
            outs = {}
            for body in BODIES:
                fn = _scan(KP, body, mode).lower(*args).compile()
                mem = fn.memory_analysis()
                row[body] = {"temp_mb": mem.temp_size_in_bytes / 1e6}
                if not a.compile_only:
                    outs[body], timed = measure(fn, args, a.calls, a.traced)
                    row[body].update(timed)
            if outs:
                (s0, i0), (s1, i1) = (jax.device_get(outs[x]) for x in BODIES)
                row["scores_bit_equal"] = bool(
                    (s0.view(np.uint32) == s1.view(np.uint32)).all()
                )
                row["ids_equal"] = bool((i0 == i1).all())
            rows.append(row)
            print(json.dumps(row), flush=True)
    if not a.compile_only:
        os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
        with open(a.out, "w") as fh:
            json.dump({"device": jax.devices()[0].device_kind, "rows": rows}, fh)
    return 0


if __name__ == "__main__":
    sys.exit(main())
