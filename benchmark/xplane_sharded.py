"""xplane_sharded.py — the trace reduction of a cell that runs ONE program
across several chips.

    python3 benchmark/xplane_sharded.py <trace_dir> <out.json>

xplane.py's reduction over all the device planes (its ``busy_s`` is their
mean, its ``programs`` and ``program_calls`` their sums), and beside it what a
sharded cell needs plane by plane, through the same helpers:

- ``busy_by_plane``: ``reduce_planes`` called a plane at a time — the busy
  seconds of each chip (``shard_busy_skew``: largest over mean);
- ``shard_ops_s`` / ``shard_ops_calls``: device seconds, summed over the
  planes, of the sharded program's collective and merge operations, and the
  runs of the program they were found in. A device runs a program's
  operations one after another, and the sharded program ends scan -> local
  rescore -> all-gather -> merge: in every run of ``jit__sharded_topk`` (an
  "XLA Modules" event) the collective is found by its operation NAME
  (``all-gather``, in any of its spellings, on the ops line or the async
  one), and from its start to the run's end is the gather — the wait for the
  slowest shard included — and the merge. (A TPU's trace names an operation
  by its HLO text and keeps no ``jax.named_scope``: the program's
  ``retrieval.shard.gather`` / ``.merge`` scopes name these ops in the
  compiled HLO and in a trace viewer's source view only.) No separate launch
  is added to make the merge measurable.

Runs in a process of its own, held to the CPU, after the chips' owner has
exited (drivers/sharded.py)."""

from __future__ import annotations

import bisect
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import xplane  # noqa: E402

PROGRAM = "jit__sharded_topk"
OP_LINES = (xplane.OPS_LINE, "Async XLA Ops")
COLLECTIVES = ("all-gather", "all-reduce", "collective-permute", "all-to-all")


def is_collective(name: str) -> bool:
    """Is an op event (named by its HLO text) a collective?"""
    return xplane.op_label(name).startswith(COLLECTIVES)


def shard_tail(lines) -> tuple[float, int, dict]:
    """(device seconds from the collective's start to the end of the run,
    summed over the runs of PROGRAM on this plane that hold a collective; how
    many such runs; seconds by op label) for one plane's
    [(line_name, [(name, start_s, end_s)])]."""
    by = dict(lines)
    ops = sorted((s, e, name) for ln in OP_LINES for name, s, e in by.get(ln, []))
    starts = [s for s, _, _ in ops]
    total, runs, by_op = 0.0, 0, {}
    for name, ms, me in by.get(xplane.MODULES_LINE, []):
        if xplane.program_name(name) != PROGRAM:
            continue
        inside = ops[bisect.bisect_left(starts, ms):bisect.bisect_left(starts, me)]
        first = next((s for s, _, n in inside if is_collective(n)), None)
        if first is None:
            continue
        tail = [(s, min(e, me), n) for s, e, n in inside if s >= first]
        total += xplane.union_length([(s, e) for s, e, _ in tail])
        runs += 1
        for s, e, n in tail:
            label = xplane.op_label(n)
            by_op[label] = by_op.get(label, 0.0) + (e - s)
    return total, runs, by_op


def reduce_file(path: str) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    planes = [
        (p.name, [(ln.name, xplane._events(ln)) for ln in p.lines]) for p in pd.planes
    ]
    out = xplane.reduce_planes(planes)
    host = [pl for pl in planes if pl[0].startswith("/host:CPU")]
    device = [pl for pl in planes if pl[0].startswith("/device:TPU:")] \
        or [pl for pl in planes if pl[0].startswith("/device:")]
    out["busy_by_plane"] = {
        name: xplane.reduce_planes([(name, lines), *host])["busy_s"] for name, lines in device
    }
    out["shard_ops_s"], out["shard_ops_calls"], by_op = 0.0, 0, {}
    for _, lines in device:
        s, n, ops = shard_tail(lines)
        out["shard_ops_s"] += s
        out["shard_ops_calls"] += n
        for k, v in ops.items():
            by_op[k] = by_op.get(k, 0.0) + v
    out["shard_ops"] = sorted(([k, v] for k, v in by_op.items()), key=lambda kv: -kv[1])[:10]
    return out


def main(argv: list[str]) -> int:
    src = argv[1]
    path = src if src.endswith(".pb") else xplane.newest_xplane(src)
    out = reduce_file(path)
    out["file"] = os.path.basename(path)
    with open(argv[2], "w") as fh:
        json.dump(out, fh)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
