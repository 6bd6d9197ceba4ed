"""xplane_shardstore.py — the trace reduction of the sharded storefront cell.

    python3 benchmark/xplane_shardstore.py <trace_dir> <out.json>

xplane_sharded.py's reduction (every device plane, ``busy_by_plane``, the
collective-to-end tail of every run of the sharded program) with the program
that serves under business rules, ``jit__sharded_topk_masked``, in the
unmasked one's place, and beside it ``program_s_by_plane``: the masked
program's device seconds on each plane — ``masked_shard_scan_roofline`` reads
the slowest shard's. A trace without the masked program (a program that has
none) reduces to zeros and empty maps, and the readers then report nothing.

Runs in a process of its own, held to the CPU, after the chips' owner has
exited (drivers/shardstore.py)."""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import xplane  # noqa: E402
import xplane_sharded  # noqa: E402

PROGRAM = "jit__sharded_topk_masked"


def program_seconds(lines) -> float:
    """Device seconds of PROGRAM's runs on one plane's
    [(line_name, [(name, start_s, end_s)])]."""
    return sum(e - s for name, s, e in dict(lines).get(xplane.MODULES_LINE, [])
               if xplane.program_name(name) == PROGRAM)


def reduce_file(path: str) -> dict:
    from jax.profiler import ProfileData

    xplane_sharded.PROGRAM = PROGRAM  # whose runs ``shard_tail`` looks into
    out = xplane_sharded.reduce_file(path)
    pd = ProfileData.from_file(path)
    planes = [(p.name, [(ln.name, xplane._events(ln)) for ln in p.lines])
              for p in pd.planes if p.name.startswith("/device:")]
    if any(n.startswith("/device:TPU:") for n, _ in planes):
        planes = [pl for pl in planes if pl[0].startswith("/device:TPU:")]
    out["program_s_by_plane"] = {name: program_seconds(lines) for name, lines in planes}
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
