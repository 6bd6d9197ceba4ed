"""xplane.py — the reduction from a profiler trace to device metrics.

    python3 benchmark/xplane.py <trace_dir> <out.json>

Reads the newest ``*.xplane.pb`` under ``trace_dir`` with
``jax.profiler.ProfileData`` (so it runs in a process of its own, held to
the CPU, after the chip's owner has exited) and writes:

- ``busy_s``: seconds in which an operation ran on the device — the union
  of the intervals of the device plane's "XLA Ops" line (its "XLA Modules"
  line where a plane has no ops line), averaged over the device planes;
- ``programs`` / ``program_calls``: device seconds and number of runs per
  jitted program, from "XLA Modules" events named
  ``jit_<name>(<fingerprint>)``, summed over planes;
- ``device_ops``: the ten operations that took most device time;
- ``idle_gaps``: the ten longest gaps between device operations, each named
  by what the host was doing in it (the innermost host event that covers
  most of the gap);
- ``span_s``: first device op start to last device op end.
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys

OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
_PROGRAM = re.compile(r"^(jit_[A-Za-z0-9_.<>-]+?)\(\d+\)$")
_OP = re.compile(r"^%([^ =]+) = (\S+)")


def union_length(intervals) -> float:
    """Total length covered by [start, end) intervals, overlaps once."""
    total = 0.0
    cur_s = cur_e = None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps_between(intervals, min_len: float = 0.0):
    """The uncovered stretches between the first start and the last end."""
    out = []
    cur_e = None
    for s, e in sorted(intervals):
        if cur_e is not None and s > cur_e and s - cur_e >= min_len:
            out.append((cur_e, s))
        cur_e = e if cur_e is None else max(cur_e, e)
    return out


def program_name(event_name: str) -> str:
    m = _PROGRAM.match(event_name)
    return m.group(1) if m else event_name


def op_label(event_name: str) -> str:
    """'%fusion.14 = f32[262144]{...} fusion(...)' -> 'fusion.14 f32[262144]'."""
    m = _OP.match(event_name)
    if not m:
        return event_name[:60]
    shape = re.sub(r"\{[^}]*\}", "", m.group(2))
    return f"{m.group(1)} {shape}"[:80]


def _events(line):
    return [(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
            for e in line.events]


def reduce_planes(planes) -> dict:
    """planes: iterable of (plane_name, [(line_name, [(name, start_s, end_s)])])."""
    device = [(n, ls) for n, ls in planes if n.startswith("/device:TPU:")]
    if not device:  # a CPU rehearsal has no TPU plane: its ops are host-side
        device = [(n, ls) for n, ls in planes if n.startswith("/device:")]
    host = [(n, ls) for n, ls in planes if n.startswith("/host:CPU")]
    busy, programs, calls, ops = [], {}, {}, {}
    all_gaps = []
    first, last = float("inf"), float("-inf")
    for _, lines in device:
        by_name = dict(lines)
        line = by_name.get(OPS_LINE) or by_name.get(MODULES_LINE) or []
        iv = [(s, e) for _, s, e in line]
        busy.append(union_length(iv))
        if iv:
            first = min(first, min(s for s, _ in iv))
            last = max(last, max(e for _, e in iv))
        all_gaps.extend(gaps_between(iv))
        for name, s, e in by_name.get(MODULES_LINE, []):
            p = program_name(name)
            programs[p] = programs.get(p, 0.0) + (e - s)
            calls[p] = calls.get(p, 0) + 1
        for name, s, e in by_name.get(OPS_LINE, []):
            label = op_label(name)
            ops[label] = ops.get(label, 0.0) + (e - s)
    host_events = [
        (f"{ln}:{name}", s, e)
        for _, lines in host for ln, evs in lines for name, s, e in evs
    ]
    gaps = sorted(all_gaps, key=lambda g: g[0] - g[1])[:10]
    idle = []
    for gs, ge in gaps:
        best, best_len = "unknown", float("inf")
        for name, s, e in host_events:
            cover = min(e, ge) - max(s, gs)
            if cover >= 0.5 * (ge - gs) and (e - s) < best_len:
                best, best_len = name, e - s
        idle.append([best[:96], ge - gs])
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    n = len(device)
    return {
        "device_planes": n,
        "busy_s": sum(busy) / n if n else 0.0,
        "span_s": (last - first) if last > first else 0.0,
        "programs": programs,
        "program_calls": calls,
        "device_ops": [[k, v] for k, v in top],
        "idle_gaps": idle,
    }


def newest_xplane(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(found, key=os.path.getmtime)


def reduce_file(path: str) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    planes = [
        (p.name, [(ln.name, _events(ln)) for ln in p.lines]) for p in pd.planes
    ]
    return reduce_planes(planes)


def main(argv: list[str]) -> int:
    src = argv[1]
    path = src if src.endswith(".pb") else newest_xplane(src)
    out = reduce_file(path)
    out["file"] = os.path.basename(path)
    with open(argv[2], "w") as fh:
        json.dump(out, fh)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
