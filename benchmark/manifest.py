"""The rules BENCHMARK.json has to keep, as code: checked by the tests
before the driver checks them (names, units, every metric's `moves`
reported by each of its cells, every configuration used, every named file
there). ``validate`` returns the list of faults, empty when there is none."""

from __future__ import annotations

import os
import re

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
KEYS = {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}


def _line(s) -> bool:
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def validate(m: dict, root: str) -> list[str]:
    bad: list[str] = []
    if set(m) != KEYS:
        bad.append(f"keys {sorted(set(m) ^ KEYS)} missing or unknown")
        return bad
    bench = os.path.join(root, m["paths"][0])
    if not (1 <= len(m["paths"]) <= 16 and all(PATH.match(p) and not p.startswith("/") and ".." not in p for p in m["paths"])):
        bad.append("paths")
    if not (isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51):
        bad.append("run_seconds")
    if not (1 <= len(m["command"]) <= 32 and all(_line(c) for c in m["command"])):
        bad.append("command")

    def under_paths(f):
        return any(f == p or f.startswith(p.rstrip("/") + "/") for p in m["paths"])

    names = set()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        seen = set()
        for e in m[group]:
            n = e.get("name", "")
            if not NAME.match(n):
                bad.append(f"{group}: name {n!r}")
            if n in seen or (group in ("end_to_end", "per_layer") and n in names):
                bad.append(f"{group}: {n!r} twice")
            seen.add(n)
            if group in ("end_to_end", "per_layer"):
                names.add(n)
    configs = {c["name"]: c for c in m["configs"]}
    cells = {w["name"]: w for w in m["workloads"]}
    if not (1 <= len(configs) <= 24 and 1 <= len(cells) <= 24):
        bad.append("1 to 24 configs and cells")
    files = set()
    for c in m["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            bad.append(f"config {c['name']}: keys")
            continue
        if not (_line(c["source"]) and _line(c["why"])):
            bad.append(f"config {c['name']}: source/why")
        if not (under_paths(c["file"]) and PATH.match(c["file"])) or c["file"] in files:
            bad.append(f"config {c['name']}: file {c['file']}")
        files.add(c["file"])
        if not os.path.exists(os.path.join(root, c["file"])):
            bad.append(f"config {c['name']}: {c['file']} missing")
        if len(c["reduced"]) > 16 or not all(NAME.match(k) for k in c["reduced"]):
            bad.append(f"config {c['name']}: reduced")
        if not any(w["config"] == c["name"] for w in m["workloads"]):
            bad.append(f"config {c['name']}: no cell uses it")
    pairs = set()
    for w in m["workloads"]:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            bad.append(f"cell {w['name']}: keys")
            continue
        if w["config"] not in configs:
            bad.append(f"cell {w['name']}: unknown config")
        if not NAME.match(w["traffic"]) or not os.path.exists(
                os.path.join(bench, "traffic", w["traffic"] + ".json")):
            bad.append(f"cell {w['name']}: traffic file {w['traffic']}")
        if w["chips"] not in (1, 4) or not _line(w["why"]):
            bad.append(f"cell {w['name']}: chips/why")
        if (w["config"], w["traffic"]) in pairs:
            bad.append(f"cell {w['name']}: pair twice")
        pairs.add((w["config"], w["traffic"]))
    if sum(w["chips"] == 4 for w in m["workloads"]) > max(1, len(cells) // 4):
        bad.append("too many four-chip cells")

    def reported_in(metric) -> set[str]:
        return set(metric.get("workloads", cells))

    e2e = {e["name"]: e for e in m["end_to_end"]}
    if "setup_s" not in e2e or "workloads" in e2e.get("setup_s", {}):
        bad.append("setup_s has to be an end-to-end metric of every cell")
    for e in m["end_to_end"]:
        if set(e) - {"workloads"} != {"name", "unit", "better", "bound", "source"}:
            bad.append(f"metric {e['name']}: keys")
            continue
        if not UNIT.match(e["unit"]) or e["better"] not in ("lower", "higher"):
            bad.append(f"metric {e['name']}: unit/better")
        if e["source"] not in ("host_clock", "device_trace"):
            bad.append(f"metric {e['name']}: source")
        if not (0.01 <= e["bound"] <= 0.1):
            bad.append(f"metric {e['name']}: bound")
        if not reported_in(e) <= set(cells):
            bad.append(f"metric {e['name']}: unknown cell")
    for p in m["per_layer"]:
        if set(p) - {"workloads"} != {"name", "unit", "better", "source", "layer", "moves"}:
            bad.append(f"metric {p['name']}: keys")
            continue
        if not UNIT.match(p["unit"]) or p["better"] not in ("lower", "higher"):
            bad.append(f"metric {p['name']}: unit/better")
        if p["source"] not in SOURCES or not _line(p["layer"]):
            bad.append(f"metric {p['name']}: source/layer")
        if p["moves"] not in e2e:
            bad.append(f"metric {p['name']}: moves {p['moves']!r} is no end-to-end metric")
            continue
        where = reported_in(p) if "workloads" in p else reported_in(e2e[p["moves"]])
        if not where or not where <= reported_in(e2e[p["moves"]]):
            bad.append(f"metric {p['name']}: a cell of it does not report {p['moves']}")
        if p["name"].endswith("_roofline") and p["unit"] != "%":
            bad.append(f"metric {p['name']}: a roofline share is in %")
    for n in names:
        if not any(os.path.exists(os.path.join(bench, "metrics", n + ext)) for ext in (".json", ".py")):
            bad.append(f"metric {n}: no reader file")
    for name in cells:
        own = [e for e in m["end_to_end"] if name in reported_in(e)]
        if len(own) < 2:
            bad.append(f"cell {name}: needs setup_s and one more end-to-end metric")
        if not any(name in (reported_in(p) if "workloads" in p else reported_in(e2e[p["moves"]]))
                   for p in m["per_layer"] if p["moves"] in e2e):
            bad.append(f"cell {name}: no per-layer metric")
    return bad
