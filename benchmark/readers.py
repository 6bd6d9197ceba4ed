"""The generic readers a metric file may name. A metric is a small file of
its own under benchmark/metrics/: ``<metric>.json`` names one of these
readers with its parameters, ``<metric>.py`` brings a ``read(raw, spec,
cell)`` of its own. A reader that finds nothing to read returns None, and
the harness leaves the metric out of the result line.

``raw`` is what a driver measured: latencies, counter deltas, the reduced
trace. ``cell`` carries the configuration and the traffic mix.
"""

from __future__ import annotations

import importlib.util
import json
import os

import costs
import peaks
import stats


def latency_percentile(raw, spec, cell):
    xs = raw.get("latencies_ms")
    return stats.percentile(xs, spec["q"]) if xs is not None and len(xs) else None


def lateness_percentile(raw, spec, cell):
    xs = raw.get("late_ms")
    return stats.percentile(xs, spec["q"]) if xs is not None and len(xs) else None


def ratio(raw, spec, cell):
    """raw[num] / raw[den] * scale — a rate over all the work and all the
    time of the window."""
    num, den = raw.get(spec["num"]), raw.get(spec["den"])
    if num is None or not den:
        return None
    return spec.get("scale", 1.0) * num / den


def value(raw, spec, cell):
    v = raw.get(spec["key"])
    return None if v is None else spec.get("scale", 1.0) * v


def histogram_mean(raw, spec, cell):
    """Mean of one of the program's histograms over the window, from the
    difference of two /metrics scrapes (or of two registry reads)."""
    d = raw.get("counters_delta")
    if not d:
        return None
    name = spec["series"]
    labels = spec.get("labels")
    if labels:
        lab = "{" + ",".join(f'{k}="{v}"' for k, v in sorted(labels.items())) + "}"
        total, n = d.get(f"{name}_sum{lab}"), d.get(f"{name}_count{lab}")
        m = total / n if total is not None and n else None
    else:
        m = stats.histogram_mean(d, name)
    return None if m is None else spec.get("scale", 1.0) * m


def device_idle_share(raw, spec, cell):
    t = raw.get("trace")
    if not t or not t.get("window_s"):
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def _needed(kind, cell):
    cfg = cell["config"]
    if kind == "shortlist":
        r = cfg["retrieval"]
        return costs.shortlist_bytes(
            cfg["num_items"], cfg["rank"], r["tile"], r["coarse_dtype"]
        ), costs.shortlist_flops(cfg["num_items"], cfg["rank"], r["tile"])
    raise ValueError(f"unknown cost {kind!r}")


def program_roofline(raw, spec, cell):
    """Share of its roofline one jitted program reached: the least time the
    chip could take for the calls in the trace (bytes over peak bandwidth
    or operations over peak rate, whichever is larger) over the device time
    of the program's events. Not clamped: over 100 % is a counting fault."""
    t = raw.get("trace")
    if not t:
        return None
    secs = t["programs"].get(spec["program"])
    calls = t.get("program_calls", {}).get(spec["program"])
    if not secs or not calls:
        return None
    nbytes, flops = _needed(spec["cost"], cell)
    peak = peaks.peaks_for(raw["device"]["kind"])
    least, _ = costs.roofline_seconds(flops, nbytes, peak, spec.get("flops_peak", "bf16_flops"))
    return 100.0 * calls * least / secs


READERS = {
    f.__name__: f for f in (
        latency_percentile, lateness_percentile, ratio, value, histogram_mean,
        device_idle_share, program_roofline,
    )
}


def load_metric(metrics_dir: str, name: str):
    """The reader of metric ``name``: a callable(raw, cell)."""
    py = os.path.join(metrics_dir, name + ".py")
    js = os.path.join(metrics_dir, name + ".json")
    if os.path.exists(py):
        mod_spec = importlib.util.spec_from_file_location("metric_" + name.replace(".", "_").replace("-", "_"), py)
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        return lambda raw, cell: mod.read(raw, getattr(mod, "SPEC", {}), cell)
    if os.path.exists(js):
        with open(js) as fh:
            spec = json.load(fh)
        fn = READERS[spec["reader"]]
        return lambda raw, cell: fn(raw, spec, cell)
    raise FileNotFoundError(f"metric {name!r}: no {name}.json or {name}.py under {metrics_dir}")
