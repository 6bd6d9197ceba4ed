"""gc_pause_ms_sum — the collector's pauses in the window, all generations:
the sum of pio_gc_pause_seconds_sum{generation}, in ms (a sum over the window,
not a mean: one full collection of a large heap is the finding, and a mean
over thousands of young collections hides it). None from a program without the
collector hook (the parent of PR 34)."""


def read(raw, spec, cell):
    d = raw.get("counters_delta") or {}
    sums = [v for k, v in d.items() if k.startswith("pio_gc_pause_seconds_sum{")]
    if not sums:
        return None
    return float("%.4g" % (1e3 * sum(sums)))
