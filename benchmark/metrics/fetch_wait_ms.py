"""fetch_wait_ms — the mean of pio_retrieval_fetch_wait_seconds in the window,
in ms: the fetch.wait region in front of a dispatch's one read
(ops/retrieval.py _fetch: jax.block_until_ready of the answer) — what the
device still had to do when the host had nothing left to enqueue. The program
tells the read apart on one dispatch in seven (obs/trace.py CPU_EVERY: a read
asked for once the device is done no longer overlaps its last work, 0.23 ms a
single's dispatch on a v5e), so this is a mean over those dispatches; on them
fetch.wait + xfer.d2h is the dispatch.fetch region less two clock readings,
and ~0.2 ms more than fetch_ms, the mean of ALL fetches. None from a program
without the series (the parent of PR 50). Four significant digits."""

SERIES = "pio_retrieval_fetch_wait_seconds"


def read(raw, spec, cell):
    d = raw.get("counters_delta") or {}
    n = d.get(SERIES + "_count")
    if not n:
        return None
    return float("%.4g" % (1e3 * d.get(SERIES + "_sum", 0.0) / n))
