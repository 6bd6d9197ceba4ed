"""fetch_read_ms — the mean of
pio_device_transfer_seconds{direction="d2h",op="serve.answers"} in the window,
in ms: the xfer.d2h[serve.answers] region (ops/retrieval.py _fetch: the one
jax.device_get of a dispatch's finished scores and ids) — the copy back alone,
the device's work being over when it starts; taken on the dispatches on which
the program tells the read apart (one in seven: fetch_wait_ms). None from a
program without the series (the parent of PR 50). Four significant digits."""

SERIES = "pio_device_transfer_seconds"
SITE = '{direction="d2h",op="serve.answers"}'


def read(raw, spec, cell):
    d = raw.get("counters_delta") or {}
    n = d.get(SERIES + "_count" + SITE)
    if not n:
        return None
    return float("%.4g" % (1e3 * d.get(SERIES + "_sum" + SITE, 0.0) / n))
