"""upload_ms — per dispatch, the host time of the serving chain's uploads, each
to its return: pio_device_transfer_seconds_sum{direction="h2d"} of the sites
serve.dispatch (every host array a stage converts and puts up: the vectors,
then the indices; under rules the one packed buffer) and serve.rules (the
recall probe's three, each 256th dispatch) over
pio_batch_dispatch_seconds_count, in the window, in ms. The xfer.h2d[<op>]
regions inside dispatch.shortlist and dispatch.rescore (obs/device.py
transfer). None from a program without the series (the parent of PR 50). Four
significant digits."""

SITES = ("serve.dispatch", "serve.rules")


def read(raw, spec, cell):
    d = raw.get("counters_delta") or {}
    dispatches = d.get("pio_batch_dispatch_seconds_count")
    sums = [d.get('pio_device_transfer_seconds_sum{direction="h2d",op="%s"}' % s)
            for s in SITES]
    if not dispatches or sums[0] is None:
        return None
    return float("%.4g" % (1e3 * sum(v or 0.0 for v in sums) / dispatches))
