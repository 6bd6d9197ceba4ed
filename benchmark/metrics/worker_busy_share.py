"""worker_busy_share — of the batch worker's wall time in the window, the share
it was not idle (nothing queued): 100 x (1 - idle / sum of the four states) of
pio_batch_worker_seconds_total{state}, in %. What "batch-1 utilisation" was by
hand (rate x a dispatch's ms): here a reading, and it counts the collect
window and the futures' resolution as well. None from a program without the
counter (the parent of PR 34). Four significant digits: the result line of a
CPU rehearsal is cut at 2,000 characters."""

STATES = ("idle", "collect", "dispatch", "resolve")


def states(raw):
    """Seconds by state in the window, or None where the counter is not there."""
    d = raw.get("counters_delta") or {}
    secs = [d.get('pio_batch_worker_seconds_total{state="%s"}' % s) for s in STATES]
    if any(v is None for v in secs) or sum(secs) <= 0:
        return None
    return dict(zip(STATES, secs))


def read(raw, spec, cell):
    secs = states(raw)
    if secs is None:
        return None
    return float("%.4g" % (100.0 * (1.0 - secs["idle"] / sum(secs.values()))))
