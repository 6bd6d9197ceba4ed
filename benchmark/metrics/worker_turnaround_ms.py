"""worker_turnaround_ms — what the batch worker spends around a dispatch, per
dispatch: (collect + resolve) of pio_batch_worker_seconds_total{state} over
pio_batch_dispatch_seconds_count, in ms. collect runs from the first item taken
to the dispatch region's start (the window, the grouping), resolve from the
region's end to the turn's end (the futures, the per-query fallback): the time
between two dispatches in which requests were queued and nothing was launched.
The states come from worker_busy_share.py's reader."""

import os
import runpy

_states = runpy.run_path(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "worker_busy_share.py")
)["states"]


def read(raw, spec, cell):
    secs = _states(raw)
    n = (raw.get("counters_delta") or {}).get("pio_batch_dispatch_seconds_count")
    if secs is None or not n:
        return None
    return float("%.4g" % (1e3 * (secs["collect"] + secs["resolve"]) / n))
