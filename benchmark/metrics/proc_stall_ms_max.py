"""proc_stall_ms_max — the longest time in the window in which nothing of the
server process ran, to a bucket: the upper edge of the highest non-empty bucket
of pio_process_stall_seconds (how late the 20 ms obs-beat thread woke), in ms.
A few ms is a host that shares its cores; 50 ms and up is a stop, and the
server's log and /stats.json runtime.stalls say whether the process burned CPU
in it (a thread of its own held the interpreter) or none (it was not
scheduled). The overflow bucket reads as twice the last edge. None from a
program without the beat (the parent of PR 34)."""

PREFIX = 'pio_process_stall_seconds_bucket{le="'


def read(raw, spec, cell):
    d = raw.get("counters_delta") or {}
    cum = sorted(
        (float("inf") if k[len(PREFIX):-2] == "+Inf" else float(k[len(PREFIX):-2]), v)
        for k, v in d.items() if k.startswith(PREFIX)
    )
    top, below, last = None, 0.0, 0.0
    for edge, n in cum:
        if n > below:
            top = edge if edge != float("inf") else 2.0 * last
        below, last = n, edge
    return None if top is None else float("%.4g" % (1e3 * top))
