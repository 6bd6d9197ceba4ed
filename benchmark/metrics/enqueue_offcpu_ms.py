"""enqueue_offcpu_ms — per dispatch, the part of the two enqueues
(dispatch.shortlist, dispatch.rescore: one call of each a two-stage dispatch)
in which their thread was not on the CPU: for each stage the mean of its wall
histogram less the mean of its thread_time histogram
(pio_retrieval_{shortlist,rescore}_seconds, ..._cpu_seconds), summed, in ms.
Means and not sums: the program reads the thread's CPU clock on one call in
seven (obs/trace.py CPU_EVERY), so the two histograms of a stage count
different numbers of calls. Both stages only convert, upload and launch, so
wall minus thread_time is time the worker wanted to run and did not: the
interpreter held by a request thread, or the runtime blocking inside an upload
or a launch. Where the host's CPU clock ticks at 10 ms the reading of a short
window is coarse, and can come out under 0. None without the CPU histograms
(the parent of PR 34)."""

STAGES = ("pio_retrieval_shortlist", "pio_retrieval_rescore")


def read(raw, spec, cell):
    d = raw.get("counters_delta") or {}
    off = 0.0
    for stage in STAGES:
        wall_n, cpu_n = d.get(stage + "_seconds_count"), d.get(stage + "_cpu_seconds_count")
        if not wall_n or not cpu_n:
            return None
        off += d[stage + "_seconds_sum"] / wall_n - d[stage + "_cpu_seconds_sum"] / cpu_n
    return float("%.4g" % (1e3 * off))
