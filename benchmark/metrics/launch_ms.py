"""launch_ms — per dispatch, the host time inside jitted calls: the sum over
every tracked program of pio_jit_call_seconds_sum{fn} (obs/device.py track_jit:
the launch[<fn>] region around a call that hit the executable cache; a call
that compiled is left out) over pio_batch_dispatch_seconds_count, in the
window, in ms. A two-stage dispatch launches twice (the scan, the rescore);
nothing is waited for inside a launch, so this is what the runtime and Python
compute on the dispatching thread to hand a program over. None from a program
without the series (the parent of PR 50). Four significant digits: the result
line of a CPU rehearsal is cut at 2,000 characters."""


def read(raw, spec, cell):
    d = raw.get("counters_delta") or {}
    dispatches = d.get("pio_batch_dispatch_seconds_count")
    sums = [v for k, v in d.items() if k.startswith("pio_jit_call_seconds_sum{")]
    if not dispatches or not sums:
        return None
    return float("%.4g" % (1e3 * sum(sums) / dispatches))
