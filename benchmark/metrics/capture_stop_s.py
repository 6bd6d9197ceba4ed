"""capture_stop_s — seconds of the window the server spent in
jax.profiler.stop_trace (the profile.stop phase of obs/device.py
profile_capture: the trace it was asked for collected, converted and written,
while it goes on serving): the window's delta of
pio_profile_seconds_total{phase="stop"}. The counters of a traced run are
deltas over the WHOLE window — the capture, this, and what is left
undisturbed: this number says how much of the window that was. None from a
program without the counter (the parent of PR 50). Four significant digits."""

SERIES = 'pio_profile_seconds_total{phase="stop"}'


def read(raw, spec, cell):
    v = (raw.get("counters_delta") or {}).get(SERIES)
    return None if v is None else float("%.4g" % v)
