"""resident_gb.int8 — device bytes the served model keeps resident, summed over
the parts of pio_model_resident_bytes{part} (table, table_scales, coarse,
coarse_scales, coarse_ids, users) as the scrape that closes the window shows
them, in GB (1e9 bytes). A gauge set at load: read, not reckoned — the int8
pair's values lie on the chip twice (the rescore's table and the scan's tiles),
and this is where that shows. None from a program without the gauge (the
parent of PR 41). Four significant digits."""

import stats

SERIES = "pio_model_resident_bytes"


def read(raw, spec, cell):
    parts = stats.family(raw.get("gauges_close") or {}, SERIES)
    if not parts or sum(parts.values()) <= 0:
        return None
    return float("%.4g" % (sum(parts.values()) / 1e9))
