"""coarse_int8_dot_share.int8 — of the shortlist calls in the window, the share
whose coarse catalog scored in mode int8_dot (the query quantized too, int8 x
int8 accumulated in int32): pio_retrieval_coarse_mode_total{mode="int8_dot"}
over all modes, in %. The catalog picks ONE mode at load by rule
(ops/retrieval.py CoarseCatalog), so this reads 0 or 100: which branch of the
int8 scan the cell's numbers are numbers OF. None from a program without the
counter (the parent of PR 41). Four significant digits, as
worker_busy_share."""

import stats

SERIES = "pio_retrieval_coarse_mode_total"


def read(raw, spec, cell):
    calls = stats.family(raw.get("counters_delta") or {}, SERIES)
    total = sum(calls.values())
    if total <= 0:
        return None
    return float("%.4g" % (100.0 * calls.get(SERIES + '{mode="int8_dot"}', 0.0) / total))
