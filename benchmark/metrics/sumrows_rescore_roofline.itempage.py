"""sumrows_rescore_roofline.itempage — share of its roofline the exact rescore
of a sum-of-rows query under rules reached: the least time the chip could take
for the calls of ``jit__rescore_sum_rows_masked`` in the trace over the device
time of those calls, in %. Bandwidth binds (the dots are 2 x k' x D operations
a query). Not clamped: over 100 % is a counting fault.

The bytes one call must move are counted HERE (tested against a hand sum): for
each of its B queries the L catalog rows summed into the query vector and the
k' shortlisted rows, D f32 values each, plus their int32 ids. B and L are the
window's means as the program counted them (``pio_batch_size``,
``pio_similar_query_rows``: the queries' own, not the padded shapes), k' the
mean shortlist size. Rows are gathered one at a time, so expect a few per cent
at most: 128 rows of 512 B are latency, not bandwidth."""

import costs
import peaks
import stats

PROGRAM = "jit__rescore_sum_rows_masked"


def sumrows_rescore_bytes(rank: int, shortlist: float, summed_rows: float,
                          batch: float = 1.0) -> float:
    return float(batch * (summed_rows + shortlist) * (rank * 4 + 4))


def sumrows_rescore_flops(rank: int, shortlist: float, summed_rows: float,
                          batch: float = 1.0) -> float:
    return float(batch * (2.0 * shortlist * rank + summed_rows * rank))


def read(raw, spec, cell):
    t = raw.get("trace")
    d = raw.get("counters_delta")
    if not t or not d:
        return None
    secs = t.get("programs", {}).get(PROGRAM)
    calls = t.get("program_calls", {}).get(PROGRAM)
    if not secs or not calls:
        return None
    shortlist = stats.histogram_mean(d, "pio_retrieval_shortlist_size")
    summed = stats.histogram_mean(d, "pio_similar_query_rows")
    batch = stats.histogram_mean(d, "pio_batch_size")
    if not shortlist or not summed or not batch:
        return None
    rank = cell["config"]["rank"]
    least, _ = costs.roofline_seconds(
        sumrows_rescore_flops(rank, shortlist, summed, batch),
        sumrows_rescore_bytes(rank, shortlist, summed, batch),
        peaks.peaks_for(raw["device"]["kind"]))
    return 100.0 * calls * least / secs
