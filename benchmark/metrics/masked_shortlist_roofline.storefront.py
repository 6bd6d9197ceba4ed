"""masked_shortlist_roofline.storefront — share of its roofline the coarse
scan UNDER BUSINESS RULES reached: the least time the chip could take for the
calls of ``jit__coarse_topk_masked`` in the trace over the device time of
those calls, in %. Bandwidth binds. Not clamped: over 100 % is a counting
fault.

The bytes one call must read are counted HERE, beside the reader (tested
against a hand sum): every stored row of the coarse catalog once — its bf16
(or int8 + f32 scale) values, its int32 row id, its availability byte and one
int32 category id per category column — padded rows included (they are
stored and scanned), plus the f32 queries. The queries' own exclusion lists
(128 int32 each) and the [tiles, B, tile] mask the program lays out from them
are the program's choice of method, not bytes the algorithm needs: not
counted."""

import costs
import peaks

PROGRAM = "jit__coarse_topk_masked"


def masked_shortlist_bytes(num_items: int, rank: int, tile: int, coarse_dtype: str,
                           category_columns: int = 1, batch: int = 1) -> float:
    rows = costs.coarse_tiles(num_items, tile) * tile
    per_row = rank * costs.DTYPE_BYTES[coarse_dtype] + 4 + 1 + 4 * category_columns
    if coarse_dtype == "int8":
        per_row += 4  # scale
    return float(rows * per_row + batch * rank * 4)


def read(raw, spec, cell):
    t = raw.get("trace")
    if not t:
        return None
    secs = t.get("programs", {}).get(PROGRAM)
    calls = t.get("program_calls", {}).get(PROGRAM)
    if not secs or not calls:
        return None
    cfg = cell["config"]
    r = cfg["retrieval"]
    nbytes = masked_shortlist_bytes(
        cfg["num_items"], cfg["rank"], r["tile"], r["coarse_dtype"],
        cfg.get("categories_per_item", 1))
    flops = costs.shortlist_flops(cfg["num_items"], cfg["rank"], r["tile"])
    least, _ = costs.roofline_seconds(flops, nbytes, peaks.peaks_for(raw["device"]["kind"]))
    return 100.0 * calls * least / secs
