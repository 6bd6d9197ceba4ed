"""Bytes the masked scan of ONE shard needs, from shapes alone — the numerator
of ``masked_shard_scan_roofline.shardstore``. Kept with the yardstick so that
no later PR can move it; tested against a hand sum. It counts the work, not a
method: whatever program applies the business rules to every stored row of a
shard has to read these."""

from __future__ import annotations

import costs


def shard_stored_rows(num_items: int, tile: int, shards: int) -> int:
    """Rows one shard stores: ceil(I / shards) rows in whole tiles of at most
    ``tile`` rows (a power of two no wider than the shard needs)."""
    rows = -(-num_items // shards)
    t = min(tile, 1 << max(0, rows - 1).bit_length())
    return -(-rows // t) * t


def masked_shard_scan_bytes(num_items: int, rank: int, tile: int, shards: int,
                            coarse_dtype: str = "bfloat16",
                            category_columns: int = 1, batch: int = 1) -> float:
    """HBM bytes one shard must read to scan its rows under the rules for a
    batch: every stored row once, padding included (it is stored and
    scanned) — its ``rank`` coarse values, its int32 row id, one int32
    category id per category column and its availability byte — plus, a
    query, one mask byte a stored row (whether the row is on the query's own
    list: seen, blackList) and its f32 vector."""
    stored = shard_stored_rows(num_items, tile, shards)
    per_row = rank * costs.DTYPE_BYTES[coarse_dtype] + 4 + 4 * category_columns + 1
    return float(stored * (per_row + batch) + batch * rank * 4)
