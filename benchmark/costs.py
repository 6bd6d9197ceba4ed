"""Bytes and operations the algorithms NEED, from shapes alone — the
numerators of the roofline shares. Kept with the yardstick so that no
later PR can move them; tested against hand sums."""

from __future__ import annotations

DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "int8": 1}


def coarse_tiles(num_items: int, tile: int) -> int:
    """Tiles the coarse catalog is cut into (the last one padded)."""
    return -(-num_items // tile)


def shortlist_bytes(num_items: int, rank: int, tile: int, coarse_dtype: str,
                    batch: int = 1) -> float:
    """HBM bytes one coarse shortlist call must read: every tile of the
    coarse catalog once (padded rows included: they are stored and
    scanned), its int32 row ids, per-row f32 scales where the coarse form
    is int8, and the f32 queries. One call serves the whole batch."""
    rows = coarse_tiles(num_items, tile) * tile
    per_row = rank * DTYPE_BYTES[coarse_dtype] + 4  # values + row id
    if coarse_dtype == "int8":
        per_row += 4  # scale
    return float(rows * per_row + batch * rank * 4)


def shortlist_flops(num_items: int, rank: int, tile: int, batch: int = 1) -> float:
    """Multiply-adds x2 of scoring every stored row against the batch."""
    return 2.0 * coarse_tiles(num_items, tile) * tile * rank * batch


def roofline_seconds(flops: float, nbytes: float, peak: dict,
                     flops_key: str = "bf16_flops") -> tuple[float, str]:
    """The least time the chip could take, and which peak binds."""
    t_c = flops / peak[flops_key]
    t_b = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c > t_b else (t_b, "bandwidth")
