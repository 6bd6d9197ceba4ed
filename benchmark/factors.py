"""Seeded factor tables: the benchmark's weights.
Used by the writer child (to persist a model in the program's format) and
by the reference (which regenerates them and takes nothing back from the
program). NumPy only."""

from __future__ import annotations

import numpy as np

STREAM_USER_FACTORS, STREAM_ITEM_FACTORS = 1, 2


CHUNK_ROWS = 1 << 21  # fixed, so the values never depend on the machine


def factor_table(seed: int, stream: int, rows: int, rank: int) -> np.ndarray:
    """[rows, rank] f32 Gaussian factors scaled so that a score (a dot of
    two rows) has unit variance: scores read like ratings. Filled in fixed
    chunks of rows, each from its own stream of the seed, by a few threads
    (NumPy's generators fill without the GIL)."""
    from concurrent.futures import ThreadPoolExecutor

    t = np.empty((rows, rank), np.float32)
    scale = np.float32(rank ** -0.25)

    def fill(c: int) -> None:
        lo = c * CHUNK_ROWS
        part = t[lo:lo + CHUNK_ROWS]
        np.random.default_rng([int(seed), int(stream), c]).standard_normal(
            out=part, dtype=np.float32)
        part *= scale

    chunks = range(-(-rows // CHUNK_ROWS))
    with ThreadPoolExecutor(max_workers=min(8, len(chunks))) as pool:
        list(pool.map(fill, chunks))
    return t


def user_factors(seed: int, num_users: int, rank: int) -> np.ndarray:
    return factor_table(seed, STREAM_USER_FACTORS, num_users, rank)


def item_factors(seed: int, num_items: int, rank: int) -> np.ndarray:
    return factor_table(seed, STREAM_ITEM_FACTORS, num_items, rank)
