"""factors.py's tables a block at a time: the same values, never the table.

``factors.factor_table`` fills a [rows, rank] table in fixed chunks of
``factors.CHUNK_ROWS`` rows, chunk ``c`` from the stream ``[seed, stream, c]``.
A 48.19 M x 64 f32 table is 12.34 GB: the sharded cell's writer and its
reference both regenerate it chunk by chunk from the seed and hold only what
they are working on. A test holds ``rows`` equal to ``factor_table``'s slice,
bit for bit. NumPy only."""

from __future__ import annotations

import numpy as np

import factors


def chunk(seed: int, stream: int, c: int, total_rows: int, rank: int) -> np.ndarray:
    """Chunk ``c`` of the [total_rows, rank] table: its rows
    [c * CHUNK_ROWS, min((c + 1) * CHUNK_ROWS, total_rows))."""
    n = min(factors.CHUNK_ROWS, total_rows - c * factors.CHUNK_ROWS)
    part = np.empty((n, rank), np.float32)
    np.random.default_rng([int(seed), int(stream), c]).standard_normal(
        out=part, dtype=np.float32)
    part *= np.float32(rank ** -0.25)
    return part


def rows(seed: int, stream: int, total_rows: int, rank: int, lo: int, hi: int) -> np.ndarray:
    """Rows [lo, hi) of the table, from the chunks that hold them."""
    hi = min(hi, total_rows)
    parts = []
    for c in range(lo // factors.CHUNK_ROWS, -(-hi // factors.CHUNK_ROWS)):
        base = c * factors.CHUNK_ROWS
        part = chunk(seed, stream, c, total_rows, rank)
        parts.append(part[max(lo, base) - base: hi - base])
    if len(parts) == 1:
        return parts[0]
    return np.concatenate(parts) if parts else np.empty((0, rank), np.float32)


class SeededRows:
    """A factor table as a row source (``shape``, ``dtype``, ``rows(lo,
    hi)``): what the program's spanning writer takes in an array's place."""

    def __init__(self, seed: int, stream: int, total_rows: int, rank: int):
        self.seed, self.stream = int(seed), int(stream)
        self.shape = (int(total_rows), int(rank))
        self.dtype = np.dtype(np.float32)

    def rows(self, lo: int, hi: int) -> np.ndarray:
        return rows(self.seed, self.stream, self.shape[0], self.shape[1], lo, hi)
