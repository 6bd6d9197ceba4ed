"""The plain reference of the sharded cell: exact f32 top-k over the WHOLE
catalog, the item table regenerated from the seed a block at a time (never a
12.34 GB array beside the server's). NumPy only; imports nothing of the
program, takes no weights from it, and knows nothing of shards: a block here
is a chunk of the generator, not a device's share.

``precision`` is the switch the CONTROL uses (reference.py): the same
reference one precision down, in the program's place; it has to come out as
not correct."""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

import factor_blocks
import factors
import reference


def _merge(best, cand, k: int):
    """Two ([S, k] scores, [S, k] ids) lists -> the k best of both, scores
    descending, ties towards the lower row id (reference.top_k_scan's rule)."""
    s = np.concatenate([best[0], cand[0]], axis=1)
    i = np.concatenate([best[1], cand[1]], axis=1)
    out_s = np.empty_like(best[0])
    out_i = np.empty_like(best[1])
    for row in range(len(s)):
        keep = i[row] >= 0
        rs, ri = s[row][keep], i[row][keep]
        order = np.lexsort((ri, -rs))[:k]
        out_s[row], out_i[row] = -np.inf, -1
        out_s[row, :len(order)], out_i[row, :len(order)] = rs[order], ri[order]
    return out_s, out_i


def scan(seed: int, num_items: int, rank: int, queries: np.ndarray, k: int,
         served=None, precision: str = "float32", workers: int = 8):
    """([S, k] scores descending, [S, k] row ids) of ``queries`` against the
    seeded item table, and — where ``served`` gives each query's served row ids
    ([S, n], -1 padded) — the reference's own score of every served row
    ([S, n]; NaN where padded). One pass over the table: a chunk is
    regenerated, scored (``reference.top_k_scan`` / ``score_items``: the
    one-table reference's functions, per chunk) and dropped."""
    S = len(queries)
    best = (np.full((S, k), -np.inf, np.float32), np.full((S, k), -1, np.int64))
    own = None
    if served is not None:
        served = np.asarray(served, np.int64)
        own = np.full(served.shape, np.nan, np.float32)

    def one(c: int):
        base = c * factors.CHUNK_ROWS
        block = factor_blocks.chunk(seed, factors.STREAM_ITEM_FACTORS, c, num_items, rank)
        s, i = reference.top_k_scan(queries, block, min(k, len(block)), precision)
        if s.shape[1] < k:  # a last chunk shorter than k
            s = np.pad(s, ((0, 0), (0, k - s.shape[1])), constant_values=-np.inf)
            i = np.pad(i, ((0, 0), (0, k - i.shape[1])), constant_values=-1)
        hits = []
        if served is not None:
            r, col = np.nonzero((served >= base) & (served < base + len(block)))
            for row in np.unique(r):
                cols = col[r == row]
                hits.append((row, cols, reference.score_items(
                    queries[row], block, served[row, cols] - base, precision)))
        return s, np.where(i >= 0, i + base, -1), hits

    chunks = range(-(-num_items // factors.CHUNK_ROWS))
    with ThreadPoolExecutor(max_workers=max(1, min(workers, len(chunks)))) as pool:
        for s, i, hits in pool.map(one, chunks):
            best = _merge(best, (s, i), k)
            for row, cols, sc in hits:
                own[row, cols] = sc
    return best[0], best[1], own
