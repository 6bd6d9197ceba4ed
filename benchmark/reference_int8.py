"""The plain reference of the int8 cell: exact f32 top-k over the WHOLE
catalog of a model that IS a stored int8 pair. The f32 block is regenerated
from the seed a chunk at a time (factor_blocks.py), quantized a row at a time
by the configuration's stated rule (``quantize_rows``: the one place the
benchmark has it — the writer calls it too, the program's own quantizer is
not used), dequantized, scored with float32 accumulation as reference.py does
it, and dropped: never a 12.34 GB array, never anything of the program's.

A served score has to be the f32 dot of the dequantized user row with the
dequantized item row. The CONTROLS put something one step away in the
program's place, in the same pass, and each has to come out as not correct:

- ``bfloat16``: the dequantized rows rounded to bf16 (reference.py's switch);
- ``unquantized``: the f32 rows the pair was made FROM, served in the pair's
  place — what a server that held another model than the one written would
  answer.

(The coarse ``int8_dot`` score "served as if final" is no control here: a
query's vector is a dequantized int8 user row, whose largest value is 127
scales, so quantizing it again gives the stored values back and the coarse
score times the query's scale IS the exact score — ``requantized`` below, held
by a test.)"""

from __future__ import annotations

import threading
from concurrent.futures import ThreadPoolExecutor

import numpy as np

import factor_blocks
import factors
import reference


_SLAB = 1 << 16  # rows quantized at a time: the temporaries stay 16 MB


def quantize_rows(block: np.ndarray):
    """[n, D] f32 rows -> (int8 [n, D] values, f32 [n] scales): scale =
    max|row| / 127 (1 where the row is all zeros), values = rint(row /
    scale). f32 arithmetic throughout; a slab of rows at a time, so that a
    2 M-row chunk costs its own bytes and a quarter more, not four times."""
    block = np.asarray(block, np.float32)
    values = np.empty(block.shape, np.int8)
    scales = np.empty(len(block), np.float32)
    for lo in range(0, len(block), _SLAB):
        rows = block[lo:lo + _SLAB]
        s = np.abs(rows).max(axis=1) / np.float32(127.0)
        s = np.where(s > 0, s, np.float32(1.0)).astype(np.float32)
        scales[lo:lo + _SLAB] = s
        values[lo:lo + _SLAB] = np.rint(rows / s[:, None])
    return values, scales


def dequantize(values: np.ndarray, scales: np.ndarray) -> np.ndarray:
    return values.astype(np.float32) * np.asarray(scales, np.float32)[:, None]


def requantized(rows: np.ndarray) -> np.ndarray:
    """The int8 values ``int8_dot``'s query quantization makes of f32 query
    rows (ops/retrieval.py: q / (max|q| / 127), rounded, clipped)."""
    qs = np.abs(rows).max(axis=1, keepdims=True) / np.float32(127.0)
    return np.clip(np.rint(rows / np.maximum(qs, np.float32(1e-12))),
                   -127, 127).astype(np.int8)


def chunk_pair(seed: int, stream: int, c: int, total_rows: int, rank: int):
    """Chunk ``c`` of a table as (f32 source rows, int8 values, f32 scales)."""
    src = factor_blocks.chunk(seed, stream, c, total_rows, rank)
    return (src, *quantize_rows(src))


def table_rows(seed: int, stream: int, total_rows: int, rank: int, ixs,
               quantized: bool = True) -> np.ndarray:
    """f32 rows ``ixs`` of a table — dequantized int8 rows, or the source
    rows they were made from — out of the chunks that hold them."""
    ixs = np.asarray(ixs, np.int64)
    out = np.empty((len(ixs), rank), np.float32)
    for c in np.unique(ixs // factors.CHUNK_ROWS):
        sel = ixs // factors.CHUNK_ROWS == c
        src, v, s = chunk_pair(seed, stream, int(c), total_rows, rank)
        local = ixs[sel] - int(c) * factors.CHUNK_ROWS
        out[sel] = dequantize(v[local], s[local]) if quantized else src[local]
    return out


class QuantizedRows:
    """One half of a quantized factor table as a row source (``shape``,
    ``dtype``, ``rows(lo, hi)``): what the program's spanning writer takes in
    an array's place. ``part`` is "values" ([rows, rank] int8) or "scales"
    ([rows] f32). A block is made a chunk at a time by a few threads: the f32
    rows are regenerated, quantized and dropped. The two halves of one table
    share their chunks (``pairs``: whichever half asks first quantizes the
    chunk, the other takes its part and the entry goes), and however many
    blocks are asked for side by side, at most ``IN_FLIGHT`` chunks (0.7 GB
    each at rank 64) are being made at once: the first writer met a 40 GiB
    limit."""

    IN_FLIGHT = threading.BoundedSemaphore(8)
    _PAIRS = threading.Lock()  # guards every ``pairs`` dict: held for a look-up

    def __init__(self, seed: int, stream: int, total_rows: int, rank: int,
                 part: str, workers: int = 4, pairs: dict | None = None):
        self.seed, self.stream, self.rank = int(seed), int(stream), int(rank)
        self.total, self.part, self.workers = int(total_rows), part, workers
        self.shape = (self.total, self.rank) if part == "values" else (self.total,)
        self.dtype = np.dtype(np.int8 if part == "values" else np.float32)
        self.pairs = {} if pairs is None else pairs  # (chunk, part) -> that half

    def _half(self, c: int) -> np.ndarray:
        with self._PAIRS:
            kept = self.pairs.pop((c, self.part), None)
        if kept is not None:
            return kept
        with self.IN_FLIGHT:
            _, v, s = chunk_pair(self.seed, self.stream, c, self.total, self.rank)
        mine, other = (v, s) if self.part == "values" else (s, v)
        with self._PAIRS:  # for the other half, unless it made its own meanwhile
            self.pairs[(c, "scales" if self.part == "values" else "values")] = other
        return mine

    def rows(self, lo: int, hi: int) -> np.ndarray:
        hi = min(hi, self.total)
        out = np.empty((hi - lo, *self.shape[1:]), self.dtype)
        step = factors.CHUNK_ROWS

        def fill(c: int) -> None:
            a, b = max(lo, c * step), min(hi, (c + 1) * step)
            out[a - lo: b - lo] = self._half(c)[a - c * step: b - c * step]

        chunks = range(lo // step, -(-hi // step))
        with ThreadPoolExecutor(max_workers=max(1, min(self.workers, len(chunks)))) as pool:
            list(pool.map(fill, chunks))
        return out


def _merge(best, cand, k: int):
    """Two lists of ([S, k] scores, [S, k] ids, [S, k] payload) -> the k best
    of both by score, ties towards the lower row id (reference.top_k_scan's
    rule); the payload rides along."""
    s, i, p = (np.concatenate([b, c], axis=1) for b, c in zip(best, cand))
    out = (np.full_like(best[0], -np.inf), np.full_like(best[1], -1),
           np.full_like(best[2], np.nan))
    for row in range(len(s)):
        keep = np.flatnonzero(i[row] >= 0)
        order = keep[np.lexsort((i[row][keep], -s[row][keep]))[:k]]
        for o, a in zip(out, (s, i, p)):
            o[row, :len(order)] = a[row][order]
    return out


def scan(seed: int, num_items: int, rank: int, queries: np.ndarray, k: int,
         served=None, controls: dict | None = None, workers: int = 8):
    """([S, k] scores descending, [S, k] row ids) of ``queries`` against the
    dequantized item table, the reference's own score of every ``served`` row
    ([S, n] ids, -1 padded -> [S, n] scores, NaN where padded), and for each
    control ``name -> (queries, "int8" | "unquantized", precision)`` its
    ([S, k] scores, [S, k] ids, [S, k] EXACT scores of those ids). One pass: a
    chunk is regenerated, quantized, scored for everything asked, dropped."""
    S = len(queries)
    controls = controls or {}

    def empty():
        return (np.full((S, k), -np.inf, np.float32), np.full((S, k), -1, np.int64),
                np.full((S, k), np.nan, np.float32))

    best = {name: empty() for name in ("", *controls)}
    own = None
    if served is not None:
        served = np.asarray(served, np.int64)
        own = np.full(served.shape, np.nan, np.float32)

    def padded(s, i, p):
        short = k - s.shape[1]  # a last chunk shorter than k
        if short > 0:
            s = np.pad(s, ((0, 0), (0, short)), constant_values=-np.inf)
            i = np.pad(i, ((0, 0), (0, short)), constant_values=-1)
            p = np.pad(p, ((0, 0), (0, short)), constant_values=np.nan)
        return s, i, p

    def one(c: int):
        base = c * factors.CHUNK_ROWS
        src, v, sc = chunk_pair(seed, factors.STREAM_ITEM_FACTORS, c, num_items, rank)
        deq = dequantize(v, sc)
        kk = min(k, len(deq))
        found = {}
        s, i = reference.top_k_scan(queries, deq, kk)
        found[""] = padded(s, np.where(i >= 0, i + base, -1), s)
        for name, (cq, table, precision) in controls.items():
            s, i = reference.top_k_scan(cq, deq if table == "int8" else src, kk, precision)
            exact = np.einsum("sd,skd->sk", queries, deq[np.maximum(i, 0)])
            found[name] = padded(s, np.where(i >= 0, i + base, -1), exact)
        hits = []
        if served is not None:
            r, col = np.nonzero((served >= base) & (served < base + len(deq)))
            for row in np.unique(r):
                cols = col[r == row]
                hits.append((row, cols, reference.score_items(
                    queries[row], deq, served[row, cols] - base)))
        return found, hits

    chunks = range(-(-num_items // factors.CHUNK_ROWS))
    with ThreadPoolExecutor(max_workers=max(1, min(workers, len(chunks)))) as pool:
        for found, hits in pool.map(one, chunks):
            for name, cand in found.items():
                best[name] = _merge(best[name], cand, k)
            for row, cols, scores in hits:
                own[row, cols] = scores
    return best[""][0], best[""][1], own, {n: best[n] for n in controls}
