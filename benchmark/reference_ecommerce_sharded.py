"""The plain reference of the sharded storefront: the E-Commerce template's
business rules (reference_ecommerce.py ``top_k_allowed`` / ``excluded_served``:
allowed = every item minus seen, unavailable and blackList, inside the
category where one is named; the answer its top ``num`` by f32 score) over the
WHOLE 48.19 M-item catalog, the item table regenerated from the seed a chunk at
a time (factor_blocks.py) and never held: 12.34 GB beside nothing. NumPy,
float32, no kernels; imports nothing of the program, takes no weights from it,
and knows nothing of shards: a chunk here is a chunk of the generator, not a
device's share.

The limits ``correct`` is decided by, restated (the configuration file carries
them; they are ``recommendation-amazon23``'s and ``ecommerce-taobao``'s):

- ``excluded_served`` = 0 over EVERY answered query of the window: the
  guarantee itself, by set look-ups — no seen, unavailable, black-listed or
  out-of-category item is ever served;
- ``score_gap_max`` <= 1e-4 over a seeded sample, per kind of query and
  overall: every served score is the f32 dot of the stored rows (sound runs
  read ~2e-6; one precision down reads 1e-3 and more);
- ``overlap_min`` >= 0.9: at most one of ten items may differ from the exact
  list (a shard's answers lost before the merge falls under it);
- ``overlap_mean_min`` >= 0.999: the recall@num gate of two-stage retrieval.

``precision`` and ``no_unavailable_rows`` are the switches the two CONTROLS
use: the same reference one precision down, or WITHOUT the unavailable rule on
the rows ``[lo, hi)`` — the fault of one shard that lost its availability
vector — put in the program's place. Each has to come out as not correct."""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

import factor_blocks
import factors
import reference
import reference_ecommerce as ref
from reference_sharded import _merge


def scan(seed: int, num_items: int, rank: int, queries: np.ndarray, k: int, *,
         unavailable: np.ndarray, excluded: list, item_category: np.ndarray,
         query_category: list, served=None, precision: str = "float32",
         no_unavailable_rows: tuple[int, int] | None = None, workers: int = 8,
         group: int = 256):
    """([S, k] scores descending, [S, k] row ids; -inf and -1 where a query
    has fewer than k allowed items) of ``queries`` over each one's allowed
    set of the seeded item table, and — where ``served`` gives each query's
    served row ids ([S, n], -1 padded) — the reference's own f32 score of
    every served row ([S, n]; NaN where padded). One pass over the table: a
    chunk is regenerated, scored under the rules cut to its rows
    (``reference_ecommerce.top_k_allowed``, the one-table reference's own
    function, ``group`` queries at a time: a worker's [group, 2^18] block of
    scores is what bounds its memory, whatever S) and dropped.

    ``unavailable``: sorted rows no query may be served; ``excluded[s]``:
    sorted rows query s alone may not be served (seen and blackList);
    ``query_category[s]``: the category query s is restricted to, or None;
    ``item_category`` [I]: each row's category."""
    S = len(queries)
    best = (np.full((S, k), -np.inf, np.float32), np.full((S, k), -1, np.int64))
    own = None
    if served is not None:
        served = np.asarray(served, np.int64)
        own = np.full(served.shape, np.nan, np.float32)
    unavailable = np.asarray(unavailable, np.int64)
    if no_unavailable_rows is not None:  # the control: a range without the rule
        lo, hi = no_unavailable_rows
        unavailable = unavailable[(unavailable < lo) | (unavailable >= hi)]
    excluded = [np.asarray(e, np.int64) for e in excluded]

    def one(c: int):
        base = c * factors.CHUNK_ROWS
        block = factor_blocks.chunk(seed, factors.STREAM_ITEM_FACTORS, c, num_items, rank)
        end = base + len(block)

        def cut(rows):  # the sorted rows that lie in this chunk, from its row 0
            a, b = np.searchsorted(rows, (base, end))
            return rows[a:b] - base

        gone, cats = cut(unavailable), item_category[base:end]
        parts = [
            ref.top_k_allowed(
                queries[a:a + group], block, min(k, len(block)), unavailable=gone,
                excluded=[cut(e) for e in excluded[a:a + group]], item_category=cats,
                query_category=query_category[a:a + group], precision=precision)
            for a in range(0, S, group)
        ]
        s, i = (np.concatenate(x) for x in zip(*parts))
        if s.shape[1] < k:  # a last chunk shorter than k
            s = np.pad(s, ((0, 0), (0, k - s.shape[1])), constant_values=-np.inf)
            i = np.pad(i, ((0, 0), (0, k - i.shape[1])), constant_values=-1)
        hits = []
        if served is not None:
            r, col = np.nonzero((served >= base) & (served < end))
            for row in np.unique(r):
                cols = col[r == row]
                hits.append((row, cols, reference.score_items(
                    queries[row], block, served[row, cols] - base)))
        return s, np.where(i >= 0, i + base, -1), hits

    chunks = range(-(-num_items // factors.CHUNK_ROWS))
    with ThreadPoolExecutor(max_workers=max(1, min(workers, len(chunks)))) as pool:
        for s, i, hits in pool.map(one, chunks):
            best = _merge(best, (s, i), k)
            for row, cols, sc in hits:
                own[row, cols] = sc
    return best[0], best[1], own
