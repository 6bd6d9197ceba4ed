"""The plain reference of a fold-in: the row a user has AFTER their ratings
were folded into a model that IS a stored int8 pair (or f32 / bf16 rows, for
the CPU tests), from nothing but the ratings and the item rows. NumPy only,
float64 solve then float32; nothing is read from the program.

For a user whose rated items are S (their history replayed in order, the last
rating of an item wins, items without a factor row skipped) with ratings r:

    x = (V_S^T V_S + lambda |S| I)^-1 V_S^T r

with V_S the DEQUANTIZED item rows — one explicit-feedback ALS half-step with
the regularizer weighted by the count (the program's ``solve_bucket_explicit``
with ``weighted_reg``). The row is then stored by the configuration's rule
(``reference_int8.quantize_rows``: scale = max|x| / 127, rint) and a served
score is ``reference_int8``'s: the f32 dot of the dequantized user row with
the dequantized item row. A user with no rated item that has a factor row
keeps the row they had, or none.

Departures from the program's ``realtime/foldin.py ALSFoldIn``, each on
purpose: the solve is float64 normal equations by ``numpy.linalg.solve``, not
an f32 batched Cholesky on the device; one user at a time, no padding to
(B, K); the item rows come from the seed (regenerated, quantized by the
benchmark's own rule), not from a resident table; the history is the
benchmark's own record of what it sent, not a read of the event store.

A rounded row makes near-ties real: where x_j / scale lies within ``TIE`` of a
half, an f32 solve that differs in the last bits may round the other way and
be right. ``stored_variants`` ENUMERATES those codes — both roundings of each
such coordinate; the coordinate that sets the scale is +-127 whichever way the
last bits fall, and a scale that moves in ITS last bits moves a score by a
part in 10^7, far inside the limit — so the comparison passes an answer that
matches one variant and does not widen its limit."""

from __future__ import annotations

import itertools
import multiprocessing
import os
from concurrent.futures import ProcessPoolExecutor, ThreadPoolExecutor

import numpy as np

import factors
import reference
import reference_int8

TIE = 0.001  # |frac(x_j / scale) - 1/2| below which both roundings are held
MAX_TIES = 8  # coordinates enumerated a row (2^8 variants), the nearest first
# chunks scanned side by side, a process each: on the benchmark's machine a
# chunk's process holds 3.3 GB of the machine's memory by its end (1.4 GB of
# it RSS) and twelve met the 40 GiB limit; the twin's twelve THREADS over 160
# rows peak at 33 GB there (my chip runs, PR 45)
SCAN_PROCESSES = 5


def rated(history, num_items: int) -> tuple[np.ndarray, np.ndarray]:
    """(items, ratings) a solve sees of ``history`` [(item, rating), ...] in
    store order: the last rating of an item wins, items with no factor row
    (item >= num_items) skipped; in order of first appearance."""
    seen: dict[int, float] = {}
    for item, rating in history:
        if 0 <= item < num_items:
            seen[int(item)] = float(rating)
    return (np.fromiter(seen, np.int64, len(seen)),
            np.fromiter(seen.values(), np.float64, len(seen)))


def solve(item_rows: np.ndarray, ratings: np.ndarray, reg: float) -> np.ndarray:
    """x [D] f32 of the normal equations above, solved in float64."""
    v = np.asarray(item_rows, np.float64)
    a = v.T @ v + reg * len(v) * np.eye(v.shape[1])
    return np.linalg.solve(a, v.T @ np.asarray(ratings, np.float64)).astype(np.float32)


def stored_variants(x: np.ndarray, storage: str = "int8", tie: float = TIE):
    """The f32 rows ``x`` may be served as once stored in ``storage``
    ("int8": quantized by the configuration's rule and dequantized, every
    near-tie both ways; "bfloat16" / "float32": the one cast row), the
    reference's own rounding FIRST. Returns (rows [n, D] f32, int8 codes
    [n, D] or None, the scale or None)."""
    x = np.asarray(x, np.float32)
    if storage != "int8":
        return reference._lower(x, storage)[None, :], None, None
    codes, scale = reference_int8.quantize_rows(x[None, :])
    codes, scale = codes[0].astype(np.int64), np.float32(scale[0])
    ratio = x / scale
    ties = np.flatnonzero(np.abs(np.abs(ratio - np.floor(ratio)) - 0.5) < tie)
    if len(ties) > MAX_TIES:  # the nearest to a half: the ones that can fall either way
        ties = ties[np.argsort(np.abs(np.abs(ratio - np.floor(ratio)) - 0.5)[ties])[:MAX_TIES]]
    other = np.where(np.floor(ratio) == codes, codes + 1, codes - 1)  # the other rounding
    out = []
    for flips in itertools.product((False, True), repeat=len(ties)):
        c = codes.copy()
        sel = ties[np.asarray(flips, bool)] if len(ties) else ties
        c[sel] = other[sel]
        out.append(np.clip(c, -127, 127))
    codes_all = np.asarray(out, np.int8)
    return reference_int8.dequantize(codes_all, np.full(len(out), scale)), codes_all, scale


def item_rows(seed: int, num_items: int, rank: int, ixs, workers: int = 6) -> np.ndarray:
    """Dequantized f32 rows ``ixs`` of the seeded int8 item table
    (``reference_int8.table_rows``'s answer), a few chunks at a time: a
    history's items lie in every chunk of a Zipf catalog."""
    ixs = np.asarray(ixs, np.int64)
    out = np.empty((len(ixs), rank), np.float32)
    chunk_of = ixs // factors.CHUNK_ROWS

    def one(c: int) -> None:
        sel = chunk_of == c
        _, v, s = reference_int8.chunk_pair(seed, factors.STREAM_ITEM_FACTORS, c, num_items, rank)
        local = ixs[sel] - c * factors.CHUNK_ROWS
        out[sel] = reference_int8.dequantize(v[local], s[local])

    chunks = np.unique(chunk_of).tolist()
    with ThreadPoolExecutor(max_workers=max(1, min(workers, len(chunks)))) as pool:
        list(pool.map(one, chunks))
    return out


def required_and_allowed(acked, posted, sent: float, answered: float, guarantee_s: float):
    """The prefix rule for one answer: of a user's events in store order,
    (how many MUST be in the answer, how many MAY be). An event acknowledged
    at least ``guarantee_s`` before the query was sent must be; one posted
    before the answer came back may be (it is in the store from some moment
    between its post and its acknowledgement); the events in are always a
    prefix of the user's events — never a later one without an earlier."""
    acked, posted = np.asarray(acked, float), np.asarray(posted, float)
    due = np.flatnonzero(acked <= sent - guarantee_s)
    # store order is post order: an event that must be in brings along
    # every event posted before it
    must = int(due[-1]) + 1 if len(due) else 0
    return must, max(must, int(np.sum(posted < answered)))


def _scan_chunk(task):
    """One chunk of ``scan``, in a process of its own: what
    ``reference_int8.scan`` does for a chunk (regenerate, quantize, dequantize,
    score every row asked, the controls' rows too, the served items' scores)."""
    seed, num_items, rank, c, queries, k, served, controls = task
    base = c * factors.CHUNK_ROWS
    src, v, sc = reference_int8.chunk_pair(seed, factors.STREAM_ITEM_FACTORS, c, num_items, rank)
    deq = reference_int8.dequantize(v, sc)
    kk = min(k, len(deq))

    def padded(s, i, p):  # a last chunk shorter than k
        short = k - s.shape[1]
        if short > 0:
            s = np.pad(s, ((0, 0), (0, short)), constant_values=-np.inf)
            i = np.pad(i, ((0, 0), (0, short)), constant_values=-1)
            p = np.pad(p, ((0, 0), (0, short)), constant_values=np.nan)
        return s, i, p

    s, i = reference.top_k_scan(queries, deq, kk, block=1 << 16)
    found = {"": padded(s, np.where(i >= 0, i + base, -1), s)}
    for name, (cq, table, precision) in controls.items():
        s, i = reference.top_k_scan(cq, deq if table == "int8" else src, kk, precision,
                                    block=1 << 16)
        exact = np.einsum("sd,skd->sk", queries, deq[np.maximum(i, 0)])
        found[name] = padded(s, np.where(i >= 0, i + base, -1), exact)
    hits = []
    r, col = np.nonzero((served >= base) & (served < base + len(deq)))
    for row in np.unique(r):
        cols = col[r == row]
        hits.append((row, cols, reference.score_items(queries[row], deq, served[row, cols] - base)))
    return found, hits


def scan(seed: int, num_items: int, rank: int, queries: np.ndarray, k: int, served,
         controls: dict | None = None, workers: int = 8):
    """``reference_int8.scan``'s answer — ([S, k] scores, [S, k] ids, the
    reference's own score of every ``served`` id, each control's (scores, ids,
    exact scores)) — a chunk a PROCESS instead of a chunk a thread, each
    process ended after its chunk. The same arithmetic by the same functions
    (``chunk_pair``, ``dequantize``, ``reference.top_k_scan``, ``_merge``); why:
    a live run asks about twice the twin's rows (every refresh answer, every
    near-tie, the controls' rows), and on the benchmark's machine the memory
    one long-lived process frees between its chunks is not given back in time
    — the twin's own scan of 160 rows peaks at 33 GB of the machine's 40, and
    three live runs met the limit inside it at S = 300 (PR 45). A process that
    ends gives everything back; ``SCAN_PROCESSES`` of them run side by side,
    one BLAS thread each."""
    controls = controls or {}
    S = len(queries)
    served = np.asarray(served, np.int64)
    own = np.full(served.shape, np.nan, np.float32)

    def empty():
        return (np.full((S, k), -np.inf, np.float32), np.full((S, k), -1, np.int64),
                np.full((S, k), np.nan, np.float32))

    best = {name: empty() for name in ("", *controls)}
    chunks = range(-(-num_items // factors.CHUNK_ROWS))
    tasks = [(seed, num_items, rank, c, queries, k, served, controls) for c in chunks]
    # one thread a process: a chunk's products are small, and a dozen
    # processes with a BLAS pool each would leave the machine no core
    threads = {v: os.environ.get(v) for v in
               ("OPENBLAS_NUM_THREADS", "OMP_NUM_THREADS", "MKL_NUM_THREADS")}
    os.environ.update(dict.fromkeys(threads, "1"))
    try:
        with ProcessPoolExecutor(max_workers=max(1, min(workers, len(tasks))),
                                 mp_context=multiprocessing.get_context("spawn"),
                                 max_tasks_per_child=1) as pool:
            for found, hits in pool.map(_scan_chunk, tasks):
                for name, cand in found.items():
                    best[name] = reference_int8._merge(best[name], cand, k)
                for row, cols, scores in hits:
                    own[row, cols] = scores
    finally:
        for var, was in threads.items():
            os.environ.pop(var) if was is None else os.environ.update({var: was})
    return best[""][0], best[""][1], own, {n: best[n] for n in controls}
