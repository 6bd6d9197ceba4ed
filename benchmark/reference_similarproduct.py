"""The plain reference of the Similar Product template: what an item-page
answer has to agree with. NumPy only; imports nothing of the program, and the
raw factor table and the categories it is given are regenerated from the seed
(benchmark/factors.py, benchmark/ecomm_data.py).

The template scores by cosine: every row of the raw table is normalised to
unit length (``unit_rows``), a query's vector is the SUM of its items' unit
rows (``query_vectors``; up to 8 of them in a session, so a score is a sum of
up to 8 cosines and lies in [-8, 8]), and the answer is the top ``num`` by f32
dot over the whole catalog of the ALLOWED set: every item minus the query's own
items and its blackList, intersected with the members of the query's
categories where ``categories`` is given.

``precision`` and ``apply_category`` are the switches the CONTROLS use: the
same reference one precision down, or without the category rule, put in the
program's place. Each has to come out as not correct."""

from __future__ import annotations

import numpy as np

import reference


def unit_rows(table: np.ndarray, block: int = 1 << 19) -> np.ndarray:
    """[rows, D] f32: every row of ``table`` over its own length."""
    out = np.empty(table.shape, np.float32)
    for lo in range(0, len(table), block):
        part = np.asarray(table[lo:lo + block], np.float32)
        norm = np.sqrt(np.einsum("ij,ij->i", part, part, dtype=np.float32))
        out[lo:lo + block] = part / np.maximum(norm, np.float32(1e-12))[:, None]
    return out


def query_vectors(unit: np.ndarray, items: list) -> np.ndarray:
    """[S, D] f32: the sum of each query's items' unit rows, in the order
    listed (an item listed twice counts twice)."""
    return np.stack([
        unit[np.asarray(its, np.int64)].sum(axis=0, dtype=np.float32) for its in items])


def _in_categories(item_category: np.ndarray, wanted) -> np.ndarray:
    """[rows] bool: does each row belong to one of the ``wanted`` categories?
    ``item_category`` is [rows] (one category an item) or [rows, W] (-1 = none)."""
    hit = np.isin(item_category, np.asarray(list(wanted), np.int64))
    return hit if hit.ndim == 1 else hit.any(axis=1)


def top_k_allowed(queries: np.ndarray, unit: np.ndarray, k: int, *, excluded: list,
                  item_category, query_categories: list, precision: str = "float32",
                  apply_category: bool = True, block: int = 1 << 18):
    """Exact top-k of ``queries @ unit.T`` over each query's allowed set,
    scanning the table in blocks of rows: ([S, k] scores descending, [S, k]
    row ids, -1 and -inf where a query has fewer than k allowed items). Ties
    break towards the lower row id.

    ``excluded[s]``: sorted rows query s may not be served (its own items and
    its blackList); ``query_categories[s]``: the category ids query s is
    restricted to (a collection; None = unrestricted; empty = nothing allowed);
    ``item_category``: each row's category id(s)."""
    q = reference._lower(queries, precision)
    S = q.shape[0]
    best_s = np.full((S, k), -np.inf, np.float32)
    best_i = np.full((S, k), -1, np.int64)
    restricted = [s for s in range(S) if query_categories[s] is not None] \
        if apply_category else []
    for lo in range(0, unit.shape[0], block):
        tb = reference._lower(unit[lo:lo + block], precision)
        hi = lo + len(tb)
        sc = q @ tb.T  # [S, B] f32
        for s in range(S):
            a, b = np.searchsorted(excluded[s], (lo, hi))
            sc[s, excluded[s][a:b] - lo] = -np.inf
        for s in restricted:
            sc[s, ~_in_categories(item_category[lo:hi], query_categories[s])] = -np.inf
        for s in range(S):
            # only what can still enter: at or above the k-th best so far
            live = np.flatnonzero((sc[s] >= best_s[s, -1]) & (sc[s] > -np.inf))
            if len(live) > k:
                live = live[np.argpartition(-sc[s, live], k - 1)[:k]]
            cand_s = np.concatenate([best_s[s][best_i[s] >= 0], sc[s, live]])
            cand_i = np.concatenate([best_i[s][best_i[s] >= 0], live + lo])
            order = np.lexsort((cand_i, -cand_s))[:k]
            best_s[s], best_i[s] = -np.inf, -1
            best_s[s, :len(order)], best_i[s, :len(order)] = cand_s[order], cand_i[order]
    return best_s, best_i


def excluded_served(items, *, excluded: np.ndarray, item_category,
                    query_categories) -> int:
    """How many of the served ``items`` no rule allows: one of the query's
    own items or black-listed (``excluded``, sorted rows), or outside every
    category the query names."""
    items = np.asarray(items, np.int64)
    if len(items) == 0:
        return 0
    bad = np.zeros(len(items), bool)
    if len(excluded):
        at = np.minimum(np.searchsorted(excluded, items), len(excluded) - 1)
        bad |= excluded[at] == items
    if query_categories is not None:
        bad |= ~_in_categories(item_category[items], query_categories)
    return int(bad.sum())


def allowed_count(num_items: int, *, excluded: np.ndarray, item_category,
                  query_categories) -> int:
    """Size of a query's allowed set (for an answer shorter than ``num``)."""
    if query_categories is None:
        return int(num_items - len(np.unique(excluded)))
    ok = _in_categories(item_category, query_categories)
    return int(ok.sum() - ok[np.unique(excluded)].sum())


def compare_answer(served_items, served_scores, ref_items, ref_scores,
                   ref_scores_of_served) -> dict:
    """``reference.compare_answer`` over the slots the reference fills (an
    allowed set smaller than k leaves the rest -1)."""
    n = int((np.asarray(ref_items) >= 0).sum())
    if n == 0:
        return {"score_gap": 0.0 if len(served_items) == 0 else float("inf"),
                "overlap": 1.0 if len(served_items) == 0 else 0.0, "shortfall": 0.0}
    return reference.compare_answer(
        served_items, served_scores, ref_items[:n], ref_scores[:n], ref_scores_of_served)
