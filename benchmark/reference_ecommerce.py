"""The plain reference of the E-Commerce template's business rules: what a
storefront answer has to agree with. NumPy only; imports nothing of the
program, and the tables, categories, events and unavailable list it is given
are regenerated from the seed (benchmark/factors.py, benchmark/ecomm_data.py).

For a query, the ALLOWED set is every item minus seen ∪ unavailable ∪
blackList, intersected with the category's members where ``categories`` is
given; the answer is the top ``num`` of the allowed set by f32 score.

``precision`` and ``apply_unavailable`` are the switches the CONTROLS use:
the same reference one precision down, or without the unavailable rule, put
in the program's place. Each has to come out as not correct."""

from __future__ import annotations

import numpy as np

import reference


def top_k_allowed(queries: np.ndarray, table: np.ndarray, k: int, *,
                  unavailable: np.ndarray, excluded: list, item_category,
                  query_category: list, precision: str = "float32",
                  apply_unavailable: bool = True, block: int = 1 << 18):
    """Exact top-k of ``queries @ table.T`` over each query's allowed set,
    scanning the table in blocks of rows (as ``reference.top_k_scan``, with
    the mask applied to each block): ([S, k] scores descending, [S, k] row
    ids, -1 and -inf where a query has fewer than k allowed items). Ties
    break towards the lower row id.

    ``unavailable``: sorted rows no query may be served; ``excluded[s]``:
    sorted rows query s alone may not be served (seen ∪ blackList);
    ``query_category[s]``: the category query s is restricted to, or None;
    ``item_category`` [I]: each row's category."""
    q = reference._lower(queries, precision)
    S = q.shape[0]
    best_s = np.full((S, k), -np.inf, np.float32)
    best_i = np.full((S, k), -1, np.int64)
    restricted = [s for s in range(S) if query_category[s] is not None]
    for lo in range(0, table.shape[0], block):
        tb = reference._lower(table[lo:lo + block], precision)
        hi = lo + len(tb)
        sc = q @ tb.T  # [S, B] f32
        if apply_unavailable:
            a, b = np.searchsorted(unavailable, (lo, hi))
            sc[:, unavailable[a:b] - lo] = -np.inf
        for s in range(S):
            a, b = np.searchsorted(excluded[s], (lo, hi))
            sc[s, excluded[s][a:b] - lo] = -np.inf
        for s in restricted:
            sc[s, item_category[lo:hi] != query_category[s]] = -np.inf
        thr = best_s[:, -1].copy()
        short = best_i[:, -1] < 0  # nothing to beat yet: the block's own k best
        if short.any() and sc.shape[1] > k:
            at = sc.shape[1] - k
            thr[short] = np.partition(sc[short], at, axis=1)[:, at]
        thr = np.maximum(thr, np.float32(-3e38))  # a masked row never enters
        r, c = np.nonzero(sc >= thr[:, None])
        if len(r) == 0:
            continue
        starts = np.flatnonzero(np.r_[True, r[1:] != r[:-1]])
        for a, b in zip(starts, np.r_[starts[1:], len(r)]):
            row, cc = r[a], c[a:b]
            cand_s = np.concatenate([best_s[row], sc[row, cc]])
            cand_i = np.concatenate([best_i[row], cc + lo])
            keep = cand_i >= 0
            cand_s, cand_i = cand_s[keep], cand_i[keep]
            order = np.lexsort((cand_i, -cand_s))[:k]
            n = len(order)
            best_s[row, :n], best_i[row, :n] = cand_s[order], cand_i[order]
    return best_s, best_i


def excluded_served(items, *, excluded: np.ndarray, unavailable_flags: np.ndarray,
                    item_category, query_category) -> int:
    """How many of the served ``items`` no rule allows: seen or black-listed
    (``excluded``, sorted rows), unavailable (``unavailable_flags`` [I]
    bool), or outside the category the query names."""
    items = np.asarray(items, np.int64)
    if len(items) == 0:
        return 0
    bad = unavailable_flags[items]
    if len(excluded):
        at = np.minimum(np.searchsorted(excluded, items), len(excluded) - 1)
        bad = bad | (excluded[at] == items)
    if query_category is not None:
        bad = bad | (item_category[items] != query_category)
    return int(bad.sum())


def allowed_count(num_items: int, *, excluded: np.ndarray,
                  unavailable_flags: np.ndarray, item_category,
                  query_category) -> int:
    """Size of a query's allowed set (for an answer shorter than ``num``)."""
    ok = ~unavailable_flags
    if query_category is not None:
        ok = ok & (item_category == query_category)
    return int(ok.sum() - ok[excluded].sum())


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
