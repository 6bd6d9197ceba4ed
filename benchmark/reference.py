"""The plain reference: what the served answers have to agree with. NumPy
only; imports nothing of the program and takes no weights from it (the
serving tables are regenerated from the seed).

``precision`` is the switch the CONTROL uses: the same reference computed
in the nearest precision below the one the configuration states, put in
the program's place. It has to come out as not correct."""

from __future__ import annotations

import numpy as np

PRECISIONS = ("float32", "bfloat16")


def _lower(x: np.ndarray, precision: str) -> np.ndarray:
    """x as the stated precision sees it, back in f32 (f32 accumulation of
    rounded operands is what a one-pass bf16 matmul on the chip does)."""
    if precision == "float32":
        return np.asarray(x, np.float32)
    if precision == "bfloat16":
        import ml_dtypes

        return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(np.float32)
    raise ValueError(f"unknown precision {precision!r}")


def top_k_scan(queries: np.ndarray, table: np.ndarray, k: int,
               precision: str = "float32", block: int = 1 << 18):
    """Exact top-k of ``queries @ table.T`` per query, scanning the table in
    blocks of rows: ([S, k] scores descending, [S, k] row ids). Ties break
    towards the lower row id."""
    q = _lower(queries, precision)
    S = q.shape[0]
    best_s = np.full((S, k), -np.inf, np.float32)
    best_i = np.full((S, k), -1, np.int64)
    for lo in range(0, table.shape[0], block):
        tb = _lower(table[lo:lo + block], precision)
        sc = q @ tb.T  # [S, B] f32
        thr = best_s[:, -1]
        if lo == 0 and sc.shape[1] > k:
            # nothing to beat yet: only this block's own k best can stay
            thr = np.partition(sc, sc.shape[1] - k, axis=1)[:, sc.shape[1] - k]
        r, c = np.nonzero(sc >= thr[:, None])
        if len(r) == 0:
            continue
        # merge the few candidates of this block into the running best
        # (np.nonzero walks row by row, so each row's candidates are a run)
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


def score_items(query: np.ndarray, table: np.ndarray, items: np.ndarray,
                precision: str = "float32") -> np.ndarray:
    """f32 scores of the given table rows for one query."""
    return _lower(table[items], precision) @ _lower(query, precision)


def compare_answer(served_items, served_scores, ref_top_items, ref_top_scores,
                   ref_scores_of_served) -> dict:
    """The numbers one served answer is held to, against the reference:

    - ``score_gap``: largest |served score - reference score of that item|;
    - ``overlap``: share of the served items inside the reference top-k;
    - ``shortfall``: how far the worst served item scores below the
      reference's k-th best (0 when the lists agree up to ties)."""
    served_scores = np.asarray(served_scores, np.float32)
    k = len(ref_top_items)
    gap = float(np.abs(served_scores - ref_scores_of_served).max()) if len(served_scores) else float("inf")
    overlap = len(set(served_items) & set(int(i) for i in ref_top_items)) / k
    shortfall = float(max(0.0, ref_top_scores[-1] - ref_scores_of_served.min())) if len(served_scores) else float("inf")
    return {"score_gap": gap, "overlap": overlap, "shortfall": shortfall}


def well_formed(items, scores, k: int) -> str | None:
    """None when an answer is k distinct items with finite descending
    scores, else what is wrong with it."""
    if len(items) != k or len(set(items)) != k:
        return f"{len(items)} items, {len(set(items))} distinct, expected {k}"
    s = np.asarray(scores, np.float64)
    if not np.isfinite(s).all():
        return "non-finite score"
    if (np.diff(s) > 0).any():
        return "scores not descending"
    return None
