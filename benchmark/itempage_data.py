"""The item-page deployment's seeded traffic: what each request of the
Similar Product cell asks. The catalog's categories are the storefront
configuration's (``ecomm_data.item_categories``: same law, same seed rule);
this file adds the requests. Used by the load generator's entry and by the
driver's checker, which re-derives what was sent and takes nothing back from
the program. NumPy only; every draw is from ``--seed``.

Requests are derived from uniform draws only (``Generator.random``), one
stream per field, so request i reads the same whatever the number of
requests drawn."""

from __future__ import annotations

import numpy as np

import ecomm_data

(STREAM_KIND, STREAM_POPULARITY, STREAM_ITEMS, STREAM_COUNT,
 STREAM_BLACK_LEN, STREAM_BLACK) = range(41, 47)

SIMILAR, SAME_CATEGORY, SESSION = 0, 1, 2
KINDS = ("similar", "same_category", "session")
MAX_ITEMS = 8  # a session holds 2..8 recently viewed items
MAX_BLACK = 5  # and black-lists 1..5


def by_popularity(seed: int, num_items: int) -> np.ndarray:
    """Popularity rank r -> item: a seeded permutation of the catalog."""
    return ecomm_data.rng_for(seed, STREAM_POPULARITY).permutation(num_items)


def zipf_items(u: np.ndarray, ranked: np.ndarray) -> np.ndarray:
    """Items drawn by Zipf (s = 1) popularity from uniform draws ``u``: the
    law ``ecomm_data.user_events`` uses (rank floor((N + 1)^u) - 1)."""
    n = len(ranked)
    rank = np.floor((n + 1.0) ** u).astype(np.int64) - 1
    return ranked[np.clip(rank, 0, n - 1)]


def requests(seed: int, count: int, num_items: int, shares: dict) -> dict:
    """What requests 0 .. count-1 ask: ``kind`` (SIMILAR / SAME_CATEGORY /
    SESSION by the mix's shares), ``items`` [count, 8] with ``n_items`` of
    them read (1 for the first two kinds, the item whose page is shown;
    2..8 for a session, uniform, fewer where two draws met: a strip of
    recently viewed items holds each once), all by Zipf popularity;
    ``black`` [count, 5] uniform over the catalog with ``n_black`` 1..5 read
    (SESSION only)."""
    u = ecomm_data.rng_for(seed, STREAM_KIND).random(count)
    kind = np.full(count, SIMILAR, np.int8)
    kind[u >= shares["similar"]] = SAME_CATEGORY
    kind[u >= shares["similar"] + shares["same_category"]] = SESSION
    items = zipf_items(
        ecomm_data.rng_for(seed, STREAM_ITEMS).random((count, MAX_ITEMS)),
        by_popularity(seed, num_items))
    n_items = np.ones(count, np.int64)
    want = 2 + np.floor(ecomm_data.rng_for(seed, STREAM_COUNT).random(count)
                        * (MAX_ITEMS - 1)).astype(np.int64)
    for i in np.flatnonzero(kind == SESSION).tolist():
        _, first = np.unique(items[i, : want[i]], return_index=True)
        distinct = items[i, np.sort(first)]
        items[i, : len(distinct)] = distinct
        n_items[i] = len(distinct)
    n_black = 1 + np.floor(ecomm_data.rng_for(seed, STREAM_BLACK_LEN).random(count)
                           * MAX_BLACK).astype(np.int64)
    black = np.floor(ecomm_data.rng_for(seed, STREAM_BLACK).random((count, MAX_BLACK))
                     * num_items).astype(np.int64)
    return {"kind": kind, "items": items, "n_items": n_items,
            "black": black, "n_black": n_black}


def query_of(req: dict, i: int, item_cat: np.ndarray):
    """(the query's items, its blackList, its category or None) of request i."""
    kind = int(req["kind"][i])
    items = req["items"][i, : int(req["n_items"][i])]
    if kind == SESSION:
        return items, req["black"][i, : int(req["n_black"][i])], None
    none = np.zeros(0, np.int64)
    return items, none, (int(item_cat[items[0]]) if kind == SAME_CATEGORY else None)


def request_body(num: int, req: dict, i: int, item_cat: np.ndarray) -> bytes:
    """The JSON body of request i."""
    items, black, cat = query_of(req, i, item_cat)
    body = b'{"items":[%s],"num":%d' % (b",".join(b'"i%d"' % int(x) for x in items), num)
    if cat is not None:
        body += b',"categories":["c%d"]' % cat
    if len(black):
        body += b',"blackList":[%s]' % b",".join(b'"i%d"' % int(x) for x in black)
    return body + b"}"
