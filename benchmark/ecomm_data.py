"""The storefront deployment's seeded data: which category each item is
in, which items are unavailable, which users are active and what they have
viewed and bought, and what each request of the traffic asks. Used by the
writer child (model file, event store), by the load generator's entry and
by the reference, which regenerates all of it and takes nothing back from
the program. NumPy only; every draw is from ``--seed``.

Requests are derived from uniform draws only (``Generator.random``), one
stream per field, so request i reads the same whatever the number of
requests drawn: the checker re-derives what the generator sent."""

from __future__ import annotations

import numpy as np

(STREAM_CATEGORIES, STREAM_UNAVAILABLE, STREAM_ACTIVE, STREAM_EVENTS,
 STREAM_KIND, STREAM_CATEGORY, STREAM_LIST_LEN, STREAM_LIST_ITEMS) = range(21, 29)

HOME, CATEGORY, CART = 0, 1, 2
KINDS = ("home", "category", "cart")
MAX_LIST = 5  # a cart holds 1..5 items


def rng_for(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), int(stream)])


def category_sizes(num_items: int, num_categories: int) -> np.ndarray:
    """Items per category, Zipf (s = 1): the c-th largest category holds a
    share 1/c of the catalog, every category at least one item, all of them
    exactly ``num_items`` together."""
    w = 1.0 / np.arange(1, num_categories + 1)
    sizes = np.maximum(1, np.floor(w / w.sum() * num_items).astype(np.int64))
    sizes[0] += num_items - sizes.sum()  # the rounding goes to the largest
    if sizes[0] < 1:
        raise ValueError(f"{num_categories} categories do not fit {num_items} items")
    return sizes


def item_categories(seed: int, num_items: int, num_categories: int) -> np.ndarray:
    """[num_items] int32: the one category of each item (category c is named
    ``c<c>``), Zipf sizes laid over the catalog by a seeded permutation."""
    cats = np.repeat(np.arange(num_categories, dtype=np.int32),
                     category_sizes(num_items, num_categories))
    return rng_for(seed, STREAM_CATEGORIES).permutation(cats)


def unavailable_items(seed: int, num_items: int, count: int) -> np.ndarray:
    """Sorted distinct items of the ``unavailableItems`` constraint."""
    return np.sort(rng_for(seed, STREAM_UNAVAILABLE).choice(
        num_items, int(count), replace=False))


def active_users(seed: int, num_users: int, count: int) -> np.ndarray:
    """Sorted distinct users whose behaviours the event store holds."""
    return np.sort(rng_for(seed, STREAM_ACTIVE).choice(
        num_users, int(count), replace=False))


def user_events(seed: int, num_items: int, num_active: int, num_events: int,
                buy_share: float):
    """The behaviour log of the active users: (position of the user in
    ``active_users`` [N], item [N], is_buy [N]), grouped by user. Every
    user gets N / num_active events to within one; items are drawn by Zipf
    (s = 1) popularity over the catalog, popularity rank r being item
    perm[r], so one user's ~100 events hold ~80 distinct items."""
    rng = rng_for(seed, STREAM_EVENTS)
    base, extra = divmod(int(num_events), int(num_active))
    counts = np.full(num_active, base, np.int64)
    counts[:extra] += 1
    who = np.repeat(np.arange(num_active, dtype=np.int64), counts)
    rank = np.floor((num_items + 1.0) ** rng.random(len(who))).astype(np.int64) - 1
    by_popularity = rng.permutation(num_items)
    item = by_popularity[np.clip(rank, 0, num_items - 1)]
    return who, item, rng.random(len(who)) < buy_share


def seen_sets(who: np.ndarray, item: np.ndarray, num_active: int) -> list[np.ndarray]:
    """Per active user, the sorted distinct items of their events."""
    bounds = np.searchsorted(who, np.arange(num_active + 1))
    return [np.unique(item[a:b]) for a, b in zip(bounds[:-1], bounds[1:])]


def requests(seed: int, count: int, num_items: int, num_categories: int,
             shares: dict) -> dict:
    """What requests 0 .. count-1 ask: ``kind`` (HOME / CATEGORY / CART by
    the mix's shares), ``category`` (drawn in proportion to the category's
    item count; read for CATEGORY only), ``list_len`` 1..5 and ``list_items``
    [count, 5] uniform over the catalog (read for CART only)."""
    u = rng_for(seed, STREAM_KIND).random(count)
    kind = np.full(count, HOME, np.int8)
    kind[u >= shares["home"]] = CATEGORY
    kind[u >= shares["home"] + shares["category"]] = CART
    cum = np.cumsum(category_sizes(num_items, num_categories)) / float(num_items)
    category = np.minimum(
        np.searchsorted(cum, rng_for(seed, STREAM_CATEGORY).random(count), side="right"),
        num_categories - 1).astype(np.int32)
    list_len = 1 + np.floor(
        rng_for(seed, STREAM_LIST_LEN).random(count) * MAX_LIST).astype(np.int64)
    list_items = np.floor(
        rng_for(seed, STREAM_LIST_ITEMS).random((count, MAX_LIST)) * num_items
    ).astype(np.int64)
    return {"kind": kind, "category": category, "list_len": list_len,
            "list_items": list_items}


def request_body(user: int, num: int, req: dict, i: int) -> bytes:
    """The JSON body of request i for ``user``."""
    kind = int(req["kind"][i])
    if kind == CATEGORY:
        return b'{"user":"u%d","num":%d,"categories":["c%d"]}' % (
            user, num, int(req["category"][i]))
    if kind == CART:
        items = req["list_items"][i, : int(req["list_len"][i])]
        return b'{"user":"u%d","num":%d,"blackList":[%s]}' % (
            user, num, b",".join(b'"i%d"' % int(x) for x in items))
    return b'{"user":"u%d","num":%d}' % (user, num)
