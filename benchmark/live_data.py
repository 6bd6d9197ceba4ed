"""The live deployment's seeded data: which held users write ratings (the
pool of "writers"), the rating history each has in the store before the model
was trained, the warm-up bursts, and the stream of `rate` events of a run.
Used by the writer child (the event store), the driver (warm-up), the load
generator's entry (the stream) and the reference, which regenerates all of it
and takes nothing back from the program. NumPy only; every draw is from
``--seed``, one stream a field.

An event is (user, item, rating): ``user`` a row of the model (u<n>, n <
num_users) or, for a user the model does not hold, n >= num_users; ``item``
likewise (i<n>; n >= num_items is an item with no factor row). Items are
drawn by Zipf (s = 1) popularity over the catalog as the storefront's are
(ecomm_data.user_events), the item of popularity rank r being (a r + b) mod
num_items with a seeded a coprime to num_items — a permutation of 48 M items
that costs nothing to hold."""

from __future__ import annotations

import math

import numpy as np

(STREAM_WRITERS, STREAM_LENGTHS, STREAM_HISTORY, STREAM_POPULARITY,
 STREAM_KIND, STREAM_WHO, STREAM_ITEM, STREAM_RATING, STREAM_WARM) = range(31, 40)

WRITER, NEW_USER, COLD_ITEM = 0, 1, 2
WARM_BATCHES = (1, 9)  # users a warm-up burst touches: B = 8 and B = 16


def rng_for(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), int(stream)])


def writers(seed: int, num_users: int, pool: int) -> np.ndarray:
    """Sorted distinct held users whose histories the store holds."""
    return np.sort(rng_for(seed, STREAM_WRITERS).choice(num_users, int(pool), replace=False))


def popularity(seed: int, num_items: int) -> tuple[int, int]:
    rng = rng_for(seed, STREAM_POPULARITY)
    while True:
        a = int(rng.integers(1, num_items))
        if math.gcd(a, num_items) == 1:
            return a, int(rng.integers(0, num_items))


def zipf_items(u: np.ndarray, num_items: int, ab: tuple[int, int]) -> np.ndarray:
    """Catalog items of uniform draws ``u``: rank floor((I + 1)^u) - 1."""
    rank = np.clip(np.floor((num_items + 1.0) ** u).astype(np.int64) - 1, 0, num_items - 1)
    return (ab[0] * rank + ab[1]) % num_items


def stars(u: np.ndarray, shares) -> np.ndarray:
    """1..5 stars of uniform draws, by the configuration's shares of each."""
    return 1 + np.minimum(np.searchsorted(np.cumsum(shares), u, side="right"), len(shares) - 1)


def history_lengths(seed: int, pool: int, mean: float, longest: int) -> np.ndarray:
    """Events a writer has in the store: geometric from 1, cut at ``longest``,
    its parameter solved so that the cut distribution's mean is ``mean``."""
    ks = np.arange(1, longest + 1)

    def cut_mean(p):
        w = (1 - p) ** (ks - 1) * p
        return float((w * ks).sum() / w.sum())

    lo, hi = 1e-6, 1.0 - 1e-6
    for _ in range(80):  # cut_mean falls as p rises
        mid = 0.5 * (lo + hi)
        lo, hi = (mid, hi) if cut_mean(mid) > mean else (lo, mid)
    w = (1 - lo) ** (ks - 1) * lo
    u = rng_for(seed, STREAM_LENGTHS).random(pool)
    return 1 + np.minimum(np.searchsorted(np.cumsum(w / w.sum()), u, side="right"), longest - 1)


class Deployment:
    """What the store holds before the model's train watermark, and what a
    run sends after it, as (user, item, rating) triples in order."""

    def __init__(self, cfg: dict, seed: int):
        ev = cfg["events"]
        self.num_users, self.num_items = int(cfg["num_users"]), int(cfg["num_items"])
        self.shares = ev["rating_shares"]
        self.ab = popularity(seed, self.num_items)
        self.writers = writers(seed, self.num_users, ev["writers"])
        lengths = history_lengths(seed, len(self.writers), ev["history_mean"], ev["history_longest"])
        self.bounds = np.concatenate([[0], np.cumsum(lengths)])
        rng = rng_for(seed, STREAM_HISTORY)
        n = int(self.bounds[-1])
        self.hist_items = zipf_items(rng.random(n), self.num_items, self.ab)
        self.hist_stars = stars(rng.random(n), self.shares)
        self.seed = int(seed)
        # the items each user has rated so far, the stream's included, so
        # that no user rates an item twice after the watermark
        self._rated: dict[int, set] = {}

    def history(self, user: int) -> tuple[np.ndarray, np.ndarray]:
        """(items, stars) a held writer has in the store, in order; empty
        for anyone else."""
        pos = int(np.searchsorted(self.writers, user))
        if pos >= len(self.writers) or self.writers[pos] != user:
            return np.zeros(0, np.int64), np.zeros(0, np.int64)
        a, b = self.bounds[pos], self.bounds[pos + 1]
        return self.hist_items[a:b], self.hist_stars[a:b]

    def _fresh_item(self, user: int, rng) -> int:
        rated = self._rated.setdefault(user, set(self.history(user)[0].tolist()))
        while True:
            item = int(zipf_items(rng.random(1), self.num_items, self.ab)[0])
            if item not in rated:
                rated.add(item)
                return item

    def distinct(self, user: int) -> int:
        """Distinct catalog items ``user`` has rated so far."""
        return len(self._rated.get(user, set(self.history(user)[0].tolist())))

    def warm_bursts(self) -> list[list[tuple[int, int, int]]]:
        """The bursts that compile every (B, K) shape a window can meet in
        the fold's programs: for each K (8, 16, 32: the powers of two over
        the distinct items of the longest history and a few more) a burst
        touching 1 user (B = 8) and one touching 9 (B = 16), each with a
        user whose history then needs that K and none needing more. Call
        once, before ``stream``: the items rated here are rated."""
        rng = rng_for(self.seed, STREAM_WARM)
        counts = np.asarray([len(set(self.hist_items[a:b].tolist()))
                             for a, b in zip(self.bounds[:-1], self.bounds[1:])])
        used: set[int] = set()
        bursts = []
        for k in (8, 16, 32):
            lo = k // 2 if k > 8 else 0  # distinct + 1 in (k/2, k]
            for users in WARM_BATCHES:
                top = [p for p in np.flatnonzero((counts + 1 > lo) & (counts + 1 <= k)).tolist()
                       if p not in used][:1]
                rest = [p for p in np.flatnonzero(counts + 1 <= k).tolist()
                        if p not in used and p not in top][:users - 1]
                if not top:
                    raise ValueError(f"no writer's history needs K = {k}")
                burst = []
                for p in top + rest:
                    used.add(p)
                    u = int(self.writers[p])
                    burst.append((u, self._fresh_item(u, rng),
                                  int(stars(rng.random(1), self.shares)[0])))
                bursts.append(burst)
        return bursts

    def stream(self, count: int, shares: dict) -> list[tuple[int, int, int, int]]:
        """Events 0 .. count-1 of the run as (kind, user, item, stars):
        WRITER (a pool user, a catalog item they have not rated), NEW_USER
        (user num_users + j: the model does not hold them) and COLD_ITEM (a
        pool user, item num_items + j: no factor row), by ``shares``."""
        kind_u = rng_for(self.seed, STREAM_KIND).random(count)
        who = rng_for(self.seed, STREAM_WHO).integers(0, len(self.writers), count)
        star = stars(rng_for(self.seed, STREAM_RATING).random(count), self.shares)
        rng = rng_for(self.seed, STREAM_ITEM)
        out = []
        for j in range(count):
            if kind_u[j] < shares["new_user"]:
                kind, user = NEW_USER, self.num_users + j
            else:
                user = int(self.writers[who[j]])
                kind = COLD_ITEM if kind_u[j] < shares["new_user"] + shares["cold_item"] else WRITER
            item = self.num_items + j if kind == COLD_ITEM else self._fresh_item(user, rng)
            out.append((kind, user, item, int(star[j])))
        return out


def event_body(user: int, item: int, rating: int) -> bytes:
    """The JSON body of one `rate` event, as the template's quickstart POSTs it."""
    return (b'{"event":"rate","entityType":"user","entityId":"u%d",'
            b'"targetEntityType":"item","targetEntityId":"i%d",'
            b'"properties":{"rating":%d}}' % (user, item, rating))
