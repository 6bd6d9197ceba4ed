"""BiMap: immutable bidirectional map, used for string id <-> dense index.

Capability parity with the reference's BiMap
(data/.../storage/BiMap.scala:28-110): ``string_int``/``string_long``
constructors assign each distinct key a dense index — on TPU this is the
mapping from entity ids to rows of factor matrices. Also provides vectorized
numpy paths for bulk conversion (the RDD ``zipWithUniqueId`` analog).
"""

from __future__ import annotations

import itertools
from typing import Generic, Hashable, Iterable, Iterator, Mapping, Sequence, TypeVar

import numpy as np

K = TypeVar("K", bound=Hashable)
V = TypeVar("V", bound=Hashable)


class BiMapError(ValueError):
    pass


class BiMap(Generic[K, V]):
    """Immutable one-to-one mapping with an inverse view."""

    def __init__(self, forward: Mapping[K, V], _inverse: "BiMap[V, K] | None" = None):
        self._m: dict[K, V] = dict(forward)
        if _inverse is None:
            rev: dict[V, K] = {}
            for k, v in self._m.items():
                if v in rev:
                    raise BiMapError(f"duplicate value {v!r}: BiMap must be one-to-one")
                rev[v] = k
            self._inverse = BiMap(rev, _inverse=self)
        else:
            self._inverse = _inverse

    # -- mapping ----------------------------------------------------------
    def __getitem__(self, key: K) -> V:
        return self._m[key]

    def get(self, key: K, default: V | None = None) -> V | None:
        return self._m.get(key, default)

    def __contains__(self, key: object) -> bool:
        return key in self._m

    def __len__(self) -> int:
        return len(self._m)

    def __iter__(self) -> Iterator[K]:
        return iter(self._m)

    def items(self):
        return self._m.items()

    def keys(self):
        return self._m.keys()

    def values(self):
        return self._m.values()

    def to_dict(self) -> dict[K, V]:
        return dict(self._m)

    @property
    def inverse(self) -> "BiMap[V, K]":
        """The value->key view (reference BiMap.inverse)."""
        return self._inverse

    def appended(self, keys: Sequence[K]) -> "BiMap[K, V]":
        """This dense ``key -> index`` map plus ``keys`` at the next
        indices (len, len + 1, ...), as a NEW map; this one is unchanged.
        The cost is the appended keys', never the map's: a served model's
        million-id index gains a user a patch without being copied."""
        return _Appended(self, keys)

    def take(self, keys: Iterable[K]) -> "BiMap[K, V]":
        return BiMap({k: self._m[k] for k in keys if k in self._m})

    def __eq__(self, other: object) -> bool:
        return isinstance(other, BiMap) and self._m == other._m

    def __repr__(self) -> str:
        return f"BiMap({self._m!r})"

    # -- constructors (reference object BiMap:66-110) ---------------------
    @staticmethod
    def string_int(keys: Iterable[str]) -> "BiMap[str, int]":
        """Assign each distinct key a dense int index in first-seen order."""
        seen: dict[str, int] = {}
        for k in keys:
            if k not in seen:
                seen[k] = len(seen)
        return BiMap(seen)

    string_long = string_int  # Python ints are unbounded

    @staticmethod
    def from_dense(ids: Sequence[str]) -> "BiMap[str, int]":
        """Wrap an already-dense id list (index = list position) — the
        zero-copy constructor for columnar reads whose id lists came out
        of ``scan_ratings``/``index_spans`` pre-indexed."""
        return BiMap({k: i for i, k in enumerate(ids)})

    # -- vectorized paths --------------------------------------------------
    def index_of(self, keys: Sequence[K]) -> np.ndarray:
        """[len(keys)] int64 indices of ``keys`` in a dense ``key ->
        index`` map, -1 for a key it does not hold. What a caller with a
        handful of keys and a map of tens of millions asks (a fold-in's
        item ids): a map over an encoded dictionary answers it without
        decoding the dictionary (``modelfile._LazyDenseBiMap``)."""
        return np.fromiter(
            map(self._m.get, keys, itertools.repeat(-1)), np.int64, len(keys)
        )

    def to_index_array(self, keys: Sequence[K]) -> np.ndarray:
        """Bulk key->index conversion to an int32 numpy array."""
        return np.fromiter((self._m[k] for k in keys), dtype=np.int32, count=len(keys))


class _Appended(BiMap):
    """``BiMap.appended``: a dense map read through, plus the few keys
    appended since, in a dictionary of their own. Point reads (``[]``,
    ``get``, ``in``, ``len``) touch the base map or that dictionary; only
    a caller that walks the whole mapping pays for one merged dictionary.
    Never calls ``BiMap.__init__`` (as ``modelfile._LazyDenseBiMap``)."""

    def __init__(self, base: BiMap, keys: Sequence, _forward: "_Appended | None" = None):
        if _forward is not None:  # the inverse view of ``_forward``
            self._base, self._inv = _forward._base.inverse, _forward
            self._extra = {v: k for k, v in _forward._extra.items()}
            self._merged = None
            return
        extra = {}
        if isinstance(base, _Appended):
            base, extra = base._base, dict(base._extra)
        n = len(base) + len(extra)
        keys = list(keys)
        held = base.index_of(keys) >= 0
        for i, k in enumerate(keys):
            if held[i] or k in extra:
                raise BiMapError(f"{k!r} is in the map already")
            extra[k] = n + i
        self._base, self._extra = base, extra
        self._inv = self._merged = None

    @property
    def _m(self) -> dict:
        if self._merged is None:
            self._merged = {**self._base._m, **self._extra}
        return self._merged

    @property
    def _inverse(self) -> BiMap:
        if self._inv is None:
            self._inv = _Appended(None, (), _forward=self)
        return self._inv

    def __getitem__(self, key):
        v = self._extra.get(key, self)
        return self._base[key] if v is self else v

    def get(self, key, default=None):
        v = self._extra.get(key, self)
        return self._base.get(key, default) if v is self else v

    def __contains__(self, key) -> bool:
        return key in self._extra or key in self._base

    def __len__(self) -> int:
        return len(self._base) + len(self._extra)

    def index_of(self, keys: Sequence) -> np.ndarray:
        out = self._base.index_of(keys)
        for j, i in enumerate(out.tolist()):
            if i < 0:
                out[j] = self._extra.get(keys[j], -1)
        return out

    def __reduce__(self):
        return (BiMap, (self._m,))
