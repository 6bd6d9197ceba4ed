"""Zero-copy model file format: flat, versioned, checksummed, mmap-served.

The pickle manifest in core/persistence.py deserializes a model by copying
every factor table through the unpickler — O(bytes) cold load, and K
replicas or variants serving the same instance each hold a private copy.
This module writes the same models as ONE flat file (the columnar cache in
data/storage/columnar_cache.py:392 is the in-repo pattern): MAGIC, an
8-byte little-endian header length, a crc32 of the header, a JSON header
describing per-entry field specs and 64-byte-aligned array blocks, then
the raw array bytes. Loading is ``mmap`` + ``np.frombuffer`` read-only
views — O(pages touched), and every process mapping the same file shares
page-cache pages. Fold-in never mutates served arrays in place
(realtime/foldin.py), so read-only views are safe to serve.

Entry kinds mirror the persistence manifest: ``arrays`` (a dataclass whose
fields are numpy arrays / BiMaps / JSON values — the four ALS templates),
``pickle`` (arbitrary payload, the fallback), ``persistent`` and
``retrain`` (markers whose semantics live in core/persistence.py).

Integrity: the header crc is always verified; per-block crc32s are stored
and checked only under ``PIO_MODEL_VERIFY=1`` (a full-file read would
defeat the O(pages-touched) load). Truncation is caught unconditionally by
block bounds checks. Every validation failure raises ``ModelFileError`` —
never garbage scores.

A model too large for one file SPANS files (``write_spanning``): the
head file (same magic, ``"version": 2``) holds only the header, which
names segment files of at most ``SEGMENT_BYTES`` (1 GiB) beside it; a
block lives in one segment, and an array larger than a segment is cut
row-wise into several blocks and comes back as a ``SpannedArray`` — its
parts mmap'd where they lie, never one host array. Segments are written
and checksummed part by part, by a few threads, from arrays or from row
sources that produce a range of rows at a time (so neither side ever
holds a 12 GB table whole). One-file models are written and read as
before.

``shared_entries(path)`` is the serving-side entry point: a process-wide
cache keyed by the file's identity ``(realpath, mtime_ns, size)`` so N
variants mounting the same instance share ONE mapping and ONE resolved
model object — the marginal RSS of tenant N+1 is bookkeeping, not factors.
"""

from __future__ import annotations

import bisect
import dataclasses
import importlib
import io
import json
import logging
import mmap
import os
import threading
import time
import zlib
from pathlib import Path
from typing import Any, NamedTuple

import numpy as np

from predictionio_tpu import faults
from predictionio_tpu.data.bimap import BiMap

logger = logging.getLogger(__name__)

MAGIC = b"PIOMODF1"
VERSION = 1
SPAN_VERSION = 2  # a head file whose blocks lie in segment files
SEGMENT_BYTES = 1 << 30  # the largest file of a spanning model
_ALIGN = 64
_HDR_FIXED = len(MAGIC) + 8 + 4  # magic + header length + header crc32


class ModelFileError(RuntimeError):
    """The model file is corrupt, truncated, or structurally invalid."""


def mmap_enabled() -> bool:
    """``PIO_MODEL_MMAP=0`` opts out of the zero-copy format entirely
    (write pickle manifests, load via bytes)."""
    return os.environ.get("PIO_MODEL_MMAP", "1").strip().lower() not in (
        "0", "false", "no", "off",
    )


def is_modelfile(blob: bytes) -> bool:
    return blob[: len(MAGIC)] == MAGIC


# --------------------------------------------------------------------------
# dtype round-trip (bfloat16 has no stable ``.str``; go by name)
# --------------------------------------------------------------------------


def _dtype_tag(dt: np.dtype) -> str:
    if dt.name == "bfloat16":
        return "bfloat16"
    return dt.str


def _tag_dtype(tag: str) -> np.dtype:
    if tag == "bfloat16":
        try:
            import ml_dtypes
        except ImportError as e:  # pragma: no cover - jax ships ml_dtypes
            raise ModelFileError("bfloat16 block but ml_dtypes missing") from e
        return np.dtype(ml_dtypes.bfloat16)
    try:
        return np.dtype(tag)
    except TypeError as e:
        raise ModelFileError(f"unknown dtype tag {tag!r}") from e


# --------------------------------------------------------------------------
# encode
# --------------------------------------------------------------------------


def _dense_ids(bm: BiMap) -> list[str] | None:
    """The id list when the BiMap is exactly str -> dense 0..n-1 (what
    every template index is), else None."""
    n = len(bm)
    ids: list[Any] = [None] * n
    for k, v in bm.items():
        if not isinstance(v, int) or isinstance(v, bool) or not (0 <= v < n):
            return None
        if not isinstance(k, str) or ids[v] is not None:
            return None
        ids[v] = k
    return ids


def _json_ok(v: Any) -> bool:
    try:
        json.dumps(v)
        return True
    except (TypeError, ValueError):
        return False


def can_encode(model: Any) -> bool:
    """True when ``model`` is a dataclass whose fields are all numpy
    arrays, dense BiMaps, None, or JSON values — reconstructable via
    ``cls(**fields)`` with zero-copy array views."""
    if not dataclasses.is_dataclass(model) or isinstance(model, type):
        return False
    try:
        flds = dataclasses.fields(model)
    except TypeError:
        return False
    for f in flds:
        v = getattr(model, f.name)
        if isinstance(v, np.ndarray) or _is_rows(v):
            continue
        if isinstance(v, BiMap):
            if _dense_ids(v) is None:
                return False
            continue
        if v is None or _json_ok(v):
            continue
        return False
    return True


def _encode_ids(ids: list[str]) -> tuple[np.ndarray, np.ndarray]:
    """utf-8 blob + [n+1] int64 offsets for one string dictionary
    (columnar_cache idiom)."""
    enc = [s.encode("utf-8") for s in ids]
    offs = np.zeros(len(enc) + 1, dtype=np.int64)
    if enc:
        np.cumsum([len(b) for b in enc], out=offs[1:])
    blob = np.frombuffer(b"".join(enc), dtype=np.uint8).copy()
    return blob, offs


def _aligned(off: int) -> int:
    return (off + _ALIGN - 1) // _ALIGN * _ALIGN


def _describe_entries(entries, put_array, put_block) -> list[dict]:
    """The header's ``entries`` list for manifest ``entries``, shared by
    the one-file and the spanning writer, which differ only in where
    bytes go: every array (an ndarray or a row source) is handed to
    ``put_array(name, v)`` -> its field spec, every other block (an id
    dictionary's blob and offsets, a pickle) to ``put_block(name, arr)``
    -> its name. An ``arrays`` payload is a model object or ``Fields``;
    an id dictionary a dense BiMap, one loaded from a model file (taken
    as stored: no decode) or ``EncodedIds``."""
    out: list[dict] = []
    for i, (kind, payload) in enumerate(entries):
        if kind == "arrays":
            if isinstance(payload, Fields):
                cls_path, values = list(payload.cls), payload.values
            else:
                cls = type(payload)
                cls_path = [cls.__module__, cls.__qualname__]
                values = {f.name: getattr(payload, f.name)
                          for f in dataclasses.fields(payload)}
            fields: dict[str, dict] = {}
            for fname, v in values.items():
                if isinstance(v, np.ndarray) or _is_rows(v):
                    fields[fname] = put_array(f"e{i}.{fname}", v)
                elif isinstance(v, (BiMap, EncodedIds)):
                    if isinstance(v, _LazyDenseBiMap):
                        v = EncodedIds(v._blob, v._offs)
                    elif isinstance(v, BiMap):
                        ids = _dense_ids(v)
                        if ids is None:
                            raise ModelFileError(
                                f"entry {i} field {fname}: BiMap is not dense"
                            )
                        v = EncodedIds(*_encode_ids(ids))
                    fields[fname] = {
                        "t": "bimap",
                        "blob": put_block(f"e{i}.{fname}.blob", v.blob),
                        "offs": put_block(f"e{i}.{fname}.offs", v.offs),
                    }
                elif v is None:
                    fields[fname] = {"t": "none"}
                else:
                    fields[fname] = {"t": "json", "v": v}
            out.append({"kind": "arrays", "cls": cls_path, "fields": fields})
        elif kind == "pickle":
            blob = np.frombuffer(payload, dtype=np.uint8)
            out.append({"kind": "pickle", "block": put_block(f"e{i}.pickle", blob)})
        elif kind == "persistent":
            out.append({"kind": "persistent", "cls": list(payload)})
        elif kind == "retrain":
            out.append({"kind": "retrain"})
        else:
            raise ModelFileError(f"unknown entry kind {kind!r}")
    return out


def serialize(entries: list[tuple[str, Any]], model_id: str) -> bytes:
    """Encode manifest entries to the flat format.

    ``entries`` is a list of ``(kind, payload)``: ``("arrays", model)``
    with ``can_encode(model)`` true, ``("pickle", bytes)``,
    ``("persistent", (module, qualname))``, or ``("retrain", None)``.
    """
    arrays: list[tuple[str, np.ndarray]] = []

    def _block(name: str, arr: np.ndarray) -> str:
        arrays.append((name, np.ascontiguousarray(arr)))
        return name

    header_entries = _describe_entries(
        entries,
        lambda name, v: {
            "t": "array", "block": _block(name, np.asarray(v)),
            "shape": list(v.shape),
        },
        _block,
    )

    header: dict = {
        "version": VERSION,
        "model_id": model_id,
        "entries": header_entries,
        "blocks": {},
    }
    offset = 0
    layout: list[tuple[str, np.ndarray, int]] = []
    for name, arr in arrays:
        offset = _aligned(offset)
        layout.append((name, arr, offset))
        offset += arr.nbytes
    for name, arr, off in layout:
        header["blocks"][name] = {
            "dtype": _dtype_tag(arr.dtype),
            "count": int(arr.size),
            "offset": off,  # relative; absolute = payload_base + offset
            "crc32": zlib.crc32(arr.tobytes()) & 0xFFFFFFFF,
        }
    hdr = json.dumps(header, sort_keys=True).encode("utf-8")
    payload_base = _aligned(_HDR_FIXED + len(hdr))
    buf = io.BytesIO()
    buf.write(MAGIC)
    buf.write(len(hdr).to_bytes(8, "little"))
    buf.write((zlib.crc32(hdr) & 0xFFFFFFFF).to_bytes(4, "little"))
    buf.write(hdr)
    for name, arr, off in layout:
        buf.seek(payload_base + off)
        buf.write(arr.tobytes())
    # pad to the full payload extent so truncation checks are exact even
    # when the last block ends short of a page
    end = payload_base + offset
    if buf.tell() < end:
        buf.seek(end - 1)
        buf.write(b"\0")
    return buf.getvalue()


# --------------------------------------------------------------------------
# models that span files
# --------------------------------------------------------------------------


class Fields(NamedTuple):
    """An ``arrays`` payload given as its parts instead of as a model
    object: the class to rebuild and its field values. For writers
    whose values are not the model's own types — a row source in an
    array's place, ``EncodedIds`` in a BiMap's."""

    cls: tuple[str, str]  # (module, qualname)
    values: dict[str, Any]


class EncodedIds(NamedTuple):
    """A dense id dictionary already in its stored form (``_encode_ids``):
    the utf-8 blob and the [n+1] int64 offsets."""

    blob: np.ndarray
    offs: np.ndarray


def _is_rows(v: Any) -> bool:
    """An array's stand-in: ``shape``, ``dtype`` and ``rows(lo, hi)`` ->
    that range of leading-axis rows as an ndarray (``SpannedArray`` is
    one; a writer may bring its own, made block by block)."""
    return (not isinstance(v, np.ndarray) and hasattr(v, "shape")
            and hasattr(v, "dtype") and callable(getattr(v, "rows", None)))


class SpannedArray:
    """A read-only [rows, ...] array whose rows lie in several mmap'd
    blocks (one a segment). Looks like an ndarray where the templates
    look — ``shape`` / ``dtype`` / ``len`` / row indexing by int, slice
    or index array — and is never concatenated unless a caller asks
    (``np.asarray``: small models and tests). ``rows(lo, hi)`` is what
    a loader stages a shard from: a view where the range lies in one
    part, else a copy of just that range."""

    def __init__(self, parts: list[np.ndarray], shape):
        self.parts = parts
        self.shape = tuple(int(n) for n in shape)
        self.dtype = parts[0].dtype
        self.ndim = len(self.shape)
        self.bounds = np.cumsum([0] + [len(p) for p in parts])
        if int(self.bounds[-1]) != self.shape[0]:
            raise ModelFileError(
                f"parts hold {int(self.bounds[-1])} rows, shape says "
                f"{self.shape[0]}"
            )

    @property
    def nbytes(self) -> int:
        return int(np.prod(self.shape)) * self.dtype.itemsize

    def __len__(self) -> int:
        return self.shape[0]

    def rows(self, lo: int, hi: int) -> np.ndarray:
        lo, hi = max(0, int(lo)), min(int(hi), self.shape[0])
        first = int(np.searchsorted(self.bounds, lo, side="right")) - 1
        pieces = []
        for j in range(first, len(self.parts)):
            a, b = int(self.bounds[j]), int(self.bounds[j + 1])
            if a >= hi:
                break
            pieces.append(self.parts[j][max(lo, a) - a: min(hi, b) - a])
        if len(pieces) == 1:
            return pieces[0]
        if not pieces:
            return np.empty((0, *self.shape[1:]), self.dtype)
        return np.concatenate(pieces)

    def __getitem__(self, ix):
        if isinstance(ix, slice):
            lo, hi, step = ix.indices(self.shape[0])
            return self.rows(lo, hi)[::step] if step > 0 else np.asarray(self)[ix]
        ix = np.asarray(ix)
        if ix.dtype == bool:
            ix = np.flatnonzero(ix)
        flat = np.where(ix < 0, ix + self.shape[0], ix).reshape(-1)
        part = np.searchsorted(self.bounds, flat, side="right") - 1
        out = np.empty((len(flat), *self.shape[1:]), self.dtype)
        for j in np.unique(part):
            sel = part == j
            out[sel] = self.parts[j][flat[sel] - int(self.bounds[j])]
        return out.reshape(*ix.shape, *self.shape[1:])

    def __array__(self, dtype=None, copy=None):
        a = np.concatenate(self.parts) if len(self.parts) > 1 else self.parts[0]
        return a if dtype is None else a.astype(dtype, copy=False)


class _Part(NamedTuple):
    """One block of a spanning model as planned: where it goes and which
    rows of which value it holds."""

    name: str
    value: Any  # ndarray or row source
    lo: int
    hi: int
    dtype: np.dtype
    nbytes: int
    segment: int
    offset: int


def segment_name(head: str | os.PathLike, j: int) -> str:
    return f"{os.path.basename(os.fspath(head))}.seg{j:04d}"


def write_spanning(head_path: str | os.PathLike,
                   entries: list[tuple[str, Any]], model_id: str, *,
                   segment_bytes: int | None = None,
                   workers: int | None = None) -> dict:
    """Write ``entries`` (as ``serialize`` takes them; an ``arrays``
    payload may also be ``Fields``, its arrays row sources and its
    BiMaps ``EncodedIds``) as a model that spans files: segments of at
    most ``segment_bytes`` (``SEGMENT_BYTES``) beside ``head_path``, then
    the head. Every
    array is cut row-wise into blocks no larger than a segment; a block
    is produced (``rows(lo, hi)``), checksummed and written at its place
    by one of ``workers`` threads, so the segments fill side by side and
    no more than ``workers`` blocks are in memory at once. Segments and
    head are written under temporary names, synced, and renamed — the
    head last, so a reader never finds a head whose segments are not
    there. Returns
    ``{"segments": n, "bytes": total, "seconds": {"fill", "sync"}}``: the
    blocks produced and written, then the syncs, renames and the head."""
    from concurrent.futures import ThreadPoolExecutor

    head_path = os.fspath(head_path)
    segment_bytes = int(segment_bytes or SEGMENT_BYTES)
    where = os.path.dirname(os.path.abspath(head_path))
    parts: list[_Part] = []
    seg_sizes: list[int] = []

    def place(name: str, value, lo: int, hi: int, dtype, nbytes: int):
        if nbytes > segment_bytes:
            raise ModelFileError(  # one row of an array, or an id dictionary
                f"block {name} of {nbytes} bytes exceeds a segment of "
                f"{segment_bytes}"
            )
        if not seg_sizes or _aligned(seg_sizes[-1]) + nbytes > segment_bytes:
            seg_sizes.append(0)
        off = _aligned(seg_sizes[-1])
        seg_sizes[-1] = off + nbytes
        parts.append(_Part(name, value, lo, hi, np.dtype(dtype), nbytes,
                           len(seg_sizes) - 1, off))
        return name

    def array_field(name: str, v) -> dict:
        shape = tuple(int(n) for n in v.shape)
        dt = np.dtype(v.dtype)
        row = int(np.prod(shape[1:], dtype=np.int64)) * dt.itemsize if shape else dt.itemsize
        n = shape[0] if shape else 1
        per = max(1, segment_bytes // max(1, row))
        if n <= per:
            block = place(name, v, 0, n, dt, n * row)
            return {"t": "array", "block": block, "shape": list(shape)}
        blocks = [
            place(f"{name}.p{j}", v, lo, min(lo + per, n), dt,
                  (min(lo + per, n) - lo) * row)
            for j, lo in enumerate(range(0, n, per))
        ]
        return {"t": "array", "blocks": blocks, "shape": list(shape)}

    header_entries = _describe_entries(
        entries, array_field,
        lambda name, a: place(name, a, 0, len(a), a.dtype, a.nbytes),
    )
    names = [segment_name(head_path, j) for j in range(len(seg_sizes))]
    tmp = [os.path.join(where, f"{n}.tmp.{os.getpid()}") for n in names]
    fds = [os.open(t, os.O_CREAT | os.O_TRUNC | os.O_WRONLY, 0o644) for t in tmp]
    crcs: dict[str, int] = {}

    def write(p: _Part) -> None:
        v = p.value
        a = v.rows(p.lo, p.hi) if _is_rows(v) else (v[p.lo:p.hi] if v.ndim else v)
        a = np.ascontiguousarray(a, dtype=p.dtype)
        if a.nbytes != p.nbytes:
            raise ModelFileError(
                f"block {p.name}: rows [{p.lo}, {p.hi}) came as {a.nbytes} "
                f"bytes, planned {p.nbytes}"
            )
        buf = memoryview(a.reshape(-1)).cast("B")
        crcs[p.name] = zlib.crc32(buf) & 0xFFFFFFFF
        done = 0
        while done < len(buf):  # pwrite caps at 2 GiB - 4 KiB a call
            done += os.pwrite(fds[p.segment], buf[done:], p.offset + done)

    t_start = time.perf_counter()
    try:
        for fd, size in zip(fds, seg_sizes):
            os.ftruncate(fd, size)
        with ThreadPoolExecutor(max_workers=workers or min(8, os.cpu_count() or 1)) as pool:
            list(pool.map(write, parts))
        t_filled = time.perf_counter()
        for fd in fds:
            faults.fault_point("storage.fsync")
            os.fsync(fd)
    except BaseException:
        for fd, t in zip(fds, tmp):
            os.close(fd)
            try:
                os.unlink(t)
            except OSError:
                pass
        raise
    for fd in fds:
        os.close(fd)
    header = {
        "version": SPAN_VERSION,
        "model_id": model_id,
        "entries": header_entries,
        "segments": [{"file": n, "bytes": b} for n, b in zip(names, seg_sizes)],
        "blocks": {
            p.name: {
                "dtype": _dtype_tag(p.dtype),
                "count": p.nbytes // p.dtype.itemsize,
                "segment": p.segment,
                "offset": p.offset,
                "crc32": crcs[p.name],
            }
            for p in parts
        },
    }
    hdr = json.dumps(header, sort_keys=True).encode("utf-8")
    faults.fault_point("storage.rename")
    for t, n in zip(tmp, names):
        os.replace(t, os.path.join(where, n))
    head_tmp = f"{head_path}.tmp.{os.getpid()}"
    with open(head_tmp, "wb") as f:
        f.write(MAGIC)
        f.write(len(hdr).to_bytes(8, "little"))
        f.write((zlib.crc32(hdr) & 0xFFFFFFFF).to_bytes(4, "little"))
        f.write(hdr)
        f.flush()
        os.fsync(f.fileno())
    os.replace(head_tmp, head_path)
    return {
        "segments": len(names),
        "bytes": sum(seg_sizes) + _HDR_FIXED + len(hdr),
        "seconds": {"fill": t_filled - t_start,
                    "sync": time.perf_counter() - t_filled},
    }


def spans(model: Any) -> bool:
    """Would ``serialize`` make of this model one file over a segment's
    size (``SEGMENT_BYTES``)? Then it is written spanning."""
    if not dataclasses.is_dataclass(model) or isinstance(model, type):
        return False
    total = 0
    for f in dataclasses.fields(model):
        total += int(getattr(getattr(model, f.name), "nbytes", 0) or 0)
    return total > SEGMENT_BYTES


# --------------------------------------------------------------------------
# decode
# --------------------------------------------------------------------------


def _parse_header(buf, where: str | None = None) -> tuple[dict, int]:
    """Validate magic / length / crc and return (header, payload_base).
    ``buf`` is any buffer (mmap or bytes); ``where`` the directory of a
    spanning head's segments."""
    total = len(buf)
    if total < _HDR_FIXED or bytes(buf[: len(MAGIC)]) != MAGIC:
        raise ModelFileError("bad magic: not a model file")
    hlen = int.from_bytes(buf[len(MAGIC): len(MAGIC) + 8], "little")
    if hlen <= 0 or _HDR_FIXED + hlen > total:
        raise ModelFileError(f"header length {hlen} out of bounds ({total})")
    hcrc = int.from_bytes(buf[len(MAGIC) + 8: _HDR_FIXED], "little")
    hdr_bytes = bytes(buf[_HDR_FIXED: _HDR_FIXED + hlen])
    if (zlib.crc32(hdr_bytes) & 0xFFFFFFFF) != hcrc:
        raise ModelFileError("header checksum mismatch")
    try:
        header = json.loads(hdr_bytes)
    except ValueError as e:
        raise ModelFileError(f"header is not JSON: {e}") from e
    if header.get("version") not in (VERSION, SPAN_VERSION):
        raise ModelFileError(f"unsupported version {header.get('version')!r}")
    payload_base = _aligned(_HDR_FIXED + hlen)
    segments = header.get("segments", [])
    if segments and where is None:
        raise ModelFileError(
            "a model that spans files loads from its head file's path, "
            "not from bytes"
        )
    sizes = []
    for seg in segments:
        try:
            sizes.append(os.path.getsize(os.path.join(where, seg["file"])))
        except OSError as e:
            raise ModelFileError(f"segment {seg['file']} missing: {e}") from e
    for name, spec in header.get("blocks", {}).items():
        dt = _tag_dtype(spec["dtype"])
        j = spec.get("segment")
        base, size = (payload_base, total) if j is None else (0, sizes[j])
        end = base + spec["offset"] + spec["count"] * dt.itemsize
        if spec["offset"] < 0 or end > size:
            raise ModelFileError(
                f"block {name} [{end} bytes] exceeds file size {size}: "
                "truncated model file"
            )
    return header, payload_base


def _verify_blocks() -> bool:
    return os.environ.get("PIO_MODEL_VERIFY", "").strip() == "1"


# -- an encoded dictionary's id -> index look-ups ---------------------------
#
# An id's hash folds its bytes a little-endian 8-byte word at a time, the
# LAST word (zero-filled) first: h = (h ^ word) * _HASH_MUL mod 2^64. From
# the end, the zero words past a short id's end leave h at 0, so ids of
# every length fold in the same passes with no length test. The bulk side
# (`_hash_ids`) gathers a word of every id of a chunk per pass; a look-up
# reads the key's own bytes (`_hash_key` one key, `_find_all` a list's as
# one matrix); the tests hold the three equal bit for bit.

_HASH_MUL = np.uint64(0x9E3779B97F4A7C15)  # odd: a word -> its product is one-to-one
_HASH_CHUNK = 1 << 18  # ids hashed at a time: every array of a pass stays in cache
_VECTOR_FROM = 32  # keys from which one NumPy pass beats a Python loop a key (3 us a key against ~60 + 0.5 a key)
_VECTOR_WIDTH = 64  # ... while the list's longest key is no longer than this
_VECTOR_MOST = 1 << 16  # keys a pass: a warm start's million ids go a part at a time
_BUCKET_BITS = 3  # the directory has a bucket for about every 2^3 ids
_BUCKET_MOST = 64  # a list's pass scans whole buckets while none holds more than this
_U64 = (1 << 64) - 1
_WORD_MASKS = np.array([(1 << (8 * k)) - 1 for k in range(9)], np.uint64)


def _hash_key(raw: bytes) -> int:
    mul, h = int(_HASH_MUL), 0
    for r in range(max(len(raw) - 1, 0) // 8 * 8, -1, -8):
        h = ((h ^ int.from_bytes(raw[r: r + 8], "little")) * mul) & _U64
    return h


def _hash_ids(blob: np.ndarray, offs: np.ndarray, chunk: int = _HASH_CHUNK) -> np.ndarray:
    """[n] uint64 hashes of the ids of an encoded dictionary, made from
    the blob ``chunk`` ids at a time: no id becomes a Python string, and
    no pass streams an n-wide array through main memory."""
    n = len(offs) - 1
    out = np.empty(n, np.uint64)
    for a in range(0, n, chunk):
        o = np.asarray(offs[a: a + chunk + 1], np.int64)
        starts, lens, size = o[:-1] - o[0], np.diff(o), int(o[-1] - o[0])
        part = np.zeros(size + 8, np.uint8)  # a last word may read past the last id
        part[:size] = blob[o[0]: o[-1]]
        # the word that starts at every byte: 8-byte reads a byte apart
        words = np.lib.stride_tricks.as_strided(part[:8].view("<u8"), (size + 1,), (1,))
        h = out[a: a + chunk]
        h[:] = 0
        for r in range(max(int(lens.max()) - 1, 0) // 8 * 8, -1, -8):
            word = words[np.minimum(starts + r, size)]
            word &= _WORD_MASKS[np.clip(lens - r, 0, 8)]  # nothing left of a shorter id: 0
            h ^= word
            h *= _HASH_MUL
    return out


class _IdIndex(NamedTuple):
    """What a look-up searches: one sorted uint64 word an id, the hash's
    high ``64 - shift`` bits over the id's index in the low ``shift`` (so
    ONE in-place sort orders hashes and indices together, where an argsort
    of 48 M hashes and the gather after it took four times the hashing).
    ``first[b]`` is the position of the first word whose top ``bits`` bits
    are ``b`` or more — the words are hashes, so evenly spread: a search
    reads its bucket (``most`` words at most) where a binary search over
    all the words would miss the cache at every level. ``cells`` /
    ``firsts`` / ``starts`` / ``raw`` are the words, the directory, the
    offsets and the blob again as memoryviews: a single key's search
    stays Python ints."""

    keys: np.ndarray
    shift: int
    bits: int
    first: np.ndarray
    most: int
    cells: memoryview
    firsts: memoryview
    starts: memoryview
    raw: memoryview


_id_metrics = None  # lazy, as the counters below: (hashed, decoded, builds, decodes)


def _id_counters():
    global _id_metrics
    if _id_metrics is None:
        from predictionio_tpu.obs import metrics as obs_metrics

        looked = "keys looked up in a model file's id dictionary, by what answered"
        _id_metrics = (
            obs_metrics.counter("pio_model_id_lookups_total", looked, path="hashed"),
            obs_metrics.counter("pio_model_id_lookups_total", looked, path="decoded"),
            obs_metrics.histogram(
                "pio_model_id_index_build_seconds",
                "hashing and sorting one encoded id dictionary for look-ups",
            ),
            obs_metrics.counter(
                "pio_model_id_decodes_total",
                "encoded id dictionaries decoded whole into Python strings",
            ),
        )
    return _id_metrics


def id_stats_block() -> dict:
    """``/stats.json``'s ``model_ids``: the id look-ups of this process's
    model files, by what answered them."""
    hashed, decoded, builds, decodes = _id_counters()
    _, seconds, built = builds.merged()
    return {
        "lookups": {"hashed": hashed.value(), "decoded": decoded.value()},
        "index_builds": built,
        "index_build_seconds": round(seconds, 3),
        "decodes": decodes.value(),
    }


class _LazyDenseBiMap(BiMap):
    """A BiMap over an encoded dense id dictionary that no point look-up
    decodes: ``[]`` / ``get`` / ``in`` / ``index_of`` hash the key, search
    the ids' sorted hashes (built from the blob at the first look-up, a
    chunk at a time) and compare the bytes of the ids that share the
    hash. A million-id index costs two array views at load, 9 bytes an id
    once something is looked up (a word an id and the directory over the
    words), and no Python string ever. Only a caller
    that WALKS the mapping (``items``, iteration, ``to_dict``, ``==``,
    pickling, ``_Appended._m``) decodes the dictionary — counted, and
    from then on the dictionary answers.

    Never calls ``BiMap.__init__``; ``_m``/``_inverse`` are materializing
    properties shadowing the base class's instance attributes, so every
    inherited accessor works unchanged once touched. The inverse
    (index -> id) decodes nothing either: ``_OffsetInverse`` reads one id
    at a time from the blob and its offsets."""

    def __init__(self, blob: np.ndarray, offs: np.ndarray):
        self._blob = blob
        self._offs = offs
        self._fwd: dict | None = None
        self._inv: BiMap | None = None
        self._index: _IdIndex | None = None
        self._index_lock = threading.Lock()  # the speed layer's thread and the batch worker may both ask first

    def _ids(self) -> list[str]:
        raw = self._blob.tobytes()
        offs = self._offs
        return [
            raw[offs[j]: offs[j + 1]].decode("utf-8")
            for j in range(len(offs) - 1)
        ]

    @property
    def _m(self) -> dict:
        if self._fwd is None:
            with self._index_lock:
                if self._fwd is None:
                    self._fwd = {k: i for i, k in enumerate(self._ids())}
                    _id_counters()[3].inc()
        return self._fwd

    @property
    def _inverse(self) -> BiMap:
        if self._inv is None:
            self._inv = _OffsetInverse(self)
        return self._inv

    def id_at(self, i: int) -> str:
        """The id of dense index ``i``, read from the blob and its
        offsets: an answer needs k ids, not the dictionary."""
        if not 0 <= i < len(self._offs) - 1:
            raise KeyError(i)
        lo, hi = self._offs[i], self._offs[i + 1]
        return self._blob[lo:hi].tobytes().decode("utf-8")

    def __len__(self) -> int:  # cheap without decoding
        return len(self._offs) - 1

    def _hashed(self) -> _IdIndex:
        """The sorted hash words of the ids and the directory over them,
        built once (two threads asking at once wait for one build)."""
        if self._index is None:
            with self._index_lock:
                if self._index is None:
                    t0 = time.perf_counter()
                    n = len(self)
                    shift = max(n - 1, 0).bit_length()
                    blob = self._blob = np.asarray(self._blob).view(np.uint8)
                    keys = _hash_ids(blob, self._offs)
                    keys >>= np.uint64(shift)
                    keys <<= np.uint64(shift)
                    for a in range(0, n, _HASH_CHUNK):
                        keys[a: a + _HASH_CHUNK] |= np.arange(
                            a, min(a + _HASH_CHUNK, n), dtype=np.uint64
                        )
                    keys.sort()
                    bits = max(n.bit_length() - _BUCKET_BITS, 1)
                    first = np.empty((1 << bits) + 1, np.int64)
                    first[:-1] = keys.searchsorted(
                        np.arange(1 << bits, dtype=np.uint64) << np.uint64(64 - bits)
                    )
                    first[-1] = n
                    self._index = _IdIndex(
                        keys, shift, bits, first, int(np.diff(first).max()),
                        memoryview(keys), memoryview(first),
                        memoryview(self._offs), memoryview(blob),
                    )
                    took = time.perf_counter() - t0
                    _id_counters()[2].observe(took)
                    if took >= 1.0:
                        logger.info("id index: %d ids hashed and sorted in %.2f s", n, took)
        return self._index

    def _find(self, key) -> int:
        """The index of the id ``key``, or -1 (not held, or not a
        string): one key, Python ints all the way."""
        if not isinstance(key, str):
            return -1
        raw = key.encode("utf-8")
        ix = self._hashed()
        cells, starts, low = ix.cells, ix.starts, (1 << ix.shift) - 1
        word = _hash_key(raw) & ~low
        b = word >> 64 - ix.bits
        at = bisect.bisect_left(cells, word, ix.firsts[b], ix.firsts[b + 1])
        while at < len(cells) and cells[at] & ~low == word:  # equal hashes: compare the bytes
            i = cells[at] & low
            if ix.raw[starts[i]: starts[i + 1]] == raw:
                return i
            at += 1
        return -1

    def _find_all(self, keys: list[str]) -> np.ndarray | None:
        """``_find`` of every key of a list in one NumPy pass ([n] int64),
        for keys that are their own bytes (ASCII) and none too long for a
        matrix of the list: None for any other list."""
        n, width = len(keys), max(map(len, keys))
        if not 0 < width <= _VECTOR_WIDTH or not len(self._blob):
            return None
        span = -(-width // 8) * 8
        flat = "".join([k.ljust(span, "\0") for k in keys])
        if not flat.isascii():
            return None
        mat = np.frombuffer(flat.encode("ascii"), np.uint8).reshape(n, span)
        words = mat.view("<u8")  # [n, span / 8]: a key's words, zero-filled
        h = np.zeros(n, np.uint64)
        for r in reversed(range(span // 8)):
            h ^= words[:, r]
            h *= _HASH_MUL
        index = self._hashed()
        low, last = np.uint64((1 << index.shift) - 1), len(self) - 1
        want = h & ~low
        if index.most <= _BUCKET_MOST:  # the first word >= want lies in want's bucket, or opens the next
            at = index.first[(want >> np.uint64(64 - index.bits)).astype(np.int64)]
            bucket = index.keys[np.minimum(at[:, None] + np.arange(index.most), last)]
            at += (bucket < want[:, None]).sum(axis=1)
        else:  # hashes that crowd one bucket (ids made to collide): search all the words
            at = index.keys.searchsorted(want)
        cell = index.keys[np.minimum(at, last)]
        same = cell & ~low == want
        found = (cell & low).astype(np.int64)
        lens = np.fromiter(map(len, keys), np.int64, n)
        lo = np.asarray(self._offs[found], np.int64)
        at = np.arange(span)
        got = self._blob[np.minimum(lo[:, None] + at, len(self._blob) - 1)]
        hit = (
            same & (self._offs[found + 1] - lo == lens)
            & ((got == mat) | (at >= lens[:, None])).all(axis=1)
        )
        found[~hit] = -1
        for j in np.flatnonzero(same & ~hit).tolist():  # a hash's first id was another's: walk its ids (rare)
            found[j] = self._find(keys[j])
        return found

    def index_of(self, keys) -> np.ndarray:
        if self._fwd is not None:  # walked already: the dictionary answers
            _id_counters()[1].inc(len(keys))
            return super().index_of(keys)
        _id_counters()[0].inc(len(keys))
        if len(keys) <= _VECTOR_MOST:
            return self._found(keys)
        return np.concatenate([
            self._found(keys[a: a + _VECTOR_MOST])
            for a in range(0, len(keys), _VECTOR_MOST)
        ])

    def _found(self, keys) -> np.ndarray:
        """``index_of`` of at most ``_VECTOR_MOST`` keys."""
        if len(keys) >= _VECTOR_FROM and set(map(type, keys)) == {str}:
            found = self._find_all(keys)
            if found is not None:
                return found
        return np.fromiter(map(self._find, keys), np.int64, len(keys))

    def get(self, key, default=None):
        if self._fwd is not None:
            _id_counters()[1].inc()
            return self._fwd.get(key, default)
        _id_counters()[0].inc()
        i = self._find(key)
        return default if i < 0 else i

    def __getitem__(self, key):
        i = self.get(key, -1)
        if i == -1:
            raise KeyError(key)
        return i

    def __contains__(self, key) -> bool:
        return self.get(key, -1) != -1

    def __reduce__(self):
        # pickle as a plain BiMap: the mmap-backed views must not leak
        # into a pickle stream that outlives the mapping
        return (BiMap, (self._m,))


class _OffsetInverse(BiMap):
    """``_LazyDenseBiMap.inverse``: index -> id, answered from the blob
    and offsets one id at a time. Serving reads k ids an answer through
    ``[]`` / ``get``; only a caller that walks the whole mapping
    (``items``, iteration, equality) pays the decode of every id — at
    48 M ids that is minutes and gigabytes, so no serving path does."""

    def __init__(self, forward: "_LazyDenseBiMap"):
        self._forward = forward
        self._all: dict | None = None

    @property
    def _m(self) -> dict:
        if self._all is None:
            self._all = {i: k for k, i in self._forward._m.items()}
        return self._all

    @property
    def _inverse(self) -> BiMap:
        return self._forward

    @staticmethod
    def _is_index(i) -> bool:
        return isinstance(i, (int, np.integer)) and not isinstance(i, bool)

    def __getitem__(self, i):
        if self._all is not None:
            return self._all[i]
        if not self._is_index(i):
            raise KeyError(i)
        return self._forward.id_at(int(i))

    def get(self, i, default=None):
        try:
            return self[i]
        except KeyError:
            return default

    def __contains__(self, i) -> bool:
        return self._is_index(i) and 0 <= i < len(self)

    def __len__(self) -> int:
        return len(self._forward)


class ModelFile:
    """A parsed model file over an mmap (or bytes) buffer. Arrays are
    read-only zero-copy views; the buffer must outlive them (the loader
    caches keep a reference)."""

    def __init__(self, buf, *, source: str = "<bytes>"):
        self._buf = buf
        self._source = source
        self._where = (
            os.path.dirname(os.path.abspath(source))
            if source != "<bytes>" else None
        )
        self._header, self._base = _parse_header(buf, self._where)
        self._segments: dict[int, Any] = {}  # mapped on first use
        self._seg_lock = threading.Lock()
        if _verify_blocks():
            self._verify()

    @property
    def segments(self) -> list[dict]:
        """The segment files of a spanning model ([] for one file)."""
        return list(self._header.get("segments", []))

    def _segment(self, j: int):
        with self._seg_lock:
            mm = self._segments.get(j)
            if mm is None:
                path = os.path.join(self._where, self._header["segments"][j]["file"])
                with open(path, "rb") as f:
                    mm = mmap.mmap(f.fileno(), 0, access=mmap.ACCESS_READ)
                self._segments[j] = mm
                _count_segment()
            return mm

    @property
    def model_id(self) -> str:
        return self._header.get("model_id", "")

    def _arr(self, name: str) -> np.ndarray:
        spec = self._header["blocks"][name]
        j = spec.get("segment")
        return np.frombuffer(
            self._buf if j is None else self._segment(j),
            dtype=_tag_dtype(spec["dtype"]),
            count=spec["count"],
            offset=spec["offset"] + (self._base if j is None else 0),
        )

    def _verify(self) -> None:
        for name, spec in self._header["blocks"].items():
            got = zlib.crc32(self._arr(name).tobytes()) & 0xFFFFFFFF
            if got != spec["crc32"]:
                raise ModelFileError(
                    f"block {name} checksum mismatch in {self._source}"
                )

    def _decode_bimap(self, fs: dict) -> BiMap:
        return _LazyDenseBiMap(self._arr(fs["blob"]), self._arr(fs["offs"]))

    def fields(self, i: int) -> dict[str, Any]:
        """The decoded fields of ``arrays`` entry ``i`` — arrays as
        read-only views of this buffer, BiMaps lazy — WITHOUT importing
        the model's class: what a reader that only wants the numbers
        (chip_smoke.py's NumPy reference) needs."""
        ent = self._header["entries"][i]
        if ent["kind"] != "arrays":
            raise ModelFileError(f"entry {i} is {ent['kind']!r}, not arrays")
        out: dict[str, Any] = {}
        for fname, fs in ent["fields"].items():
            t = fs["t"]
            if t == "array" and "blocks" in fs:  # cut row-wise over segments
                shape = fs["shape"]
                out[fname] = SpannedArray(
                    [self._arr(b).reshape(-1, *shape[1:]) for b in fs["blocks"]],
                    shape,
                )
            elif t == "array":
                a = self._arr(fs["block"])
                shape = fs.get("shape")
                if shape is not None:
                    a = a.reshape(shape)
                out[fname] = a
            elif t == "bimap":
                out[fname] = self._decode_bimap(fs)
            elif t == "none":
                out[fname] = None
            elif t == "json":
                out[fname] = fs["v"]
            else:
                raise ModelFileError(
                    f"entry {i} field {fname}: unknown type {t!r}"
                )
        return out

    def entries(self) -> list[tuple[str, Any]]:
        """Decode to persistence-manifest shape: ``(kind, payload)`` with
        ``arrays`` payloads reconstructed as model objects whose array
        fields view this buffer."""
        out: list[tuple[str, Any]] = []
        for i, ent in enumerate(self._header["entries"]):
            kind = ent["kind"]
            if kind == "arrays":
                mod_name, qual = ent["cls"]
                try:
                    cls = importlib.import_module(mod_name)
                    for part in qual.split("."):
                        cls = getattr(cls, part)
                except (ImportError, AttributeError) as e:
                    raise ModelFileError(
                        f"entry {i}: cannot resolve {mod_name}.{qual}: {e}"
                    ) from e
                if not (isinstance(cls, type) and dataclasses.is_dataclass(cls)):
                    raise ModelFileError(
                        f"entry {i}: {mod_name}.{qual} is not a model dataclass"
                    )
                try:
                    out.append(("arrays", cls(**self.fields(i))))
                except TypeError as e:
                    raise ModelFileError(
                        f"entry {i}: {qual}(**fields) failed: {e}"
                    ) from e
            elif kind == "pickle":
                out.append(("pickle", self._arr(ent["block"]).tobytes()))
            elif kind == "persistent":
                out.append(("persistent", tuple(ent["cls"])))
            elif kind == "retrain":
                out.append(("retrain", None))
            else:
                raise ModelFileError(f"entry {i}: unknown kind {kind!r}")
        return out


def deserialize(blob: bytes) -> list[tuple[str, Any]]:
    """Decode an in-memory model-file blob (still zero-copy over the
    bytes object for array fields)."""
    return ModelFile(blob).entries()


# --------------------------------------------------------------------------
# mmap loading + process-wide sharing
# --------------------------------------------------------------------------

_m_fallback = None  # lazy: obs counter for mmap -> bytes fallbacks


_m_segments = None  # lazy, as above: segment files mapped


def _count_segment() -> None:
    global _m_segments
    if _m_segments is None:
        from predictionio_tpu.obs import metrics as obs_metrics

        _m_segments = obs_metrics.counter(
            "pio_model_segments_total",
            "segment files of models that span files mapped by this process",
        )
    _m_segments.inc()


def _count_fallback() -> None:
    global _m_fallback
    if _m_fallback is None:
        from predictionio_tpu.obs import metrics as obs_metrics

        _m_fallback = obs_metrics.counter(
            "pio_model_mmap_fallback_total",
            "model file loads that fell back from mmap to a byte read",
        )
    _m_fallback.inc()


def load_path(path: str | os.PathLike) -> ModelFile:
    """mmap a model file read-only and parse it. The ``serve.model_mmap``
    fault point guards the mapping attempt; an OS error there falls back
    to reading the bytes (counted) — same contents, no page sharing.
    Validation failures raise ModelFileError either way."""
    p = Path(path)
    try:
        faults.fault_point("serve.model_mmap")
        with open(p, "rb") as f:
            mm = mmap.mmap(f.fileno(), 0, access=mmap.ACCESS_READ)
        return ModelFile(mm, source=str(p))
    except ModelFileError:
        raise
    except (OSError, ValueError) as e:
        logger.warning("mmap of %s failed (%s); reading bytes", p, e)
        _count_fallback()
        return ModelFile(p.read_bytes(), source=str(p))


# One mapping + one decoded entry list per on-disk file, process-wide:
# N variants mounting the same instance share pages AND Python objects.
_shared_lock = threading.Lock()
_shared: dict[tuple[str, int, int], tuple[ModelFile, list]] = {}
_SHARED_MAX = 8


def shared_entries(path: str | os.PathLike) -> list[tuple[str, Any]]:
    """Decoded entries for ``path``, shared across every caller mapping
    the same (realpath, mtime_ns, size). Bounded FIFO cache — stale
    versions age out once their last server drops them."""
    p = Path(path)
    st = p.stat()
    key = (str(p.resolve()), st.st_mtime_ns, st.st_size)
    with _shared_lock:
        hit = _shared.get(key)
        if hit is not None:
            return hit[1]
    mf = load_path(p)
    entries = mf.entries()
    with _shared_lock:
        hit = _shared.get(key)
        if hit is not None:
            return hit[1]
        _shared[key] = (mf, entries)
        while len(_shared) > _SHARED_MAX:
            _shared.pop(next(iter(_shared)))
    return entries


def _clear_shared() -> None:  # test hook
    with _shared_lock:
        _shared.clear()
