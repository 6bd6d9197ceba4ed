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

``shared_entries(path)`` is the serving-side entry point: a process-wide
cache keyed by the file's identity ``(realpath, mtime_ns, size)`` so N
variants mounting the same instance share ONE mapping and ONE resolved
model object — the marginal RSS of tenant N+1 is bookkeeping, not factors.
"""

from __future__ import annotations

import dataclasses
import importlib
import io
import json
import logging
import mmap
import os
import threading
import zlib
from pathlib import Path
from typing import Any

import numpy as np

from predictionio_tpu import faults
from predictionio_tpu.data.bimap import BiMap

logger = logging.getLogger(__name__)

MAGIC = b"PIOMODF1"
VERSION = 1
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
        if isinstance(v, np.ndarray):
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


def serialize(entries: list[tuple[str, Any]], model_id: str) -> bytes:
    """Encode manifest entries to the flat format.

    ``entries`` is a list of ``(kind, payload)``: ``("arrays", model)``
    with ``can_encode(model)`` true, ``("pickle", bytes)``,
    ``("persistent", (module, qualname))``, or ``("retrain", None)``.
    """
    arrays: list[tuple[str, np.ndarray]] = []
    header_entries: list[dict] = []

    def _block(name: str, arr: np.ndarray) -> str:
        arrays.append((name, np.ascontiguousarray(arr)))
        return name

    for i, (kind, payload) in enumerate(entries):
        if kind == "arrays":
            cls = type(payload)
            fields: dict[str, dict] = {}
            for f in dataclasses.fields(payload):
                v = getattr(payload, f.name)
                if isinstance(v, np.ndarray):
                    fields[f.name] = {
                        "t": "array",
                        "block": _block(f"e{i}.{f.name}", v),
                        "shape": list(v.shape),
                    }
                elif isinstance(v, BiMap):
                    ids = _dense_ids(v)
                    if ids is None:
                        raise ModelFileError(
                            f"entry {i} field {f.name}: BiMap is not dense"
                        )
                    blob, offs = _encode_ids(ids)
                    fields[f.name] = {
                        "t": "bimap",
                        "blob": _block(f"e{i}.{f.name}.blob", blob),
                        "offs": _block(f"e{i}.{f.name}.offs", offs),
                    }
                elif v is None:
                    fields[f.name] = {"t": "none"}
                else:
                    fields[f.name] = {"t": "json", "v": v}
            header_entries.append({
                "kind": "arrays",
                "cls": [cls.__module__, cls.__qualname__],
                "fields": fields,
            })
        elif kind == "pickle":
            blob = np.frombuffer(payload, dtype=np.uint8)
            header_entries.append({
                "kind": "pickle", "block": _block(f"e{i}.pickle", blob),
            })
        elif kind == "persistent":
            header_entries.append({"kind": "persistent", "cls": list(payload)})
        elif kind == "retrain":
            header_entries.append({"kind": "retrain"})
        else:
            raise ModelFileError(f"unknown entry kind {kind!r}")

    header: dict = {
        "version": VERSION,
        "model_id": model_id,
        "entries": header_entries,
        "blocks": {},
    }
    offset = 0

    def _aligned(off: int) -> int:
        return (off + _ALIGN - 1) // _ALIGN * _ALIGN

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
# decode
# --------------------------------------------------------------------------


def _parse_header(buf) -> tuple[dict, int]:
    """Validate magic / length / crc and return (header, payload_base).
    ``buf`` is any buffer (mmap or bytes)."""
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
    if header.get("version") != VERSION:
        raise ModelFileError(f"unsupported version {header.get('version')!r}")
    payload_base = (_HDR_FIXED + hlen + _ALIGN - 1) // _ALIGN * _ALIGN
    for name, spec in header.get("blocks", {}).items():
        dt = _tag_dtype(spec["dtype"])
        end = payload_base + spec["offset"] + spec["count"] * dt.itemsize
        if spec["offset"] < 0 or end > total:
            raise ModelFileError(
                f"block {name} [{end} bytes] exceeds file size {total}: "
                "truncated model file"
            )
    return header, payload_base


def _verify_blocks() -> bool:
    return os.environ.get("PIO_MODEL_VERIFY", "").strip() == "1"


class _LazyDenseBiMap(BiMap):
    """A BiMap over an encoded dense id dictionary, decoded on FIRST
    dictionary access instead of at load. Keeps the cold model-file load
    O(pages touched): a million-id index costs two array views at load
    and pays its one-time decode at warmup (or the first query), off the
    deploy critical path — and only once per process, since co-tenant
    mounts share the decoded entries.

    Never calls ``BiMap.__init__``; ``_m``/``_inverse`` are materializing
    properties shadowing the base class's instance attributes, so every
    inherited accessor works unchanged once touched."""

    def __init__(self, blob: np.ndarray, offs: np.ndarray):
        self._blob = blob
        self._offs = offs
        self._fwd: dict | None = None
        self._inv: BiMap | None = None

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
            self._fwd = {k: i for i, k in enumerate(self._ids())}
        return self._fwd

    @property
    def _inverse(self) -> BiMap:
        if self._inv is None:
            # dense by construction: values are exactly 0..n-1
            self._inv = BiMap(
                {i: k for k, i in self._m.items()}, _inverse=self
            )
        return self._inv

    def __len__(self) -> int:  # cheap without decoding
        return len(self._offs) - 1

    def __reduce__(self):
        # pickle as a plain BiMap: the mmap-backed views must not leak
        # into a pickle stream that outlives the mapping
        return (BiMap, (self._m,))


class ModelFile:
    """A parsed model file over an mmap (or bytes) buffer. Arrays are
    read-only zero-copy views; the buffer must outlive them (the loader
    caches keep a reference)."""

    def __init__(self, buf, *, source: str = "<bytes>"):
        self._buf = buf
        self._source = source
        self._header, self._base = _parse_header(buf)
        if _verify_blocks():
            self._verify()

    @property
    def model_id(self) -> str:
        return self._header.get("model_id", "")

    def _arr(self, name: str) -> np.ndarray:
        spec = self._header["blocks"][name]
        a = np.frombuffer(
            self._buf,
            dtype=_tag_dtype(spec["dtype"]),
            count=spec["count"],
            offset=self._base + spec["offset"],
        )
        return a

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
            if t == "array":
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
