"""A vectorised writer of the program's flat model file (models/modelfile.py)
for factor models with dense ids ``<prefix><n>``.

The program's own ``modelfile.serialize`` walks every id in Python: at 29 M
ids that is minutes of set-up in every run. This writes the same bytes
(header, crc32s, 64-byte-aligned blocks) with NumPy; a test holds it
byte-identical to ``modelfile.serialize``, and ``write_model.py`` loads
every file it writes back through the program's loader and falls back to
the program's serializer if the format has moved on."""

from __future__ import annotations

import json
import zlib

import numpy as np


def dense_id_blob(prefix: bytes, n: int) -> tuple[np.ndarray, np.ndarray]:
    """utf-8 blob + [n+1] int64 offsets of the ids prefix0 .. prefix<n-1>."""
    p = len(prefix)
    parts, lens = [], np.empty(n, np.int64)
    d = 1
    lo = 0
    while lo < n:
        hi = min(10 ** d, n)
        vals = np.arange(lo, hi, dtype=np.int64)
        block = np.empty((hi - lo, p + d), np.uint8)
        block[:, :p] = np.frombuffer(prefix, np.uint8)
        for j in range(d):
            block[:, p + d - 1 - j] = 48 + (vals // 10 ** j) % 10
        parts.append(block.reshape(-1))
        lens[lo:hi] = p + d
        lo, d = hi, d + 1
    offs = np.zeros(n + 1, np.int64)
    np.cumsum(lens, out=offs[1:])
    return np.concatenate(parts) if parts else np.zeros(0, np.uint8), offs


def factor_model_blob(fmt, model_id: str, cls: tuple[str, str],
                      user_prefix: bytes, item_prefix: bytes,
                      user_factors: np.ndarray, item_factors: np.ndarray) -> bytearray:
    """The model file of one dense (unquantized) factor model. ``fmt`` is
    the program's modelfile module: MAGIC, VERSION and the alignment are
    taken from it, never copied."""
    align = fmt._ALIGN
    ub, uo = dense_id_blob(user_prefix, len(user_factors))
    ib, io_ = dense_id_blob(item_prefix, len(item_factors))
    arrays = [
        ("e0.user_index.blob", ub), ("e0.user_index.offs", uo),
        ("e0.item_index.blob", ib), ("e0.item_index.offs", io_),
        ("e0.user_factors", np.ascontiguousarray(user_factors)),
        ("e0.item_factors", np.ascontiguousarray(item_factors)),
    ]
    fields = {
        "user_index": {"t": "bimap", "blob": "e0.user_index.blob", "offs": "e0.user_index.offs"},
        "item_index": {"t": "bimap", "blob": "e0.item_index.blob", "offs": "e0.item_index.offs"},
        "user_factors": {"t": "array", "block": "e0.user_factors", "shape": list(user_factors.shape)},
        "item_factors": {"t": "array", "block": "e0.item_factors", "shape": list(item_factors.shape)},
        "user_scales": {"t": "none"}, "item_scales": {"t": "none"},
    }
    header = {"version": fmt.VERSION, "model_id": model_id,
              "entries": [{"kind": "arrays", "cls": list(cls), "fields": fields}],
              "blocks": {}}
    offset = 0
    layout = []
    for name, arr in arrays:
        offset = (offset + align - 1) // align * align
        layout.append((name, arr, offset))
        header["blocks"][name] = {
            "dtype": fmt._dtype_tag(arr.dtype), "count": int(arr.size), "offset": offset,
            "crc32": zlib.crc32(memoryview(arr).cast("B")) & 0xFFFFFFFF,
        }
        offset += arr.nbytes
    hdr = json.dumps(header, sort_keys=True).encode("utf-8")
    fixed = len(fmt.MAGIC) + 8 + 4
    base = (fixed + len(hdr) + align - 1) // align * align
    out = bytearray(base + offset)
    out[:len(fmt.MAGIC)] = fmt.MAGIC
    out[len(fmt.MAGIC):len(fmt.MAGIC) + 8] = len(hdr).to_bytes(8, "little")
    out[len(fmt.MAGIC) + 8:fixed] = (zlib.crc32(hdr) & 0xFFFFFFFF).to_bytes(4, "little")
    out[fixed:fixed + len(hdr)] = hdr
    view = np.frombuffer(out, np.uint8)
    for _, arr, off in layout:
        view[base + off:base + off + arr.nbytes] = arr.reshape(-1).view(np.uint8)
    return out
