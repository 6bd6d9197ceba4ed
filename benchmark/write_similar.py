"""write_similar.py — what an item-page deployment has on disk before `pio
deploy`: the trained Similar Product model. The template reads no events at
query time, so there is no event store to fill.

    python3 benchmark/write_similar.py <spec.json>

Serve-only, as write_model.py: the seeded RAW item factors (the server
normalises them to unit rows at load, as it does for a trained model) and the
items' category block are written in the program's own model-file format and
recorded as a COMPLETED engine instance. The file is checked through the
program's own loader before the child exits 0, and a program whose model
cannot hold a category block is refused AT ONCE (exit 2), before any table is
generated: the cell cannot run on it. Imports the program's model class
(which imports jax) but touches no device.
Prints one JSON line: {"instance": id, "bytes": n, "seconds": {...}}.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import time
import zlib
from datetime import datetime, timezone

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import ecomm_data  # noqa: E402
import factors  # noqa: E402
import modelwriter  # noqa: E402

CLS = ("predictionio_tpu.models.similarproduct", "SimilarProductModel")


def model_blob(fmt, model_id: str, V, item_cat, num_categories: int) -> bytearray:
    """The model file: dense ids i<n> / c<n>, the factor table and the
    [I, 1] category block."""
    # in the order of SimilarProductModel's fields: a block's offset follows from it
    return arrays_blob(fmt, model_id, CLS, {
        "item_index": modelwriter.dense_id_blob(b"i", len(V)),
        "item_factors": np.ascontiguousarray(V),
        "categories": None, "item_scales": None,
        "category_index": modelwriter.dense_id_blob(b"c", num_categories),
        "item_categories": np.ascontiguousarray(item_cat.reshape(-1, 1)),
    })


def arrays_blob(fmt, model_id: str, cls: tuple[str, str], model: dict) -> bytearray:
    """One ``arrays`` entry of class ``cls`` laid out as ``modelfile.serialize``
    lays a model out (MAGIC, VERSION and the alignment are taken from ``fmt``):
    a field is None, a dense id blob (``modelwriter.dense_id_blob``'s pair) or
    an array. (write_ecomm.py has the same lay-out inside its ``model_blob``;
    no file the benchmark has may be edited here, so it is not folded in.)"""
    align = fmt._ALIGN
    arrays, fields = [], {}
    for name, v in model.items():
        if v is None:
            fields[name] = {"t": "none"}
        elif isinstance(v, tuple):
            arrays += [(f"e0.{name}.blob", v[0]), (f"e0.{name}.offs", v[1])]
            fields[name] = {"t": "bimap", "blob": f"e0.{name}.blob", "offs": f"e0.{name}.offs"}
        else:
            arrays.append((f"e0.{name}", v))
            fields[name] = {"t": "array", "block": f"e0.{name}", "shape": list(v.shape)}
    header = {"version": fmt.VERSION, "model_id": model_id,
              "entries": [{"kind": "arrays", "cls": list(cls), "fields": fields}],
              "blocks": {}}
    offset, layout = 0, []
    for name, arr in arrays:
        offset = (offset + align - 1) // align * align
        layout.append((arr, offset))
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
    for arr, off in layout:
        view[base + off:base + off + arr.nbytes] = arr.reshape(-1).view(np.uint8)
    return out


def model_loads_back(fmt, path, V, item_cat, num_categories) -> str | None:
    """None when the program's loader gives back the same model."""
    try:
        (kind, m), = fmt.load_path(path).entries()
        ok = (kind == "arrays" and m.item_factors.shape == V.shape
              and len(m.item_index) == len(V)
              and bool((m.item_factors[-1] == V[-1]).all())
              and m.item_scales is None
              and len(m.category_index) == num_categories
              and bool((np.asarray(m.item_categories)[:, 0] == item_cat).all()))
        return None if ok else "the loaded model differs from what was written"
    except Exception as e:
        return f"{type(e).__name__}: {e}"


def main(argv: list[str]) -> int:
    with open(argv[1]) as fh:
        spec = json.load(fh)
    t = {}
    t0 = time.perf_counter()
    from predictionio_tpu.data.storage import Storage
    from predictionio_tpu.data.storage.base import (
        EngineInstance, EngineInstanceStatus, Model,
    )
    from predictionio_tpu.models import modelfile
    from predictionio_tpu.models.similarproduct import SimilarProductModel

    if "item_categories" not in {f.name for f in dataclasses.fields(SimilarProductModel)}:
        print("write_similar: this program's SimilarProductModel keeps item categories as "
              "a JSON dictionary, not an array block: it cannot load a 4 M-item item-page "
              "model, and the cell cannot run on it", file=sys.stderr)
        return 2
    t["import"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    seed, ni, rank, nc = spec["seed"], spec["num_items"], spec["rank"], spec["num_categories"]
    V = factors.item_factors(seed, ni, rank)
    item_cat = ecomm_data.item_categories(seed, ni, nc)
    t["generate"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    storage = Storage(env={k: v for k, v in os.environ.items() if k.startswith("PIO_")})
    now = datetime.now(timezone.utc)
    variant = spec["variant"]
    instance = EngineInstance(
        id="", status=EngineInstanceStatus.INIT, start_time=now, end_time=now,
        engine_id=variant["id"], engine_version="0",
        engine_variant=spec["variant_label"], engine_factory=variant["engineFactory"],
        datasource_params=json.dumps({"name": "", "params": variant["datasource"]["params"]}),
        algorithms_params=json.dumps(variant["algorithms"]),
    )
    instances = storage.get_metadata_engine_instances()
    instance.id = instances.insert(instance)
    blob = model_blob(modelfile, instance.id, V, item_cat, nc)
    t["serialize"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    models = storage.get_model_data_models()
    models.insert(Model(instance.id, blob))
    why = model_loads_back(modelfile, models.local_path(instance.id), V, item_cat, nc)
    if why is not None:
        print(f"write_similar: the program's loader does not take the model file: {why}",
              file=sys.stderr)
        return 2
    instance.status = EngineInstanceStatus.COMPLETED
    instance.end_time = datetime.now(timezone.utc)
    instances.update(instance)
    t["store_model"] = time.perf_counter() - t0
    print(json.dumps({"instance": instance.id, "bytes": int(os.path.getsize(
        models.local_path(instance.id))), "seconds": t}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
