"""write_ecomm.py — what a storefront deployment has on disk before `pio
deploy`: the trained model and the event store.

    python3 benchmark/write_ecomm.py <spec.json>

Serve-only, as write_model.py: the seeded factor tables and the items'
category block are written in the program's own model-file format and
recorded as a COMPLETED engine instance; the active users' behaviours go
into the program's indexed event store (sqlite) in one bulk load, and the
``unavailableItems`` constraint is ``$set`` through the program's own DAO.
Everything is checked through the program's own readers before the child
exits 0, and a program whose model cannot hold a category block is refused
AT ONCE (exit 2), before any table is generated: the cell cannot run on it.
Imports the program's model class (which imports jax) but touches no device.
Prints one JSON line: {"instance": id, "bytes": n, "events": n, "seconds": {...}}.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sqlite3
import sys
import time
import zlib
from datetime import datetime, timezone

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import ecomm_data  # noqa: E402
import factors  # noqa: E402
import modelwriter  # noqa: E402

CLS = ("predictionio_tpu.models.ecommerce", "ECommModel")
T0 = datetime(2017, 11, 25, tzinfo=timezone.utc)  # the source's first day


def model_blob(fmt, model_id: str, U, V, item_cat, num_categories: int) -> bytearray:
    """The model file: dense ids u<n> / i<n> / c<n>, both factor tables and
    the [I, 1] category block, laid out as ``modelfile.serialize`` lays a
    model out (MAGIC, VERSION and the alignment are taken from ``fmt``)."""
    align = fmt._ALIGN
    # in the order of ECommModel's fields: a block's offset follows from it
    model = {
        "user_index": modelwriter.dense_id_blob(b"u", len(U)),
        "item_index": modelwriter.dense_id_blob(b"i", len(V)),
        "user_factors": np.ascontiguousarray(U),
        "item_factors": np.ascontiguousarray(V),
        "categories": None, "user_scales": None, "item_scales": None,
        "category_index": modelwriter.dense_id_blob(b"c", num_categories),
        "item_categories": np.ascontiguousarray(item_cat.reshape(-1, 1)),
    }
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
              "entries": [{"kind": "arrays", "cls": list(CLS), "fields": fields}],
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


def model_loads_back(fmt, path, U, V, item_cat, num_categories) -> str | None:
    """None when the program's loader gives back the same model."""
    try:
        (kind, m), = fmt.load_path(path).entries()
        ok = (kind == "arrays" and m.user_factors.shape == U.shape
              and m.item_factors.shape == V.shape
              and len(m.user_index) == len(U) and len(m.item_index) == len(V)
              and bool((m.item_factors[-1] == V[-1]).all())
              and m.user_scales is None and m.item_scales is None
              and len(m.category_index) == num_categories
              and bool((np.asarray(m.item_categories)[:, 0] == item_cat).all()))
        return None if ok else "the loaded model differs from what was written"
    except Exception as e:
        return f"{type(e).__name__}: {e}"


def event_rows(active, who, item, is_buy):
    """Rows of the program's sqlite event table, in its column order."""
    n = len(who)
    users = ["u%d" % u for u in active[who].tolist()]
    items = ["i%d" % i for i in item.tolist()]
    names = np.where(is_buy, "buy", "view").tolist()
    t0 = T0.timestamp()
    times = (t0 + np.arange(n) * (9 * 86400.0 / max(n, 1))).tolist()
    for j in range(n):
        yield ("%032x" % j, names[j], "user", users[j], "item", items[j], "{}",
               times[j], "0", "[]", None, times[j])


def bulk_insert(db_path: str, table: str, rows) -> None:
    """One transaction straight into the table the program created; its
    secondary indexes (whatever the program declared) are dropped for the
    load and built again afterwards from the program's own SQL."""
    conn = sqlite3.connect(db_path)
    try:
        indexes = conn.execute(
            "SELECT name, sql FROM sqlite_master WHERE type='index' AND tbl_name=? "
            "AND sql IS NOT NULL", (table,)).fetchall()
        for pragma in ("synchronous=OFF", "temp_store=MEMORY", "cache_size=-1000000"):
            conn.execute("PRAGMA " + pragma)  # a fresh database: nothing to protect
        with conn:
            for name, _ in indexes:
                conn.execute(f"DROP INDEX {name}")
            conn.executemany(
                f"INSERT INTO {table} VALUES (?,?,?,?,?,?,?,?,?,?,?,?)", rows)
            for _, sql in indexes:
                conn.execute(sql)
    finally:
        conn.close()


def main(argv: list[str]) -> int:
    with open(argv[1]) as fh:
        spec = json.load(fh)
    t = {}
    t0 = time.perf_counter()
    from predictionio_tpu.data import store
    from predictionio_tpu.data.event import Event
    from predictionio_tpu.data.storage import App, Storage, set_storage
    from predictionio_tpu.data.storage.base import (
        EngineInstance, EngineInstanceStatus, Model,
    )
    from predictionio_tpu.models import modelfile
    from predictionio_tpu.models.ecommerce import ECommModel

    if "item_categories" not in {f.name for f in dataclasses.fields(ECommModel)}:
        print("write_ecomm: this program's ECommModel keeps item categories as a JSON "
              "dictionary, not an array block: it cannot load a 4 M-item storefront "
              "model, and the cell cannot run on it", file=sys.stderr)
        return 2
    t["import"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    seed, nu, ni, rank = spec["seed"], spec["num_users"], spec["num_items"], spec["rank"]
    nc, ev = spec["num_categories"], spec["events"]
    U = factors.user_factors(seed, nu, rank)
    V = factors.item_factors(seed, ni, rank)
    item_cat = ecomm_data.item_categories(seed, ni, nc)
    t["generate"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    env = {k: v for k, v in os.environ.items() if k.startswith("PIO_")}
    storage = Storage(env=env)
    set_storage(storage)
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
    blob = model_blob(modelfile, instance.id, U, V, item_cat, nc)
    t["serialize"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    models = storage.get_model_data_models()
    models.insert(Model(instance.id, blob))
    why = model_loads_back(modelfile, models.local_path(instance.id), U, V, item_cat, nc)
    if why is not None:
        print(f"write_ecomm: the program's loader does not take the model file: {why}",
              file=sys.stderr)
        return 2
    del U, V, blob
    t["store_model"] = time.perf_counter() - t0

    # the event store: the active users' behaviours and the constraint
    t0 = time.perf_counter()
    app_name = spec["app_name"]
    app_id = storage.get_metadata_apps().insert(App(0, app_name))
    events = storage.get_events()
    events.init(app_id)
    active = ecomm_data.active_users(seed, nu, ev["active_users"])
    who, item, is_buy = ecomm_data.user_events(
        seed, ni, ev["active_users"], ev["count"], ev["buy_share"])
    try:
        bulk_insert(env["PIO_STORAGE_SOURCES_DB_PATH"], f"pio_event_{app_id}",
                    event_rows(active, who, item, is_buy))
    except (KeyError, sqlite3.Error) as e:  # another store, another schema
        print(f"write_ecomm: the event table did not take the bulk load "
              f"({type(e).__name__}: {e})", file=sys.stderr)
        return 2
    unavailable = ecomm_data.unavailable_items(seed, ni, spec["unavailable_items"])
    events.insert(Event(
        event="$set", entity_type="constraint", entity_id="unavailableItems",
        properties={"items": ["i%d" % i for i in unavailable.tolist()]}), app_id)
    # read back through the program's own serving-time reads
    probe = int(ev["active_users"]) // 2
    want = {"i%d" % i for i in np.unique(item[who == probe]).tolist()}
    got = {e.target_entity_id for e in store.find_by_entity(
        app_name=app_name, entity_type="user", entity_id="u%d" % active[probe],
        event_names=["view", "buy"], target_entity_type="item", limit=None)}
    held = store.find_by_entity(
        app_name=app_name, entity_type="constraint", entity_id="unavailableItems",
        event_names=["$set"], limit=1, latest=True)
    if got != want or not held or len(held[0].properties.get_opt("items", default=[])) != len(unavailable):
        print(f"write_ecomm: the event store reads back {len(got)} seen items of "
              f"{len(want)} and {len(held)} constraint(s)", file=sys.stderr)
        return 2
    instance.status = EngineInstanceStatus.COMPLETED
    instance.end_time = datetime.now(timezone.utc)
    instances.update(instance)
    t["store_events"] = time.perf_counter() - t0
    print(json.dumps({"instance": instance.id, "bytes": int(os.path.getsize(
        models.local_path(instance.id))), "events": int(len(who)), "seconds": t}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
