"""write_shardstore.py — what a marketplace's storefront has on disk before
`pio deploy --mesh data=4`: the E-Commerce template's trained model, SPANNING
FILES (its item table alone is 12.34 GB), and the event store.

    python3 benchmark/write_shardstore.py <spec.json>

Serve-only, as write_sharded.py and write_ecomm.py, whose parts it is made of:
the factor tables are never whole anywhere (``modelfile.write_spanning`` asks
``factor_blocks.SeededRows`` for each block of each segment), the items'
category block ([I, 1] int32, 193 MB) and the three id dictionaries go into
the same segments, and the instance is recorded COMPLETED; the active users'
purchases go into the program's indexed event store (sqlite) in one bulk load
and the ``unavailableItems`` constraint is ``$set`` through the program's own
DAO (write_ecomm.py's ``event_rows`` / ``bulk_insert``). A program that cannot
serve this deployment is refused AT ONCE (exit 2), by name, before a byte is
written: no spanning format, or an E-Commerce template without
``sharded_serving``. The head and the store are read back through the
program's own readers before the child exits 0. Imports the program's model
class (which imports jax) but touches no device.
Prints one JSON line: {"instance", "bytes", "segments", "events", "seconds"}.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sqlite3
import sys
import time
from datetime import datetime, timezone

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import ecomm_data  # noqa: E402
import factor_blocks  # noqa: E402
import factors  # noqa: E402
import write_ecomm  # noqa: E402
import write_sharded  # noqa: E402

CLS = ("predictionio_tpu.models.ecommerce", "ECommModel")


def require_sharded_storefront():
    """The program's model-file module, if the program can serve the
    E-Commerce template over a sharded catalog; else SystemExit(2) naming
    what is absent."""
    modelfile = write_sharded.spanning_format()
    from predictionio_tpu.models import ecommerce

    params = {f.name for f in dataclasses.fields(ecommerce.ECommAlgorithmParams)}
    model = {f.name for f in dataclasses.fields(ecommerce.ECommModel)}
    if "sharded_serving" not in params or "item_categories" not in model:
        print("write_shardstore: this program's E-Commerce template has no "
              "'sharded_serving' (a sharded catalog serves no query under rules): "
              "the cell's 48.19 M-item catalog fits no single chip, and the cell "
              "cannot run on it; nothing was written", file=sys.stderr)
        raise SystemExit(2)
    return modelfile


def main(argv: list[str]) -> int:
    with open(argv[1]) as fh:
        spec = json.load(fh)
    t = {}
    t0 = time.perf_counter()
    modelfile = require_sharded_storefront()
    from predictionio_tpu.data import store
    from predictionio_tpu.data.event import Event
    from predictionio_tpu.data.storage import App, Storage, set_storage
    from predictionio_tpu.data.storage.base import EngineInstance, EngineInstanceStatus

    t["import"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    nu, ni, rank, seed = spec["num_users"], spec["num_items"], spec["rank"], spec["seed"]
    nc, ev = spec["num_categories"], spec["events"]
    segment = int(spec.get("segment_bytes") or modelfile.SEGMENT_BYTES)
    env = {k: v for k, v in os.environ.items() if k.startswith("PIO_")}
    storage = Storage(env=env)
    set_storage(storage)
    models = storage.get_model_data_models()
    if not hasattr(models, "spanning_path"):
        print(f"write_shardstore: the model store {type(models).__name__} has no "
              "'spanning_path': it keeps no local files a model could span",
              file=sys.stderr)
        return 2
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
    head = models.spanning_path(instance.id)
    want = write_sharded.model_bytes(nu, ni, rank) + ni * 4
    write_sharded.probe_directory(
        os.path.dirname(head), int(want * write_sharded.HEADROOM), min(segment, want))
    t["probe"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    workers = int(spec.get("workers") or min(16, len(os.sched_getaffinity(0))))
    item_cat = ecomm_data.item_categories(seed, ni, nc)
    fields = modelfile.Fields(CLS, {
        "user_index": modelfile.EncodedIds(*write_sharded.dense_ids(b"u", nu, workers)),
        "item_index": modelfile.EncodedIds(*write_sharded.dense_ids(b"i", ni, workers)),
        "user_factors": factor_blocks.SeededRows(seed, factors.STREAM_USER_FACTORS, nu, rank),
        "item_factors": factor_blocks.SeededRows(seed, factors.STREAM_ITEM_FACTORS, ni, rank),
        "categories": None, "user_scales": None, "item_scales": None,
        "category_index": modelfile.EncodedIds(*write_sharded.dense_ids(b"c", nc, 1)),
        "item_categories": np.ascontiguousarray(item_cat.reshape(-1, 1)),
    })
    t["ids_and_categories"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    wrote = modelfile.write_spanning(
        head, [("arrays", fields)], instance.id, segment_bytes=segment, workers=workers)
    t["write"] = time.perf_counter() - t0
    t.update({"write_" + k: v for k, v in wrote.get("seconds", {}).items()})

    t0 = time.perf_counter()
    # the file as the program's loader sees it: the shapes, the last rows
    f = modelfile.load_path(head).fields(0)
    last = factor_blocks.rows(seed, factors.STREAM_ITEM_FACTORS, ni, rank, ni - 3, ni)
    if not (tuple(f["item_factors"].shape) == (ni, rank)
            and tuple(f["user_factors"].shape) == (nu, rank)
            and len(f["user_index"]) == nu and len(f["item_index"]) == ni
            and len(f["category_index"]) == nc
            and bool((np.asarray(f["item_factors"][ni - 3:ni]) == last).all())
            and bool((np.asarray(f["item_categories"])[-1000:, 0] == item_cat[-1000:]).all())
            and f["item_index"].inverse[ni - 1] == f"i{ni - 1}"):
        print("write_shardstore: the model does not load back as written", file=sys.stderr)
        return 1
    del fields, f
    t["check_model"] = time.perf_counter() - t0

    # the event store: the active users' purchases and the constraint
    t0 = time.perf_counter()
    app_name = spec["app_name"]
    app_id = storage.get_metadata_apps().insert(App(0, app_name))
    events = storage.get_events()
    events.init(app_id)
    active = ecomm_data.active_users(seed, nu, ev["active_users"])
    who, item, is_buy = ecomm_data.user_events(
        seed, ni, ev["active_users"], ev["count"], ev["buy_share"])
    try:
        write_ecomm.bulk_insert(env["PIO_STORAGE_SOURCES_DB_PATH"], f"pio_event_{app_id}",
                                write_ecomm.event_rows(active, who, item, is_buy))
    except (KeyError, sqlite3.Error) as e:  # another store, another schema
        print(f"write_shardstore: the event table did not take the bulk load "
              f"({type(e).__name__}: {e})", file=sys.stderr)
        return 2
    unavailable = ecomm_data.unavailable_items(seed, ni, spec["unavailable_items"])
    events.insert(Event(
        event="$set", entity_type="constraint", entity_id="unavailableItems",
        properties={"items": ["i%d" % i for i in unavailable.tolist()]}), app_id)
    # read back through the program's own serving-time reads
    probe = int(ev["active_users"]) // 2
    want_seen = {"i%d" % i for i in np.unique(item[who == probe]).tolist()}
    got = {e.target_entity_id for e in store.find_by_entity(
        app_name=app_name, entity_type="user", entity_id="u%d" % active[probe],
        event_names=["view", "buy"], target_entity_type="item", limit=None)}
    held = store.find_by_entity(
        app_name=app_name, entity_type="constraint", entity_id="unavailableItems",
        event_names=["$set"], limit=1, latest=True)
    if got != want_seen or not held or \
            len(held[0].properties.get_opt("items", default=[])) != len(unavailable):
        print(f"write_shardstore: the event store reads back {len(got)} seen items of "
              f"{len(want_seen)} and {len(held)} constraint(s)", file=sys.stderr)
        return 2
    instance.status = EngineInstanceStatus.COMPLETED
    instance.end_time = datetime.now(timezone.utc)
    instances.update(instance)
    t["store_events"] = time.perf_counter() - t0
    print(json.dumps({"instance": instance.id, "bytes": wrote["bytes"],
                      "segments": wrote["segments"], "events": int(len(who)),
                      "seconds": t}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
