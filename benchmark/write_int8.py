"""write_int8.py — persist seeded, QUANTIZED factor tables as a trained model
that spans files: what `pio train` with ``storage_dtype="int8"`` leaves behind
for a marketplace's catalog — int8 values (48.19 M x 64 = 3.08 GB, in segments
of at most 1 GiB) and one f32 scale a row, a quarter of the f32 model.

    python3 benchmark/write_int8.py <spec.json>

write_sharded.py with another row source: the f32 rows are regenerated from
the seed a chunk at a time, quantized by the configuration's stated rule
(``reference_int8.quantize_rows``, the benchmark's own NumPy — NOT the
program's ``ops/als.py quantize_rows``) and dropped; the ids, the directory
probe and the spanning format are write_sharded's. A program that cannot serve
such a model as it is stored is refused AT ONCE (exit 2), by name, before a
byte is written. The head is read back through the program's loader before the
child exits 0. Imports the program's retrieval module (which imports jax) but
touches no device: the driver starts it with JAX_PLATFORMS=cpu.
Prints one JSON line: {"instance": id, "bytes": n, "segments": n, "seconds": {...}}.
"""

from __future__ import annotations

import json
import os
import sys
import time
from datetime import datetime, timezone

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import factors  # noqa: E402
import reference_int8  # noqa: E402
from write_sharded import CLS, HEADROOM, dense_ids, probe_directory, spanning_format  # noqa: E402

SERVES = ("put_rows", "RESIDENT_PARTS")


def quantized_serving() -> None:
    """SystemExit(2), by name, where the program's retrieval module lacks what
    serves an int8 pair AS STORED: a table put up a part at a time (no whole
    host copy of a model that spans files) and the resident bytes by part that
    the cell's `correct` and its `resident_gb.int8` read."""
    from predictionio_tpu.ops import retrieval

    missing = [n for n in SERVES if not hasattr(retrieval, n)]
    if missing:
        print(f"write_int8: predictionio_tpu.ops.retrieval lacks {missing}: this "
              "program stages no quantized table as it is stored and reports no "
              "resident bytes; nothing was written", file=sys.stderr)
        raise SystemExit(2)


def model_bytes(num_users: int, num_items: int, rank: int) -> int:
    """Bytes of the model on disk, to a few MB: two int8 tables, their f32
    scales, the ids' blobs (a prefix byte + up to 8 digits) and int64 offsets."""
    return sum(n * (rank + 4 + 8 + 1 + len(str(max(1, n - 1)))) for n in (num_users, num_items))


def main(argv: list[str]) -> int:
    with open(argv[1]) as fh:
        spec = json.load(fh)
    t = {}
    t0 = time.perf_counter()
    modelfile = spanning_format()
    quantized_serving()
    from predictionio_tpu.data.storage import Storage
    from predictionio_tpu.data.storage.base import EngineInstance, EngineInstanceStatus

    t["import"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    nu, ni, rank, seed = spec["num_users"], spec["num_items"], spec["rank"], spec["seed"]
    segment = int(spec.get("segment_bytes") or modelfile.SEGMENT_BYTES)
    storage = Storage(env={k: v for k, v in os.environ.items() if k.startswith("PIO_")})
    models = storage.get_model_data_models()
    if not hasattr(models, "spanning_path"):
        print(f"write_int8: the model store {type(models).__name__} has no "
              "'spanning_path': it keeps no local files a model could span",
              file=sys.stderr)
        return 2
    now = datetime.now(timezone.utc)
    variant = spec["variant"]
    instance = EngineInstance(
        id="", status=EngineInstanceStatus.INIT, start_time=now, end_time=now,
        engine_id=variant["id"], engine_version="0",
        engine_variant=spec["variant_label"],
        engine_factory=variant["engineFactory"],
        datasource_params=json.dumps(
            {"name": "", "params": variant["datasource"]["params"]}),
        algorithms_params=json.dumps(variant["algorithms"]),
    )
    instances = storage.get_metadata_engine_instances()
    instance.id = instances.insert(instance)
    head = models.spanning_path(instance.id)
    want = model_bytes(nu, ni, rank)
    probe_directory(os.path.dirname(head), int(want * HEADROOM), min(segment, want))
    t["probe"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    workers = int(spec.get("workers") or min(16, len(os.sched_getaffinity(0))))

    shared = {factors.STREAM_USER_FACTORS: {}, factors.STREAM_ITEM_FACTORS: {}}

    def half(stream: int, n: int, part: str):  # a table's halves share chunks
        return reference_int8.QuantizedRows(seed, stream, n, rank, part, workers=4,
                                            pairs=shared[stream])

    fields = modelfile.Fields(CLS, {
        "user_index": modelfile.EncodedIds(*dense_ids(b"u", nu, workers)),
        "item_index": modelfile.EncodedIds(*dense_ids(b"i", ni, workers)),
        "user_factors": half(factors.STREAM_USER_FACTORS, nu, "values"),
        "item_factors": half(factors.STREAM_ITEM_FACTORS, ni, "values"),
        "user_scales": half(factors.STREAM_USER_FACTORS, nu, "scales"),
        "item_scales": half(factors.STREAM_ITEM_FACTORS, ni, "scales"),
    })
    t["ids"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    wrote = modelfile.write_spanning(
        head, [("arrays", fields)], instance.id, segment_bytes=segment,
        workers=workers,
    )
    t["write"] = time.perf_counter() - t0
    t.update({"write_" + k: v for k, v in wrote.get("seconds", {}).items()})
    t0 = time.perf_counter()
    # the file as the program's loader sees it: shapes, dtypes, the last rows
    f = modelfile.load_path(head).fields(0)
    _, last_v, last_s = reference_int8.chunk_pair(
        seed, factors.STREAM_ITEM_FACTORS, (ni - 1) // factors.CHUNK_ROWS, ni, rank)
    if not (tuple(f["item_factors"].shape) == (ni, rank)
            and tuple(f["user_factors"].shape) == (nu, rank)
            and f["item_factors"].dtype == np.int8 and f["user_factors"].dtype == np.int8
            and tuple(f["item_scales"].shape) == (ni,) and tuple(f["user_scales"].shape) == (nu,)
            and len(f["user_index"]) == nu and len(f["item_index"]) == ni
            and bool((np.asarray(f["item_factors"][ni - 3:ni]) == last_v[-3:]).all())
            and bool((np.asarray(f["item_scales"][ni - 3:ni]) == last_s[-3:]).all())
            and f["item_index"].inverse[ni - 1] == f"i{ni - 1}"):
        print("write_int8: the model does not load back as written", file=sys.stderr)
        return 1
    instance.status = EngineInstanceStatus.COMPLETED
    instance.end_time = datetime.now(timezone.utc)
    instances.update(instance)
    t["check"] = time.perf_counter() - t0
    print(json.dumps({"instance": instance.id, "bytes": wrote["bytes"],
                      "segments": wrote["segments"], "seconds": t}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
