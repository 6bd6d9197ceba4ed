"""write_model.py — persist seeded factor tables as a trained model.

    python3 benchmark/write_model.py <spec.json>

For a serve-only cell no run can train its model (Yambda: 4.79 B events),
so set-up makes the factor tables from the seed, writes them in the
program's own model-file format (benchmark/modelwriter.py, checked through
the program's loader; the program's serializer if that refuses the file)
into the program's model store, and records a COMPLETED
engine instance for `pio deploy --engine-instance-id` to load — what `pio
train` leaves behind, without the training. Imports the program's model
class (which imports jax) but touches no device: the driver starts it
with JAX_PLATFORMS=cpu, and nothing here calls jax.
Prints one JSON line: {"instance": id, "seconds": {...}}.
"""

from __future__ import annotations

import json
import os
import sys
import time
from datetime import datetime, timezone

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import factors  # noqa: E402
import modelwriter  # noqa: E402

CLS = ("predictionio_tpu.models.recommendation", "ALSModel")


def slow_blob(modelfile, instance_id, U, V):
    """Through the program's own serializer: every id walked in Python."""
    from predictionio_tpu.data.bimap import BiMap
    from predictionio_tpu.models.recommendation import ALSModel

    model = ALSModel(
        user_index=BiMap.from_dense([f"u{n}" for n in range(len(U))]),
        item_index=BiMap.from_dense([f"i{n}" for n in range(len(V))]),
        user_factors=U, item_factors=V,
    )
    return modelfile.serialize([("arrays", model)], instance_id)


def loads_back(modelfile, path, U, V) -> bool:
    """The file as the program's loader sees it: the same tables."""
    try:
        f = modelfile.load_path(path).fields(0)
        return (f["user_factors"].shape == U.shape and f["item_factors"].shape == V.shape
                and len(f["user_index"]) == len(U) and len(f["item_index"]) == len(V)
                and bool((f["item_factors"][-1] == V[-1]).all())
                and f["user_scales"] is None and f["item_scales"] is None)
    except Exception as e:  # a format that has moved on: say so, fall back
        print(f"write_model: fast file not accepted ({type(e).__name__}: {e})", file=sys.stderr)
        return False


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

    t["import"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    nu, ni, rank, seed = (spec["num_users"], spec["num_items"], spec["rank"],
                          spec["seed"])
    U = factors.user_factors(seed, nu, rank)
    V = factors.item_factors(seed, ni, rank)
    t["generate"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    storage = Storage(env={k: v for k, v in os.environ.items() if k.startswith("PIO_")})
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
    models = storage.get_model_data_models()
    path = "fast"
    blob = modelwriter.factor_model_blob(modelfile, instance.id, CLS, b"u", b"i", U, V)
    t["serialize"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    models.insert(Model(instance.id, blob))
    if not loads_back(modelfile, models.local_path(instance.id), U, V):
        path = "slow"
        blob = slow_blob(modelfile, instance.id, U, V)
        models.insert(Model(instance.id, blob))
    instance.status = EngineInstanceStatus.COMPLETED
    instance.end_time = datetime.now(timezone.utc)
    instances.update(instance)
    t["store"] = time.perf_counter() - t0
    print(json.dumps({"instance": instance.id, "bytes": len(blob), "path": path, "seconds": t}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
