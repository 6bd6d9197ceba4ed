"""Multi-host mesh initialization: two REAL processes, each with 4
virtual CPU devices, joined via jax.distributed into one 8-device
global mesh running the production sharded ALS trainer.

The CPU-process pair is the stand-in for two TPU pod hosts — the analog
of the reference testing its cluster path on Spark local masters
(core/src/test/scala/.../BaseTest.scala:31-92) while production runs
spark-submit (tools/.../Runner.scala:193-244).
"""

import json
import os
import socket
import subprocess
import sys

import pytest

WORKER = r"""
import json, os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np
from predictionio_tpu.parallel.mesh import initialize_multihost, make_mesh

initialize_multihost(
    coordinator_address=sys.argv[1],
    num_processes=2,
    process_id=int(sys.argv[2]),
)
import jax

assert jax.process_count() == 2
assert len(jax.devices()) == 8, len(jax.devices())
assert len(jax.local_devices()) == 4

from predictionio_tpu.ops import als
from predictionio_tpu.parallel.als_sharded import sharded_als_train

rng = np.random.default_rng(0)
gt_u = rng.normal(size=(40, 3)) / np.sqrt(3)
gt_v = rng.normal(size=(30, 3)) / np.sqrt(3)
mask = rng.random((40, 30)) < 0.5
rows, cols = np.nonzero(mask)
vals = (gt_u @ gt_v.T)[rows, cols].astype(np.float32)
data = als.build_ratings_data(
    rows.astype(np.int32), cols.astype(np.int32), vals, 40, 30,
    bucket_widths=(8, 32),
)
params = als.ALSParams(rank=6, iterations=8, reg=0.005)
mesh = make_mesh([("data", 8)])
U, V = sharded_als_train(data, params, mesh)

from jax.experimental import multihost_utils

U_full = np.asarray(multihost_utils.process_allgather(U, tiled=True))
V_full = np.asarray(multihost_utils.process_allgather(V, tiled=True))
pred = (U_full[rows] * V_full[cols]).sum(1)
rmse = float(np.sqrt(np.mean((pred - vals) ** 2)))
if jax.process_index() == 0:
    print(json.dumps({"rmse": rmse, "shape": list(U_full.shape)}))
"""


TRAIN_WORKER = r"""
import json, os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
from predictionio_tpu.parallel.mesh import initialize_multihost

initialize_multihost(
    coordinator_address=sys.argv[1],
    num_processes=2,
    process_id=int(sys.argv[2]),
)
import numpy as np
from predictionio_tpu.core import EngineParams
from predictionio_tpu.core.workflow import run_train
from predictionio_tpu.data.storage import get_storage
from predictionio_tpu.models import recommendation as rec

storage = get_storage()  # shared sqlite+jsonl via PIO_STORAGE_* env
engine = rec.engine()
ep = EngineParams(
    datasource=("", rec.DataSourceParams(app_name="MhApp")),
    algorithms=[(
        "als",
        rec.ALSAlgorithmParams(rank=4, num_iterations=3, sharded_train=True),
    )],
)
iid = run_train(engine, ep, engine_id="mh", storage=storage)
import jax

print(json.dumps({
    "proc": jax.process_index(),
    "instance_id": iid,
    "devices": len(jax.devices()),
}))
"""


def test_two_process_global_mesh_trains_to_parity(tmp_path):
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    coord = f"127.0.0.1:{port}"
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)  # worker sets its own device count
    env["PYTHONPATH"] = os.pathsep.join(
        filter(None, [os.path.dirname(os.path.dirname(__file__)),
                      env.get("PYTHONPATH")])
    )
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", WORKER, coord, str(i)],
            stdout=subprocess.PIPE,
            stderr=subprocess.PIPE,
            text=True,
            env=env,
        )
        for i in range(2)
    ]
    outs = []
    for i, p in enumerate(procs):
        out, err = p.communicate(timeout=240)
        assert p.returncode == 0, f"proc {i} failed:\n{err[-3000:]}"
        outs.append(out)
    payload = json.loads(outs[0].strip().splitlines()[-1])
    assert payload["shape"] == [40, 6]
    # same bar as the single-process sharded convergence test
    assert payload["rmse"] < 0.08, payload


def test_multihost_run_train_persists_once_and_serves(tmp_path):
    """The production path: BOTH hosts run the full run_train driver
    over a global mesh against SHARED storage — exactly one engine
    instance + model may be recorded (process 0), and the model must
    deploy and serve afterwards in a plain single-process context."""
    import numpy as np

    from predictionio_tpu.data.event import Event
    from predictionio_tpu.data.storage import Storage

    store_env = {
        "PIO_STORAGE_SOURCES_DB_TYPE": "sqlite",
        "PIO_STORAGE_SOURCES_DB_PATH": str(tmp_path / "pio.db"),
        "PIO_STORAGE_SOURCES_LOG_TYPE": "jsonl",
        "PIO_STORAGE_SOURCES_LOG_PATH": str(tmp_path / "events"),
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "DB",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "LOG",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "DB",
    }
    seed_storage = Storage(env=dict(store_env))
    from predictionio_tpu.data.storage import App

    app_id = seed_storage.get_metadata_apps().insert(App(0, "MhApp"))
    events = seed_storage.get_events()
    rng = np.random.default_rng(0)
    for u in range(16):
        for _ in range(6):
            events.insert(
                Event(
                    event="rate", entity_type="user", entity_id=f"u{u}",
                    target_entity_type="item",
                    target_entity_id=f"i{int(rng.integers(0, 10))}",
                    properties={"rating": float(rng.integers(1, 6))},
                ),
                app_id,
            )

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    coord = f"127.0.0.1:{port}"
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    env.update(store_env)
    env["PYTHONPATH"] = os.pathsep.join(
        filter(None, [os.path.dirname(os.path.dirname(__file__)),
                      env.get("PYTHONPATH")])
    )
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", TRAIN_WORKER, coord, str(i)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env,
        )
        for i in range(2)
    ]
    payloads = []
    for i, p in enumerate(procs):
        out, err = p.communicate(timeout=240)
        assert p.returncode == 0, f"proc {i} failed:\n{err[-3000:]}"
        payloads.append(json.loads(out.strip().splitlines()[-1]))
    by_proc = {p["proc"]: p for p in payloads}
    assert by_proc[0]["devices"] == 4  # 2 procs x 2 virtual devices
    assert by_proc[0]["instance_id"] and not by_proc[1]["instance_id"]

    # exactly one instance recorded; it deploys and serves here
    instances = seed_storage.get_metadata_engine_instances().get_all()
    assert len(instances) == 1 and instances[0].status == "COMPLETED"
    from predictionio_tpu.core.workflow import prepare_deploy
    from predictionio_tpu.models import recommendation as rec

    _, [algo], [model], _ = prepare_deploy(
        rec.engine(), instances[0], storage=seed_storage
    )
    result = algo.predict(model, rec.Query(user="u1", num=3))
    assert len(result.itemScores) == 3
