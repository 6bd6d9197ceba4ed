"""Workflow drivers: the train / deploy-prepare runtime around Engine.

Capability parity with the reference's workflow layer
(core/.../workflow/CreateWorkflow.scala:136, CoreWorkflow.scala:45-160):
engine-instance lifecycle (INIT -> COMPLETED / FAILED), model blob
persistence into MODELDATA, and the deploy path that re-hydrates (or
re-trains) models for serving. The spark-submit process boundary is gone:
drivers are plain function calls the CLI invokes in-process or in a
subprocess.
"""

from __future__ import annotations

import json
import logging
import os
import traceback
from datetime import datetime, timezone
from typing import Any, Mapping

from predictionio_tpu.core import persistence
from predictionio_tpu.core.context import WorkflowContext
from predictionio_tpu.core.engine import (
    Engine,
    EngineParams,
    StopAfterPrepareInterruption,
    StopAfterReadInterruption,
    WorkflowParams,
)
from predictionio_tpu.data.storage import (
    EngineInstance,
    EngineInstanceStatus,
    Storage,
    get_storage,
)

logger = logging.getLogger(__name__)


def _now() -> datetime:
    return datetime.now(tz=timezone.utc)


def _is_primary_process() -> bool:
    """True unless this is a non-zero process of a multi-host runtime
    (parallel/mesh.py initialize_multihost)."""
    try:
        import jax

        return jax.process_index() == 0
    except Exception:  # pragma: no cover - pre-backend-init edge
        return True


def run_train(
    engine: Engine,
    engine_params: EngineParams,
    engine_id: str = "default",
    engine_version: str = "0",
    engine_variant: str = "default",
    engine_factory: str = "",
    workflow_params: WorkflowParams | None = None,
    storage: Storage | None = None,
    ctx: WorkflowContext | None = None,
) -> str:
    """Train and persist: the `pio train` driver
    (CreateWorkflow.main + CoreWorkflow.runTrain). Returns the engine
    instance id; raises on failure after marking the instance FAILED."""
    storage = storage or get_storage()
    wp = workflow_params or WorkflowParams()
    ctx = ctx or WorkflowContext(
        mode="Training",
        batch=wp.batch,
        runtime_conf=wp.runtime_conf,
        mesh_axes=wp.mesh_axes,
    )
    # multi-host runs execute this driver on EVERY host (the collectives
    # need all of them); only process 0 touches metadata/model storage,
    # or a pod would record one instance per host
    primary = _is_primary_process()

    instances = storage.get_metadata_engine_instances()
    instance = EngineInstance(
        id="",
        status=EngineInstanceStatus.INIT,
        start_time=_now(),
        end_time=_now(),
        engine_id=engine_id,
        engine_version=engine_version,
        engine_variant=engine_variant,
        engine_factory=engine_factory,
        batch=wp.batch,
        runtime_conf={k: str(v) for k, v in wp.runtime_conf.items()},
        datasource_params=_params_json(engine_params.datasource),
        preparator_params=_params_json(engine_params.preparator),
        algorithms_params=json.dumps(
            [
                {"name": name, "params": params.to_dict()}
                for name, params in engine_params.algorithms
            ],
            sort_keys=True,
        ),
        serving_params=_params_json(engine_params.serving),
    )
    instance_id = instances.insert(instance) if primary else ""
    # adopt the generated id locally: remote backends (http) can't mutate
    # our copy server-side, and the later update() keys on instance.id
    instance.id = instance_id
    if primary:
        logger.info("engine instance %s created (INIT)", instance_id)

    try:
        algorithms = engine.make_algorithms(engine_params)
        if _warm_start_requested(wp):
            prev = _previous_models(
                storage, algorithms, engine_id, engine_version, engine_variant
            )
            if prev is not None:
                ctx.runtime_conf["warm_start_models"] = prev
        if wp.profile_dir:
            import jax.profiler

            with jax.profiler.trace(wp.profile_dir):
                models = engine.train(ctx, engine_params, wp, algorithms=algorithms)
        else:
            models = engine.train(ctx, engine_params, wp, algorithms=algorithms)
        if wp.save_model and primary:
            persistence.save_models(
                storage.get_model_data_models(), algorithms, models, instance_id
            )
        instance.status = EngineInstanceStatus.COMPLETED
        instance.end_time = _now()
        if primary:
            instances.update(instance)
            logger.info("engine instance %s COMPLETED", instance_id)
        return instance_id
    except (StopAfterReadInterruption, StopAfterPrepareInterruption) as stop:
        # debug stop requested via WorkflowParams — not a failure
        # (reference CoreWorkflow.scala:91-97)
        instance.end_time = _now()
        if primary:
            instances.update(instance)
        logger.info("training of %s interrupted by %s", instance_id, type(stop).__name__)
        return instance_id
    except Exception:
        instance.status = EngineInstanceStatus.FAILED
        instance.end_time = _now()
        if primary:
            instances.update(instance)
        logger.error(
            "engine instance %s FAILED:\n%s", instance_id, traceback.format_exc()
        )
        raise


def _warm_start_requested(wp: WorkflowParams) -> bool:
    """``pio train --warm-start`` sets PIO_WARM_START=1 (works across the
    CLI's subprocess boundary); in-process callers can set
    ``runtime_conf["warm_start"]`` instead."""
    if wp.runtime_conf.get("warm_start"):
        return True
    env = os.environ.get("PIO_WARM_START", "").strip().lower()
    return env not in ("", "0", "false", "no", "off")


def _previous_models(
    storage: Storage,
    algorithms: list[Any],
    engine_id: str,
    engine_version: str,
    engine_variant: str,
) -> list[Any] | None:
    """Models of the latest COMPLETED instance of this engine identity,
    aligned with ``algorithms``, for warm-start carries. Any failure —
    no previous instance, no persisted blob, undeserializable model —
    degrades to a cold start with a named warning; per-algorithm
    compatibility (rank/dtype) is checked by the algorithm itself."""
    try:
        instance = storage.get_metadata_engine_instances().get_latest_completed(
            engine_id, engine_version, engine_variant
        )
        if instance is None:
            logger.warning(
                "warm-start: no completed instance for engine %s/%s/%s; "
                "cold start", engine_id, engine_version, engine_variant,
            )
            return None
        model_store = storage.get_model_data_models()
        models = None
        local = model_store.local_path(instance.id)
        if local is not None:
            # zero-copy path: flat model-file entries mmap in place, so
            # the warm carry costs page faults, not a deserialize
            models = persistence.deserialize_model_path(
                local, algorithms, instance.id
            )
        if models is None:
            blob = model_store.get(instance.id)
            if blob is None:
                logger.warning(
                    "warm-start: instance %s has no persisted model; "
                    "cold start", instance.id,
                )
                return None
            models = persistence.deserialize_models(
                blob.models, algorithms, instance.id
            )
        models = [
            None if m is persistence.RETRAIN else m for m in models
        ]
        logger.info(
            "warm-start: carrying models from instance %s", instance.id
        )
        return models
    except Exception as e:
        logger.warning("warm-start: previous model unavailable (%s); "
                       "cold start", e)
        return None


def prepare_deploy(
    engine: Engine,
    instance: EngineInstance,
    storage: Storage | None = None,
    ctx: WorkflowContext | None = None,
) -> tuple[EngineParams, list[Any], list[Any], Any]:
    """Re-hydrate a completed instance for serving
    (CreateServer.createServerActorWithEngine + Engine.prepareDeploy).

    Returns (engine_params, algorithms, models, serving). Models persisted
    as RETRAIN sentinels are re-trained here — on TPU the retrained factors
    stay resident on the serving process's mesh (better than the
    reference, which re-runs Spark jobs per deploy).
    """
    storage = storage or get_storage()
    ctx = ctx or WorkflowContext(mode="Serving", batch=instance.batch)
    engine_params = engine_params_from_instance(engine, instance)
    algorithms = engine.make_algorithms(engine_params)
    serving = engine.make_serving(engine_params)

    # zero-copy fast path: when the model store keeps the blob as a local
    # file in the flat model-file format, mmap it in place — no byte
    # copy, and variants/replicas of this instance share pages and
    # decoded model objects. Falls through to the byte read for remote
    # stores and legacy pickle blobs.
    model_store = storage.get_model_data_models()
    models = None
    local = model_store.local_path(instance.id)
    if local is not None:
        models = persistence.deserialize_model_path(
            local, algorithms, instance.id
        )
    if models is None:
        blob = model_store.get(instance.id)
        if blob is None:
            raise RuntimeError(
                f"no persisted model for engine instance {instance.id}; "
                "was it trained with save_model=False?"
            )
        models = persistence.deserialize_models(
            blob.models, algorithms, instance.id
        )
    if any(m is persistence.RETRAIN for m in models):
        logger.info("instance %s has retrain-on-deploy models; training", instance.id)
        retrained = engine.train(ctx, engine_params, algorithms=algorithms)
        models = [
            retrained[i] if m is persistence.RETRAIN else m
            for i, m in enumerate(models)
        ]
    return engine_params, algorithms, models, serving


def engine_params_from_instance(
    engine: Engine, instance: EngineInstance
) -> EngineParams:
    """Instance params-JSON -> EngineParams
    (reference Engine.engineInstanceToEngineParams, Engine.scala:422-498)."""
    variant: dict[str, Any] = {}
    ds = json.loads(instance.datasource_params or "{}")
    prep = json.loads(instance.preparator_params or "{}")
    algos = json.loads(instance.algorithms_params or "[]")
    serv = json.loads(instance.serving_params or "{}")
    if ds:
        variant["datasource"] = ds
    if prep:
        variant["preparator"] = prep
    if algos:
        variant["algorithms"] = algos
    if serv:
        variant["serving"] = serv
    return engine.params_from_variant(variant)


def _params_json(pair: tuple[str, Any]) -> str:
    name, params = pair
    return json.dumps({"name": name, "params": params.to_dict()}, sort_keys=True)


def load_variant(path: str) -> dict[str, Any]:
    """Read an engine variant JSON file (engine.json analog)."""
    with open(path) as f:
        return json.load(f)


def variant_engine_params(engine: Engine, variant: Mapping[str, Any]) -> EngineParams:
    return engine.params_from_variant(variant)
