"""Model persistence: serialize trained models into the MODELDATA repo.

Capability parity with the reference's model save/load paths:
- Kryo round-trip of in-heap models into the ``Models`` store
  (workflow/CoreWorkflow.scala:76-92) -> here: the zero-copy model file
  format (models/modelfile.py) for array-table models, pickle (with
  device arrays pulled to host numpy first) for everything else.
- ``PersistentModel``/``PersistentModelLoader`` custom contract
  (controller/PersistentModel.scala) for models that manage their own
  files (e.g. orbax checkpoint dirs) -> :class:`PersistentModel`.
- PAlgorithm's "return Unit, retrain on deploy" escape hatch
  (controller/Engine.scala:211-233) -> an algorithm's
  ``make_persistent_model`` returning ``None``.

The persisted blob is the flat model-file format whenever
``PIO_MODEL_MMAP`` is on (the default): the four ALS templates' models
are plain dataclasses of numpy arrays / BiMaps / JSON values and encode
as aligned blocks; anything else rides along as a ``pickle`` entry inside
the same file. ``PIO_MODEL_MMAP=0`` restores the legacy pickled-manifest
blob. ``deserialize_models`` accepts both formats regardless (the magic
distinguishes them), so old instances keep deploying after an upgrade.
"""

from __future__ import annotations

import io
import logging
import os
import pickle
from dataclasses import dataclass
from typing import Any, Sequence

from predictionio_tpu.models import modelfile
from predictionio_tpu.models.modelfile import ModelFileError  # re-export

__all__ = [
    "PersistentModel",
    "RETRAIN",
    "ModelFileError",
    "serialize_models",
    "save_models",
    "deserialize_models",
    "deserialize_model_path",
]

logger = logging.getLogger(__name__)

_RETRAIN_SENTINEL = "__pio_tpu_retrain__"


class PersistentModel:
    """Custom save/load contract. Subclasses implement ``save`` writing
    wherever they like and classmethod ``load`` restoring; the framework
    persists only the (class, model_id) manifest
    (reference PersistentModelManifest)."""

    def save(self, model_id: str) -> bool:
        raise NotImplementedError

    @classmethod
    def load(cls, model_id: str) -> "PersistentModel":
        raise NotImplementedError


@dataclass
class _Manifest:
    """What actually lands in the MODELDATA blob for one algorithm slot
    (legacy pickle container; the model-file format stores the same
    kinds in its header)."""

    kind: str  # "pickle" | "persistent" | "retrain"
    payload: Any = None  # pickled bytes | (module, qualname) | None


def _device_to_host(tree: Any) -> Any:
    """Pull any jax arrays in a pytree to host numpy for pickling.
    Models that already hold plain numpy (the usual case — host_factors
    runs at train time) pass through untouched: no tree rebuild, no
    array copies."""
    try:
        import jax
    except ImportError:  # pure-host deployment
        return tree

    if not any(
        isinstance(x, jax.Array) for x in jax.tree_util.tree_leaves(tree)
    ):
        return tree

    def convert(x):
        if isinstance(x, jax.Array):
            import numpy as np

            return np.asarray(jax.device_get(x))
        return x

    return jax.tree_util.tree_map(convert, tree)


def _manifest_entries(
    algorithms: Sequence[Any], models: Sequence[Any], model_id: str
) -> list[tuple[str, Any]]:
    """Run the per-slot persistence contract and return (kind, payload)
    pairs in the model-file entry shape: ``arrays`` carries the model
    object itself, ``pickle`` carries pickled bytes."""
    entries: list[tuple[str, Any]] = []
    for algo, model in zip(algorithms, models):
        persistable = algo.make_persistent_model(model)
        if persistable is None:
            entries.append(("retrain", None))
        elif isinstance(persistable, PersistentModel):
            cls = type(persistable)
            if not persistable.save(model_id):
                raise RuntimeError(
                    f"{cls.__name__}.save({model_id!r}) returned False"
                )
            entries.append(("persistent", (cls.__module__, cls.__qualname__)))
        else:
            host_model = _device_to_host(persistable)
            if modelfile.can_encode(host_model):
                entries.append(("arrays", host_model))
            else:
                entries.append(
                    ("pickle", pickle.dumps(host_model, protocol=4))
                )
    return entries


def serialize_models(
    algorithms: Sequence[Any], models: Sequence[Any], model_id: str
) -> bytes:
    """Build the persisted blob for all algorithm models of one engine
    instance (the makeSerializableModels pass, Engine.scala:286-304)."""
    entries = _manifest_entries(algorithms, models, model_id)
    if modelfile.mmap_enabled():
        return modelfile.serialize(entries, model_id)
    # legacy pickle manifest (PIO_MODEL_MMAP=0): arrays entries are just
    # pickled whole, as before
    manifests = [
        _Manifest(
            kind="pickle", payload=pickle.dumps(payload, protocol=4)
        ) if kind == "arrays" else _Manifest(kind=kind, payload=payload)
        for kind, payload in entries
    ]
    buf = io.BytesIO()
    pickle.dump(manifests, buf, protocol=4)
    return buf.getvalue()


def save_models(
    model_store: Any,
    algorithms: Sequence[Any],
    models: Sequence[Any],
    model_id: str,
) -> None:
    """Persist one engine instance's models into ``model_store``: one
    blob, or — where an array-table model is larger than a segment and
    the store keeps local files (``spanning_path``) — a model file that
    spans files (models/modelfile.py ``write_spanning``), written
    segment by segment without ever being one bytes object."""
    spanning_path = getattr(model_store, "spanning_path", None)
    if spanning_path is not None and modelfile.mmap_enabled():
        entries = _manifest_entries(algorithms, models, model_id)
        if any(k == "arrays" and modelfile.spans(m) for k, m in entries):
            modelfile.write_spanning(spanning_path(model_id), entries, model_id)
            return
        blob = modelfile.serialize(entries, model_id)
    else:
        blob = serialize_models(algorithms, models, model_id)
    from predictionio_tpu.data.storage.base import Model

    model_store.insert(Model(model_id, blob))


def _resolve_entries(
    entries: list[tuple[str, Any]],
    algorithms: Sequence[Any],
    model_id: str,
) -> list[Any]:
    import importlib

    if len(entries) != len(algorithms):
        raise ValueError(
            f"model blob has {len(entries)} models but engine has "
            f"{len(algorithms)} algorithms — variant/instance mismatch"
        )
    out: list[Any] = []
    for kind, payload in entries:
        if kind == "arrays":
            out.append(payload)
        elif kind == "pickle":
            out.append(pickle.loads(payload))
        elif kind == "persistent":
            module, qualname = payload
            cls: Any = importlib.import_module(module)
            for part in qualname.split("."):
                cls = getattr(cls, part)
            out.append(cls.load(model_id))
        elif kind == "retrain":
            out.append(RETRAIN)
        else:
            raise ValueError(f"unknown model manifest kind {kind!r}")
    return out


def deserialize_models(
    blob: bytes,
    algorithms: Sequence[Any],
    model_id: str,
) -> list[Any]:
    """Restore per-algorithm models; entries marked ``retrain`` come back
    as :data:`RETRAIN` and the deploy path re-trains them
    (prepareDeploy, Engine.scala:199-268). Model-file blobs decode to
    zero-copy views over ``blob``; legacy pickle manifests still load."""
    if modelfile.is_modelfile(blob):
        return _resolve_entries(
            modelfile.deserialize(blob), algorithms, model_id
        )
    manifests: list[_Manifest] = pickle.loads(blob)
    entries = [(m.kind, m.payload) for m in manifests]
    return _resolve_entries(entries, algorithms, model_id)


def deserialize_model_path(
    path: str | os.PathLike,
    algorithms: Sequence[Any],
    model_id: str,
) -> list[Any] | None:
    """Zero-copy deploy path: mmap the model file at ``path`` directly
    (shared process-wide, so N variants of one instance resolve to the
    SAME model objects). Returns None when the file is not the flat
    format (legacy pickle blob) — caller falls back to the byte read.
    Raises :class:`ModelFileError` on a corrupt/truncated file."""
    if not modelfile.mmap_enabled():
        return None
    p = os.fspath(path)
    try:
        with open(p, "rb") as f:
            magic = f.read(len(modelfile.MAGIC))
    except OSError:
        return None
    if not modelfile.is_modelfile(magic):
        return None
    entries = modelfile.shared_entries(p)
    return _resolve_entries(entries, algorithms, model_id)


class _Retrain:
    def __repr__(self) -> str:
        return "<RETRAIN: model must be re-trained on deploy>"


RETRAIN = _Retrain()
