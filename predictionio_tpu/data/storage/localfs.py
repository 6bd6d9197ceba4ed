"""Local-filesystem model store: one file per model id.

Capability parity with the reference's localfs backend
(storage/localfs/src/main/scala/.../LocalFSModels.scala — one file per
model id under ``PIO_FS_BASEDIR``).
"""

from __future__ import annotations

import os
from pathlib import Path
from urllib.parse import quote

from predictionio_tpu import faults
from predictionio_tpu.data.storage import base


class LocalFSStorageClient:
    def __init__(self, config: dict | None = None):
        self.config = config or {}
        self.base_path = Path(self.config.get("path", "~/.pio_tpu/models")).expanduser()
        self.base_path.mkdir(parents=True, exist_ok=True)


class LocalFSModels(base.Models):
    def __init__(self, client: LocalFSStorageClient):
        self._c = client

    def _path(self, model_id: str) -> Path:
        # percent-encoding keeps distinct ids on distinct files (injective)
        safe = quote(model_id, safe="")
        return self._c.base_path / f"pio_model_{safe}.bin"

    def insert(self, model: base.Model) -> None:
        # tmp + fsync + rename: a deploy that re-reads the model mid-write
        # (or a crash during a multi-GB publish) must never see a torn
        # file — same publish discipline as the event segments and the
        # columnar cache blocks
        path = self._path(model.id)
        tmp = path.with_name(path.name + f".tmp.{os.getpid()}")
        with open(tmp, "wb") as f:
            f.write(model.models)
            f.flush()
            faults.fault_point("storage.fsync")
            os.fsync(f.fileno())
        faults.fault_point("storage.rename")
        tmp.replace(path)

    def get(self, model_id: str) -> base.Model | None:
        p = self._path(model_id)
        if not p.exists():
            return None
        return base.Model(model_id, p.read_bytes())

    def local_path(self, model_id: str) -> str | None:
        p = self._path(model_id)
        return str(p) if p.exists() else None

    def spanning_path(self, model_id: str) -> str:
        """Where a model that spans files puts its head file
        (models/modelfile.py ``write_spanning``: the segments lie beside
        it under its name + ``.segNNNN``). Only a store of local files
        has one."""
        return str(self._path(model_id))

    def delete(self, model_id: str) -> bool:
        p = self._path(model_id)
        for seg in p.parent.glob(p.name + ".seg[0-9]*"):
            seg.unlink()
        if p.exists():
            p.unlink()
            return True
        return False
