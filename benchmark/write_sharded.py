"""write_sharded.py — persist seeded factor tables as a trained model that
SPANS FILES: what a marketplace's `pio train` leaves behind when the item
table alone (48.19 M x 64 f32 = 12.34 GB) is several times the largest file
the store's machine takes.

    python3 benchmark/write_sharded.py <spec.json>

Serve-only, as write_model.py. The tables are never whole anywhere: the
program's own spanning writer (``models/modelfile.py write_spanning``) cuts
every array row-wise into segments of at most ``segment_bytes`` (1 GiB) and
asks a row source for each block, which ``factor_blocks.SeededRows``
regenerates from the seed — a few threads fill, checksum and write the
segments side by side. A program without the spanning format is refused AT
ONCE (exit 2), by name, before a byte is written; so is a work directory that
cannot take the model (free space, and one file of a segment's size: PR 21
died on EFBIG). The head is read back through the program's loader before the
child exits 0. Imports the program's storage and model-file modules; touches
no device (the driver starts it with JAX_PLATFORMS=cpu).
Prints one JSON line: {"instance": id, "bytes": n, "segments": n, "seconds": {...}}.
"""

from __future__ import annotations

import errno
import json
import os
import sys
import time
from datetime import datetime, timezone

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import factor_blocks  # noqa: E402
import factors  # noqa: E402

CLS = ("predictionio_tpu.models.recommendation", "ALSModel")
HEADROOM = 1.1  # of the model's bytes, wanted free before anything is written


def spanning_format():
    """The program's model-file module if it can write a model that spans
    files, else SystemExit(2) naming what is absent."""
    try:
        from predictionio_tpu.models import modelfile
    except ImportError as e:
        raise SystemExit(f"write_sharded: no predictionio_tpu.models.modelfile ({e})")
    for name in ("write_spanning", "Fields", "EncodedIds", "SEGMENT_BYTES"):
        if not hasattr(modelfile, name):
            print(f"write_sharded: predictionio_tpu.models.modelfile has no {name!r}: "
                  "this program cannot write a model that spans files, and the cell's "
                  "model (12.6 GB) fits no single file", file=sys.stderr)
            raise SystemExit(2)
    return modelfile


def model_bytes(num_users: int, num_items: int, rank: int) -> int:
    """Bytes of the model on disk, to a few MB: two f32 tables, the ids'
    blobs (a prefix byte + up to 8 digits) and int64 offsets."""
    return sum(n * (rank * 4 + 8 + 1 + len(str(max(1, n - 1)))) for n in (num_users, num_items))


def dense_ids(prefix: bytes, n: int, workers: int = 8, span: int = 1 << 21):
    """``modelwriter.dense_id_blob(prefix, n)`` — the utf-8 blob and [n+1]
    int64 offsets of the ids prefix0 .. prefix<n-1> — made a span of ids at a
    time by a few threads, each span of one digit count filled column by
    column as modelwriter fills it: 48.19 M ids are 13 s of set-up through the
    one call. A test holds the two equal."""
    from concurrent.futures import ThreadPoolExecutor

    p = len(prefix)
    word = np.uint32 if n < 2 ** 32 else np.int64  # 32-bit division is 5x faster
    ten = word(10)
    cuts = sorted({0, n, *range(0, n, span), *(10 ** d for d in range(1, 19) if 10 ** d < n)})

    def block(lo_hi) -> np.ndarray:
        lo, hi = lo_hi
        d = len(str(lo))  # every id of the span has as many digits
        vals = np.arange(lo, hi, dtype=word)
        out = np.empty((hi - lo, p + d), np.uint8)
        out[:, :p] = np.frombuffer(prefix, np.uint8)
        for j in range(d):
            out[:, p + d - 1 - j] = 48 + vals % ten
            vals //= ten
        return out.reshape(-1)

    spans = list(zip(cuts[:-1], cuts[1:]))
    with ThreadPoolExecutor(max_workers=workers) as pool:
        parts = list(pool.map(block, spans))
    offs = np.zeros(n + 1, np.int64)
    lens = np.concatenate([np.full(hi - lo, p + len(str(lo)), np.int64) for lo, hi in spans]) \
        if spans else np.zeros(0, np.int64)
    np.cumsum(lens, out=offs[1:])
    blob = np.concatenate(parts) if parts else np.zeros(0, np.uint8)
    return blob, offs


def probe_directory(directory: str, want: int, one_file: int) -> None:
    """Fail by name where ``directory`` cannot take ``want`` bytes in all or
    ``one_file`` bytes in one file. Free space is asked of the file system;
    the one file is ALLOCATED (and removed), because a per-file limit
    (RLIMIT_FSIZE, a quota) shows only when it is hit — chip_smoke.py's
    disk_capacity() writes its probe out for that reason; allocation meets the
    same limits without the seconds 14 GB of zeros would add to every set-up."""
    st = os.statvfs(directory)
    free = st.f_bavail * st.f_frsize
    if free < want:
        raise SystemExit(f"write_sharded: {directory} has {free} bytes free, the model "
                         f"needs {want}")
    path = os.path.join(directory, "capacity.probe")
    try:
        fd = os.open(path, os.O_CREAT | os.O_WRONLY, 0o600)
        try:
            os.posix_fallocate(fd, 0, one_file)
        finally:
            os.close(fd)
    except OSError as e:
        if e.errno in (errno.EFBIG, errno.ENOSPC, errno.EDQUOT):
            raise SystemExit(f"write_sharded: {directory} refuses one file of {one_file} "
                             f"bytes ({errno.errorcode[e.errno]}): a segment cannot be "
                             "written there") from None
        if e.errno not in (errno.EOPNOTSUPP, errno.EINVAL):
            raise
    finally:
        try:
            os.unlink(path)
        except FileNotFoundError:
            pass


def main(argv: list[str]) -> int:
    with open(argv[1]) as fh:
        spec = json.load(fh)
    t = {}
    t0 = time.perf_counter()
    modelfile = spanning_format()
    from predictionio_tpu.data.storage import Storage
    from predictionio_tpu.data.storage.base import EngineInstance, EngineInstanceStatus

    t["import"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    nu, ni, rank, seed = spec["num_users"], spec["num_items"], spec["rank"], spec["seed"]
    segment = int(spec.get("segment_bytes") or modelfile.SEGMENT_BYTES)
    storage = Storage(env={k: v for k, v in os.environ.items() if k.startswith("PIO_")})
    models = storage.get_model_data_models()
    if not hasattr(models, "spanning_path"):
        print(f"write_sharded: the model store {type(models).__name__} has no "
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
    fields = modelfile.Fields(CLS, {
        "user_index": modelfile.EncodedIds(*dense_ids(b"u", nu, workers)),
        "item_index": modelfile.EncodedIds(*dense_ids(b"i", ni, workers)),
        "user_factors": factor_blocks.SeededRows(seed, factors.STREAM_USER_FACTORS, nu, rank),
        "item_factors": factor_blocks.SeededRows(seed, factors.STREAM_ITEM_FACTORS, ni, rank),
        "user_scales": None, "item_scales": None,
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
    # the file as the program's loader sees it: the shapes, and the last rows
    f = modelfile.load_path(head).fields(0)
    last = factor_blocks.rows(seed, factors.STREAM_ITEM_FACTORS, ni, rank, ni - 3, ni)
    if not (tuple(f["item_factors"].shape) == (ni, rank)
            and tuple(f["user_factors"].shape) == (nu, rank)
            and len(f["user_index"]) == nu and len(f["item_index"]) == ni
            and bool((np.asarray(f["item_factors"][ni - 3:ni]) == last).all())
            and f["item_index"].inverse[ni - 1] == f"i{ni - 1}"):
        print("write_sharded: the model does not load back as written", file=sys.stderr)
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
