"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once through the entry points a user calls, each a
`python -m predictionio_tpu.cli.main` child: `app new` -> `import` of a
seeded JSONL event file -> `train` (twice, against one compile cache) ->
`deploy` -> `POST /queries.json` (and a second `deploy` that serves the
same model through two-stage retrieval), on the zero-config sqlite + jsonl +
localfs storage under a run-local directory. The model is the
recommendation template's ALS at the ML-20M shape (138,493 users x 26,744
items, rank 20, explicit, f32, lambda 0.05, 3 iterations) over 20,000,000
events. The jsonl log is one file per app (5.3 GB at 20M), so the script
first writes a probe file to learn what the work directory (under
`$TMPDIR`) takes, per file or in all; where that is less than the run
needs, it cuts the event count to fit — never users, items or rank,
never below 2,000,000 — and prints the cut. Events reach `pio import` in
parts of 500,000, one part on disk at a time.

This process never imports jax: a chip belongs to one process at a time,
so every chip user is a child, started one at a time, and waited for
before the next starts. The run fails unless each child's OWN report
says it computed on a TPU (the trainer through `train_progress.json`,
the server through `/stats.json`), unless the native codec is built, and
unless the answers agree with a plain NumPy reference computed here from
the persisted model. There is no CPU fallback: on any other platform the
script names what it found and exits non-zero without a result line.

With four or more devices it also runs the sharded leg (`--mesh data=4`
train, ring top-k serving).

The storefront leg runs the E-Commerce template's whole path on the chip at
a small shape: item `$set`s with categories and view/buy events imported,
`pio train`, `pio deploy` over the two-stage threshold, the three query
kinds of a storefront (home, category page, cart blackList) held to a NumPy
reference of the business rules, then a live `$set unavailableItems` and a
`view`, both gone from the next answer. The item-page leg then trains the
Similar Product template on the same app and serves its three query kinds
(similar, same category, session with a blackList) over the same threshold,
held to the f32 cosines of the persisted model and to the masked sum-rows
rescore having been staged with no temporary bytes. Before both, the int8
leg trains the same events with ``storage_dtype="int8"`` and serves that
model over the threshold: the int8 coarse scan and the dequantizing rescore,
held to the f32 product of the persisted model's dequantized rows.

Last stdout line on success:
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""

from __future__ import annotations

import argparse
import contextlib
import copy
import errno
import http.client
import importlib.metadata
import json
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time

import numpy as np

import predictionio_tpu  # places the compile cache in os.environ (no jax)
from predictionio_tpu import native
from predictionio_tpu.data.storage import Storage
from predictionio_tpu.models import modelfile

REPO = os.path.dirname(os.path.abspath(__file__))

# The north-star shape (BASELINE.json): MovieLens-20M with its degree caps.
NUM_USERS, NUM_ITEMS = 138_493, 26_744
FULL_EVENTS = 20_000_000
MIN_EVENTS = 2_000_000  # the event count may be cut to fit; never below this
MAX_USER_DEGREE, MAX_ITEM_DEGREE = 9_254, 67_310  # at FULL_EVENTS
RANK, ITERATIONS, LAMBDA = 20, 3, 0.05
TOP_K = 10
KNOWN_QUERIES = 20
TWO_STAGE_QUERIES, TWO_STAGE_THRESHOLD = 5, 100  # the second serve leg
RMSE_SAMPLE = 1_000_000
DEADLINE_S = 1200.0

# What a run leaves in its work directory, measured at this shape (PR 21, 2M
# events): per event, the jsonl event log 263 B + its columnar sidecar 33 B +
# the packed-prep entry 70 B; beside them one import part, and the model
# files, sqlite database and child logs. The log is one file per app, so a
# machine's per-file limit bounds the event count like its free space does.
DISK_BYTES_PER_EVENT = 263 + 33 + 70
IMPORT_PART_EVENTS = 500_000  # written, imported, overwritten: 176 B per event
DISK_BYTES_FIXED = IMPORT_PART_EVENTS * 176 + (64 << 20)
DISK_MARGIN = 0.9  # of what the probe found

# Serving scores are f32 matmuls at DEFAULT precision (ops/topk.py), which a
# TPU may run as one bf16 pass. Measured on TPU v5 lite (PR 21, one query
# per dispatch): every served score within 1e-6 of the f32 reference and
# overlap@10 = 1.0 on all 20 queries — at batch 1 the chip keeps f32. So
# the tolerance is ~100x the measurement, not a bf16 allowance; a served
# item may differ from the reference list only as a near-tie inside it.
# Batched dispatches (the micro-batcher under load) are not covered here.
SCORE_TOL = 1e-4
MIN_OVERLAP = 0.9  # per query, |served top-10 ∩ reference top-10| / 10

VARIANT = {
    "id": "chip-smoke",
    "engineFactory": "predictionio_tpu.models.recommendation.engine",
    "datasource": {"params": {"app_name": "ChipSmoke"}},
    "algorithms": [{"name": "als", "params": {
        "rank": RANK, "num_iterations": ITERATIONS, "lambda_": LAMBDA,
    }}],
}


class SmokeFailure(Exception):
    """A phase failed; the message says which and why."""


class Smoke:
    """One run: the work directory, the children's environment, the
    deadline, and every child started (so all are stopped on exit)."""

    def __init__(self, workdir: str, deadline_s: float, platform: str):
        self.dir = workdir
        self.platform = platform  # what every child must report
        self.t_end = time.monotonic() + deadline_s
        self.children: list[subprocess.Popen] = []
        store = {
            "PIO_FS_BASEDIR": os.path.join(workdir, "store"),
            "PIO_RUN_DIR": os.path.join(workdir, "run"),
            "PIO_PREP_CACHE_DIR": os.path.join(workdir, "prep"),
            "PIO_STORAGE_SOURCES_DB_TYPE": "sqlite",
            "PIO_STORAGE_SOURCES_DB_PATH": os.path.join(workdir, "pio.db"),
            "PIO_STORAGE_SOURCES_LOG_TYPE": "jsonl",
            "PIO_STORAGE_SOURCES_LOG_PATH": os.path.join(workdir, "events"),
            "PIO_STORAGE_SOURCES_FS_TYPE": "localfs",
            "PIO_STORAGE_SOURCES_FS_PATH": os.path.join(workdir, "models"),
            "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "DB",
            "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "LOG",
            "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "FS",
        }
        self.env = {
            **os.environ, **store,
            "PYTHONPATH": REPO + os.pathsep + os.environ.get("PYTHONPATH", ""),
        }
        # the same storage, read here (sqlite + localfs: no jax) to find
        # the persisted model files
        self.storage = Storage(env=store)

    # -- children ---------------------------------------------------------
    def remaining(self, what: str) -> float:
        left = self.t_end - time.monotonic()
        if left <= 0:
            raise SmokeFailure(f"deadline passed before {what}")
        return left

    def spawn(self, argv: list[str], log: str, **env) -> subprocess.Popen:
        with open(os.path.join(self.dir, log), "wb") as fh:
            proc = subprocess.Popen(
                [sys.executable, *argv], cwd=self.dir, env={**self.env, **env},
                stdin=subprocess.DEVNULL, stdout=fh, stderr=subprocess.STDOUT,
            )
        self.children.append(proc)
        return proc

    def log_tail(self, log: str, n: int = 1500) -> str:
        with open(os.path.join(self.dir, log), "rb") as fh:
            fh.seek(0, os.SEEK_END)
            fh.seek(max(0, fh.tell() - n))
            return fh.read().decode("utf-8", "replace")

    def run(self, name: str, argv: list[str], **env) -> tuple[str, float]:
        """Run one child to its end; return (its output, wall seconds)."""
        log = f"{name}.log"
        t0 = time.perf_counter()
        proc = self.spawn(argv, log, **env)
        try:
            rc = proc.wait(timeout=self.remaining(name))
        except subprocess.TimeoutExpired:
            raise SmokeFailure(f"{name}: still running at the deadline") from None
        wall = time.perf_counter() - t0
        if rc != 0:
            raise SmokeFailure(f"{name}: exit {rc}\n{self.log_tail(log)}")
        with open(os.path.join(self.dir, log), encoding="utf-8",
                  errors="replace") as fh:
            return fh.read(), wall

    def pio(self, name: str, *args: str, **env) -> tuple[str, float]:
        return self.run(name, ["-m", "predictionio_tpu.cli.main", *args], **env)

    def stop_all(self) -> None:
        """Stop whatever is still running: SIGTERM first — a killed chip
        holder can leave the chip locked for the next process."""
        live = [p for p in self.children if p.poll() is None]
        for p in live:
            p.terminate()
        for p in live:
            try:
                p.wait(timeout=30)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()


# The storefront leg (the E-Commerce template): small, so that training is
# seconds; over SHOP_THRESHOLD rows, so that the rules run inside the coarse
# scan and the rescore as they do at a storefront's size.
SHOP_USERS, SHOP_ITEMS, SHOP_CATEGORIES, SHOP_EVENTS = 3_000, 20_000, 40, 150_000
SHOP_RANK, SHOP_THRESHOLD, SHOP_UNAVAILABLE, SHOP_QUERIES = 16, 1_000, 200, 4
SHOP_VARIANT = {
    "id": "chip-smoke-shop",
    "engineFactory": "predictionio_tpu.models.ecommerce.engine",
    "datasource": {"params": {"appName": "ChipShop"}},
    "algorithms": [{"name": "als", "params": {
        "appName": "ChipShop", "unseenOnly": True, "seenEvents": ["buy", "view"],
        "rank": SHOP_RANK, "numIterations": ITERATIONS, "lambda": 0.01,
    }}],
}

# The item-page leg (the Similar Product template) trains on the same app's
# item `$set`s and views and is served over the same threshold.
SIMILAR_VARIANT = {
    "id": "chip-smoke-similar",
    "engineFactory": "predictionio_tpu.models.similarproduct.engine",
    "datasource": {"params": {"appName": "ChipShop"}},
    "algorithms": [{"name": "als", "params": {
        "rank": SHOP_RANK, "numIterations": ITERATIONS, "lambda": 0.01,
    }}],
}

# -- seeded event data ------------------------------------------------------


def make_ratings(seed: int, n: int, num_users: int, num_items: int):
    """(rows, cols, vals): n ratings at a MovieLens-shaped distribution —
    Pareto popularity tails capped at the real datasets' degree maxima, a
    rank-8 ground truth plus noise rounded to 1..5 stars — covering every
    user and every item at least once, so the model is full width."""
    rng = np.random.default_rng(seed)

    def capped(weights, cap):
        p = weights / weights.sum()
        for _ in range(16):  # cap-and-renormalize to a fixed point
            p = np.minimum(p, cap)
            p /= p.sum()
            if p.max() <= cap * 1.001:
                break
        return p

    user_p = capped(rng.pareto(1.2, num_users) + 1, MAX_USER_DEGREE / FULL_EVENTS)
    item_p = capped(rng.pareto(1.1, num_items) + 1, MAX_ITEM_DEGREE / FULL_EVENTS)
    rows = rng.choice(num_users, n, p=user_p).astype(np.int32)
    cols = rng.choice(num_items, n, p=item_p).astype(np.int32)
    rows[:num_users] = rng.permutation(num_users)
    cols[:num_items] = rng.permutation(num_items)
    gt = 8
    U = (rng.normal(size=(num_users, gt)) / np.sqrt(gt)).astype(np.float32)
    V = (rng.normal(size=(num_items, gt)) / np.sqrt(gt)).astype(np.float32)
    vals = np.empty(n, np.int8)
    for lo in range(0, n, 2_000_000):  # bound the gather temporaries
        hi = min(lo + 2_000_000, n)
        raw = (U[rows[lo:hi]] * V[cols[lo:hi]]).sum(1)
        raw += 0.3 * rng.standard_normal(hi - lo).astype(np.float32)
        vals[lo:hi] = np.clip(np.round(3.0 + 1.5 * raw), 1, 5)
    return rows, cols, vals


def write_events(path: str, rows, cols, vals) -> None:
    """One `rate` event per rating, in the `pio import` JSON-lines format."""
    head = [
        b'{"event":"rate","entityType":"user","entityId":"u%d",'
        b'"targetEntityType":"item","targetEntityId":"' % u
        for u in range(int(rows.max()) + 1)
    ]
    mid = [b'i%d","properties":{"rating":' % i for i in range(int(cols.max()) + 1)]
    tail = [
        b'%d.0},"eventTime":"2020-01-01T00:00:00.000Z"}\n' % v for v in range(6)
    ]
    with open(path, "wb") as fh:
        for lo in range(0, len(rows), 500_000):
            sl = slice(lo, lo + 500_000)
            fh.write(b"".join([
                head[r] + mid[c] + tail[v]
                for r, c, v in zip(
                    rows[sl].tolist(), cols[sl].tolist(), vals[sl].tolist()
                )
            ]))


# -- what the work directory can hold -------------------------------------------


def disk_need(events: int) -> int:
    return events * DISK_BYTES_PER_EVENT + DISK_BYTES_FIXED


def disk_capacity(directory: str, want: int) -> int:
    """Bytes ``directory`` takes in one file, up to ``want`` — found by
    writing them, because a machine's limit (RLIMIT_FSIZE, a quota, a small
    overlay) shows only when it is hit. The file is gone on return."""
    block = b"\xa5" * (8 << 20)
    path = os.path.join(directory, "capacity.probe")
    wrote = 0
    try:
        with open(path, "wb", buffering=0) as fh:
            while wrote < want:
                wrote += fh.write(block[: want - wrote])
    except OSError as e:
        if e.errno not in (errno.EFBIG, errno.ENOSPC, errno.EDQUOT):
            raise
    finally:
        with contextlib.suppress(FileNotFoundError):
            os.unlink(path)
    return wrote


def events_that_fit(capacity: int) -> int:
    """The event count whose files all fit in ``capacity`` bytes, whether
    that bounds one file or the directory (the sum is the safe reading)."""
    return max(0, int(capacity * DISK_MARGIN - DISK_BYTES_FIXED)) // DISK_BYTES_PER_EVENT


# -- the trainer child --------------------------------------------------------

_COMPILED = re.compile(r"Finished XLA compilation of .+? in ([0-9.]+) sec")


def cache_entries(cache_dir: str) -> int:
    try:
        return len(os.listdir(cache_dir))
    except FileNotFoundError:
        return 0


def train(smoke: Smoke, name: str, *args: str) -> dict:
    """One `pio train` child. Returns its wall time, compile seconds (the
    sum of jax's own 'Finished XLA compilation' log lines — on a cache
    hit that is the load time), persistent-cache hits and entries added,
    the engine instance id, and the trainer's own account of where it ran
    (train_progress.json), which must say tpu."""
    cache_dir = smoke.env["JAX_COMPILATION_CACHE_DIR"]
    before = cache_entries(cache_dir)
    out, wall = smoke.pio(name, "train", *args, JAX_LOG_COMPILES="1")
    compiles = [float(s) for s in _COMPILED.findall(out)]
    if not compiles:
        raise SmokeFailure(f"{name}: jax logged no compilation — log format drift?")
    done = re.search(r"Training completed\. Engine instance ID: (\w+)", out)
    if not done:
        raise SmokeFailure(f"{name}: no completion line\n{smoke.log_tail(name + '.log')}")
    with open(os.path.join(smoke.env["PIO_RUN_DIR"], "train_progress.json")) as fh:
        progress = json.load(fh)
    reading = {
        "wall_s": wall,
        "compile_s": sum(compiles),
        "programs": len(compiles),
        "cache_hits": out.count("Persistent compilation cache hit"),
        "cache_entries_added": cache_entries(cache_dir) - before,
        "instance": done.group(1),
        "trainer": progress.get("trainer"),
        "mesh": progress.get("mesh"),
        "prep_cache": progress.get("prep_cache"),
        **{k: progress.get(k) for k in ("platform", "device_kind", "device_count")},
    }
    print(f"{name}: {json.dumps(reading)}", flush=True)
    if progress.get("state") != "done" or progress.get("iteration") != ITERATIONS:
        raise SmokeFailure(f"{name}: progress file says {progress}")
    return reading


# -- the NumPy reference ------------------------------------------------------


class Reference:
    """The persisted model as plain arrays, and the answers it implies."""

    def __init__(self, smoke: Smoke, instance: str, num_users: int, num_items: int):
        path = smoke.storage.get_model_data_models().local_path(instance)
        fields = modelfile.load_path(path).fields(0)
        self.U = np.asarray(fields["user_factors"], np.float32)
        self.V = np.asarray(fields["item_factors"], np.float32)
        self.quantized = fields["item_factors"].dtype == np.int8
        if self.quantized:  # an int8 model IS its dequantized rows
            self.U *= np.asarray(fields["user_scales"], np.float32)[:, None]
            self.V *= np.asarray(fields["item_scales"], np.float32)[:, None]
        if self.U.shape != (num_users, RANK) or self.V.shape != (num_items, RANK):
            raise SmokeFailure(
                f"model {instance}: factors {self.U.shape} x {self.V.shape}, "
                f"expected ({num_users}, {RANK}) x ({num_items}, {RANK})"
            )
        if not (np.isfinite(self.U).all() and np.isfinite(self.V).all()):
            raise SmokeFailure(f"model {instance}: non-finite factors")
        # generated id n ("u<n>" / "i<n>") -> factor row
        self.user_row = np.empty(num_users, np.int64)
        for sid, pos in fields["user_index"].items():
            self.user_row[int(sid[1:])] = pos
        self.item_row = np.empty(num_items, np.int64)
        self.item_of_row = np.empty(num_items, np.int64)
        for sid, pos in fields["item_index"].items():
            self.item_row[int(sid[1:])] = pos
            self.item_of_row[pos] = int(sid[1:])

    def rmse(self, rows, cols, vals) -> float:
        pred = np.einsum(
            "nd,nd->n", self.U[self.user_row[rows]], self.V[self.item_row[cols]]
        )
        return float(np.sqrt(np.mean((pred - vals) ** 2)))

    def scores(self, user: int) -> np.ndarray:
        """f32 score of every item for generated user id ``user``, indexed
        by generated item id."""
        by_row = self.V @ self.U[self.user_row[user]]
        out = np.empty_like(by_row)
        out[self.item_of_row] = by_row
        return out


# -- the server child -----------------------------------------------------------


def _http(port: int, method: str, path: str, body: bytes | None = None,
          timeout: float = 60.0):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request(method, path, body=body,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def check_answer(ref: Reference, who: str, user: int, got: list[dict]):
    """One answer against the reference: (items, score deviation, overlap)."""
    items = [int(e["item"][1:]) for e in got]
    served = np.asarray([e["score"] for e in got], np.float32)
    if len(got) != TOP_K or len(set(items)) != TOP_K:
        raise SmokeFailure(f"{who}: {len(got)} items, expected {TOP_K} distinct")
    if not np.isfinite(served).all() or (np.diff(served) > 0).any():
        raise SmokeFailure(f"{who}: scores not finite descending: {served}")
    want = ref.scores(user)
    top = np.argsort(-want, kind="stable")[:TOP_K]
    dev = float(np.abs(served - want[items]).max())
    overlap = len(set(items) & set(top.tolist())) / TOP_K
    # an item may differ from the reference list only as a near-tie
    short = float(want[top[-1]] - want[items].min())
    if dev > SCORE_TOL or short > SCORE_TOL or overlap < MIN_OVERLAP:
        raise SmokeFailure(
            f"{who}: score deviation {dev:.3g}, worst item {short:.3g} below "
            f"the reference cut, overlap@{TOP_K} {overlap} (tolerance "
            f"{SCORE_TOL}, {MIN_OVERLAP})\nserved {items}\nreference {top.tolist()}"
        )
    return items, dev, overlap


def wait_until_serving(smoke: Smoke, proc, port: int, name: str, log: str) -> None:
    while True:  # model load + warmup compile happen before the bind
        if proc.poll() is not None:
            raise SmokeFailure(f"{name}: exited {proc.returncode} before "
                               f"serving\n{smoke.log_tail(log)}")
        smoke.remaining(f"{name} came up")
        try:
            if _http(port, "GET", "/stats.json", timeout=2.0)[0] == 200:
                return
        except (OSError, http.client.HTTPException):
            pass
        time.sleep(0.5)


def stop_server(proc, port: int, name: str) -> None:
    try:
        _http(port, "POST", "/stop")
    except (OSError, http.client.HTTPException):
        pass  # it may hang up while going down; the wait decides
    try:
        proc.wait(timeout=60)
    except subprocess.TimeoutExpired:
        proc.terminate()
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            raise SmokeFailure(f"{name}: ignored /stop and SIGTERM") from None


def serve_and_check(smoke: Smoke, name: str, variant_file: str, ref: Reference,
                    users: list[int], **env) -> dict:
    """One `pio deploy` child: wait until it answers, ask it about the
    known users and one unknown user, check every answer against the
    reference, read its own device report and what its rescore programs
    need in temporary memory, stop it and wait for it."""
    port = free_port()
    log = f"{name}.log"
    t0 = time.perf_counter()
    proc = smoke.spawn(
        ["-m", "predictionio_tpu.cli.main", "deploy", "--variant", variant_file,
         "--ip", "127.0.0.1", "--port", str(port)], log, **env,
    )
    try:
        wait_until_serving(smoke, proc, port, name, log)
        ready_s = time.perf_counter() - t0

        overlaps, deviations, lists, latencies = [], [], {}, []
        for user in [*users, None]:  # None: a user the model never saw
            body = {"user": "nobody" if user is None else f"u{user}", "num": TOP_K}
            t1 = time.perf_counter()
            status, raw = _http(port, "POST", "/queries.json",
                                json.dumps(body).encode())
            latencies.append(time.perf_counter() - t1)
            if status != 200:
                raise SmokeFailure(f"{name}: {body} -> HTTP {status} {raw[:300]!r}")
            got = json.loads(raw)["itemScores"]
            if user is None:
                if got:
                    raise SmokeFailure(f"{name}: unknown user got {got[:3]}")
                continue
            lists[user], dev, overlap = check_answer(ref, f"{name}: u{user}", user, got)
            deviations.append(dev)
            overlaps.append(overlap)

        stats = json.loads(_http(port, "GET", "/stats.json")[1])
        devices = stats["device"]["devices"]
        if {d["device"].split(":")[0] for d in devices} != {smoke.platform}:
            raise SmokeFailure(f"{name}: the server reports devices {devices}")
        # a rescore program that re-lays a factor table before it gathers
        # from it needs a table's worth of temporary memory, on every call
        # (on the chip: XLA:CPU has one layout, and the dry run's toy table
        # is smaller than a program's honest temporaries)
        temp = stats["retrieval"]["rescore_temp_bytes"]
        if smoke.platform == "tpu" and any(
            n > ref.V.nbytes // 100 for n in temp.values()
        ):
            raise SmokeFailure(
                f"{name}: a rescore program's temporaries {temp} pass 1 % of "
                f"the item table's {ref.V.nbytes} bytes"
            )
        reading = {
            "ready_s": ready_s,
            "queries_200": len(latencies),
            "query_s_first": latencies[0],
            "query_s_median": float(np.median(latencies[:-1])),
            "score_deviation_max": max(deviations),
            "overlap_min": min(overlaps),
            "overlap_mean": float(np.mean(overlaps)),
            "exact_lists": sum(o == 1.0 for o in overlaps),
            "devices": devices,
            "two_stage_queries": stats["retrieval"]["two_stage_queries"],
            "sharded_queries": stats["retrieval"].get("sharded_queries", 0),
            "shards": stats["retrieval"].get("shards", 0),
            "rescore_temp_bytes": temp,
            "coarse_mode": stats["retrieval"].get("coarse_mode"),
            "resident_bytes": stats["retrieval"].get("resident_bytes"),
        }
        stop_server(proc, port, name)
    finally:
        smoke.stop_all()
    print(f"{name}: {json.dumps(reading)}", flush=True)
    reading["lists"] = lists
    return reading


# -- the storefront leg -----------------------------------------------------------


def _ask(port: int, name: str, body: dict) -> list[dict]:
    status, raw = _http(port, "POST", "/queries.json", json.dumps(body).encode())
    if status != 200:
        raise SmokeFailure(f"{name}: {body} -> HTTP {status} {raw[:300]!r}")
    return json.loads(raw)["itemScores"]


def storefront_leg(smoke: Smoke, seed: int, scale: int) -> None:
    """The E-Commerce template end to end: events -> train -> deploy -> the
    three query kinds -> a live `$set` and `view`, every answer held to the
    top of its ALLOWED set by the f32 scores of the persisted model."""
    from predictionio_tpu.data.event import Event

    rng = np.random.default_rng(seed + 2)
    users, items, n = SHOP_USERS // scale, SHOP_ITEMS // scale, SHOP_EVENTS // scale
    cat = rng.integers(0, SHOP_CATEGORIES, items)
    who = rng.integers(0, users, n)
    what = np.minimum((items * rng.random(n) ** 2).astype(np.int64), items - 1)
    who[:users], what[:items] = rng.permutation(users), rng.permutation(items)
    smoke.pio("shop_app_new", "app", "new", "ChipShop")
    path = os.path.join(smoke.dir, "shop.jsonl")
    stamp = b'"eventTime":"2020-01-01T00:00:00.000Z"}\n'
    with open(path, "wb") as fh:
        fh.write(b"".join(
            b'{"event":"$set","entityType":"item","entityId":"i%d","properties":'
            b'{"categories":["c%d"]},' % (i, c) + stamp for i, c in enumerate(cat.tolist())))
        fh.write(b"".join(
            b'{"event":"%s","entityType":"user","entityId":"u%d","targetEntityType":'
            b'"item","targetEntityId":"i%d",' % (b"buy" if j % 50 == 0 else b"view", u, i)
            + stamp for j, (u, i) in enumerate(zip(who.tolist(), what.tolist()))))
    smoke.pio("shop_import", "import", "--appid-or-name", "ChipShop", "--input", path)
    os.unlink(path)
    with open(os.path.join(smoke.dir, "shop.json"), "w") as fh:
        json.dump(SHOP_VARIANT, fh)
    trained = train(smoke, "shop_train", "--variant", "shop.json")
    if trained["platform"] != smoke.platform:
        raise SmokeFailure(f"shop_train: the trainer reports {trained}")

    fields = modelfile.load_path(
        smoke.storage.get_model_data_models().local_path(trained["instance"])).fields(0)
    U, V = (np.asarray(fields[k], np.float32) for k in ("user_factors", "item_factors"))
    item_row = {sid: pos for sid, pos in fields["item_index"].items()}
    row_item = {pos: sid for sid, pos in item_row.items()}
    cat_of_row = np.asarray(fields["item_categories"])[:, 0]
    cat_id = dict(fields["category_index"].items())
    unavailable = {f"i{i}" for i in rng.choice(items, SHOP_UNAVAILABLE // scale, replace=False)}
    app_id = smoke.storage.get_metadata_apps().get_by_name("ChipShop").id
    events = smoke.storage.get_events()

    def set_unavailable():
        events.insert(Event(event="$set", entity_type="constraint",
                            entity_id="unavailableItems",
                            properties={"items": sorted(unavailable)}), app_id)

    set_unavailable()
    seen = {u: {f"i{i}" for i in what[who == u].tolist()} for u in range(users)}

    def check(name, user, got, category=None, black=()):
        allowed = np.ones(len(V), bool)
        allowed[[item_row[i] for i in seen[user] | unavailable | set(black)
                 if i in item_row]] = False
        if category is not None:
            allowed &= cat_of_row == cat_id[category]
        want = np.where(allowed, V @ U[fields["user_index"][f"u{user}"]], -np.inf)
        top = np.argsort(-want, kind="stable")[:TOP_K]
        rows = [item_row[e["item"]] for e in got]
        served = np.asarray([e["score"] for e in got], np.float32)
        if len(rows) != TOP_K or len(set(rows)) != TOP_K or not allowed[rows].all():
            raise SmokeFailure(f"{name}: served {[e['item'] for e in got]}: "
                               f"{TOP_K} distinct allowed items expected")
        dev = float(np.abs(served - want[rows]).max())
        overlap = len(set(rows) & set(top.tolist())) / TOP_K
        if dev > SCORE_TOL or overlap < MIN_OVERLAP or (np.diff(served) > 0).any():
            raise SmokeFailure(
                f"{name}: score deviation {dev:.3g}, overlap@{TOP_K} {overlap}\n"
                f"served {[e['item'] for e in got]}\nreference {[row_item[r] for r in top]}")
        return dev, overlap

    port = free_port()
    proc = smoke.spawn(
        ["-m", "predictionio_tpu.cli.main", "deploy", "--variant", "shop.json",
         "--ip", "127.0.0.1", "--port", str(port)], "shop_deploy.log",
        PIO_RETRIEVAL_THRESHOLD=str(SHOP_THRESHOLD // scale),
        PIO_RETRIEVAL_TILE=str(4096 // scale), PIO_RETRIEVAL_PROBE_EVERY="3",
    )
    try:
        wait_until_serving(smoke, proc, port, "shop_deploy", "shop_deploy.log")
        devs, overlaps = [], []
        for user in rng.choice(users, SHOP_QUERIES, replace=False).tolist():
            category = f"c{int(rng.integers(0, SHOP_CATEGORIES))}"
            black = [f"i{i}" for i in rng.integers(0, items, 3)]
            who_ = f"u{user}"
            for name, body, kw in (
                ("home", {}, {}),
                ("category", {"categories": [category]}, {"category": category}),
                ("cart", {"blackList": black}, {"black": black}),
            ):
                got = _ask(port, f"shop {name} {who_}", {"user": who_, "num": TOP_K, **body})
                d, o = check(f"shop {name} {who_}", user, got, **kw)
                devs.append(d)
                overlaps.append(o)
        # the live rules: the top item goes out of stock, the second is viewed
        first = _ask(port, "shop live", {"user": who_, "num": TOP_K})
        unavailable.add(first[0]["item"])
        set_unavailable()
        events.insert(Event(event="view", entity_type="user", entity_id=who_,
                            target_entity_type="item",
                            target_entity_id=first[1]["item"]), app_id)
        seen[user].add(first[1]["item"])
        again = _ask(port, "shop live", {"user": who_, "num": TOP_K})
        if {first[0]["item"], first[1]["item"]} & {e["item"] for e in again}:
            raise SmokeFailure(f"shop live: {first[:2]} still served after the $set "
                               f"and the view: {again}")
        check("shop live", user, again)
        stats = json.loads(_http(port, "GET", "/stats.json")[1])
        devices = stats["device"]["devices"]
        programs = stats["retrieval"]["rescore_temp_bytes"]
        if {d["device"].split(":")[0] for d in devices} != {smoke.platform}:
            raise SmokeFailure(f"shop_deploy: the server reports devices {devices}")
        if ("retrieval.rescore_vectors_masked" not in programs
                or stats["retrieval"]["exact_queries"]):
            raise SmokeFailure(
                f"shop_deploy: programs {programs}, {stats['retrieval']['exact_queries']} "
                "exact queries: the rules did not run inside two-stage retrieval")
        print("shop_deploy: " + json.dumps({
            "queries_200": len(devs) + 2, "score_deviation_max": max(devs),
            "overlap_min": min(overlaps), "two_stage_queries":
            stats["retrieval"]["two_stage_queries"], "probes": stats["retrieval"]["probes"],
            "rescore_temp_bytes": programs}), flush=True)
        stop_server(proc, port, "shop_deploy")
    finally:
        smoke.stop_all()


def similar_leg(smoke: Smoke, seed: int, scale: int) -> None:
    """The Similar Product template end to end, on the storefront leg's app:
    train -> deploy over the threshold -> the item page's three query kinds,
    every answer held to the top of its ALLOWED set (the query's own items, its
    blackList and every item outside its category removed) by the f32 cosines
    of the persisted model."""
    rng = np.random.default_rng(seed + 3)
    with open(os.path.join(smoke.dir, "similar.json"), "w") as fh:
        json.dump(SIMILAR_VARIANT, fh)
    trained = train(smoke, "similar_train", "--variant", "similar.json")
    if trained["platform"] != smoke.platform:
        raise SmokeFailure(f"similar_train: the trainer reports {trained}")
    fields = modelfile.load_path(
        smoke.storage.get_model_data_models().local_path(trained["instance"])).fields(0)
    V = np.asarray(fields["item_factors"], np.float32)
    unit = V / np.maximum(np.linalg.norm(V, axis=1, keepdims=True), 1e-12)
    item_row = dict(fields["item_index"].items())
    row_item = {pos: sid for sid, pos in item_row.items()}
    cat_of_row = np.asarray(fields["item_categories"])[:, 0]
    cat_name = {pos: name for name, pos in fields["category_index"].items()}

    def check(name, body, got):
        own = [item_row[i] for i in body["items"]]
        allowed = np.ones(len(unit), bool)
        allowed[own + [item_row[i] for i in body.get("blackList", ())]] = False
        for c in body.get("categories", ()):
            allowed &= cat_of_row == fields["category_index"][c]
        want = np.where(allowed, unit @ unit[own].sum(axis=0), -np.inf)
        top = np.argsort(-want, kind="stable")[:TOP_K]
        rows = [item_row[e["item"]] for e in got]
        served = np.asarray([e["score"] for e in got], np.float32)
        if len(rows) != TOP_K or len(set(rows)) != TOP_K or not allowed[rows].all():
            raise SmokeFailure(f"{name}: served {[e['item'] for e in got]}: "
                               f"{TOP_K} distinct allowed items expected")
        dev = float(np.abs(served - want[rows]).max())
        overlap = len(set(rows) & set(top.tolist())) / TOP_K
        if dev > SCORE_TOL or overlap < MIN_OVERLAP or (np.diff(served) > 0).any():
            raise SmokeFailure(
                f"{name}: score deviation {dev:.3g}, overlap@{TOP_K} {overlap}\n"
                f"served {[e['item'] for e in got]}\nreference {[row_item[r] for r in top]}")
        return dev, overlap

    port = free_port()
    proc = smoke.spawn(
        ["-m", "predictionio_tpu.cli.main", "deploy", "--variant", "similar.json",
         "--ip", "127.0.0.1", "--port", str(port)], "similar_deploy.log",
        PIO_RETRIEVAL_THRESHOLD=str(SHOP_THRESHOLD // scale),
        PIO_RETRIEVAL_TILE=str(4096 // scale), PIO_RETRIEVAL_PROBE_EVERY="3",
    )
    try:
        wait_until_serving(smoke, proc, port, "similar_deploy", "similar_deploy.log")
        devs, overlaps = [], []
        for lead in rng.choice(len(unit), SHOP_QUERIES, replace=False).tolist():
            strip = [row_item[r] for r in rng.choice(len(unit), 4, replace=False).tolist()]
            black = [row_item[r] for r in rng.integers(0, len(unit), 3).tolist()]
            for name, body in (
                ("similar", {"items": [row_item[lead]]}),
                ("same_category", {"items": [row_item[lead]],
                                   "categories": [cat_name[int(cat_of_row[lead])]]}),
                ("session", {"items": strip, "blackList": black}),
            ):
                label = f"similar {name} {body['items'][0]}"
                got = _ask(port, label, {"num": TOP_K, **body})
                d, o = check(label, body, got)
                devs.append(d)
                overlaps.append(o)
        stats = json.loads(_http(port, "GET", "/stats.json")[1])
        devices = stats["device"]["devices"]
        programs = stats["retrieval"]["rescore_temp_bytes"]
        if {d["device"].split(":")[0] for d in devices} != {smoke.platform}:
            raise SmokeFailure(f"similar_deploy: the server reports devices {devices}")
        # a rescore that re-lays its table needs a table's worth of temporary
        # bytes (0 on the chip; XLA:CPU keeps ~1 KB of scratch)
        if (programs.get("retrieval.rescore_sum_rows_masked", V.nbytes) >= V.nbytes
                or stats["retrieval"]["exact_queries"]):
            raise SmokeFailure(
                f"similar_deploy: programs {programs}, {stats['retrieval']['exact_queries']} "
                "exact queries: the filters did not run inside two-stage retrieval, or "
                "the rescore re-lays its table")
        print("similar_deploy: " + json.dumps({
            "queries_200": len(devs), "score_deviation_max": max(devs),
            "overlap_min": min(overlaps), "two_stage_queries":
            stats["retrieval"]["two_stage_queries"], "probes": stats["retrieval"]["probes"],
            "host_reads": stats["retrieval"]["host_reads"],
            "rescore_temp_bytes": programs}), flush=True)
        stop_server(proc, port, "similar_deploy")
    finally:
        smoke.stop_all()


# -- the run ----------------------------------------------------------------------


def probe_device(smoke: Smoke) -> dict:
    """A short-lived child says what jax finds; it has exited (and let
    the chip go) before anything else starts."""
    out, _ = smoke.run("probe", ["-c", (
        "import json; from predictionio_tpu.obs.device import where; "
        "print(json.dumps(where()))"
    )])
    w = json.loads(out.strip().splitlines()[-1])
    return {"platform": w["platform"], "kind": w["device_kind"],
            "count": w["device_count"]}


def versions() -> dict:
    out = {}
    for pkg in ("jax", "jaxlib", "libtpu", "numpy"):
        try:
            out[pkg] = importlib.metadata.version(pkg)
        except importlib.metadata.PackageNotFoundError:
            out[pkg] = None
    out["python"] = sys.version.split()[0]
    return out


def run(smoke: Smoke, args) -> dict:
    num_users, num_items, n = NUM_USERS, NUM_ITEMS, args.events
    floor, part = MIN_EVENTS, IMPORT_PART_EVENTS
    if args.dry_run_cpu:
        num_users, num_items, n, floor, part = 2_000, 500, 60_000, 10_000, 25_000
    print(f"versions: {json.dumps(versions())}")
    if not native.native_available():
        raise SmokeFailure(
            "native codec unavailable: native/pio_native.cpp did not build "
            "(g++ missing?) — the pure-Python fallback is a different program"
        )
    print("native_available: true", flush=True)

    device = probe_device(smoke)
    print(f"device: {json.dumps(device)}", flush=True)
    if device["platform"] != smoke.platform:
        raise SmokeFailure(
            f"jax found platform {device['platform']!r} "
            f"({device['kind']} x{device['count']}), not a TPU"
        )

    cut = "" if n == FULL_EVENTS or args.dry_run_cpu else (
        f" (CUT from {FULL_EVENTS} by --events)")
    t0 = time.perf_counter()
    need = disk_need(n)
    held = disk_capacity(smoke.dir, need)
    print(f"disk: {smoke.dir} took {held} of the {need} bytes {n} events need, "
          f"in {time.perf_counter() - t0:.1f} s", flush=True)
    if held < need:
        fit = events_that_fit(held)
        if fit < floor:
            raise SmokeFailure(
                f"{smoke.dir} takes only {held} bytes (per file or in all); "
                f"{floor} events need {disk_need(floor)} plus a "
                f"{1 - DISK_MARGIN:.0%} margin — point TMPDIR at a larger disk"
            )
        cut = (f" (CUT from {n}: the work directory takes only {held} bytes, "
               "per file or in all)")
        n = fit
    print(
        f"shape: {num_users} users x {num_items} items, rank {RANK}, "
        f"{ITERATIONS} iterations, lambda {LAMBDA}, {n} events{cut}",
        flush=True,
    )
    t0 = time.perf_counter()
    rows, cols, vals = make_ratings(args.seed, n, num_users, num_items)
    generate_s = time.perf_counter() - t0

    smoke.pio("app_new", "app", "new", "ChipSmoke")
    events_file = os.path.join(smoke.dir, "events.jsonl")
    import_s = 0.0
    for lo in range(0, n, part):  # one part on disk at a time
        sl = slice(lo, min(lo + part, n))
        t0 = time.perf_counter()
        write_events(events_file, rows[sl], cols[sl], vals[sl])
        generate_s += time.perf_counter() - t0
        out, wall = smoke.pio(
            "import", "import", "--appid-or-name", "ChipSmoke", "--input", events_file
        )
        import_s += wall
        if f"Imported {sl.stop - sl.start} events" not in out:
            raise SmokeFailure(
                f"import: expected {sl.stop - sl.start} events\n{out[-500:]}"
            )
    os.unlink(events_file)
    print(f"generate_s: {generate_s:.1f}  import_s: {import_s:.1f} "
          f"({-(-n // part)} parts)", flush=True)

    with open(os.path.join(smoke.dir, "engine.json"), "w") as fh:
        json.dump(VARIANT, fh)
    first = train(smoke, "train_1", "--variant", "engine.json")
    second = train(smoke, "train_2", "--variant", "engine.json")
    for name, r in (("train_1", first), ("train_2", second)):
        if r["platform"] != smoke.platform or r["trainer"] != "single":
            raise SmokeFailure(f"{name}: the trainer reports {r}")
    if second["cache_entries_added"] != 0:
        raise SmokeFailure(
            f"train_2 added {second['cache_entries_added']} entries to "
            f"{smoke.env['JAX_COMPILATION_CACHE_DIR']}: the cache did not hit"
        )

    rng = np.random.default_rng(args.seed + 1)
    sample = rng.choice(n, min(RMSE_SAMPLE, n), replace=False)
    s_rows, s_cols, s_vals = rows[sample], cols[sample], vals[sample].astype(np.float32)
    ref = Reference(smoke, second["instance"], num_users, num_items)
    rmse = ref.rmse(s_rows, s_cols, s_vals)
    mean_rmse = float(np.sqrt(np.mean((s_vals - vals.mean()) ** 2)))
    print(f"rmse over {len(sample)} sampled ratings: model {rmse:.4f}, "
          f"global-mean predictor {mean_rmse:.4f}", flush=True)
    if not np.isfinite(rmse) or rmse >= mean_rmse:
        raise SmokeFailure("the trained model does not beat the global mean")

    users = rng.choice(num_users, KNOWN_QUERIES, replace=False).tolist()
    dense = serve_and_check(smoke, "deploy", "engine.json", ref, users)
    # the same model through two-stage retrieval (threshold under the
    # catalog): coarse shortlist, then the exact rescore's gathers
    staged = serve_and_check(
        smoke, "deploy_two_stage", "engine.json", ref, users[:TWO_STAGE_QUERIES],
        PIO_RETRIEVAL_THRESHOLD=str(TWO_STAGE_THRESHOLD),
    )
    if (staged["two_stage_queries"] < TWO_STAGE_QUERIES  # + the deploy warm-up
            or "retrieval.rescore_gather" not in staged["rescore_temp_bytes"]):
        raise SmokeFailure(
            f"deploy_two_stage: {staged['two_stage_queries']} two-stage queries, "
            f"programs {staged['rescore_temp_bytes']}: the leg did not engage"
        )

    int8_leg(smoke, users[:TWO_STAGE_QUERIES], (s_rows, s_cols, s_vals),
             mean_rmse, num_users, num_items)
    storefront_leg(smoke, args.seed, 10 if args.dry_run_cpu else 1)
    similar_leg(smoke, args.seed, 10 if args.dry_run_cpu else 1)
    if device["count"] >= 4:
        sharded_leg(smoke, ref, rmse, dense, users, (s_rows, s_cols, s_vals),
                    num_users, num_items)
    else:
        print(f"sharded leg: SKIPPED — {device['count']} device(s) visible; "
              "sharded training and sharded serving need four", flush=True)
    return device


def int8_leg(smoke: Smoke, users: list[int], sample, mean_rmse: float,
             num_users: int, num_items: int) -> None:
    """The same events trained with ``storage_dtype="int8"`` and served over
    the two-stage threshold: a TRAINED (not only a written) int8 model on the
    normal path — quantized by the program's own ``quantize_rows``, persisted
    as values + row scales, staged as stored, scanned int8, rescored
    dequantized — held to the f32 product of the dequantized rows of the
    persisted model."""
    variant = copy.deepcopy(VARIANT)
    variant["id"] = "chip-smoke-int8"
    variant["algorithms"][0]["params"]["storage_dtype"] = "int8"
    with open(os.path.join(smoke.dir, "engine_int8.json"), "w") as fh:
        json.dump(variant, fh)
    trained = train(smoke, "train_int8", "--variant", "engine_int8.json")
    if trained["platform"] != smoke.platform:
        raise SmokeFailure(f"train_int8: the trainer reports {trained}")
    ref = Reference(smoke, trained["instance"], num_users, num_items)
    rmse = ref.rmse(*sample)
    print(f"int8 leg: rmse {rmse:.4f} (global-mean predictor {mean_rmse:.4f})",
          flush=True)
    if not ref.quantized or not np.isfinite(rmse) or rmse >= mean_rmse:
        raise SmokeFailure(
            f"train_int8: quantized {ref.quantized}, rmse {rmse} against the "
            f"global mean's {mean_rmse}")
    served = serve_and_check(
        smoke, "deploy_int8", "engine_int8.json", ref, users,
        PIO_RETRIEVAL_THRESHOLD=str(TWO_STAGE_THRESHOLD),
    )
    if served["two_stage_queries"] < len(users):
        raise SmokeFailure(f"deploy_int8: {served['two_stage_queries']} "
                           "two-stage queries: the leg did not engage")


def sharded_leg(smoke: Smoke, ref: Reference, rmse: float, dense: dict,
                users: list[int], sample, num_users: int, num_items: int) -> None:
    """Four devices: the same events through `--mesh data=4` training and
    sharded serving (item rows stationary on the four devices: the exact
    program, then two-stage retrieval over the shards with the threshold
    under the catalog), held to the one-chip run."""
    variant = copy.deepcopy(VARIANT)
    variant["id"] = "chip-smoke-sharded"
    variant["algorithms"][0]["params"].update(
        sharded_train=True, sharded_serving=True
    )
    with open(os.path.join(smoke.dir, "sharded.json"), "w") as fh:
        json.dump(variant, fh)
    r = train(smoke, "train_sharded", "--variant", "sharded.json",
              "--mesh", "data=4")
    if (r["platform"] != smoke.platform or r["trainer"] != "sharded"
            or not str(r["mesh"]).startswith("sharded:data=4:")):
        raise SmokeFailure(f"train_sharded: the trainer reports {r}")
    sref = Reference(smoke, r["instance"], num_users, num_items)
    srmse = sref.rmse(*sample)
    print(f"sharded rmse {srmse:.6f} vs one-chip {rmse:.6f} "
          f"(half-step mode: {r['mesh'].rsplit(':', 1)[1]})", flush=True)
    if abs(srmse - rmse) > 1e-3:
        raise SmokeFailure(f"sharded RMSE {srmse} is not within 1e-3 of {rmse}")
    ring = serve_and_check(smoke, "deploy_sharded", "sharded.json", sref, users,
                           PIO_MESH="data=4")
    staged = serve_and_check(
        smoke, "deploy_sharded_two_stage", "sharded.json", sref,
        users[:TWO_STAGE_QUERIES], PIO_MESH="data=4",
        PIO_RETRIEVAL_THRESHOLD=str(TWO_STAGE_THRESHOLD),
    )
    if (ring["shards"] != 4 or staged["shards"] != 4
            or staged["sharded_queries"] < TWO_STAGE_QUERIES
            or staged["two_stage_queries"]):
        raise SmokeFailure(
            f"deploy_sharded_two_stage: {staged['sharded_queries']} sharded and "
            f"{staged['two_stage_queries']} one-chip two-stage queries over "
            f"{staged['shards']} shards: the sharded chain did not engage"
        )
    # two trainings of the same events: the lists may differ in near-ties
    shared = [
        len(set(ring["lists"][u]) & set(dense["lists"][u])) / TOP_K for u in users
    ]
    item_bytes = sref.V.nbytes
    in_use = [(d["memory"] or {}).get("in_use", 0) for d in ring["devices"]]
    reading = {
        "rmse": srmse,
        "ring_lists_equal_dense": sum(
            ring["lists"][u] == dense["lists"][u] for u in users
        ),
        "ring_vs_dense_overlap_min": min(shared),
        "item_table_bytes": item_bytes,
        "bytes_in_use": in_use,
    }
    print(f"sharded: {json.dumps(reading)}", flush=True)
    if min(shared) < MIN_OVERLAP:
        raise SmokeFailure(
            f"ring answers share only {min(shared)} of a dense server's list"
        )
    # CPU devices report no allocator statistics; a TPU's must show a shard
    if smoke.platform == "tpu" and (
        len(in_use) < 4 or min(in_use) < item_bytes // len(in_use)
    ):
        raise SmokeFailure(
            f"item factors ({item_bytes} B) are not spread over the devices: "
            f"bytes_in_use {in_use}"
        )


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("--events", type=int, default=FULL_EVENTS,
                    help=f"cut the event count (never below {MIN_EVENTS})")
    ap.add_argument("--deadline", type=float, default=DEADLINE_S,
                    help="seconds the whole run may take")
    ap.add_argument(
        "--dry-run-cpu", action="store_true",
        help="debug THIS SCRIPT on XLA:CPU at a toy shape; never a result: "
        "prints no result line and exits 3 even when every phase passes",
    )
    args = ap.parse_args(argv)
    if not MIN_EVENTS <= args.events <= FULL_EVENTS:
        ap.error(f"--events must be within [{MIN_EVENTS}, {FULL_EVENTS}]")

    workdir = tempfile.mkdtemp(prefix="pio_chip_smoke_")
    smoke = Smoke(workdir, args.deadline, "cpu" if args.dry_run_cpu else "tpu")
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))  # run the finally
    t0 = time.perf_counter()
    try:
        device = run(smoke, args)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    finally:
        smoke.stop_all()
        shutil.rmtree(workdir, ignore_errors=True)
    print(f"total_s: {time.perf_counter() - t0:.1f}")
    if args.dry_run_cpu:
        print("dry run on cpu: every phase passed — this is NOT a chip result")
        return 3
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
