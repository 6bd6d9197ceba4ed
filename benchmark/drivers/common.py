"""What every driver needs: the run's work directory and storage
environment, children that are always stopped, core pinning, HTTP."""

from __future__ import annotations

import http.client
import json
import os
import shutil
import socket
import subprocess
import sys
import tempfile
import time


class BenchFailure(Exception):
    """The run cannot give a result; the message says why."""


class Run:
    """One run: work directory under $TMPDIR, the children's environment,
    every child started (so all are stopped on exit)."""

    def __init__(self, root: str, keep: bool = False):
        self.root = root
        self.keep = keep
        self.dir = tempfile.mkdtemp(prefix="pio_bench_")
        self.children: list[subprocess.Popen] = []
        d = self.dir
        self.store_env = {
            "PIO_FS_BASEDIR": os.path.join(d, "store"),
            "PIO_RUN_DIR": os.path.join(d, "run"),
            "PIO_PREP_CACHE_DIR": os.path.join(d, "prep"),
            "PIO_STORAGE_SOURCES_DB_TYPE": "sqlite",
            "PIO_STORAGE_SOURCES_DB_PATH": os.path.join(d, "pio.db"),
            "PIO_STORAGE_SOURCES_LOG_TYPE": "jsonl",
            "PIO_STORAGE_SOURCES_LOG_PATH": os.path.join(d, "events"),
            "PIO_STORAGE_SOURCES_FS_TYPE": "localfs",
            "PIO_STORAGE_SOURCES_FS_PATH": os.path.join(d, "models"),
            "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "DB",
            "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "LOG",
            "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "FS",
        }
        env = {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}
        self.env = {
            **env, **self.store_env,
            "PYTHONPATH": root + os.pathsep + env.get("PYTHONPATH", ""),
        }
        cores = sorted(os.sched_getaffinity(0))
        # the generator and this parent get a core each where there are
        # enough; the chip's owner gets the rest
        if len(cores) >= 4:
            self.gen_cores, self.parent_cores = {cores[-1]}, {cores[-2]}
            self.server_cores = set(cores[:-2])
        else:
            self.gen_cores = self.parent_cores = self.server_cores = set(cores)

    def path(self, *parts: str) -> str:
        return os.path.join(self.dir, *parts)

    def spawn(self, argv: list[str], log: str, cores: set[int] | None = None,
              **env) -> subprocess.Popen:
        def pin():
            if cores:
                os.sched_setaffinity(0, cores)

        with open(self.path(log), "wb") as fh:
            proc = subprocess.Popen(
                [sys.executable, *argv], cwd=self.dir, env={**self.env, **env},
                stdin=subprocess.DEVNULL, stdout=fh, stderr=subprocess.STDOUT,
                preexec_fn=pin,
            )
        self.children.append(proc)
        return proc

    def log_tail(self, log: str, n: int = 3000) -> str:
        try:
            with open(self.path(log), "rb") as fh:
                fh.seek(0, os.SEEK_END)
                fh.seek(max(0, fh.tell() - n))
                return fh.read().decode("utf-8", "replace")
        except FileNotFoundError:
            return ""

    def run_child(self, name: str, argv: list[str], timeout: float,
                  cores: set[int] | None = None, **env) -> tuple[str, float]:
        """Run one child to its end; return (its output, wall seconds)."""
        log = f"{name}.log"
        t0 = time.perf_counter()
        proc = self.spawn(argv, log, cores, **env)
        try:
            rc = proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            raise BenchFailure(f"{name}: still running after {timeout:.0f} s\n{self.log_tail(log)}") from None
        wall = time.perf_counter() - t0
        if rc != 0:
            raise BenchFailure(f"{name}: exit {rc}\n{self.log_tail(log)}")
        with open(self.path(log), encoding="utf-8", errors="replace") as fh:
            return fh.read(), wall

    def stop_all(self) -> None:
        """SIGTERM first: a killed chip holder can leave the chip locked."""
        live = [p for p in self.children if p.poll() is None]
        for p in live:
            p.terminate()
        for p in live:
            try:
                p.wait(timeout=30)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()

    def close(self) -> None:
        self.stop_all()
        if not self.keep:
            shutil.rmtree(self.dir, ignore_errors=True)


def http_call(port: int, method: str, path: str, body: bytes | None = None,
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


def reduce_trace(run: Run, trace_dir: str) -> dict:
    """The trace reduction, in a child held to the CPU (it imports jax's
    profile reader; the chip's owner has exited by now)."""
    out = run.path("trace.json")
    run.run_child(
        "xplane", [os.path.join(run.root, "benchmark", "xplane.py"), trace_dir, out],
        300.0, JAX_PLATFORMS="cpu",
    )
    with open(out) as fh:
        return json.load(fh)
