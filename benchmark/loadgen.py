"""loadgen.py — the load generator: its own process, one thread, a selector
over non-blocking keep-alive sockets. Everything it sends (schedule, users,
encoded requests) is drawn from the seed before the first phase; latency is
counted from the time a request was DUE; how late it was sent is reported.

    python3 benchmark/loadgen.py <plan.json>

The plan (written by the serving driver) lists phases in order: closed-loop
warm-up bursts, then the measured phase (open loop on a stratified
exponential schedule, or closed loop) with its warm-in. Counters of the server (`GET /metrics`) are read on a
connection of their own when the window opens and when it closes, and a
`POST /profile` is sent at the opening where the plan asks for a trace.
Results go to <plan.out>.npz and <plan.out>.bodies.json. No jax, no program
code: the server is reached over HTTP only.
"""

from __future__ import annotations

import gc
import json
import os
import selectors
import socket
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import traffic  # noqa: E402

now = time.perf_counter  # CLOCK_MONOTONIC: comparable across processes


class LoadgenFailure(Exception):
    pass


class Conn:
    __slots__ = ("sock", "buf", "req", "kind", "need", "head_end", "status")

    def __init__(self, host: str, port: int):
        self.sock = socket.create_connection((host, port), timeout=30)
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self.sock.setblocking(False)
        self.buf = bytearray()
        self.req = -1  # index of the query in flight, -1 when idle
        self.kind = None  # "query" | "control"
        self.need = None
        self.head_end = 0
        self.status = 0


class Generator:
    def __init__(self, plan: dict):
        self.plan = plan
        self.host, self.port = plan["host"], int(plan["port"])
        self.sel = selectors.DefaultSelector()
        self.idle: list[Conn] = []
        self.all_conns: list[Conn] = []
        self.inflight = 0
        self.control_replies: dict[str, tuple[float, int, bytes]] = {}
        self.control_pending: dict[int, str] = {}  # fileno -> tag
        self.on_query_done = None

        # every request of every phase, encoded before the first phase
        seed = int(plan["seed"])
        self.schedules = []
        total = 0
        for ph in plan["phases"]:
            if ph["loop"] == "open":
                warm = traffic.stratified_exponential_schedule(
                    seed + 7919, ph["rate_qps"], ph["warm_in_s"]
                ) if ph.get("warm_in_s", 0) > 0 else np.zeros(0)
                main = ph.get("warm_in_s", 0.0) + traffic.stratified_exponential_schedule(
                    seed, ph["rate_qps"], ph["seconds"]
                )
                due = np.concatenate([warm, main])
                self.schedules.append(due)
                total += len(due)
            else:
                self.schedules.append(None)
                total += int(ph["max_requests"])
        users = traffic.user_order(
            seed, int(plan["num_users"]), total, plan.get("users", "uniform-distinct")
        )
        self.users = users
        self.requests = traffic.encode_requests(
            users, int(plan["num"]), f"{self.host}:{self.port}"
        )
        n = len(self.requests)
        self.due = np.full(n, np.nan)
        self.sent = np.full(n, np.nan)
        self.done = np.full(n, np.nan)
        self.status = np.zeros(n, np.int16)
        self.phase_of = np.full(n, -1, np.int16)
        self.bodies: list[bytes | None] = [None] * n
        self.next_req = 0

        self.max_conns = int(plan["connections"])
        for _ in range(self.max_conns):
            self._open()
        self.control = [self._open(control=True) for _ in range(2)]

    # -- connections ---------------------------------------------------
    def _open(self, control: bool = False) -> Conn:
        c = Conn(self.host, self.port)
        self.sel.register(c.sock, selectors.EVENT_READ, c)
        if not control:
            self.idle.append(c)
            self.all_conns.append(c)
        return c

    def _drop(self, c: Conn) -> None:
        try:
            self.sel.unregister(c.sock)
        except (KeyError, ValueError):
            pass
        c.sock.close()
        if c in self.idle:
            self.idle.remove(c)
        if c in self.all_conns:
            self.all_conns.remove(c)

    def take(self) -> Conn:
        """An idle connection; a new one where the server has closed them
        all (it drops a keep-alive connection after 120 s of silence, and a
        first query that compiles can take longer)."""
        if not self.idle:
            self._open()
        return self.idle.pop()

    def _send(self, c: Conn, payload: bytes) -> None:
        n = c.sock.send(payload)
        if n != len(payload):
            raise LoadgenFailure(f"short send: {n} of {len(payload)} bytes")

    def send_query(self, c: Conn, i: int, due: float, phase: int) -> None:
        c.req, c.kind = i, "query"
        self.due[i] = due
        self.phase_of[i] = phase
        try:
            self._send(c, self.requests[i])
        except (BrokenPipeError, ConnectionResetError):
            # the server closed an idle keep-alive connection: take a new one
            self._drop(c)
            c = self._open()
            self.idle.remove(c)
            c.req, c.kind = i, "query"
            self._send(c, self.requests[i])
        self.sent[i] = now()
        self.inflight += 1

    def send_control(self, which: int, tag: str, method: str, path: str) -> None:
        c = self.control[which]
        c.kind = "control"
        self.control_pending[c.sock.fileno()] = tag
        self._send(c, (
            f"{method} {path} HTTP/1.1\r\nHost: {self.host}:{self.port}\r\n"
            "Content-Length: 0\r\n\r\n"
        ).encode())

    # -- reading ---------------------------------------------------------
    def _on_readable(self, c: Conn) -> None:
        try:
            data = c.sock.recv(1 << 16)
        except BlockingIOError:
            return
        except ConnectionResetError:
            data = b""
        if not data:
            if c.kind == "query" and c.req >= 0:
                self._complete_query(c, 599, b"")  # hung up mid-request
            self._drop(c)
            return
        c.buf += data
        while True:
            if c.need is None:
                end = c.buf.find(b"\r\n\r\n")
                if end < 0:
                    return
                head = bytes(c.buf[:end])
                c.status = int(head[9:12])
                low = head.lower()
                at = low.find(b"content-length:")
                if at < 0:
                    raise LoadgenFailure(f"response without Content-Length: {head[:200]!r}")
                eol = low.find(b"\r\n", at)
                length = int(low[at + 15: eol if eol >= 0 else len(low)])
                c.head_end = end + 4
                c.need = c.head_end + length
            if len(c.buf) < c.need:
                return
            body = bytes(c.buf[c.head_end:c.need])
            del c.buf[:c.need]
            c.need = None
            if c.kind == "query":
                self._complete_query(c, c.status, body)
            else:
                tag = self.control_pending.pop(c.sock.fileno())
                self.control_replies[tag] = (now(), c.status, body)
            if not c.buf:
                return

    def _complete_query(self, c: Conn, status: int, body: bytes) -> None:
        i = c.req
        self.done[i] = now()
        self.status[i] = status
        self.bodies[i] = body
        c.req = -1
        self.inflight -= 1
        if self.on_query_done is not None:
            self.on_query_done(c)
        else:
            self.idle.append(c)

    def pump(self, timeout: float) -> None:
        # epoll rounds a timeout up to whole milliseconds: sleep short of
        # the deadline, and poll without sleeping over the last stretch
        timeout = 0.0 if timeout < 0.002 else timeout - 0.0015
        for key, _ in self.sel.select(timeout):
            self._on_readable(key.data)

    def wait_control(self, tag: str, limit_s: float) -> tuple[float, int, bytes]:
        t_end = now() + limit_s
        while tag not in self.control_replies:
            if now() > t_end:
                raise LoadgenFailure(f"no reply to control request {tag!r} in {limit_s} s")
            self.pump(0.05)
        return self.control_replies[tag]

    def drain(self, limit_s: float) -> None:
        t_end = now() + limit_s
        while self.inflight > 0:
            if now() > t_end:
                raise LoadgenFailure(f"{self.inflight} requests unanswered after {limit_s} s")
            self.pump(0.05)

    # -- phases -----------------------------------------------------------
    def take_index(self) -> int:
        i = self.next_req
        if i >= len(self.requests):
            raise LoadgenFailure("ran out of pre-encoded requests: raise max_requests")
        self.next_req = i + 1
        return i

    def run_closed(self, pi: int, ph: dict, hooks) -> dict:
        """N clients, each sending its next query when the last is answered."""
        clients = int(ph["clients"])
        budget = int(ph["max_requests"])
        first = self.next_req
        warm_in = float(ph.get("warm_in_s", 0.0))
        t0 = now()
        t_open = t0 + warm_in
        t_close = t_open + float(ph["seconds"])
        min_requests = int(ph.get("min_requests", 0))
        stopping = False

        def keep_going() -> bool:
            if self.next_req - first >= budget:
                if ph.get("measure"):
                    raise LoadgenFailure("closed loop outran max_requests")
                return False
            if now() < t_close:
                return True
            return (self.next_req - first) < min_requests

        def again(c: Conn) -> None:
            if not stopping and keep_going():
                t = now()
                self.send_query(c, self.take_index(), t, pi)
            else:
                self.idle.append(c)

        self.on_query_done = again
        for _ in range(clients):
            self.send_query(self.take(), self.take_index(), now(), pi)
        opened = closed = False
        limit = t_close + float(self.plan["timeout_s"])
        while True:
            t = now()
            if not opened and t >= t_open:
                opened = True
                hooks("open")
            if opened and not closed and t >= t_close and not keep_going():
                closed = True
                stopping = True
                hooks("close")
            if closed and self.inflight == 0:
                break
            if t > limit:
                raise LoadgenFailure(f"phase {ph['label']}: not finished at its time limit")
            nxt = t_open if not opened else t_close
            self.pump(min(0.05, max(0.0, nxt - t)) if not closed else 0.05)
        self.on_query_done = None
        return {"t_open": t_open, "t_close": t_close, "first": first, "end": self.next_req}

    def run_open(self, pi: int, ph: dict, hooks) -> dict:
        """Requests sent at their due times whatever the server does."""
        sched = self.schedules[pi]
        first = self.next_req
        idx = np.arange(first, first + len(sched))
        self.next_req = first + len(sched)
        warm_in = float(ph.get("warm_in_s", 0.0))
        t0 = now() + 0.05
        t_open = t0 + warm_in
        t_close = t_open + float(ph["seconds"])
        due_abs = t0 + sched
        waiting: list[int] = []  # due, but no idle connection yet
        k = 0
        opened = False
        n = len(sched)
        self.on_query_done = None
        while k < n or waiting:
            t = now()
            if not opened and t >= t_open:
                opened = True
                hooks("open")
            while k < n and due_abs[k] <= t:
                waiting.append(k)
                k += 1
            while waiting and (self.idle or len(self.all_conns) < self.max_conns):
                j = waiting.pop(0)
                self.send_query(self.take(), int(idx[j]), float(due_abs[j]), pi)
            if waiting:
                self.pump(0.0005)
                continue
            if k >= n:
                break
            nxt = due_abs[k] if opened else min(due_abs[k], t_open)
            self.pump(nxt - now())
        while now() < t_close:
            self.pump(t_close - now())
        if not opened:
            hooks("open")
        self.drain(float(self.plan["timeout_s"]))
        hooks("close")
        return {"t_open": t_open, "t_close": t_close, "first": first, "end": self.next_req}


def main(argv: list[str]) -> int:
    with open(argv[1]) as fh:
        plan = json.load(fh)
    gen = Generator(plan)
    gc.collect()
    gc.freeze()
    gc.disable()
    windows = []
    marks: dict[str, float] = {}
    for pi, ph in enumerate(plan["phases"]):
        tag = f"{pi}"

        def hooks(edge: str, tag=tag, ph=ph) -> None:
            if not ph.get("measure"):
                return
            marks[f"{tag}.{edge}"] = now()
            gen.send_control(0, f"metrics.{tag}.{edge}", "GET", "/metrics")
            prof = ph.get("profile")
            if edge == "open" and prof:
                gen.send_control(
                    1, f"profile.{tag}", "POST",
                    f"/profile?seconds={prof['seconds']}&out={prof['out']}",
                )
                marks[f"{tag}.profile_sent"] = now()

        run = gen.run_closed if ph["loop"] == "closed" else gen.run_open
        w = run(pi, ph, hooks)
        w["label"] = ph["label"]
        w["measure"] = bool(ph.get("measure"))
        if ph.get("measure"):
            for edge in ("open", "close"):
                t, status, body = gen.wait_control(f"metrics.{tag}.{edge}", 60.0)
                if status != 200:
                    raise LoadgenFailure(f"GET /metrics -> {status}")
                w[f"metrics_{edge}"] = body.decode("utf-8", "replace")
                w[f"metrics_{edge}_at"] = t
            if ph.get("profile"):
                t, status, body = gen.wait_control(
                    f"profile.{tag}", float(ph["profile"]["seconds"]) + 120.0
                )
                w["profile"] = {"status": status, "reply": body.decode("utf-8", "replace"),
                                "sent_at": marks[f"{tag}.profile_sent"], "done_at": t}
        windows.append(w)
    out = plan["out"]
    n = gen.next_req
    np.savez(
        out + ".npz", due=gen.due[:n], sent=gen.sent[:n], done=gen.done[:n],
        status=gen.status[:n], phase=gen.phase_of[:n], user=gen.users[:n],
    )
    with open(out + ".bodies.json", "w") as fh:
        json.dump([
            b.decode("utf-8", "replace") if b is not None else None
            for b in gen.bodies[:n]
        ], fh)
    with open(out + ".windows.json", "w") as fh:
        json.dump(windows, fh)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main(sys.argv))
    except LoadgenFailure as e:
        print(f"loadgen: FAILED: {e}", file=sys.stderr)
        sys.exit(1)
