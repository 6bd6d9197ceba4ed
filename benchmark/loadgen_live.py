"""loadgen_live.py — the load generator with a stream of `rate` events beside
its queries: loadgen.py's process, schedule, sockets and results, unchanged,
and in every measured phase

- `rate` events POSTed to the Event Server (``POST /events.json``) at their
  own seeded due times (stratified exponential, traffic.py), from
  ``start_after_s`` into the phase to ``stop_before_s`` before its close;
- a REFRESH query for the user of each event, due ``refresh_after_s`` after
  that event's acknowledgement (HTTP 201) came back. Refresh queries are
  queries of the phase like any other: they take the next request slots, are
  sent over the same connections and are written to the same arrays.

All of it on the generator's one thread: ``pump`` wakes for whichever is due
first. What was posted, when it was acknowledged and which request asked
after it goes to <plan.out>.events.json (times on the generator's clock,
CLOCK_MONOTONIC), for the checker's prefix rule.

    python3 benchmark/loadgen_live.py <plan.json>

The plan is the serving plan plus ``live``: event_port, access_key, the
configuration (for live_data.Deployment), rate_eps, start_after_s,
stop_before_s, refresh_after_s, shares, ack_limit_s."""

from __future__ import annotations

import heapq
import json
import os
import selectors
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import live_data  # noqa: E402
import loadgen  # noqa: E402
import traffic  # noqa: E402

now = loadgen.now
EVENT_CONNS = 4
SCHEDULE_OFFSET = 104729  # the events' schedule is not the queries'


def event_span(live: dict, ph: dict) -> float:
    """Seconds of a measured phase in which events are due."""
    return ph.get("warm_in_s", 0.0) + ph["seconds"] - live["start_after_s"] - live["stop_before_s"]


def event_count(live: dict, ph: dict) -> int:
    return max(0, int(round(live["rate_eps"] * event_span(live, ph))))


class LiveGenerator(loadgen.Generator):
    def __init__(self, plan: dict):
        super().__init__(plan)
        live = self.live = plan["live"]
        dep = live_data.Deployment(live["config"], int(plan["seed"]))
        dep.warm_bursts()  # the driver sent these: their items are rated
        counts = [event_count(live, ph) if ph.get("measure") else 0 for ph in plan["phases"]]
        self.stream = dep.stream(sum(counts), live["shares"])
        self.stream_at = 0
        # room for one refresh query an event, behind every other request
        extra = sum(counts)
        self.requests += [b""] * extra
        self.bodies += [None] * extra
        for name, fill in (("due", np.nan), ("sent", np.nan), ("done", np.nan),
                           ("status", 0), ("phase_of", -1), ("users", -1)):
            a = getattr(self, name)
            setattr(self, name, np.concatenate([a, np.full(extra, fill, a.dtype)]))
        self.head = (
            "POST /queries.json HTTP/1.1\r\nHost: %s:%d\r\n"
            "Content-Type: application/json\r\nContent-Length: " % (self.host, self.port)
        ).encode()
        self.ev_head = (
            "POST /events.json?accessKey=%s HTTP/1.1\r\nHost: %s:%d\r\n"
            "Content-Type: application/json\r\nContent-Length: "
            % (live["access_key"], self.host, int(live["event_port"]))
        ).encode()
        self.ev_conns = [self._open_event() for _ in range(EVENT_CONNS)]
        self.log: list[dict] = []  # every event of every measured phase
        self._phase = None

    def _open_event(self) -> loadgen.Conn:
        c = loadgen.Conn(self.host, int(self.live["event_port"]))
        self.sel.register(c.sock, selectors.EVENT_READ, c)
        return c

    # -- one measured phase ------------------------------------------------
    def run_open(self, pi: int, ph: dict, hooks) -> dict:
        if not ph.get("measure"):
            return super().run_open(pi, ph, hooks)
        live = self.live
        n = event_count(live, ph)
        t0 = now() + 0.05  # as run_open sets its own, a few microseconds on
        span = event_span(live, ph)
        due = t0 + live["start_after_s"] + traffic.stratified_exponential_schedule(
            int(self.plan["seed"]) + SCHEDULE_OFFSET, n / span, span) if n else np.zeros(0)
        events = []
        for j in range(n):
            kind, user, item, star = self.stream[self.stream_at + j]
            events.append({"kind": kind, "user": user, "item": item, "stars": star,
                           "phase": pi, "due": float(due[j]), "posted": None,
                           "acked": None, "status": 0, "refresh": -1})
        self.stream_at += n
        self.log += events
        self._phase = {"pi": pi, "events": events, "next": 0, "posted": {},
                       "refresh": [], "limit": float(live["ack_limit_s"])}
        try:
            return super().run_open(pi, ph, hooks)
        finally:
            self._phase = None
            with open(self.plan["out"] + ".events.json", "w") as fh:
                json.dump(self.log, fh)

    def _next_due(self):
        p = self._phase
        nxt = [p["events"][p["next"]]["due"]] if p["next"] < len(p["events"]) else []
        if p["refresh"]:
            nxt.append(p["refresh"][0][0])
        if p["posted"]:
            nxt.append(now() + 0.02)  # an acknowledgement is on its way
        return min(nxt) if nxt else None

    def pump(self, timeout: float) -> None:
        if self._phase is None:
            return super().pump(timeout)
        nxt = self._next_due()
        if nxt is not None:
            timeout = min(timeout, nxt - now())
        super().pump(max(0.0, timeout))
        self._step()

    def _step(self) -> None:
        p = self._phase
        t = now()
        # acknowledgements: each schedules its user's refresh query
        for tag, (c, j) in list(p["posted"].items()):
            e = p["events"][j]
            reply = self.control_replies.pop(tag, None)
            if reply is None and t - e["posted"] < p["limit"] and c.sock.fileno() >= 0:
                continue
            del p["posted"][tag]
            if reply is None:  # no acknowledgement: the event may or may not be stored
                e["status"] = 599
                self.control_pending.pop(c.sock.fileno(), None)
                if c.sock.fileno() >= 0:
                    self._drop(c)
                self.ev_conns[self.ev_conns.index(c)] = self._open_event()
                continue
            e["acked"], e["status"] = reply[0], reply[1]
            try:
                e["event_id"] = json.loads(reply[2]).get("eventId")
            except ValueError:
                e["event_id"] = None
            self.ev_conns.append(self.ev_conns.pop(self.ev_conns.index(c)))  # free again, last
            if e["status"] == 201:
                heapq.heappush(p["refresh"], (e["acked"] + self.live["refresh_after_s"], j))
        # events that are due, while a connection is free
        busy = {id(c) for c, _ in p["posted"].values()}
        while p["next"] < len(p["events"]) and p["events"][p["next"]]["due"] <= t:
            free = [c for c in self.ev_conns if id(c) not in busy]
            if not free:
                break
            c, j = free[0], p["next"]
            if c.sock.fileno() < 0:  # the server closed it while idle
                at = self.ev_conns.index(c)
                c = self.ev_conns[at] = self._open_event()
            e = p["events"][j]
            body = live_data.event_body(e["user"], e["item"], e["stars"])
            tag = f"event.{p['pi']}.{j}"
            c.kind = "control"
            self.control_pending[c.sock.fileno()] = tag
            self._send(c, self.ev_head + b"%d\r\n\r\n" % len(body) + body)
            e["posted"] = now()
            p["posted"][tag] = (c, j)
            busy.add(id(c))
            p["next"] += 1
        # refresh queries that are due
        while p["refresh"] and p["refresh"][0][0] <= t:
            due, j = heapq.heappop(p["refresh"])
            e = p["events"][j]
            i = self.take_index()
            body = b'{"user":"u%d","num":%d}' % (e["user"], int(self.plan["num"]))
            self.requests[i] = self.head + b"%d\r\n\r\n" % len(body) + body
            self.users[i] = e["user"]
            e["refresh"] = i
            self.send_query(self.take(), i, due, p["pi"])

    def drain(self, limit_s: float) -> None:
        # an acknowledgement that came late leaves its refresh query due
        # after the window: it is still sent, and waited for, within the limit
        t_end = now() + limit_s
        p = self._phase
        while p is not None and (p["posted"] or p["refresh"] or p["next"] < len(p["events"])):
            if now() > t_end:
                raise loadgen.LoadgenFailure("events or refresh queries unfinished at the time limit")
            self.pump(0.05)
        super().drain(max(0.0, t_end - now()))


if __name__ == "__main__":
    loadgen.Generator = LiveGenerator
    try:
        sys.exit(loadgen.main(sys.argv))
    except loadgen.LoadgenFailure as e:
        print(f"loadgen: FAILED: {e}", file=sys.stderr)
        sys.exit(1)
