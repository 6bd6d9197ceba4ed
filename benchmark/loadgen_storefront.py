"""loadgen_storefront.py — the load generator with the storefront's request
encoder: loadgen.py's process, schedule, sockets and results, unchanged; the
users are drawn from the deployment's active users and each request's kind
(home, category page, cart) is derived from the seed and the request's index
(benchmark/ecomm_data.py requests), so the checker re-derives what was sent.

    python3 benchmark/loadgen_storefront.py <plan.json>

The plan is the serving plan plus ``storefront``: num_users, active_users,
num_items, num_categories, shares. ``num_users`` of the plan itself is the
count of active users (the generator draws positions in that list)."""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import ecomm_data  # noqa: E402
import loadgen  # noqa: E402


def encode_requests(users, num: int, host: str, req: dict) -> list[bytes]:
    """One whole HTTP/1.1 keep-alive request per user, as bytes."""
    head = (
        "POST /queries.json HTTP/1.1\r\nHost: %s\r\n"
        "Content-Type: application/json\r\nContent-Length: " % host
    ).encode()
    out = []
    for i, u in enumerate(users.tolist()):
        body = ecomm_data.request_body(u, num, req, i)
        out.append(head + b"%d\r\n\r\n" % len(body) + body)
    return out


class StorefrontGenerator(loadgen.Generator):
    def __init__(self, plan: dict):
        super().__init__(plan)  # self.users: positions among the active users
        sf = plan["storefront"]
        seed = int(plan["seed"])
        active = ecomm_data.active_users(seed, sf["num_users"], sf["active_users"])
        self.users = active[self.users]
        req = ecomm_data.requests(
            seed, len(self.users), sf["num_items"], sf["num_categories"], sf["shares"])
        self.requests = encode_requests(
            self.users, int(plan["num"]), f"{self.host}:{self.port}", req)


if __name__ == "__main__":
    loadgen.Generator = StorefrontGenerator
    try:
        sys.exit(loadgen.main(sys.argv))
    except loadgen.LoadgenFailure as e:
        print(f"loadgen: FAILED: {e}", file=sys.stderr)
        sys.exit(1)
