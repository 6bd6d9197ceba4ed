"""loadgen_itempage.py — the load generator with the item page's request
encoder: loadgen.py's process, schedule, sockets and results, unchanged; each
request's kind (similar, same category, session) and its items are derived
from the seed and the request's index (benchmark/itempage_data.py requests),
so the checker re-derives what was sent.

    python3 benchmark/loadgen_itempage.py <plan.json>

The plan is the serving plan plus ``itempage``: num_items, num_categories,
shares. What the results call ``user`` is the request's first item."""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import ecomm_data  # noqa: E402
import itempage_data  # noqa: E402
import loadgen  # noqa: E402


def encode_requests(count: int, num: int, host: str, req: dict, item_cat) -> list[bytes]:
    """One whole HTTP/1.1 keep-alive request per query, as bytes."""
    head = (
        "POST /queries.json HTTP/1.1\r\nHost: %s\r\n"
        "Content-Type: application/json\r\nContent-Length: " % host
    ).encode()
    out = []
    for i in range(count):
        body = itempage_data.request_body(num, req, i, item_cat)
        out.append(head + b"%d\r\n\r\n" % len(body) + body)
    return out


class ItemPageGenerator(loadgen.Generator):
    def __init__(self, plan: dict):
        super().__init__(plan)  # the schedule, and how many requests there are
        ip = plan["itempage"]
        seed, count = int(plan["seed"]), len(self.users)
        req = itempage_data.requests(seed, count, ip["num_items"], ip["shares"])
        item_cat = ecomm_data.item_categories(seed, ip["num_items"], ip["num_categories"])
        self.users = req["items"][:, 0].copy()
        self.requests = encode_requests(
            count, int(plan["num"]), f"{self.host}:{self.port}", req, item_cat)


if __name__ == "__main__":
    loadgen.Generator = ItemPageGenerator
    try:
        sys.exit(loadgen.main(sys.argv))
    except loadgen.LoadgenFailure as e:
        print(f"loadgen: FAILED: {e}", file=sys.stderr)
        sys.exit(1)
