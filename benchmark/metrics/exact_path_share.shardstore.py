"""exact_path_share.shardstore — of the queries the sharded catalog served in
the window, the share that left the two-stage masked program for the exact
one: pio_retrieval_queries_total{path="exact"} over exact + sharded, in %.
The rules are applied inside every shard's scan and rescore, so no kind of
query has a reason to leave: 0 in a sound run (the recall probe is counted by
neither)."""


def read(raw, spec, cell):
    d = raw.get("counters_delta") or {}
    exact = d.get('pio_retrieval_queries_total{path="exact"}')
    sharded = d.get('pio_retrieval_queries_total{path="sharded"}')
    masked = d.get("pio_retrieval_sharded_masked_total")
    if exact is None or sharded is None or not masked or exact + sharded <= 0:
        return None
    return 100.0 * exact / (exact + sharded)
