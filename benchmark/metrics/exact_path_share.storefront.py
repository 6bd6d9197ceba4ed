"""exact_path_share.storefront — of the queries served at retrieval scale in
the window, the share that left two-stage retrieval for the exact dense path:
pio_retrieval_queries_total{path="exact"} over both paths, in %. The
storefront's rules are applied inside the scan and the rescore, so a category
or a blackList query has no reason to leave: anything above 0 names a kind of
query that still does."""


def read(raw, spec, cell):
    d = raw.get("counters_delta") or {}
    exact = d.get('pio_retrieval_queries_total{path="exact"}')
    two = d.get('pio_retrieval_queries_total{path="two_stage"}')
    if exact is None or two is None or exact + two <= 0:
        return None
    return 100.0 * exact / (exact + two)
