"""exact_path_share.itempage — of the item-page cell's queries served at
retrieval scale in the window, the share that left two-stage retrieval for the
exact dense path, in %: the storefront's reader (exact_path_share.storefront,
pio_retrieval_queries_total{path}), read in this cell. A query's own items,
its blackList and its categories are rules inside the scan and the rescore, so
no kind has a reason to leave: the cell's `correct` holds it at 0."""

import os

import readers

_read = readers.load_metric(
    os.path.dirname(os.path.abspath(__file__)), "exact_path_share.storefront")


def read(raw, spec, cell):
    return _read(raw, cell)
