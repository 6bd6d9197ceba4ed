"""masked_shortlist_roofline.itempage — share of its roofline the coarse scan
under rules reached IN THE ITEM-PAGE CELL: the same program
(``jit__coarse_topk_masked``), the same shape (16 tiles of 2^18 rows, rank 128,
bf16, one category column) and therefore the same byte count as the
storefront's reader, whose file this one loads: ``masked_shortlist_bytes`` is
its function, not a copy. Bandwidth binds. Not clamped."""

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "metric_masked_shortlist_roofline_storefront", os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "masked_shortlist_roofline.storefront.py"))
_storefront = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_storefront)

PROGRAM = _storefront.PROGRAM
masked_shortlist_bytes = _storefront.masked_shortlist_bytes
read = _storefront.read
