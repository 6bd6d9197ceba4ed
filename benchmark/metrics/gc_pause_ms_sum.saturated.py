"""gc_pause_ms_sum.saturated — gc_pause_ms_sum in the saturated cell (it moves serve_qps
there): the same reader."""

import os
import runpy

read = runpy.run_path(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "gc_pause_ms_sum.py")
)["read"]
