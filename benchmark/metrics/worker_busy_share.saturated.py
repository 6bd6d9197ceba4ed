"""worker_busy_share.saturated — worker_busy_share in the saturated cell (it moves serve_qps
there): the same reader."""

import os
import runpy

read = runpy.run_path(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "worker_busy_share.py")
)["read"]
