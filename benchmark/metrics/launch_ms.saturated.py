"""launch_ms.saturated — launch_ms in the saturated cell (it moves serve_qps there): the
same reader."""

import os
import runpy

read = runpy.run_path(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "launch_ms.py")
)["read"]
