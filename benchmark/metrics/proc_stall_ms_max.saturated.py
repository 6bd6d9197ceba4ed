"""proc_stall_ms_max.saturated — proc_stall_ms_max in the saturated cell (it moves serve_qps
there): the same reader."""

import os
import runpy

read = runpy.run_path(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "proc_stall_ms_max.py")
)["read"]
