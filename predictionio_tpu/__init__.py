"""PredictionIO-TPU: a TPU-native machine-learning serving and lifecycle framework.

A ground-up rebuild of the capability surface of Apache PredictionIO
(incubating) — event collection, DASE engines (Data source / Preparator /
Algorithm(s) / Serving), training, deployment as an HTTP query server, and
evaluation/tuning — with the Spark/MLlib execution substrate replaced by
JAX/XLA/Pallas on TPU:

- arrays + ``jit``/``shard_map`` over a ``jax.sharding.Mesh`` replace
  RDDs + spark-submit + shuffle,
- Pallas kernels implement the hot per-block normal-equation solves of ALS,
- XLA collectives (psum/all_gather) over ICI replace the Spark shuffle for
  factor exchange,
- a plain Python/HTTP control plane replaces the JVM/akka one.

Reference capability map: see SURVEY.md at the repo root. Reference layer
map: /root/reference SURVEY §1 (L0 Spark substrate → L5 CLI).
"""

import os as _os

__version__ = "0.1.0"

# The persistent XLA compile cache, placed by ONE rule for every entry
# point that compiles (pio train / deploy / eval, bench.py, chip_smoke.py
# and all their children): where JAX_COMPILATION_CACHE_DIR is set, jax
# uses it and nothing here touches it; where it is not, the cache is
# <checkout>/.jax_cache — a FIXED path, because the path is part of the
# cache key and a directory that moves never hits. Set in os.environ
# (never jax.config.update) so it lands before jax is imported and is
# inherited by daemon, supervisor and worker children. The two
# thresholds cache every program: serving top-k programs compile fast
# but would otherwise recompile on every restart.
_os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    _os.path.join(
        _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
        ".jax_cache",
    ),
)
_os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
_os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
