"""Test fixtures.

Forces JAX onto a virtual 8-device CPU platform so multi-chip sharding
(mesh/pjit/shard_map) is exercised without TPU hardware — the analog of the
reference's Spark local[4] stand-in for a cluster
(core/src/test/scala/org/apache/predictionio/workflow/BaseTest.scala:31-92).
"""

import os

# force CPU regardless of the ambient platform: unit tests are specified
# against the virtual multi-device CPU mesh (TPU runs happen via bench.py)
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# jax reads its environment at import. Importing it BEFORE the package
# keeps this process off the persistent compile cache that
# predictionio_tpu/__init__.py places for processes that import it first:
# the in-process suite compiles everything fresh, every run.
import jax  # noqa: E402,F401
import pytest  # noqa: E402

from predictionio_tpu.data.storage import set_storage, test_storage  # noqa: E402
from predictionio_tpu.ops import retrieval  # noqa: E402

# The CPU fixtures stand for catalogs whose stored scores are past the
# bytes no pass is cut under (``retrieval._UNCUT``: a tile of 96 or 2^14
# rows is not): set once, before anything traces, so the rule that cuts
# a served catalog's batch cuts theirs. The bytes themselves are held by
# ``scan_chunk``'s own cases (tests/test_retrieval.py) and the
# subprocesses (``bench.py --smoke``, the benchmark's) keep them.
retrieval._UNCUT = 0


@pytest.fixture(autouse=True, scope="session")
def _prep_cache_dir(tmp_path_factory):
    """Keep packed-prep cache writes out of ~/.pio_tpu during tests.
    setdefault so an explicit operator/test override still wins."""
    os.environ.setdefault(
        "PIO_PREP_CACHE_DIR", str(tmp_path_factory.mktemp("prep_cache"))
    )


@pytest.fixture()
def storage():
    """Fresh in-memory storage installed as the process singleton."""
    s = test_storage()
    set_storage(s)
    yield s
    set_storage(None)


@pytest.fixture()
def region_uploads(monkeypatch):
    """``watch(name)`` -> a list that gets, for every later region
    ``name``, (how many uploads of the serving chain the transfer family
    booked inside it — ``pio_device_transfers_total{direction="h2d"}`` at
    ``serve.dispatch`` and ``serve.rules`` — the ``jnp.asarray`` /
    ``jax.device_put`` calls made inside it): what the template tests
    hold the build regions to."""
    import jax.numpy as jnp

    from predictionio_tpu.obs import device as obs_device
    from predictionio_tpu.obs import trace as obs_trace

    open_, calls = [], []

    def uploads():
        return obs_device.transfer_count("h2d", "serve.dispatch", "serve.rules")

    def watch(name):
        seen = []

        class Watched(obs_trace.region):
            def __enter__(self):
                if self.name == name:
                    open_.append((uploads(), len(calls)))
                return super().__enter__()

            def __exit__(self, *exc):
                if self.name == name:
                    before, n = open_.pop()
                    seen.append((uploads() - before, calls[n:]))
                return super().__exit__(*exc)

        monkeypatch.setattr(obs_trace, "region", Watched)
        return seen

    for owner, fn in ((jnp, "asarray"), (jax, "device_put")):
        real = getattr(owner, fn)

        def counted(*a, _real=real, _fn=fn, **kw):
            if open_:
                calls.append(_fn)
            return _real(*a, **kw)

        monkeypatch.setattr(owner, fn, counted)
    return watch
