"""Mesh construction helpers and multi-host runtime initialization.

Single-host: ``make_mesh`` arranges this process's devices. Multi-host
(a TPU pod, or several CPU hosts): call :func:`initialize_multihost`
FIRST — it brings up JAX's distributed runtime so ``jax.devices()``
returns the GLOBAL device set and every collective in the sharded
trainers (all_gather/psum over the mesh axis) spans hosts via ICI/DCN.
This replaces the reference's cluster-submission path (spark-submit to
YARN/Mesos masters, tools/.../Runner.scala:193-244): instead of
shipping jars to executors, every host runs the same ``pio train
--multihost`` and the runtime stitches their chips into one mesh.
"""

from __future__ import annotations

import logging
import os
from typing import Sequence

import numpy as np

logger = logging.getLogger(__name__)

_initialized = False


def initialize_multihost(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
    local_device_ids: Sequence[int] | None = None,
) -> bool:
    """Join this process into a multi-host JAX runtime (idempotent).

    Arguments default to the ``PIO_COORDINATOR_ADDRESS`` /
    ``PIO_NUM_PROCESSES`` / ``PIO_PROCESS_ID`` environment variables;
    with everything unset, ``jax.distributed.initialize()`` auto-detects
    on TPU pod slices (its own env/metadata discovery). Call before any
    other JAX API — backend initialization pins the device set.

    Returns True if the distributed runtime was (already) initialized.
    """
    global _initialized
    if _initialized:
        return True
    import jax

    coordinator_address = coordinator_address or os.environ.get(
        "PIO_COORDINATOR_ADDRESS"
    )
    if num_processes is None and os.environ.get("PIO_NUM_PROCESSES"):
        num_processes = int(os.environ["PIO_NUM_PROCESSES"])
    if process_id is None and os.environ.get("PIO_PROCESS_ID"):
        process_id = int(os.environ["PIO_PROCESS_ID"])
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
        local_device_ids=local_device_ids,
    )
    _initialized = True
    logger.info(
        "multi-host runtime up: process %d/%d, %d global / %d local devices",
        jax.process_index(),
        jax.process_count(),
        len(jax.devices()),
        len(jax.local_devices()),
    )
    return True


def device_count() -> int:
    import jax

    return len(jax.devices())


def make_mesh(axes: Sequence[tuple[str, int]] | None = None):
    """Build a Mesh from (name, size) axes; one ``-1`` absorbs the rest.

    Default: 1-D ``("data", n_devices)``. Axis sizes must multiply to at
    most the device count.
    """
    import jax
    from jax.sharding import Mesh

    devices = jax.devices()
    if not axes:
        return Mesh(np.array(devices), ("data",))
    names = [n for n, _ in axes]
    sizes = [int(s) for _, s in axes]
    if sizes.count(-1) > 1:
        raise ValueError("at most one mesh axis may be -1")
    if -1 in sizes:
        known = int(np.prod([s for s in sizes if s != -1])) or 1
        inferred = len(devices) // known
        if inferred == 0:
            raise ValueError(
                f"mesh axes {list(zip(names, sizes))}: fixed sizes need "
                f"{known} devices but only {len(devices)} available"
            )
        sizes[sizes.index(-1)] = inferred
    total = int(np.prod(sizes))
    if total > len(devices):
        raise ValueError(
            f"mesh axes {list(zip(names, sizes))} need {total} devices, "
            f"have {len(devices)}"
        )
    return Mesh(np.array(devices[:total]).reshape(sizes), tuple(names))


def parse_axes(spec: str | None) -> list[tuple[str, int]] | None:
    """'data=4,model=2' -> [("data", 4), ("model", 2)]; None for an empty
    spec. A size is a positive integer, or -1 once for the remainder."""
    if not spec:
        return None
    axes = []
    for part in spec.split(","):
        name, _, size = part.partition("=")
        try:
            n = int(size)
        except ValueError:
            n = 0
        if not name.strip() or n == 0 or n < -1:
            raise ValueError(
                f"bad mesh axis {part!r}; expected name=size with a "
                "positive integer size (or -1 once for the remainder)"
            )
        axes.append((name.strip(), n))
    if sum(1 for _, n in axes if n == -1) > 1:
        raise ValueError("at most one mesh axis may be -1")
    return axes


def serving_mesh():
    """The 1-D ``data`` mesh a serving process shards a catalog over:
    ``PIO_MESH`` (``pio deploy --mesh data=4``) where set, else every
    device this process sees."""
    axes = parse_axes(os.environ.get("PIO_MESH"))
    if axes and [n for n, _ in axes] != ["data"]:
        raise ValueError(
            f"serving shards item rows over one axis, 'data'; PIO_MESH "
            f"gives {axes}"
        )
    return make_mesh(axes)


def factor_sharding(mesh, axis: str = "data"):
    """Row sharding for factor tables / packed bucket tables: ``P(axis)``
    on dim 0. A pytree-prefix of this covers the int8 ``(values, scales)``
    pair too (both leaves row-sharded), which is how the fused trainer
    spells its ``in_shardings``."""
    from jax.sharding import NamedSharding, PartitionSpec

    return NamedSharding(mesh, PartitionSpec(axis))


def replicated_sharding(mesh):
    """Fully replicated placement (scatter row-id vectors, scalars)."""
    from jax.sharding import NamedSharding, PartitionSpec

    return NamedSharding(mesh, PartitionSpec())
