"""shard_busy_skew.sharded — largest over mean busy time of the device planes
in the traced window (xplane_sharded.py ``busy_by_plane``: xplane.py's
``reduce_planes`` called a plane at a time, since its ``busy_s`` is their
mean). 1.0 = every chip equally busy; a shard that holds the work back reads
above it."""


def read(raw, spec, cell):
    t = raw.get("trace")
    busy = list((t or {}).get("busy_by_plane", {}).values())
    if not busy or not sum(busy):
        return None
    return max(busy) / (sum(busy) / len(busy))
