"""shard_busy_skew.shardstore — largest over mean busy time of the device
planes in the traced window (``busy_by_plane``). 1.0 = every chip equally
busy; a shard whose rules leave it more (or less) to do than the others — a
category that lies on one shard — reads above it."""


def read(raw, spec, cell):
    t = raw.get("trace")
    busy = list((t or {}).get("busy_by_plane", {}).values())
    if not busy or not sum(busy):
        return None
    return max(busy) / (sum(busy) / len(busy))
