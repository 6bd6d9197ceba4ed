"""masked_shard_scan_roofline.shardstore — share of the memory's speed the
masked sharded program reached ON THE SLOWEST SHARD: the bytes ONE shard must
read a dispatch (shardstore_costs.py ``masked_shard_scan_bytes``: its stored
bf16 rows, ids, category ids, availability bytes and a query's mask byte a
row) over 819 GB/s, times the dispatches, over the device time of
``jit__sharded_topk_masked`` on the device plane where that time is largest
(xplane_shardstore.py ``program_s_by_plane``). One shard's bytes over one
shard's seconds, and the seconds hold the whole program (the rules' resolution,
scan, local rescore, the all-gather, the merge), so the share cannot read over
100 % by counting. Bandwidth binds. Not clamped. A program without the masked
sharded program leaves nothing to read: None."""

import peaks
import shardstore_costs

PROGRAM = "jit__sharded_topk_masked"


def read(raw, spec, cell):
    t = raw.get("trace")
    if not t:
        return None
    by_plane = t.get("program_s_by_plane") or {}
    calls = t.get("program_calls", {}).get(PROGRAM)
    slowest = max(by_plane.values(), default=0.0)
    if not slowest or not calls:
        return None
    cfg = cell["config"]
    r = cfg["retrieval"]
    nbytes = shardstore_costs.masked_shard_scan_bytes(
        cfg["num_items"], cfg["rank"], r["tile"], len(by_plane), r["coarse_dtype"],
        cfg.get("categories_per_item", 1))
    bw = peaks.peaks_for(raw["device"]["kind"])["hbm_bytes_per_s"]
    dispatches = calls / len(by_plane)  # every plane runs its part of each dispatch
    return 100.0 * dispatches * (nbytes / bw) / slowest
