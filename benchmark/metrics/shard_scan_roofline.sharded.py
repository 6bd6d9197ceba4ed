"""shard_scan_roofline.sharded — share of the memory's speed the sharded
program reached, over ALL chips: the bytes all chips must read a dispatch
(``sharded_scan_bytes``: every stored bf16 row and its int32 id once, padding
included — each chip its own shard) over 819 GB/s, over the device time of
``jit__sharded_topk`` SUMMED over the device planes as xplane.py sums it.
Bytes and seconds are both over the same set of chips, so the share cannot
read over 100 % by counting; the seconds hold the whole program (scan, local
rescore, the wait in the all-gather for the slowest shard, the merge).
Bandwidth binds. Not clamped."""

import costs
import peaks

PROGRAM = "jit__sharded_topk"


def sharded_scan_bytes(num_items: int, rank: int, tile: int, shards: int,
                       coarse_dtype: str = "bfloat16") -> float:
    """Bytes all ``shards`` read in one dispatch: a shard holds ceil(I /
    shards) rows in whole tiles of at most ``tile`` rows (a power of two no
    wider than the shard needs), each row ``rank`` coarse values + one id."""
    rows = -(-num_items // shards)
    t = min(tile, 1 << max(0, rows - 1).bit_length())
    stored = -(-rows // t) * t
    return float(shards * stored * (rank * costs.DTYPE_BYTES[coarse_dtype] + 4))


def read(raw, spec, cell):
    t = raw.get("trace")
    if not t:
        return None
    secs = t["programs"].get(PROGRAM)
    calls = t.get("program_calls", {}).get(PROGRAM)
    planes = t.get("device_planes")
    if not secs or not calls or not planes:
        return None
    cfg = cell["config"]
    r = cfg["retrieval"]
    nbytes = sharded_scan_bytes(cfg["num_items"], cfg["rank"], r["tile"],
                                planes, r["coarse_dtype"])
    bw = peaks.peaks_for(raw["device"]["kind"])["hbm_bytes_per_s"]
    dispatches = calls / planes  # every plane runs its part of each dispatch
    return 100.0 * dispatches * (nbytes / bw) / secs
