"""shard_merge_ms.shardstore — device time a masked dispatch spends, on one
chip, from the start of the sharded program's collective to the end of the
program: the all-gather of every shard's [B, k] scores and ids (the wait for
the slowest shard included) and the top-k over the gathered candidates, in ms
(xplane_shardstore.py: xplane_sharded.py's ``shard_tail`` inside every run of
``jit__sharded_topk_masked``), summed over the planes and divided by the runs
it was found in — a chip's mean."""


def read(raw, spec, cell):
    t = raw.get("trace")
    if not t or not t.get("shard_ops_calls"):
        return None
    return 1e3 * t["shard_ops_s"] / t["shard_ops_calls"]
