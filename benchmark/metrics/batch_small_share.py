"""batch_small_share — of the device dispatches in the window, the share that
carried at most two queries: pio_batch_size_bucket{le="2"} over
pio_batch_size_count, in %. A mean batch size hides a two-mode distribution
(a small batch, then a large one); this does not."""


def read(raw, spec, cell):
    d = raw.get("counters_delta") or {}
    small = d.get('pio_batch_size_bucket{le="2"}')
    total = d.get("pio_batch_size_count")
    if small is None or not total:
        return None
    return 100.0 * small / total
