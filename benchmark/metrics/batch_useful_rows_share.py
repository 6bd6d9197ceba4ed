"""batch_useful_rows_share — of the query rows the server dispatched to the
device in the window, the share that were real queries (the rest is padding
to a power-of-two batch): pio_batch_rows_total{kind="real"} over
{kind="padded"}, in %. None where the program has no such counter."""


def read(raw, spec, cell):
    d = raw.get("counters_delta") or {}
    real = d.get('pio_batch_rows_total{kind="real"}')
    padded = d.get('pio_batch_rows_total{kind="padded"}')
    if real is None or not padded:
        return None
    return 100.0 * real / padded
