"""patch_h2d_mb.live — bytes the server booked as sent to the device a patch:
pio_device_transfer_bytes_total{direction="h2d",op="serve.model_patch"} over
pio_device_transfers_total of the same site, in the window, in MB (1e6 bytes).
A fold-in that patches the resident user table where it lies sends the solved
rows, their scales and their indices (576 B for up to 8 users of an int8
model at rank 64); one that replaces the model books the model. None where no
patch was applied in the window. Four significant digits."""

SITE = '{direction="h2d",op="serve.model_patch"}'


def read(raw, spec, cell):
    d = raw.get("counters_delta") or {}
    n = d.get("pio_device_transfers_total" + SITE)
    if not n:
        return None
    return float("%.4g" % (d.get("pio_device_transfer_bytes_total" + SITE, 0.0) / n / 1e6))
