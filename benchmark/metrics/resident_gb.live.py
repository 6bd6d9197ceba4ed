"""resident_gb.live — resident_gb.int8's reading in the live cell: the device
bytes the served model keeps resident at the scrape that CLOSES the window,
after every patch of it, in GB. One item table, one set of tiles, one user
table with room to grow (1,048,576 rows for 1,000,000 users): within 0.1 GB of
the twin's resident_gb.int8, or a patch has staged something again."""

import os

import readers

_twin = readers.load_metric(os.path.dirname(os.path.abspath(__file__)), "resident_gb.int8")


def read(raw, spec, cell):
    return _twin(raw, cell)
