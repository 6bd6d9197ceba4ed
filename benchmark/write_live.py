"""write_live.py — what a live Recommendation deployment has on disk before
`pio deploy --realtime`: the trained int8 model (write_int8.py, whole and
unchanged), the app with its access key, and the writers' rating histories in
the indexed event store — all of them BEFORE the model's train watermark, so
the speed layer's cursor starts after them and folds none at start.

    python3 benchmark/write_live.py <spec.json>

The histories go in by write_ecomm.py's bulk load (one transaction into the
table the program created) and are read back through the program's own
by-entity read before the child exits 0. Touches no device.
Prints one JSON line: write_int8's, plus {"app_id", "access_key", "history_events"}.
"""

from __future__ import annotations

import contextlib
import io
import json
import os
import sqlite3
import sys
import time
from datetime import datetime, timezone

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import live_data  # noqa: E402
import write_int8  # noqa: E402
from write_ecomm import bulk_insert  # noqa: E402

T0 = datetime(2023, 9, 1, tzinfo=timezone.utc)  # the source's last month


def history_rows(dep: live_data.Deployment):
    """Rows of the program's sqlite event table, in its column order."""
    users = np.repeat(dep.writers, np.diff(dep.bounds))
    t0 = T0.timestamp()
    for j, (u, i, s) in enumerate(zip(users.tolist(), dep.hist_items.tolist(),
                                      dep.hist_stars.tolist())):
        t = t0 + j * 1e-3
        yield ("%032x" % j, "rate", "user", "u%d" % u, "item", "i%d" % i,
               '{"rating": %d}' % s, t, "0", "[]", None, t)


def main(argv: list[str]) -> int:
    with open(argv[1]) as fh:
        spec = json.load(fh)
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = write_int8.main(argv)
    if rc != 0:
        return rc
    written = json.loads(out.getvalue().strip().splitlines()[-1])
    t0 = time.perf_counter()
    from predictionio_tpu.data import store
    from predictionio_tpu.data.storage import AccessKey, App, Storage, set_storage

    env = {k: v for k, v in os.environ.items() if k.startswith("PIO_")}
    storage = Storage(env=env)
    set_storage(storage)
    app_name = spec["variant"]["datasource"]["params"]["app_name"]
    app_id = storage.get_metadata_apps().insert(App(0, app_name))
    key = storage.get_metadata_access_keys().insert(AccessKey("", app_id, []))
    events = storage.get_events()
    events.init(app_id)
    dep = live_data.Deployment(spec["config"], spec["seed"])
    try:
        bulk_insert(env["PIO_STORAGE_SOURCES_DB_PATH"], f"pio_event_{app_id}", history_rows(dep))
    except (KeyError, sqlite3.Error) as e:  # another store, another schema
        print(f"write_live: the event table did not take the bulk load "
              f"({type(e).__name__}: {e})", file=sys.stderr)
        return 2
    probe = int(dep.writers[len(dep.writers) // 2])
    items, stars = dep.history(probe)
    got = [(e.target_entity_id, e.properties.get("rating")) for e in store.find_by_entity(
        app_name=app_name, entity_type="user", entity_id="u%d" % probe,
        event_names=["rate"], target_entity_type="item", limit=None, latest=False)]
    if got != [("i%d" % i, s) for i, s in zip(items.tolist(), stars.tolist())]:
        print(f"write_live: u{probe}'s history reads back as {got[:4]}.. "
              f"({len(got)} events of {len(items)})", file=sys.stderr)
        return 2
    written["seconds"]["histories"] = time.perf_counter() - t0
    written.update(app_id=app_id, access_key=key, history_events=int(dep.bounds[-1]))
    print(json.dumps(written))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
