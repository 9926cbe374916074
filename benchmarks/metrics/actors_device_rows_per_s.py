"""Rows a second the device pool's environments wrote into the ring: the
window mean of `devactor_rows_per_s`, which a program with a device pool
(`--actor_backend=device`) writes into every record for the interval since
the record before. No host actor and no ingest path carries these rows."""

import statistics


def read(run):
    rates = [r["devactor_rows_per_s"] for r in run["window"] if "devactor_rows_per_s" in r]
    return statistics.fmean(rates) if rates else None
