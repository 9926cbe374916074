"""Share of the device's busy time that went to ring inserts, over the traced
span: what ingest takes from the learner. 0 where no insert ran in it."""

from harness import inside


def read(run):
    found = inside.insert_launches(run)
    if found is None:
        return None
    return 100.0 * sum(v["total_s"] for v in found) / run["trace"]["busy_s"]
