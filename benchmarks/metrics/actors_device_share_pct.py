"""Share of the device's busy time that went to the device pool, over the
traced span: the launches of its rollout program (`jit_devactor_rollout` on
the trace's `XLA Modules` line: actors/device_pool.py) and of the ring inserts
that land its rows (`jit_ring_insert...`, which `ingest.device_share_pct`
counts too): what the actors take from the learner on the one device queue.
Nothing to read where no rollout program ran."""

from harness import inside

ROLLOUT = "jit_devactor_rollout"


def read(run):
    inserts = inside.insert_launches(run)
    if inserts is None:
        return None
    rollouts = [v for k, v in run["trace"]["launches"].items() if k.startswith(ROLLOUT)]
    if not rollouts:
        return None
    return 100.0 * sum(v["total_s"] for v in rollouts + inserts) / run["trace"]["busy_s"]
