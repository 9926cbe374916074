"""Set-up stage `setup_fill` of the trainer (metrics.SetupStages, train.py), in
seconds: everything built until the ring holds replay_min_size rows: the
actors' start and their first deliveries."""

from harness import inside


def read(run):
    return inside.setup_span(run, "setup_fill")
