"""Set-up stage `setup_backend` of the trainer (metrics.SetupStages, train.py),
in seconds: entry of train() until the devices are known: the backend's start."""

from harness import inside


def read(run):
    return inside.setup_span(run, "setup_backend")
