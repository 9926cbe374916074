"""Set-up stage `setup_import` of the trainer (metrics.SetupStages, train.py), in
seconds: importing what the trainer pulls in that the process had not loaded:
its own module, then the modules it defers until the backend is up (orbax
above all)."""

from harness import inside


def read(run):
    return inside.setup_span(run, "setup_import")
