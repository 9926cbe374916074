"""Set-up stage `setup_build` of the trainer (metrics.SetupStages, train.py), in
seconds: devices known until env spec, learner, replay ring and actor pool are
built."""

from harness import inside


def read(run):
    return inside.setup_span(run, "setup_build")
