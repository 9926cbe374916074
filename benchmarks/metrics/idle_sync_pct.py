"""Share of the traced span in which no operation ran on the chip and the
learner thread was inside a `sync` span (train.py's phase of that name,
on the profiler's host plane)."""

from harness import inside


def read(run):
    return inside.idle_pct(run, "sync")
