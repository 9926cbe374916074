"""Share of the traced span in which no operation ran on the chip, away from
the span's two ends, while the learner thread copied the actor's parameters
or the newest launch's metrics to the host (`params_d2h`, `metrics_d2h` in
parallel/learner.py: the copy and the fold, the queue already drained)."""

from harness import timeline


def read(run):
    return timeline.under_pct(run, "params_d2h", "metrics_d2h")
