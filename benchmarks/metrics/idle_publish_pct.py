"""Share of the traced span in which no operation ran on the chip, away from
the span's two ends, while the learner thread published the fetched
parameters to the host actors (`param_broadcast`, actors/pool.py)."""

from harness import timeline


def read(run):
    return timeline.under_pct(run, "param_broadcast")
