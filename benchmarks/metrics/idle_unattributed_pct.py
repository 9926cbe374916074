"""Share of the traced span in which no operation ran on the chip and the
learner thread was inside none of its phases' spans: between phases (records,
fleet supervision, the loop's own bookkeeping) or before a span could begin."""

from harness import inside


def read(run):
    return inside.idle_pct(run, None)
