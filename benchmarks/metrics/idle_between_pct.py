"""Share of the traced span in which no operation ran on the chip, away from
the span's two ends, and the learner thread had none of the program's spans
open: between phases (records, fleet supervision, the loop's own bookkeeping),
or under a span that began before the profiler's session."""

from harness import timeline


def read(run):
    return timeline.part_pct(run, "between")
