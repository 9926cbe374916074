"""Share of the traced span in which no operation ran on the chip, away from
the span's two ends, while the learner thread waited for a queued launch
(`launch_wait`, metrics.LaunchQueue.drain): idle though the host holds work
queued: the gaps between one launch and the next and, on several chips, a
chip waiting for the others."""

from harness import timeline


def read(run):
    return timeline.under_pct(run, "launch_wait")
