"""Share of the traced span, mean over chips, from the span's start to the
chip's first operation and from its last to the span's end: when the
profiler's session reached the chip, not idleness. `device.idle_pct` holds it."""

from harness import timeline


def read(run):
    return timeline.part_pct(run, "edge")
