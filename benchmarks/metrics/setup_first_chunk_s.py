"""Set-up stage `setup_first_chunk` of the trainer (metrics.SetupStages,
train.py), in seconds: first dispatch until its result is read back: the chunk
program compiled or loaded, then run once (in a benchmark run the harness's
check rides inside it)."""

from harness import inside


def read(run):
    return inside.setup_span(run, "setup_first_chunk")
