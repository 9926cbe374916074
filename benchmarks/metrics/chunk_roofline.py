"""The chunk launch against the chip's roofline: the least time the chip
could take for the launch's updates (the reference's `work`, harness/flops.py,
peaks.py) over the launch's device time."""

from . import chunk_device_ms


def read(run):
    found = chunk_device_ms.launch(run)
    if not found:
        return None
    need = run["reference"].work(run["config"]["env"], run["config"]["reference"]["hp"])
    pct, _ = run["flops"].roofline_pct(
        need, run["summary"]["learner_chunk"], found["median_s"], run["peaks"]
    )
    return pct
