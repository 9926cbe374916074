"""Host time of one parameter refresh with nothing in flight: the `refresh`
phase less its `refresh_drain` (the wait for the queued launches, the device
busy throughout), per refresh over the window. What is left is the copy, the
fold and the broadcast, during which the device has nothing to run: the idle
time one refresh costs, by the host's clock. Where `refresh` is a pointer
swap (no host worker) nothing drains under it and this reads `loop.refresh_ms`."""

from harness import timeline


def read(run):
    ms = timeline.host_ms(run["window"], "refresh")
    calls = run["records"].phase_calls(run["window"], "refresh")
    return ms / calls if ms is not None and calls else None
