"""Host time of one parameter refresh (the `refresh` phase: the d2h that
waits out the queued chunks, then the broadcast to the actors)."""


def read(run):
    return run["records"].phase_mean_ms(run["window"], "refresh")
