"""Host time of one chunk dispatch (the `dispatch` phase), window mean."""


def read(run):
    return run["records"].phase_mean_ms(run["window"], "dispatch")
