"""Host time of one ingest beat (the `ingest` phase), window mean."""


def read(run):
    return run["records"].phase_mean_ms(run["window"], "ingest")
