"""Device time a launch of the chunk program spends between the gather and
the updates: the gathered rows cut into fields or kernel streams, and the
launch's noise (the program's scopes `cut` and `noise`, harness/scopes.py),
in milliseconds."""

from harness import scopes


def read(run):
    return scopes.ms(run, "cut", "noise")
