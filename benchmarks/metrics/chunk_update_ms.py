"""Device time a launch of the chunk program spends in its K updates: the
scan with all in it, or the Pallas call (the program's scope `update` and
all beneath it, harness/scopes.py), in milliseconds. Collectives are a scope
of their own and not in it."""

from harness import scopes


def read(run):
    return scopes.ms(run, "update")
