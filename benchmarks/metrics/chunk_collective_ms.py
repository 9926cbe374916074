"""Device time a launch of the chunk program spends in collective
instructions (all-reduce and its kin, whatever scope they serve;
harness/scopes.py), in milliseconds, a chip."""

from harness import scopes


def read(run):
    return scopes.ms(run, "collective")
