"""Device time a launch of the chunk program spends getting its rows: the
index draw and the gather out of the ring (the program's scopes `draw` and
`gather`, harness/scopes.py), in milliseconds."""

from harness import scopes


def read(run):
    return scopes.ms(run, "draw", "gather")
