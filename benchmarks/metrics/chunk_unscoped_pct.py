"""Share of the operation time inside the chunk program's launches whose
instruction the program's table names no scope for: what the other readers
of harness/scopes.py do not see."""

from harness import scopes


def read(run):
    return scopes.pct(run, (scopes.UNSCOPED,))
