"""Share of the updates' device time that is the optimiser and the target
updates (the program's scopes `update/optim` and `update/polyak` over
`update` with all beneath it, harness/scopes.py)."""

from harness import scopes


def read(run):
    return scopes.pct(run, ("update/optim", "update/polyak"), ("update",))
