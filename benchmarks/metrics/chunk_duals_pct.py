"""Share of the updates' device time that is MPO's dual variables: their
loss, its gradient and their own Adam, some tens of scalars an update (the
program's scope `update/duals` over `update` with all beneath it,
harness/scopes.py). Only a program that brackets `duals` has the scope; any
other gives nothing to read."""

from harness import scopes


def read(run):
    return scopes.pct(run, ("update/duals",), ("update",)) or None
