"""Share of the updates' device time that is MPO's E-step: the target policy
at s', the drawn actions, the target critic on batch x samples rows, their
expectations, the mixture and the weights, no gradient in any of it (the
program's scope `update/estep` over `update` with all beneath it,
harness/scopes.py). Only a program that brackets `estep` has the scope; any
other gives nothing to read."""

from harness import scopes


def read(run):
    return scopes.pct(run, ("update/estep",), ("update",)) or None
