"""Share of the updates' device time that is the convolutional encoder: both
forward passes (the observation's with gradient, the next observation's
without) and the backward pass, the trunks not included (the program's scope
`update/encoder` over `update` with all beneath it, harness/scopes.py). Only
a program that brackets `encoder` has the scope; any other gives nothing to
read."""

from harness import scopes


def read(run):
    return scopes.pct(run, ("update/encoder",), ("update",)) or None
