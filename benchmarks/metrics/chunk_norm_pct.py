"""Share of the updates' device time that is batch normalisation: the
moments, the normalising, the running statistics' step and their backward
pass (the program's scopes `update/critic/norm` and `update/actor/norm` over
`update` with all beneath it, harness/scopes.py). A lower bound, as
`chunk.optim_pct` is: what XLA fuses into a matmul's prologue or epilogue
reads as that matmul's scope. Only a program that brackets `norm` has the
scopes; any other gives nothing to read."""

from harness import scopes


def read(run):
    return scopes.pct(run, ("update/critic/norm", "update/actor/norm"), ("update",)) or None
