"""Share of the updates' device time that is a residual net's normalisation:
its LayerNorms (one a block and one in front of the head: the row moments,
the normalising, scale and shift, and their backward pass) and its input
normaliser (the normalising and the statistics' merge), in critics and actor
(the program's scopes `update/critic/lnorm`, `update/critic/rsnorm`,
`update/actor/lnorm` and `update/actor/rsnorm` over `update` with all beneath
it, harness/scopes.py). A lower bound, as `chunk.norm_pct` is: what XLA fuses
into a matmul's prologue or epilogue reads as that matmul's scope. Only a
program that brackets `lnorm` has the scopes; any other gives nothing to
read."""

from harness import scopes

SCOPES = ("update/critic/lnorm", "update/critic/rsnorm", "update/actor/lnorm", "update/actor/rsnorm")


def read(run):
    return scopes.pct(run, SCOPES, ("update",)) or None
