"""Share of the updates' device time in which the scan's loop runs none of
its body's operations: the `while` events' self time over the program's
scope `update` with all beneath it (harness/scopes.py)."""

from harness import scopes


def read(run):
    return scopes.loop_self_pct(run, ("update",))
