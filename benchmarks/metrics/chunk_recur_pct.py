"""Share of the updates' device time that is the recurrence: every pass
through a net's LSTM, the scan over a window's steps forward (the two targets',
the critic's, the actor's) and back through time (the critic's, the actor's),
the embedders and heads not included: the device time of every scope of the
program whose path holds `recur` (`update/target/recur`, `update/critic/recur`,
`update/actor/recur`) over `update` with all beneath it (harness/scopes.py).
Only a program that brackets `recur` has such scopes; any other gives nothing
to read."""

from harness import scopes


def recur_ns(found):
    """Device time a launch under every scope whose path holds `recur`."""
    return sum(t for s, t in found["scopes"].items() if "recur" in s.split("/"))


def read(run):
    found = scopes.of_run(run)
    whole = found and scopes.ns(found, "update")
    if not whole:
        return None
    return 100.0 * recur_ns(found) / whole or None
