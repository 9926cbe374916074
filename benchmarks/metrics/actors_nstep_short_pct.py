"""The share of the rows the actors emitted inside the window that carry
fewer than n steps (episode ends and truncation flushes): the counts
`nstep_short_rows` over `nstep_rows`, which the trainer's records carry since
the run began. Only a program whose actors fold n > 1 steps writes them."""


def read(run):
    first, last = run["open"], run["close"]
    if "nstep_rows" not in last or "nstep_rows" not in first:
        return None
    rows = last["nstep_rows"] - first["nstep_rows"]
    return 100.0 * (last["nstep_short_rows"] - first["nstep_short_rows"]) / rows if rows else None
