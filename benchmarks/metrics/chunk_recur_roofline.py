"""The recurrence against the chip's roofline: the least time the chip could
take for the LSTM products of a launch (the reference's
`work()["recur_flops"]` an update: (X + H) x 4 H multiply-adds a row, window
step and pass, every pass once; the launch's updates; the bf16 peak of
harness/peaks.py) over the device time the launch spends under the program's
scopes whose path holds `recur`, in per cent. A chain of dependent [B, X + H] x
[X + H, 4 H] products reads near 1% by design: the chip waits on each step's
result, not on its arithmetic. The count lives with the reference; a reference
without the key, or a program without the scopes, gives nothing to read."""

from harness import scopes

from .chunk_recur_pct import recur_ns


def read(run):
    found = scopes.of_run(run)
    spent_ns = found and recur_ns(found)
    work = getattr(run.get("reference"), "work", None)
    if not spent_ns or work is None:
        return None
    need = work(run["config"]["env"], run["config"]["reference"]["hp"]).get("recur_flops")
    if not need:
        return None
    least_s = run["summary"]["learner_chunk"] * need / run["peaks"]["flops_per_s"]
    return 100.0 * least_s / (spent_ns / 1e9)
