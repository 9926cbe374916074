"""MPO's E-step against the chip's roofline: the least time the chip could
take for the E-step's passes of a launch (the reference's
`work()["estep_flops"]` an update: the target policy on the batch's rows and
the target critic on batch x samples rows, each once; the launch's updates;
the bf16 peak of harness/peaks.py) over the device time the launch spends
under the program's scope `update/estep`, in per cent. The count lives with
the reference; a reference without the key, or a program without the scope,
gives nothing to read."""

from harness import scopes


def read(run):
    spent_ms = scopes.ms(run, "update/estep")
    work = getattr(run.get("reference"), "work", None)
    if not spent_ms or work is None:
        return None
    need = work(run["config"]["env"], run["config"]["reference"]["hp"]).get("estep_flops")
    if not need:
        return None
    least_s = run["summary"]["learner_chunk"] * need / run["peaks"]["flops_per_s"]
    return 100.0 * least_s / (spent_ms / 1e3)
