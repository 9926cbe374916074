"""How far the evaluation-mode critics the actor climbs are from the
training-mode critics the loss fits: the mean over the critics' normalised
features of |batch mean - running mean| / running standard deviation
(`bn_stat_gap` on each `"train"` record: the newest chunk's last update),
mean over the window's records, in running standard deviations. It is the
quantity batch renormalisation exists to bound, and moves no rate. Only a
CrossQ program (`DDPGConfig.crossq`) writes the key."""


def read(run):
    gaps = [r["bn_stat_gap"] for r in run["window"] if "bn_stat_gap" in r]
    return sum(gaps) / len(gaps) if gaps else None
