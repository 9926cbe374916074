"""How far MPO's online policy mean has moved from the target's against its
bound: the mean over the action's dimensions of KL_mean / epsilon_mean
(`mpo_kl_mean_ratio` on each `"train"` record: the newest chunk's last
update), mean over the window's records. Above 1 the bound is broken and its
multiplier grows; it falls to 0 at every copy of the targets. Only an MPO
program (`DDPGConfig.mpo`) writes the key."""


def read(run):
    ratios = [r["mpo_kl_mean_ratio"] for r in run["window"] if "mpo_kl_mean_ratio" in r]
    return sum(ratios) / len(ratios) if ratios else None
