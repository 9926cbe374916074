"""How many of its sampled actions a state MPO's improved policy is fitted
to: the batch mean of 1 / sum_j w_j^2 over the E-step's value weights
(`mpo_weight_ess` on each `"train"` record: the newest chunk's last update),
mean over the window's records. It runs from 1 (one action takes all the
weight: the temperature has collapsed) to the number of samples (uniform
weights: the critic tells the actions apart by less than the temperature).
Only an MPO program (`DDPGConfig.mpo`) writes the key."""


def read(run):
    sizes = [r["mpo_weight_ess"] for r in run["window"] if "mpo_weight_ess" in r]
    return sum(sizes) / len(sizes) if sizes else None
