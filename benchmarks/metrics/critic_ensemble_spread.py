"""How far the N online critics lie apart: the batch mean of the standard
deviation over the ensemble's Q_i(s, a) on the replay rows (`redq_q_spread` on
each `"train"` record: the newest chunk's last update), mean over the window's
records, in units of return. It is the quantity REDQ's in-target minimum over
a drawn subset acts on, and moves no rate. Only a program with a critic
ensemble (`DDPGConfig.redq`) writes the key."""


def read(run):
    spreads = [r["redq_q_spread"] for r in run["window"] if "redq_q_spread" in r]
    return sum(spreads) / len(spreads) if spreads else None
