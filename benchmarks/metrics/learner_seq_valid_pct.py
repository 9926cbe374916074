"""Share of a batch's window steps that are real: 100 x `seq_valid_frac` (on
each `"train"` record: the newest chunk's mean over its updates of the mean of
the rows' masks), mean over the window's records. 100 where every drawn window
is full; lower where the ring holds the prefixes of young episodes, whose
padded steps the update computes and masks out. Only a recurrent program
(`DDPGConfig.recurrent`) writes the key."""


def read(run):
    shares = [r["seq_valid_frac"] for r in run["window"] if "seq_valid_frac" in r]
    return 100.0 * sum(shares) / len(shares) if shares else None
