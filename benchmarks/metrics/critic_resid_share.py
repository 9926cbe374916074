"""How much of the residual stream each block of the critics rewrites: the
mean over the critics' blocks and the batch of |f(LN(x))| / |x + f(LN(x))|
(`resid_share` on each `"train"` record: the newest chunk's last update),
mean over the window's records. Near 0 the blocks are the identity and the
critic is its embedding and head; near 1 the stream is what the last block
wrote. It moves no rate. Only a residual (SimBa) program (`DDPGConfig.simba`)
writes the key."""


def read(run):
    shares = [r["resid_share"] for r in run["window"] if "resid_share" in r]
    return sum(shares) / len(shares) if shares else None
