"""How much of the return distribution the categorical critic's support
clips: the share of the projected target's mass on atoms 0 and A-1
(`c51_edge_mass` on each `"train"` record: the newest chunk's last update),
mean over the window's records. It informs the choice of `v_min` / `v_max`
and no rate. Only a program with a categorical critic writes the key."""


def read(run):
    shares = [r["c51_edge_mass"] for r in run["window"] if "c51_edge_mass" in r]
    return 100.0 * sum(shares) / len(shares) if shares else None
