"""Share of the window's dispatches that found no launch in flight
(`n_dispatch_starved` of `n_dispatch`): the device had run dry when the host
came back, the host-side cause of device idle."""

from harness import inside


def read(run):
    return inside.share_pct(run["window"], "n_dispatch_starved", "n_dispatch")
