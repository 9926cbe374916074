"""Launches the device had not finished as each dispatch was made, that one
included (metrics.LaunchQueue: `launches_in_flight_mean`), mean over the
window's dispatches: what a refresh waits out and what policy lag is made of."""

from harness import inside


def read(run):
    return inside.weighted_mean(run["window"], "launches_in_flight_mean", "n_dispatch")
