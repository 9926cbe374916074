"""How old, in time, the parameters are that the actors act on: the median
over the window's records of `staleness_mean` (learner updates between the
version a worker's newest rows acted on and the newest broadcast), over the
window's update rate."""

import statistics


def read(run):
    lags = [r["staleness_mean"] for r in run["window"] if "staleness_mean" in r]
    if not lags:
        return None
    rate = run["records"].rate(run["open"], run["close"], "learner_steps", run["window_s"])
    return 1000.0 * statistics.median(lags) / rate
