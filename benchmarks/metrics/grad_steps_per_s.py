"""Learner updates the device finished per second of the window."""


def read(run):
    return run["records"].rate(run["open"], run["close"], "learner_steps", run["window_s"])
