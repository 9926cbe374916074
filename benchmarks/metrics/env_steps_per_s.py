"""Transitions the actors delivered into the ring per second of the window
(`step` of a "train" record is the env-step count)."""


def read(run):
    return run["records"].rate(run["open"], run["close"], "step", run["window_s"])
