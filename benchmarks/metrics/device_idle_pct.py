"""Share of the traced span in which no operation ran on the device."""


def read(run):
    trace = run["trace"]
    if not trace:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
