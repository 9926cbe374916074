"""Process start to window open, without the time the reference took."""


def read(run):
    return run["start_to_open_s"]
