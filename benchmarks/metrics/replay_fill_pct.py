"""How much of the ring held rows when the window opened: `buffer_fill` of
the opening record over the ring's capacity (the trainer's
`--replay_capacity`, among the configuration's flags). 100 says that every
gather of the window drew its indices over the whole ring."""


def read(run):
    capacity = [f.split("=", 1)[1] for f in run["config"]["flags"] if f.startswith("--replay_capacity=")]
    if not capacity or "buffer_fill" not in run["open"]:
        return None
    return 100.0 * run["open"]["buffer_fill"] / int(float(capacity[0]))
