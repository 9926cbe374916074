"""Device time of one launch of the learner's chunk program, median over
the traced launches. The configuration names the program (`chunk_module`),
as the trace's `XLA Modules` line names it."""


def launch(run):
    trace = run["trace"]
    if not trace:
        return None
    name = run["config"].get("chunk_module")
    hits = [v for k, v in trace["launches"].items() if name and name in k]
    return max(hits, key=lambda v: v["total_s"]) if hits else None


def read(run):
    found = launch(run)
    return 1000.0 * found["median_s"] if found else None
