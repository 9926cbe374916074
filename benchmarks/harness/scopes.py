"""Device time inside one launch of the chunk program, by the program's own
scopes.

The trace names an operation by its HLO instruction (`fusion.39`, `while.3`);
which part of the launch that is, only the program can say. A program that
brackets its parts writes the table beside its records when it returns
(`chunk_ops.json`: instruction name -> scope, the collectives' names, the
`while` loops' names; distributed_ddpg_tpu/trace.py), and this module joins
it to the run's own `.xplane.pb`:

- the launches of the configuration's `chunk_module` on each chip's
  `XLA Modules` line, those that lie whole inside the traced span;
- the `XLA Ops` events inside each, with SELF time: a `while` or a
  `conditional` event holds its body's events on the same line, so an
  operation's time is its duration less the events nested in it;
- per launch, self time by scope. An instruction the table lacks counts as
  UNSCOPED; a loop's self time (inside it, under none of its body's
  operations) stays with the loop's scope and is kept apart too;
- per scope the median over a chip's launches, averaged over chips.

By construction the scopes' times, UNSCOPED among them, add up to the
operation time inside the launch. A run without the table (a program that
writes none), without a trace or without a whole launch in it gives every
reader here nothing to read: None, and the line leaves the metric out.
"""

import bisect
import functools
import json
import os
import statistics

from . import inside, xplane

TABLE_FILE = "chunk_ops.json"
UNSCOPED = "unscoped"
EDGE_NS = 1e-3  # two readings of one instant differ by float rounding only


def table_file(run):
    log_path = run["summary"].get("log_path")
    path = log_path and os.path.join(os.path.dirname(log_path), TABLE_FILE)
    return path if path and os.path.isfile(path) else None


def self_times(events):
    """[(name, start, dur)] of one trace line -> [(name, self time)]: each
    event's duration less the events directly nested in it."""
    out, open_ = [], []  # open_: [name, end, self time] of the enclosing events

    def close(until):
        while open_ and open_[-1][1] <= until + EDGE_NS:
            name, _, own = open_.pop()
            out.append((name, own))

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if open_:
            open_[-1][2] -= min(dur, open_[-1][1] - start)
        open_.append([name, start + dur, dur])
    close(float("inf"))
    return out


def whole_launches(lines, module):
    """[(start, end)] of the chip's launches of `module` (the program whose
    name holds it and ran longest, as the launch's own metric picks it) that
    touch neither end of the chip's traced span: a launch the trace cut is
    recorded from the cut, shorter than it was."""
    every = [(s, s + d) for evs in lines.values() for _, s, d in evs if d > 0]
    by_name = {}
    for name, s, d in lines.get("XLA Modules", []):
        if module and module in xplane._base(name):
            by_name.setdefault(xplane._base(name), []).append((s, s + d))
    if not by_name:
        return []
    t0, t1 = min(s for s, _ in every), max(e for _, e in every)
    runs = max(by_name.values(), key=lambda v: sum(e - s for s, e in v))
    return [(s, e) for s, e in runs if s > t0 + EDGE_NS and e < t1 - EDGE_NS]


def launch_scopes(ops, table):
    """One launch's operations -> ({scope: self time}, loops' self time)."""
    scopes, loops = {}, 0.0
    names, loop_names = table["ops"], set(table.get("loops", ()))
    for name, own in self_times(ops):
        name = xplane._base(name)
        scope = names.get(name, UNSCOPED)
        scopes[scope] = scopes.get(scope, 0.0) + own
        if name in loop_names:
            loops += own
    return scopes, loops


def per_launch(trace, table, module):
    """{"scopes": {scope: ns a launch}, "loop_self": ns a launch, "launches":
    whole launches read, a chip}: medians over each chip's whole launches,
    averaged over chips; None where no chip has a whole launch."""
    per_chip = []
    for _, lines in sorted(trace["device"].items()):
        ops = sorted(lines.get("XLA Ops", []), key=lambda e: e[1])
        starts = [e[1] for e in ops]
        read = []
        for t0, t1 in whole_launches(lines, module):
            began = ops[bisect.bisect_left(starts, t0 - EDGE_NS):bisect.bisect_right(starts, t1)]
            read.append(launch_scopes([e for e in began if e[1] + e[2] <= t1 + EDGE_NS], table))
        if read:
            keys = {k for scopes, _ in read for k in scopes}
            per_chip.append((
                {k: statistics.median(s.get(k, 0.0) for s, _ in read) for k in keys},
                statistics.median(loops for _, loops in read),
                len(read),
            ))
    if not per_chip:
        return None
    n = len(per_chip)
    keys = {k for scopes, _, _ in per_chip for k in scopes}
    return {
        "scopes": {k: sum(s.get(k, 0.0) for s, _, _ in per_chip) / n for k in keys},
        "loop_self": sum(loops for _, loops, _ in per_chip) / n,
        "launches": sum(count for _, _, count in per_chip) / n,
    }


@functools.lru_cache(maxsize=2)
def _read(trace_path, table_path, module):
    with open(table_path) as f:
        table = json.load(f)
    return per_launch(inside.load(trace_path)[0], table, module)


def of_run(run):
    """`per_launch` of this run's own trace and table, read once a process."""
    if not run["trace"]:
        return None
    trace_path, table_path = inside.trace_file(run), table_file(run)
    if trace_path is None or table_path is None:
        return None
    return _read(trace_path, table_path, run["config"].get("chunk_module"))


def ns(found, *scopes):
    """Time a launch of the named scopes with all beneath them (`update`
    holds `update/optim`); with no name, of every operation."""
    return sum(
        t for s, t in found["scopes"].items()
        if not scopes or any(s == want or s.startswith(want + "/") for want in scopes)
    )


def ms(run, *scopes):
    found = of_run(run)
    return found and ns(found, *scopes) / 1e6


def pct(run, part, whole=()):
    """100 x the scopes `part` over the scopes `whole` (all operations where
    empty); None where the whole took no time."""
    found = of_run(run)
    if not found or not ns(found, *whole):
        return None
    return 100.0 * ns(found, *part) / ns(found, *whole)


def loop_self_pct(run, whole):
    """100 x the loops' self time over the scopes `whole`."""
    found = of_run(run)
    if not found or not ns(found, *whole):
        return None
    return 100.0 * found["loop_self"] / ns(found, *whole)
