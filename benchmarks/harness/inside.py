"""What the program measures from inside, for the per-layer readers: its
set-up stages and launch queue (summary and records), its ring-insert
programs by name on the device plane, and its spans on the profiler's host
plane, laid over the device's idle gaps.

The program writes every span as a `jax.profiler.TraceAnnotation`
(distributed_ddpg_tpu/trace.py), so a traced run's `.xplane.pb` holds them
on the clock the device events share, one line per host thread. `run` has
no path to that file: the summary names the run's records file
(`log_path`), and run.py puts the tracer's output in `trace/` beside it.

A program that writes no spans (its summary has no set-up stages) gives
every reader here nothing to read: `None`, and the line leaves the metric
out. A program that does, in a traced span that happens to hold none of a
phase's spans or no insert, reads 0: nothing was idle under that phase.
"""

import functools
import os

from . import xplane

INSERT_PREFIX = "jit_ring_insert"
# The learner loop's phases that have a reader of their own; the records
# name the rest (`phases_of`).
PHASES = frozenset({"dispatch", "ingest", "refresh", "sync"})


def setup_span(run, name):
    """Seconds of one set-up stage, from the summary `train()` returned."""
    return (run["summary"].get("setup_spans") or {}).get(name)


def writes_spans(run):
    return run["summary"].get("setup_spans") is not None


def weighted_mean(window_records, key, weight):
    """Mean of a per-interval mean over the window, each record weighed by
    its calls; None where no record has the key."""
    total, calls = 0.0, 0
    for r in window_records:
        n = r.get(weight, 0)
        if n and key in r:
            total += r[key] * n
            calls += n
    return total / calls if calls else None


def share_pct(window_records, part, whole):
    """100 * sum(part) / sum(whole) over the window's records."""
    have = [r for r in window_records if part in r and r.get(whole, 0)]
    if not have:
        return None
    return 100.0 * sum(r[part] for r in have) / sum(r[whole] for r in have)


def insert_launches(run):
    """The reduced trace's entries for the ring-insert programs, or None
    where there is no trace or the program does not name them."""
    trace = run["trace"]
    if not trace or not writes_spans(run):
        return None
    return [v for k, v in trace["launches"].items() if k.startswith(INSERT_PREFIX)]


def phases_of(window_records):
    """Names of the trainer's phases, from its records' `t_<phase>_ms` /
    `n_<phase>` pairs: the top-level spans of the learner thread."""
    return {
        k[2:] for r in window_records for k in r
        if k.startswith("n_") and f"t_{k[2:]}_ms" in r
    }


def trace_file(run):
    log_path = run["summary"].get("log_path")
    if not log_path:
        return None
    return xplane.find(os.path.join(os.path.dirname(log_path), "trace"))


@functools.lru_cache(maxsize=2)
def load(path):
    """(what `xplane.load` gives, {host line: [(name, start_ns, dur_ns)]}):
    one parse of the file for all the readers of a process. A line is a
    host thread; threads may share a name, so the key carries its index."""
    from jax.profiler import ProfileData

    lines = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                events = [(e.name, float(e.start_ns), float(e.duration_ns)) for e in line.events]
                lines[f"{line.name}#{i}"] = [e for e in events if e[2] > 0]
    return xplane.load(path), lines


def gaps(trace):
    """((t0, t1), [[(start, end)] per chip]): the traced span and each
    chip's idle gaps in it, taken as `xplane.reduce` takes them (the
    complement of the union of op intervals)."""
    device = trace["device"]
    every = [(s, s + d) for lines in device.values() for evs in lines.values() for _, s, d in evs if d > 0]
    every += [(s, s + d) for _, s, d in trace["host"]]
    if not device or not every:
        return None
    t0, t1 = min(s for s, _ in every), max(e for _, e in every)
    per_chip = []
    for _, lines in sorted(device.items()):
        ops = lines.get("XLA Ops") or lines.get("XLA Modules") or []
        merged = xplane._union([(s, s + d) for _, s, d in ops if d > 0])
        edges = [t0] + [x for s, e in merged for x in (s, e)] + [t1]
        per_chip.append([(edges[i], edges[i + 1]) for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]])
    return (t0, t1), per_chip


def learner_line(lines, phases):
    """The events of the host line that holds most spans named after a
    phase (the `dispatch` annotations above all): the learner thread's.
    Empty where no line holds any."""
    best, most = [], 0
    for events in lines.values():
        n = sum(1 for name, _, _ in events if name in phases)
        if n > most:
            best, most = events, n
    return best


def overlap(gap_list, spans):
    """Total length of the intersection of two sorted lists of disjoint
    intervals, in one sweep."""
    total, j = 0.0, 0
    for g0, g1 in gap_list:
        while j < len(spans) and spans[j][1] <= g0:
            j += 1
        k = j
        while k < len(spans) and spans[k][0] < g1:
            total += min(g1, spans[k][1]) - max(g0, spans[k][0])
            k += 1
    return total


def idle_shares(trace, lines, phases):
    """{phase: % of the traced span in which no op ran on the chip and the
    learner thread was inside that phase's span, None: the same under no
    phase's span}, averaged over chips. Their sum is the idle share."""
    found = gaps(trace)
    if found is None:
        return None
    (t0, t1), per_chip = found
    learner = learner_line(lines, phases)
    by_phase = {
        p: xplane._union([(s, s + d) for name, s, d in learner if name == p]) for p in phases
    }
    covered = xplane._union([iv for spans in by_phase.values() for iv in spans])
    scale = 100.0 / (len(per_chip) * (t1 - t0))
    shares = {p: scale * sum(overlap(g, spans) for g in per_chip) for p, spans in by_phase.items()}
    idle = scale * sum(e - s for g in per_chip for s, e in g)
    shares[None] = idle - scale * sum(overlap(g, covered) for g in per_chip)
    return shares


@functools.lru_cache(maxsize=2)
def shares_of(path, phases):
    """`idle_shares` of the trace at `path`, computed once per process."""
    trace, lines = load(path)
    return idle_shares(trace, lines, phases)


def idle_pct(run, phase):
    """The idle share under `phase` (None: under no phase) of this run's
    own trace; None where there is no trace or the program writes no spans."""
    if not run["trace"] or not writes_spans(run):
        return None
    path = trace_file(run)
    if path is None:
        return None
    shares = shares_of(path, frozenset(phases_of(run["window"]) | PHASES))
    return shares and shares[phase]
