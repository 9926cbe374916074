"""The device's idle time parted by where it lies in the trace and by what
the learner thread was doing under it, and the read-backs' cost by the
records: the readers of what the program brackets since its read-backs drain
the launch queue first (train.py `read_back`, metrics.LaunchQueue.drain).

A chip's gaps are taken as `inside.gaps` and `xplane.reduce` take them, over
the span from the first to the last event on any plane. Two of them touch
that span's ends: `[t0, the chip's first op]` and `[its last op, t1]`. They
say when the profiler's session reached that chip, not that the chip had
nothing to do, so they are the tracer's EDGE. Every other gap is INTERIOR and
belongs to the innermost of the program's spans the learner thread had open
over it (a gap under `refresh` > `params_d2h` is `params_d2h`'s), or to none
(BETWEEN). A span that began before the session, or was open when it
stopped, is not in the file; its children that began and ended inside are,
which is why the innermost one is asked.

The edge, the shares by innermost name and the share under none sum to the
idle share: 1 less the busy share `xplane.reduce` gives.

A program that does not drain (no `launch_wait` in its trace and no
`n_<phase>_drain` in its records) holds the wait for its launches inside
`params_d2h` and `metrics_d2h`: the readers of the named shares and of the
records' drains give nothing there, and the line leaves them out. The edge
and the share under no span need nothing of the program.
"""

import functools

from . import inside

# What a read-back is made of on the learner thread, innermost spans: the
# wait for one launch, the two copies, the publication to the actors.
READ_BACK = frozenset({"launch_wait", "params_d2h", "metrics_d2h", "param_broadcast"})
# The phases whose drain the records carry beside them.
DRAINED = ("refresh", "sync")


def innermost(spans):
    """[(start, end, name)], disjoint and in order: every instant some span
    of `spans` [(name, start, dur)] covers, under the one that began last
    (spans of one thread nest, so that is the innermost). A child that
    outlasts its parent by the clock's grain is cut to it."""
    out, stack, at = [], [], 0.0  # stack: (end, name), outermost first

    def emit(until):
        nonlocal at
        if until > at:
            out.append((at, until, stack[-1][1]))
        at = max(at, until)

    for name, start, dur in sorted(spans, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= start:
            emit(stack[-1][0])
            stack.pop()
        if stack:
            emit(start)
        at = start
        stack.append((min(start + dur, stack[-1][0]) if stack else start + dur, name))
    while stack:
        emit(stack[-1][0])
        stack.pop()
    return out


def edges(trace):
    """[(ns from t0 to the chip's first op, ns from its last op to t1)] per
    chip in plane order; a chip with no op in the trace reads the whole span
    twice. None where `inside.gaps` finds nothing."""
    found = inside.gaps(trace)
    if found is None:
        return None
    (t0, t1), per_chip = found
    return [
        (sum(e - s for s, e in g if s == t0), sum(e - s for s, e in g if e == t1))
        for g in per_chip
    ]


def table(trace, lines, names):
    """{"edge": %, "under": {span name: %}, "between": %} of the traced span,
    mean over chips; `names` are the program's span names, the learner
    thread is the line `inside.learner_line` finds with them. None where
    the trace holds no device."""
    found = inside.gaps(trace)
    if found is None:
        return None
    (t0, t1), per_chip = found
    interior = [[g for g in gaps if g[0] != t0 and g[1] != t1] for gaps in per_chip]
    learner = [e for e in inside.learner_line(lines, names) if e[0] in names]
    by_name = {}
    for start, end, name in innermost(learner):
        by_name.setdefault(name, []).append((start, end))
    scale = 100.0 / (len(per_chip) * (t1 - t0))
    idle = scale * sum(e - s for gaps in per_chip for s, e in gaps)
    inner = scale * sum(e - s for gaps in interior for s, e in gaps)
    under = {
        name: scale * sum(inside.overlap(gaps, spans) for gaps in interior)
        for name, spans in by_name.items()
    }
    return {"edge": idle - inner, "under": under, "between": inner - sum(under.values())}


@functools.lru_cache(maxsize=2)
def table_of(path, names):
    """`table` of the trace at `path`, computed once per process."""
    trace, lines = inside.load(path)
    return table(trace, lines, names)


def records_drain(window_records):
    """Whether the records carry a drain beside a phase that reads back."""
    return any(f"n_{phase}_drain" in r for r in window_records for phase in DRAINED)


def of_run(run):
    """`table` of this run's own trace, or None where there is no trace."""
    if not run["trace"]:
        return None
    path = inside.trace_file(run)
    return path and table_of(path, frozenset(inside.phases_of(run["window"]) | inside.PHASES | READ_BACK))


def under_pct(run, *spans):
    """The interior idle share under the named innermost spans, 0 where the
    traced span holds none of them; None without a trace or where the
    program does not drain."""
    found = of_run(run)
    if not found or not (records_drain(run["window"]) or "launch_wait" in found["under"]):
        return None
    return sum(found["under"].get(name, 0.0) for name in spans)


def part_pct(run, part):
    """`edge` or `between` of this run's trace: nothing of the program needed."""
    found = of_run(run)
    return found and found[part]


def host_ms(window_records, phase):
    """Milliseconds the window spent inside `phase` less its drain: the
    host's turnaround with nothing in flight, the device idle throughout.
    None where no record carries a drain (the program does not drain); a
    phase the window never entered, or never drained under, counts 0."""
    if not records_drain(window_records):
        return None

    def total(name):
        return sum(r.get(f"t_{name}_ms", 0.0) * r.get(f"n_{name}", 0) for r in window_records)

    return total(phase) - total(f"{phase}_drain")
