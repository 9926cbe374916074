"""From the profiler's `.xplane.pb` to device busy time, launches and gaps.

`jax.profiler.ProfileData` reads the file with nothing but JAX. A TPU trace
has one plane per chip, `/device:TPU:<n>`, whose line `XLA Modules` holds
one event per program launch (named `jit_<function>(<id>)`) and whose line
`XLA Ops` holds one event per operation inside a launch; host threads are
lines of the `/host:CPU` plane. Times are nanoseconds on one clock.

Busy time is the union of the op intervals of a chip (the module intervals
where a trace has no op line), averaged over chips; the window is the span
from the first to the last event on any plane. An idle gap is named after
the host event that overlaps it longest, where the profiler's host plane
has one, and `unattributed` otherwise: the program writes no
`TraceAnnotation` yet.
"""

import glob
import os
import re
import statistics
import threading
import time

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
# Host events that say nothing about what the host was doing.
DULL_HOST = ("ThreadpoolListener", "ParseArguments", "$")


def find(trace_dir):
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def load(path):
    """{"device": {plane: {line: [(name, start_ns, dur_ns)]}}, "host": [(name, start_ns, dur_ns)]}"""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device, host = {}, []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            device[plane.name] = {
                line.name: [(e.name, float(e.start_ns), float(e.duration_ns)) for e in line.events]
                for line in plane.lines
            }
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.duration_ns > 0 and not e.name.startswith(DULL_HOST):
                        host.append((e.name, float(e.start_ns), float(e.duration_ns)))
    return {"device": device, "host": host}


def _union(intervals):
    """Merged, sorted [(start, end)] of possibly overlapping intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _base(name):
    """`jit_f(123)` -> `jit_f`; an op, which the trace names by its whole
    HLO text (`%fusion.3 = f32[...] fusion(...)`), -> `fusion.3`."""
    return re.sub(r"\(\d+\)$", "", name.split(" = ", 1)[0].lstrip("%"))


def reduce(trace, top=10, top_gaps=5):
    """The numbers the per-layer readers and the breakdown take."""
    device = trace["device"]
    if not device:
        return None
    every = [(s, s + d) for lines in device.values() for evs in lines.values() for _, s, d in evs if d > 0]
    every += [(s, s + d) for _, s, d in trace["host"]]
    if not every:
        return None
    t0, t1 = min(s for s, _ in every), max(e for _, e in every)
    busy, op_time, launches, gaps = [], {}, {}, []
    for plane, lines in sorted(device.items()):
        ops = lines.get("XLA Ops") or lines.get("XLA Modules") or []
        merged = _union([(s, s + d) for _, s, d in ops if d > 0])
        busy.append(sum(e - s for s, e in merged))
        for name, _, d in ops:
            op_time[_base(name)] = op_time.get(_base(name), 0.0) + d
        for name, _, d in lines.get("XLA Modules", []):
            launches.setdefault(_base(name), []).append(d)
        edges = [t0] + [x for s, e in merged for x in (s, e)] + [t1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
    n = len(device)
    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "window_s": (t1 - t0) / 1e9,
        "busy_s": sum(busy) / n / 1e9,
        "chips": n,
        "device_ops": [
            [name, t / n / 1e9] for name, t in sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
        ],
        "idle_gaps": [[_gap_name(g, trace["host"]), (g[1] - g[0]) / 1e9] for g in gaps[:top_gaps]],
        "launches": {
            name: {"count": len(d), "median_s": statistics.median(d) / 1e9, "total_s": sum(d) / n / 1e9}
            for name, d in launches.items()
        },
    }


def _gap_name(gap, host):
    best, best_overlap = "unattributed", 0.0
    for name, s, d in host:
        overlap = min(gap[1], s + d) - max(gap[0], s)
        if overlap > best_overlap:
            best, best_overlap = name, overlap
    return best if best_overlap >= 0.5 * (gap[1] - gap[0]) else "unattributed"


class Tracer(threading.Thread):
    """Traces `seconds` of the window, from `start_frac` of the way into it,
    on a thread of its own: stopping the profiler can take long (it
    serialises every event), and the window's thread has records to follow."""

    def __init__(self, out_dir, seconds, window_seconds, start_frac=0.4):
        super().__init__(name="bench-tracer", daemon=True)
        self.out_dir, self.seconds = out_dir, seconds
        self.delay = start_frac * window_seconds

    def run(self):
        import jax

        time.sleep(self.delay)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.out_dir, profiler_options=options)
        time.sleep(self.seconds)
        jax.profiler.stop_trace()
