"""The measured window of a run that goes through `train()`.

`train()` has no wall-clock stop: it ends on its env-step budget or on
SIGTERM. So a run is a run with a budget no run can reach, and this thread
follows the records file the trainer writes (line-buffered), decides from
the records when warm-up is over and when the window has lasted
`--seconds`, and then sends the process SIGTERM; the trainer finishes its
chunk and returns a summary that says `preempted`, which is expected.

Warm-up ends at the first `"train"` record after which at least
`warm_chunks` chunks are done and no program has been built (compiled or
loaded from the cache) for `quiet_s`, and at most `max_warm_s` after the
first record. The window opens at that record and closes at the first
record seen at least `seconds` later. Its edges are records, so its true
length is a second or so over the nominal one; the length used for every
rate is the harness's own clock between the two sightings (the file is
polled every 5 ms, under 0.1% of the shortest window), and the counts at an
edge are of chunks the device had finished when the record was written.
Chunks queued behind that one (at most a refresh interval's worth, 0.1 s)
are not yet counted: under 1% of a window, and the same at both edges.
"""

import os
import signal
import threading
import time

from . import records


class Compiles:
    """Counts programs built in this process, by `jax.monitoring`: every
    build (a compile or a load from the persistent cache) and the cache
    hits among them."""

    BUILD = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.builds = []  # (monotonic time, seconds, name)
        self.hits = []
        self.last = time.monotonic()

    def install(self):
        import jax.monitoring as m

        m.register_event_duration_secs_listener(self._on_duration)
        m.register_event_listener(self._on_event)
        return self

    def _on_duration(self, event, seconds, **kw):
        if event == self.BUILD:
            self.last = time.monotonic()
            self.builds.append((self.last, seconds, str(kw.get("fun_name", "?"))))

    def _on_event(self, event, **kw):
        if event == self.HIT:
            self.hits.append(time.monotonic())

    def between(self, t0, t1):
        builds = [(s, n) for t, s, n in self.builds if t0 <= t <= t1]
        hits = sum(1 for t in self.hits if t0 <= t <= t1)
        return {
            "built": len(builds),
            "cache_hits": hits,
            "compiled": max(len(builds) - hits, 0),
            "names": sorted({n for _, n in builds})[:8],
        }


class Window(threading.Thread):
    # A run that never closes its window is ended after DEADLINE_S: under the
    # 1200 s a cell's first run in a checkout may take while everything compiles.
    DEADLINE_S = 1100.0

    def __init__(self, path, seconds, rule, compiles, tracer=None, deadline_s=DEADLINE_S, end=None):
        super().__init__(name="bench-window", daemon=True)
        self.path, self.seconds, self.compiles, self.tracer = path, seconds, compiles, tracer
        self.warm_chunks = int(rule.get("warm_chunks", 3))
        self.quiet_s = float(rule.get("quiet_s", 2.0))
        self.max_warm_s = float(rule.get("max_warm_s", 20.0))
        self.deadline = time.monotonic() + deadline_s
        self.header = None
        self.train = []  # every "train" record seen
        self.open_i = self.close_i = None
        self.t_first = self.t_open = self.t_close = None  # monotonic
        self.error = None
        self._end = end or (lambda: os.kill(os.getpid(), signal.SIGTERM))
        self._halt = threading.Event()

    def stop(self):
        self._halt.set()

    @property
    def window_records(self):
        """Records after the opening one, up to the closing one: the ones
        whose intervals lie inside the window."""
        if self.close_i is None:
            return []
        return self.train[self.open_i + 1 : self.close_i + 1]

    def run(self):
        try:
            self._follow()
        except Exception as e:  # report through the result, never die silently
            self.error = repr(e)
        finally:
            if not self._halt.is_set():
                self._end()

    def _follow(self):
        while not os.path.exists(self.path):
            if self._wait():
                return
        buf = ""
        with open(self.path) as f:
            while True:
                chunk = f.read()
                if not chunk:
                    if self._wait():
                        return
                    continue
                buf += chunk
                *lines, buf = buf.split("\n")
                for rec in records.parse_lines(lines):
                    if rec.get("kind") == "header":
                        self.header = rec
                    if rec.get("kind") != "train":
                        continue
                    now = time.monotonic()
                    self.train.append(rec)
                    self.t_first = self.t_first or now
                    if self.open_i is None:
                        chunk_len = (self.header or {}).get("learner_chunk", 1)
                        warm = rec["learner_steps"] >= self.warm_chunks * chunk_len and (
                            now - self.compiles.last >= self.quiet_s
                            or now - self.t_first >= self.max_warm_s
                        )
                        if warm:
                            self.open_i, self.t_open = len(self.train) - 1, now
                            if self.tracer is not None:
                                self.tracer.start()
                    elif now - self.t_open >= self.seconds:
                        self.close_i, self.t_close = len(self.train) - 1, now
                        return

    def _wait(self):
        """Sleep a little; True when the run is over or out of time."""
        if time.monotonic() > self.deadline:
            self.error = "no closing record before the harness's deadline"
            return True
        return self._halt.wait(0.005)
