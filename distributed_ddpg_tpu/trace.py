"""Flight-recorder tracing: a preallocated, lock-light ring of span events
every hot component brackets (the cross-component timeline visibility
Podracer/TorchBeast attribute their scaling wins to — PAPERS.md
arXiv 2104.06272 / 1910.03552).

The system is a five-thread machine — learner loop, ingest shipper,
ChunkPrefetcher, eval worker, checkpoint writer, plus N actor processes —
and point metrics (PhaseTimers means, IngestStats) cannot answer "what was
every thread doing in the seconds before the wedge/regression". This
module answers it cheaply enough to leave ON in production runs:

  - `TraceRecorder`: a fixed-size ring of event tuples. Recording is one
    `perf_counter_ns` + one tuple build + one list-slot store behind a
    GIL-atomic `itertools.count` — no lock on the hot path, no allocation
    growth, old events silently overwritten (that is the flight-recorder
    contract: the LAST window is always available, a run of any length
    never grows memory).
  - `span(name)` / `instant(name)` / `complete(name, t0, dur)`: the
    bracket API. Thread identity is captured per event, so the exported
    timeline separates learner / shipper / prefetcher / eval / saver
    activity into Perfetto tracks.
  - `export(path)`: Chrome trace-event JSON (the `{"traceEvents": [...]}`
    wrapper), loadable in Perfetto (ui.perfetto.dev) or chrome://tracing.
    Exports happen on demand (SIGUSR2 in train.py), on clean exit, and —
    critically — from the watchdog's stall path (watchdog.py), so every
    hang ships the last-N-seconds timeline next to the stack dump.
  - `stall_report(...)`: the structured stall artifact: thread list with
    stacks as JSON (machine-parseable, unlike the faulthandler dump) plus
    the trace tail.

Enablement: module-level singleton, off by default (every `span()` is then
a shared no-op context manager — the <2% overhead guard in test_trace.py
holds for the ENABLED path; disabled is nanoseconds). train_jax enables it
when `config.trace_dir` is set; actor worker processes (separate
interpreters) enable their own recorder and export per-process files that
Perfetto merges by pid.

Second sink: `set_annotator(factory)` installs a context-manager factory
that every `span()` ALSO enters. The learner process installs
`jax.profiler.TraceAnnotation` (train.py, once the backend is up), which
puts the program's spans on the profiler's host plane — the device
trace's clock — and costs well under a microsecond while no profiler
session runs. This module itself never imports JAX: actor workers import
it and must not load JAX or reach the chip. `instant()`/`complete()` stay
ring-only.

Consistency note: the ring index is advanced atomically but slot writes
are not fenced against concurrent export — an export racing a writer can
see a slot from either side of the wrap. Exports sort by timestamp and
tolerate a torn tail; this is diagnostics, not accounting.
"""

from __future__ import annotations

import itertools
import json
import math
import os
import re
import sys
import threading
import time
import traceback
from typing import Any, Dict, List, Optional

# Event kinds (Chrome trace "ph" phases we emit).
_SPAN = "X"      # complete event: ts + dur
_INSTANT = "i"   # instant event: ts only


class _Span:
    """Reusable-shape span context manager: records ONE complete event at
    exit (one ring slot per span, not a begin/end pair — halves ring
    pressure and keeps export trivially well-formed)."""

    __slots__ = ("_rec", "_name", "_args", "_t0")

    def __init__(self, rec: "TraceRecorder", name: str, args):
        self._rec = rec
        self._name = name
        self._args = args

    def __enter__(self):
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb):
        t1 = time.perf_counter_ns()
        self._rec._record(
            _SPAN, self._name, self._t0, t1 - self._t0, self._args
        )
        return False


class _NullSpan:
    """Shared no-op context manager for the disabled path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return False


_NULL_SPAN = _NullSpan()


class _BothSpan:
    """Ring span + annotator context, entered together: the bracket
    records once in each sink."""

    __slots__ = ("_ring", "_ann")

    def __init__(self, ring: _Span, ann):
        self._ring = ring
        self._ann = ann

    def __enter__(self):
        self._ann.__enter__()
        self._ring.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb):
        self._ring.__exit__(exc_type, exc, tb)
        self._ann.__exit__(exc_type, exc, tb)
        return False


class TraceRecorder:
    def __init__(self, capacity: int = 65_536):
        if capacity < 16:
            raise ValueError(f"capacity must be >= 16, got {capacity}")
        self.capacity = int(capacity)
        # Preallocated slots. Each holds a tuple:
        #   (ph, name, t_ns, dur_ns, thread_name, thread_id, args|None)
        self._buf: List[Optional[tuple]] = [None] * self.capacity
        self._n = itertools.count()          # GIL-atomic slot allocator
        self._t0_ns = time.perf_counter_ns() # export time origin
        # Thread identity cached per thread: current_thread() each event
        # would be ~10% of the span budget (the <2% overhead guard).
        self._tl = threading.local()
        # Wall-clock anchor for correlating trace timestamps with JSONL
        # wall_time / log lines — and for re-basing N per-host traces
        # onto one timeline (tools.runs merge-trace): absolute wall time
        # of any event is wall_t0 + ts/1e6.
        self._wall_t0 = time.time()
        # Caller-attached export metadata (set_meta): the multi-host
        # clock handshake lands its per-host offsets here so the merge
        # tool can correct cross-host wall-clock skew.
        self._meta: Dict[str, Any] = {}
        self._meta_lock = threading.Lock()

    def set_meta(self, **kv: Any) -> None:
        """Attach key/values to the export's otherData block (merged over
        the defaults). JSON-serializable values only."""
        with self._meta_lock:
            self._meta.update(kv)

    # --- recording (hot path) ---

    def _record(self, ph: str, name: str, t_ns: int, dur_ns: int, args) -> None:
        tl = self._tl
        try:
            tname, tid = tl.info
        except AttributeError:
            t = threading.current_thread()
            tname, tid = tl.info = (t.name, t.ident)
        self._buf[next(self._n) % self.capacity] = (
            ph, name, t_ns, dur_ns, tname, tid, args
        )

    def span(self, name: str, **args) -> _Span:
        return _Span(self, name, args or None)

    def instant(self, name: str, **args) -> None:
        self._record(_INSTANT, name, time.perf_counter_ns(), 0, args or None)

    def complete(self, name: str, start_s: float, dur_s: float, **args) -> None:
        """Record a span from explicit perf_counter()-based times — for
        sites that already measured a wait/stall and only want to log it
        when it actually happened (e.g. ingest backpressure)."""
        self._record(
            _SPAN, name, int(start_s * 1e9), int(dur_s * 1e9), args or None
        )

    # --- export ---

    def events(self, window_s: Optional[float] = None) -> List[Dict[str, Any]]:
        """Chrome trace-event dicts, oldest first. `window_s` keeps only
        events ENDING within the last `window_s` seconds — the stall path's
        "what led up to the wedge" view."""
        n = next(self._n)  # burns one slot index; harmless (diagnostics)
        live = min(n, self.capacity)
        raw = [e for e in self._buf[:live] if e is not None]
        raw.sort(key=lambda e: e[2])
        if window_s is not None:
            cutoff = time.perf_counter_ns() - int(window_s * 1e9)
            raw = [e for e in raw if e[2] + e[3] >= cutoff]
        pid = os.getpid()
        out: List[Dict[str, Any]] = []
        seen_tids = {}
        for ph, name, t_ns, dur_ns, tname, tid, args in raw:
            if tid not in seen_tids:
                seen_tids[tid] = tname
            ev: Dict[str, Any] = {
                "name": name,
                "ph": ph,
                "pid": pid,
                "tid": tid,
                "ts": (t_ns - self._t0_ns) / 1e3,  # microseconds
            }
            if ph == _SPAN:
                ev["dur"] = dur_ns / 1e3
            else:
                ev["s"] = "t"  # instant scope: thread
            if args:
                ev["args"] = dict(args)
            out.append(ev)
        # Thread-name metadata so Perfetto labels tracks "learner",
        # "ingest-ship", "prefetch", ... instead of bare thread ids.
        meta = [
            {
                "name": "thread_name",
                "ph": "M",
                "pid": pid,
                "tid": tid,
                "args": {"name": tname},
            }
            for tid, tname in seen_tids.items()
        ]
        return meta + out

    def export(self, path: str, window_s: Optional[float] = None) -> int:
        """Write Chrome trace JSON; returns the number of events written.
        Parent directories are created; failures raise (callers on crash
        paths wrap in try/except — see watchdog.py)."""
        events = self.events(window_s=window_s)
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        with self._meta_lock:
            meta = dict(self._meta)
        with open(path, "w") as f:
            json.dump(
                {
                    "traceEvents": events,
                    "displayTimeUnit": "ms",
                    "otherData": {
                        "wall_t0": self._wall_t0,
                        "pid": os.getpid(),
                        "argv": " ".join(sys.argv[:6]),
                        **meta,
                    },
                },
                f,
            )
        return len(events)


# ---------------------------------------------------------------------------
# Module-level singleton: the recorder every subsystem brackets against.
# Off by default; `configure()` turns it on (train.py, worker.py, tests).
# ---------------------------------------------------------------------------

_recorder: Optional[TraceRecorder] = None
# Annotator sink: `factory(name, **args)` -> context manager, or None.
_annotator = None


def set_annotator(factory) -> None:
    """Install (or, with None, remove) the second sink every `span()`
    enters beside the ring. Process-wide, like the ring."""
    global _annotator
    _annotator = factory


def configure(capacity: int = 65_536) -> TraceRecorder:
    """Enable tracing process-wide (idempotent: reconfiguring replaces the
    ring, so tests get a fresh one)."""
    global _recorder
    _recorder = TraceRecorder(capacity=capacity)
    return _recorder


def disable() -> None:
    global _recorder
    _recorder = None


def enabled() -> bool:
    return _recorder is not None


def get() -> Optional[TraceRecorder]:
    return _recorder


def span(name: str, **args):
    r, a = _recorder, _annotator
    if a is None:
        return _NULL_SPAN if r is None else r.span(name, **args)
    if r is None:
        return a(name, **args)
    return _BothSpan(r.span(name, **args), a(name, **args))


def instant(name: str, **args) -> None:
    r = _recorder
    if r is not None:
        r.instant(name, **args)


def complete(name: str, start_s: float, dur_s: float, **args) -> None:
    r = _recorder
    if r is not None:
        r.complete(name, start_s, dur_s, **args)


def export(path: str, window_s: Optional[float] = None) -> int:
    """Export the singleton's ring; 0 events (and no file) when disabled."""
    r = _recorder
    if r is None:
        return 0
    return r.export(path, window_s=window_s)


def set_meta(**kv) -> None:
    """Attach otherData metadata to the singleton's exports (no-op while
    disabled) — the clock-handshake / process-identity hook."""
    r = _recorder
    if r is not None:
        r.set_meta(**kv)


def install_signal_export(path: str) -> bool:
    """Install a SIGUSR2 handler that exports the singleton's ring to
    `path` — the live-run timeline poke (train.py arms it alongside the
    watchdog; the /trace endpoint is the network sibling). Returns True
    when installed; False on platforms without SIGUSR2 or off the main
    thread (embedded callers), where signals cannot be installed. The
    handler never raises: a read-only diagnostic poke must not crash the
    healthy run it inspects."""
    import signal as _signal

    if not hasattr(_signal, "SIGUSR2"):
        return False

    def _export_on_signal(*_):
        try:
            export(path)
        except Exception as e:
            print(f"[trace] SIGUSR2 export failed: {e!r}",
                  file=sys.stderr, flush=True)

    try:
        _signal.signal(_signal.SIGUSR2, _export_on_signal)
    except ValueError:
        return False  # not on the main thread
    return True


# ---------------------------------------------------------------------------
# Stall artifacts (the watchdog's structured crash report)
# ---------------------------------------------------------------------------

STALL_REPORT = "stall_report.json"
STALL_TRACE = "stall_trace.json"


def thread_stacks() -> List[Dict[str, Any]]:
    """Every live thread's stack as structured JSON (the machine-parseable
    complement to faulthandler's stderr dump)."""
    frames = sys._current_frames()
    by_ident = {t.ident: t for t in threading.enumerate()}
    out = []
    for ident, frame in frames.items():
        t = by_ident.get(ident)
        out.append(
            {
                "ident": ident,
                "name": t.name if t else f"<unknown-{ident}>",
                "daemon": bool(t.daemon) if t else None,
                "stack": [
                    f"{fs.filename}:{fs.lineno} {fs.name}: {fs.line or ''}"
                    for fs in traceback.extract_stack(frame)
                ],
            }
        )
    return out


def stall_report(
    directory: str,
    reason: str,
    timeout_s: float = 0.0,
    window_s: float = 30.0,
    extra: Optional[Dict[str, Any]] = None,
) -> Dict[str, str]:
    """Write `stall_report.json` (+ `stall_trace.json` when tracing is on)
    into `directory`. Returns {artifact: path}. Never raises — this runs on
    the crash path, where a secondary failure must not mask the stall dump
    (each artifact is attempted independently)."""
    paths: Dict[str, str] = {}
    try:
        os.makedirs(directory, exist_ok=True)
    except Exception:
        return paths
    trace_path = os.path.join(directory, STALL_TRACE)
    n_events = 0
    try:
        n_events = export(trace_path, window_s=window_s)
        if n_events:
            paths["trace"] = trace_path
    except Exception:
        pass
    report_path = os.path.join(directory, STALL_REPORT)
    try:
        report = {
            "reason": reason,
            "timeout_s": timeout_s,
            "wall_time": time.time(),
            "pid": os.getpid(),
            "argv": sys.argv,
            "threads": thread_stacks(),
            "trace_events": n_events,
            "trace_path": paths.get("trace"),
            **(extra or {}),
        }
        with open(report_path, "w") as f:
            json.dump(report, f, indent=1)
        paths["report"] = report_path
    except Exception:
        pass
    return paths


# ---------------------------------------------------------------------------
# Device-side scopes: the parts of a chunk program, by name.
#
# The spans above are the host's. Inside one launch of a chunk program the
# device trace names an operation by its HLO instruction (`fusion.39`,
# `while.3`), which says nothing to whoever reads it. The chunk programs
# bracket their parts with `jax.named_scope` under this one vocabulary
# (`device_scope`); a scope is metadata, no operation is added, removed or
# reordered. The compiled text of the program carries the bracket on every
# instruction (`metadata={op_name="jit(f)/update/while/body/.../optim/add"}`),
# fusions, `while` bodies and `conditional` branches included, so the
# program can write the table from instruction name to scope (`op_scopes`;
# `ShardedLearner.chunk_ops`, `chunk_ops.json` beside the records) that a
# reader joins to any profile of the run (docs/OBSERVABILITY.md §5).
# ---------------------------------------------------------------------------

CHUNK_SCOPES = (
    "draw",           # the launch's replay indices (uniform or PER)
    "gather",         # the rows behind them, out of the ring
    "cut",            # the gathered rows cut into fields / kernel streams
    "prep/pixels",    # a pixel launch's image words cut for the scan, and their relayout
    "noise",          # the launch's noise; REDQ's subsets; DrQ-v2's crop offsets
    "update",         # the K updates: the lax.scan, or the pallas_call
    "update/augment", # DrQ-v2's random shift: unpack, crop, the conversion to float
    "update/encoder", # its convolutional encoder: both forward passes and the backward
    "update/estep",   # MPO's E-step: target policy, draws, target critic on batch x samples rows, weights
    "update/estep/lnorm",    # the LayerNorms of its two target nets
    "update/target",  # a recurrent update's targets y: both target nets, forward
    "update/target/recur",   # their memories: the LSTM scanned over the window
    "update/critic",  # critic loss, forward and backward
    "update/critic/recur",   # a recurrent critic's memory, forward and back through time
    "update/critic/norm",  # its batch norm: moments, normalising, running step
    "update/critic/lnorm",   # a residual critic's LayerNorms, forward and backward
    "update/critic/rsnorm",  # its input normaliser, and the statistics' merge
    "update/actor",   # actor loss, forward and backward
    "update/actor/recur",    # a recurrent actor's memory, forward and back through time
    "update/actor/norm",   # its own batch norm, and the critics' under it
    "update/actor/lnorm",    # a residual actor's LayerNorms, and the critics' under it
    "update/actor/rsnorm",   # its input normaliser, and the critics' under it
    "update/duals",   # MPO's dual variables: their loss and its gradient
    "update/duals/optim",    # their own Adam
    "update/optim",   # Adam
    "update/polyak",  # target updates
    "metrics",        # the chunk's metrics out of the K updates'
    "priority",       # PER's priority write-back and maximum
)
# The device pool's rollout program (actors/device_pool.py) brackets its
# parts in the same manner, under a vocabulary of its own: the op table
# above is the chunk program's and reads none of these words.
ROLLOUT_SCOPES = (
    "rollout",         # the K-step scan over E environments
    "rollout/policy",  # mu(s) and the exploration noise
    "rollout/policy/recur",  # a recurrent policy's one LSTM step
    "rollout/render",  # a pixel environment's frames out of its state
    "rollout/env",     # the vmapped environment step, auto-reset included
    "rollout/fold",    # the n-step window: fold, flush, the emitted row; a recurrent run's window row
)
# What a collective instruction reads as, whatever scope it served.
COLLECTIVE = "collective"
CHUNK_OPS_FILE = "chunk_ops.json"

_SCOPE_WORDS = frozenset(w for s in CHUNK_SCOPES for w in s.split("/"))
_ROLLOUT_WORDS = frozenset(w for s in ROLLOUT_SCOPES for w in s.split("/"))
_COLLECTIVE_OPCODES = frozenset({
    "all-reduce", "all-gather", "reduce-scatter", "collective-permute",
    "all-to-all",
})
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
# Computations an instruction names: `calls=` and `to_apply=` are inlined
# into it (a fusion's body, a reducer) unless it is a `call`; the rest run
# as operations of their own under it.
_INLINED = re.compile(r"(?:calls|to_apply)=%?([\w.\-]+)")
_FUSED = re.compile(r"calls=%?([\w.\-]+)")
# Instructions no device runs: a trace has no event for them.
_NO_OP = frozenset({"parameter", "constant", "tuple", "get-tuple-element"})
_TUPLE_GLUE = frozenset({"tuple", "get-tuple-element", "bitcast"})
# What a fusion of nothing but a collective's step holds beside the step:
# the compiler's glue between one step and the next.
_NO_COMPUTE = _NO_OP | {"bitcast", "custom-call"}
# What an instruction names between its opcode's parentheses: its operands.
_OPERAND = re.compile(r"%([\w.\-]+)")
# A `while`'s body, and what counts as arithmetic there: XLA's elementwise
# opcodes. The TPU's compiler fuses none of them on a `[]` shape, so in a
# loop's body each is an instruction of the loop by itself, run on the
# scalar core. Those that wait for nothing cost nothing measurable; one
# that reads a fusion's `f32[]` result makes the loop wait for it (PERF.md
# §6, PR 46), and the count is where to look for such chains.
_BODY = re.compile(r"body=%?([\w.\-]+)")
_SCALAR = re.compile(r"\w+\[\]")
_ELEMENTWISE = frozenset({
    "abs", "add", "and", "atan2", "bitcast-convert", "cbrt", "ceil", "clamp",
    "clz", "compare", "convert", "cosine", "divide", "erf", "exponential",
    "exponential-minus-one", "floor", "is-finite", "log", "log-plus-one",
    "logistic", "maximum", "minimum", "multiply", "negate", "not", "or",
    "popcnt", "power", "remainder", "round-nearest-afz",
    "round-nearest-even", "rsqrt", "select", "shift-left",
    "shift-right-arithmetic", "shift-right-logical", "sign", "sine", "sqrt",
    "subtract", "tan", "tanh", "xor",
})
# What moves an array and computes nothing: a relayout the compiler could not
# fold into a neighbour's operand or result. A `reshape` is among them: what
# the optimiser could make a `bitcast` it has, and one left in a compiled
# text moves its array (the TPU's tiles pad the minor dimensions, so a
# flatten is a compaction). A fusion is one where its root is one or its
# body holds nothing else. An array's bytes as its shape states them
# (`bf16[256,32,35,35]{...}`: the type's width times the dimensions, no
# tile's padding).
_RELAYOUT = frozenset({"copy", "transpose", "reshape"})
_NO_ARITHMETIC = _NO_OP | _RELAYOUT | {"bitcast"}
_ARRAY = re.compile(r"[a-z]+(\d*)\w*\[([\d,]*)\]")
_RUN = re.compile(
    r"(?:body|condition|true_computation|false_computation|calls|to_apply)"
    r"=%?([\w.\-]+)|branch_computations=\{([^}]*)\}"
)


def device_scope(word: str):
    """`jax.named_scope(word)` for one word of CHUNK_SCOPES (or of
    ROLLOUT_SCOPES, in the rollout program): the bracket the chunk
    programs' parts are traced under. Nested brackets join with
    `/` (`optim` entered inside `update` reads `update/optim`)."""
    if word not in _SCOPE_WORDS and word not in _ROLLOUT_WORDS:
        raise ValueError(
            f"{word!r} is no word of trace.CHUNK_SCOPES or ROLLOUT_SCOPES"
        )
    import jax  # traced code only: this module's importers may not load JAX

    return jax.named_scope(word)


def _is_relayout(root: Optional[str], opcodes) -> bool:
    """Whether a fusion of these opcodes under this root only moves an array."""
    opcodes = set(opcodes)
    return root in _RELAYOUT or bool(
        opcodes and opcodes <= _NO_ARITHMETIC and opcodes & _RELAYOUT
    )


def _array_bytes(shape: str) -> int:
    m = _ARRAY.match(shape)
    if m is None:  # a tuple (a fusion of several results), a token
        return 0
    width = int(m.group(1) or 8) // 8  # `pred` names no width: a byte
    return width * math.prod(int(d) for d in m.group(2).split(",") if d)


def _instructions(hlo_text: str):
    """([(name, opcode, scope, in the entry computation?)], fused, carriers,
    scalars, copies, gathers) of a compiled module's text. The list holds every
    instruction that runs as an operation of its own: the entry computation's, `while`
    bodies' and conditions', `conditional` branches' and `call` targets'. What is
    inlined into another instruction (a fusion's body, a reducer) is left
    out, and so are parameters, constants and tuples: no trace has an event
    for them. Names are unique in a module.

    The scope is the CHUNK_SCOPES words on the instruction's `op_name`
    path, outermost first, joined by `/`. An instruction whose path has
    none, in a computation that another instruction runs (the copies the
    compiler puts into a loop's body carry no metadata at all), takes the
    scope of that instruction: whatever runs inside the scan's `while` is
    part of `update`, also where XLA cut its path short of the loop's
    bracket (`critic/jvp()/gather` in a body reads `update/critic`). A
    fusion the compiler left without an `op_name` takes the scope most of
    its body's instructions were traced under. An instruction that is still
    under none, stands between two that have one (something upstream of it
    was traced under a bracket, and so was what it feeds) and carries no
    `op_name` at all, is the compiler's own step on the way from the one to
    the other and takes the scope of what it feeds: the TPU's compiler
    took PR 47's `bitcast_convert_type` of a launch's byte images apart into
    a copy, a broadcast and a reshape of the whole block in front of the
    fusion that kept the name (`prep/pixels`: PERF.md §6, PR 47; the pixel
    step makes no such bitcast since PR 48). The copies of
    the state round the launch have a parameter before them or the result
    behind them, and stay under no scope.

    A fusion is one operation and carries one `op_name`, its root's: XLA
    fuses across brackets (Adam and Polyak into the epilogue of the
    weight-gradient matmul that feeds them), and the device's time for the
    fusion cannot be split. `fused` is {fusion: the other scopes the
    instructions fused into it were traced under}, so that a reader of
    `update/critic` can tell what else it paid for.

    `carriers` is {fusion: does it compute?} of the fusions whose body
    holds a collective instruction: the TPU compiler's asynchronous
    collective (libtpu 0.0.34 writes no `-start` / `-done` opcode for it).
    An all-reduce it overlaps is cut into steps, the transfer in flight
    between them: the first and the last are fusions of nothing but the
    step (`async-collective-start`, `-done`: False, they ARE the
    collective), those between ride fusions of the computations the reduce
    does not feed (True: compute, and read as their own scope).

    `scalars` is {`while` instruction: the instructions of its body that
    are unfused arithmetic (_ELEMENTWISE) on a `[]` shape}: what one trip
    of the loop issues one by one between its fusions. A `conditional`'s
    branches under the body are not the body's.

    `copies` is {`while` instruction: (count, bytes)} of its body's
    relayouts that run as operations of their own (_RELAYOUT): `copy`,
    `transpose` and `reshape` instructions, and the fusions whose root is
    one or that hold nothing else, each with its result's bytes. The TPU's
    `copy-start` / `copy-done` pairs move an array between memory spaces
    in its layout and are not among them.

    `gathers` is {`while` instruction: its body's `gather` instructions and
    fusions that hold one}: the TPU's compiler runs a gather it cannot turn
    into slices as a fusion of its own (`kind=kCustom`, the gather its
    body's root)."""
    found, inlined, runs, computation, entry = [], set(), {}, "", False
    loop_bodies, arithmetic = {}, {}  # while -> its body; computation -> count
    roots, held = {}, {}  # computation -> its root's opcode; -> its opcodes
    moved = {}  # computation -> [(opcode, fusion's body or None, bytes)], candidates
    bodies, within = {}, {}  # fusion -> its body; body -> {scope: instructions}
    operands, nameless = {}, set()  # instruction -> its operands; no op_name
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m is not None:
            computation, entry = m.group(1), line.startswith("ENTRY ")
            continue
        m = _INSTRUCTION.match(line)
        if m is None:
            continue
        name, rest = m.groups()
        if rest.startswith("("):  # a tuple type: skip to its closing paren
            depth = 0
            for i, ch in enumerate(rest):
                depth += (ch == "(") - (ch == ")")
                if depth == 0:
                    break
            shape, rest = "", rest[i + 1:]
        else:
            shape, _, rest = rest.partition(" ")
        opcode = rest.lstrip().partition("(")[0]
        if opcode in _ELEMENTWISE and _SCALAR.match(shape):
            arithmetic[computation] = arithmetic.get(computation, 0) + 1
        if line.lstrip().startswith("ROOT "):
            roots[computation] = opcode
        held.setdefault(computation, set()).add(opcode)
        if opcode in _RELAYOUT or opcode in ("fusion", "gather"):  # what `copies` and `gathers` choose from
            callee = _FUSED.search(rest).group(1) if opcode == "fusion" else None
            moved.setdefault(computation, []).append(
                (opcode, callee, _array_bytes(shape))
            )
        if opcode == "while":
            loop_bodies[name] = _BODY.search(rest).group(1)
        if opcode != "call":
            inlined.update(_INLINED.findall(rest))
            bodies.update((name, body) for body in _FUSED.findall(rest))
        for one, several in _RUN.findall(rest):
            for called in (one, *several.split(",")):
                runs.setdefault(called.strip().lstrip("%"), name)
        op_name = _OP_NAME.search(rest)
        scope = _scope_of(op_name.group(1) if op_name else "")
        found.append((computation, name, opcode, scope, entry))
        operands[name] = _operands(rest)
        if op_name is None and opcode not in _NO_OP:
            nameless.add(name)
        if scope:
            counts = within.setdefault(computation, {})
            counts[scope] = counts.get(scope, 0) + 1
    own = {name: (comp, scope) for comp, name, _, scope, _ in found}

    def scope_of(name):
        comp, scope = own[name]
        inside = within.get(bodies.get(name))
        if not scope and inside:  # a fusion XLA left nameless: its body's
            scope = max(inside, key=inside.get)
        outer = ""
        while not outer and comp in runs:
            comp, outer = own[runs[comp]]
        top = outer.partition("/")[0]
        if not scope or not top or scope.partition("/")[0] == top:
            return scope or outer
        return f"{top}/{scope}"  # a path XLA cut short: `critic/jvp()/gather`

    scopes = {name: scope_of(name) for name in own}
    opcodes = {name: opcode for _, name, opcode, _, _ in found}
    users = {}
    for name, names in operands.items():
        for operand in names:
            users.setdefault(operand, []).append(name)

    def reach(name, step, seen):
        """The scope of the first scoped instruction from `name` along
        `step` (operands or users), through nameless instructions and, up
        the operands, through the tuples' glue."""
        for other in step.get(name, ()):
            if other in seen or other not in own:
                continue
            seen.add(other)
            through = other in nameless or (
                step is operands and opcodes[other] in _TUPLE_GLUE
            )
            scope = scopes[other] or (through and reach(other, step, seen))
            if scope:
                return scope
        return ""

    scopes.update({
        name: reach(name, users, {name}) for name in nameless
        if not scopes[name] and reach(name, operands, {name})
    })
    instructions = [
        (name, opcode, scopes[name], entry)
        for comp, name, opcode, _, entry in found
        if comp not in inlined and opcode not in _NO_OP
    ]
    fused = {}
    for name, _, scope, _ in instructions:
        others = set(within.get(bodies.get(name), ())) - {scope}
        if others:
            fused[name] = sorted(others)
    holds = {comp for comp, _, opcode, _, _ in found if _is_collective(opcode)}
    computes = {
        comp for comp, _, opcode, _, _ in found
        if not _is_collective(opcode) and opcode not in _NO_COMPUTE
    }
    carriers = {
        name: body in computes for name, body in bodies.items() if body in holds
    }
    scalars = {
        loop: arithmetic.get(body, 0) for loop, body in loop_bodies.items()
    }
    copies, gathers = {}, {}
    for loop, body in loop_bodies.items():
        sizes = [
            size for opcode, callee, size in moved.get(body, ())
            if opcode in _RELAYOUT
            or callee and _is_relayout(roots.get(callee), held.get(callee, ()))
        ]
        copies[loop] = (len(sizes), sum(sizes))
        gathers[loop] = sum(
            opcode == "gather" or "gather" in held.get(callee, ())
            for opcode, callee, _ in moved.get(body, ())
        )
    return instructions, fused, carriers, scalars, copies, gathers


def _operands(rest: str):
    start = rest.find("(")
    depth = 0
    for i in range(start, len(rest)):
        depth += (rest[i] == "(") - (rest[i] == ")")
        if depth == 0:
            return _OPERAND.findall(rest[start:i])
    return []


def _scope_of(op_name: str) -> str:
    # The path's last component is the primitive (`gather`, `add`): never a
    # bracket, and one of them shares a word with the vocabulary. Where XLA
    # made one instruction of several it joins their paths with `;`: the
    # first speaks.
    path = op_name.partition(";")[0]
    return "/".join(filter(None, map(_word, path.split("/")[:-1])))


# A bracket entered inside a differentiated function reaches the text
# wrapped in the transforms it was traced under (`jvp(recur)`, `transpose(
# jvp(recur))`), and a wrapped word is not read: the backward pass of a
# bracket inside a loss counts under the loss's own bracket, entered outside
# (`update/critic`), and the tables of every program written so far stand on
# that. The words of _THROUGH_TRANSFORMS are read through the wrappers: a
# recurrent update's time is its memories' forward AND backward passes, all
# of them inside the losses.
_THROUGH_TRANSFORMS = frozenset({"recur"})
_WRAPPED = re.compile(r"^(?:\w+\()+(\w+)\)+$")


def _word(part: str) -> Optional[str]:
    if part in _SCOPE_WORDS:
        return part
    m = _WRAPPED.match(part)
    return m.group(1) if m and m.group(1) in _THROUGH_TRANSFORMS else None


_ASYNC_HALVES = ("-start", "-done")


def _is_collective(opcode: str) -> bool:
    for suffix in _ASYNC_HALVES:
        if opcode.endswith(suffix):
            opcode = opcode[: -len(suffix)]
    return opcode in _COLLECTIVE_OPCODES


def _collectives(instructions, carriers) -> set:
    """Names of the instructions that are collectives and nothing else."""
    return {
        name for name, opcode, _, _ in instructions
        if _is_collective(opcode) or carriers.get(name) is False
    }


def _scopes(instructions, collectives) -> Dict[str, str]:
    table = {}
    for name, _, scope, _ in instructions:
        scope = COLLECTIVE if name in collectives else scope
        if scope:
            table[name] = scope
    return table


def op_scopes(hlo_text: str) -> Dict[str, str]:
    """{instruction name: scope} from a compiled module's text
    (`compiled.as_text()`): the scope `_instructions` reads; COLLECTIVE for
    a collective instruction, whatever its path (`chunk_ops_table` keeps
    that as `served`); an instruction under no bracket is absent."""
    instructions, _, carriers, *_ = _instructions(hlo_text)
    return _scopes(instructions, _collectives(instructions, carriers))


def chunk_ops_table(hlo_text: str) -> Dict[str, Any]:
    """What `chunk_ops.json` holds, from the text of the executable a run
    launched: the module's name as a device trace names its launches, the
    vocabulary, `ops` (op_scopes), `served` (each collective's scope),
    `asynchronous` (the collectives the device runs beside other
    operations, each with its scope: a `-start` and its `-done`, which read
    `collective` in `ops`, and the fusions that carry a step of one, which
    read as the compute they are: _instructions; a plain `all-reduce` holds
    the core until it has landed and is not among them), `fused` (what else
    each fusion holds), `loops`, the `while` instructions: a device trace
    nests a loop's body under the loop's own event, and a reader tells the
    loop's time under no body operation by the names here; `scalars`,
    the unfused arithmetic instructions on a `[]` shape in the bodies of
    `loops`, a trip of each: where to look for what that time is made of
    (the TPU's compiler fuses no arithmetic on scalars, and a chain of it
    behind a fusion's `f32[]` result makes the loop wait for the result:
    PERF.md §6, PR 46); and `copies`, the relayouts those bodies run as
    operations of their own, a trip of each: `count` of the `copy`,
    `transpose` and `reshape` instructions and of the fusions that only move
    an array, and the `bytes` of their results (an array handed from one
    layer to the next in a layout the next cannot read is paid for here:
    PERF.md §6, PR 50); and `gathers`, the `gather` instructions and the
    fusions that hold one in those bodies, a trip of each (a table indexed
    inside the loop is an operation of its own and a relayout behind it:
    PERF.md §6, PR 52)."""
    module = re.match(r"HloModule ([\w.\-]+)", hlo_text)
    instructions, fused, carriers, scalars, copies, gathers = _instructions(hlo_text)
    collectives = _collectives(instructions, carriers)
    return {
        "module": module.group(1) if module else "",
        "scopes": list(CHUNK_SCOPES),
        "ops": _scopes(instructions, collectives),
        "served": {
            name: scope for name, _, scope, _ in instructions
            if name in collectives
        },
        "asynchronous": {
            name: scope for name, opcode, scope, _ in instructions
            if name in carriers
            or (name in collectives and opcode.endswith(_ASYNC_HALVES))
        },
        "fused": fused,
        "loops": [
            name for name, opcode, _, _ in instructions if opcode == "while"
        ],
        "scalars": sum(scalars.values()),
        "copies": {
            "count": sum(count for count, _ in copies.values()),
            "bytes": sum(size for _, size in copies.values()),
        },
        "gathers": sum(gathers.values()),
    }
