"""Layer-2 program-contract analyzer: jaxpr/lowering-level verification
of the compiled training programs (docs/ANALYSIS.md "Layer 2").

The PR-11 lint engine checks SOURCE — but the invariants that actually
kill a pod live in the COMPILED programs. Replicas fork when their
collective op order diverges (the PodPeerLost/exit-76 class; Podracer's
SPMD discipline, PAPERS.md arXiv 2104.06272), and donation that silently
fails to alias doubles HBM on exactly the buffers sharded replay (D4PG
scale, arXiv 1804.08617) was built to shrink. This module abstractly
traces every hot jitted program — `jax.make_jaxpr` + `.lower()`, never
executing or compiling anything — and checks the artifact:

1. **donation-aliasing** — every leaf of every `donate_argnums` entry
   must be able to alias an output in the lowered computation
   (`tf.aliasing_output` in the StableHLO signature, or a
   `jax.buffer_donor` with a type-matching output for XLA to pair it
   with). A donated-but-unaliasable buffer is a finding, not a silent
   2x HBM cost.
2. **collective-order fingerprint** — the ordered sequence of
   psum/all-gather/ppermute-family primitives in the traced jaxpr
   (including nested scan/pjit/shard_map bodies), canonicalized and
   compared against golden files in tests/golden_programs/. Any reorder
   across a PR is a reviewed golden diff, never an accident. This pins
   the collectives the programs EXPLICITLY stage (shard_map bodies,
   the sharded-replay exchange); collectives the SPMD partitioner
   inserts at compile time are downstream of this jaxpr and follow it
   deterministically.
3. **beat-group consistency** — program variants that must share pod
   beat order (the guarded vs unguarded chunk, dispatched
   interchangeably at the same lockstep site) must have IDENTICAL
   collective subsequences.
4. **host-callback leak** — no `pure_callback`/`io_callback`/
   `debug_callback` primitives in any hot program: a host round-trip
   inside a lockstep program couples every peer's beat to one host's
   scheduler.

Program specs come from cheap `program_specs()` hooks on each subsystem
that owns a jitted program (parallel/learner.py, replay/device.py,
actors/device_pool.py, serve/server.py) — each builds its
hot programs tiny (8-wide batches, 16-wide hiddens, chunks of 2) under
the 2-device CPU probe mesh. jit is lazy, so building costs tracing
only; the whole live-tree run stays under a 30 s CPU budget
(tests/test_programs.py pins it).

This module imports jax — it is NOT part of the jax-free lint path.
The static half (jit-key hazards) lives in progrules.py instead.

    python -m distributed_ddpg_tpu.tools.proganalyze            # check
    python -m distributed_ddpg_tpu.tools.proganalyze --update-golden
    scripts/proganalyze_gate.sh                                 # CI gate
"""

from __future__ import annotations

import contextlib
import dataclasses
import fnmatch
import hashlib
import json
import re
import time
import warnings
from collections import Counter
from pathlib import Path
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

import jax
import numpy as np

# Collective primitives whose ORDER is the pod contract: every process
# must stage these identically or the pod's device-op streams fork.
COLLECTIVE_PRIMITIVES = frozenset({
    "psum", "pmin", "pmax", "ppermute", "pbroadcast",
    "all_gather", "all_to_all", "reduce_scatter",
})
# Host round-trips that must never appear inside a hot program.
CALLBACK_PRIMITIVES = frozenset({
    "pure_callback", "io_callback", "debug_callback",
})

# The probe mesh every spec builds under: 2 data-parallel CPU devices —
# the smallest mesh where sharded placement and collectives are real.
PROBE_MESH_DEVICES = 2


class ProgramBuildError(RuntimeError):
    """A program spec failed to construct its jitted program (reported as
    a build-error finding — a spec that cannot build must gate)."""


@dataclasses.dataclass
class BuiltProgram:
    """One constructed jitted program plus the example arguments to trace
    it with. `donated` mirrors the jit callsite's donate_argnums — the
    spec owner keeps them in sync (they sit lines apart in the source),
    and the donation-aliasing check verifies the LOWERED artifact agrees."""

    fn: Callable
    args: Tuple
    donated: Tuple[int, ...] = ()


@dataclasses.dataclass
class ProgramSpec:
    """Registry entry: a named factory for one hot jitted program.
    `owner` is the package-relative module the program lives in (what
    findings and --changed-only scoping report); `beat_group` marks
    variants that must share pod beat order."""

    name: str
    owner: str
    build: Callable[[], BuiltProgram]
    beat_group: Optional[str] = None


@dataclasses.dataclass
class ProgramFinding:
    program: str
    check: str    # donation-aliasing | collective-order | beat-group |
                  # host-callback | build-error | stale-golden |
                  # seed-constant (progrules.seed_constant_findings)
    message: str

    def to_json(self) -> Dict[str, object]:
        return dataclasses.asdict(self)

    def render(self) -> str:
        return f"{self.program} [{self.check}] {self.message}"


@dataclasses.dataclass
class ProgramReport:
    findings: List[ProgramFinding]
    programs: List[Dict[str, object]]
    updated: List[str]
    elapsed_s: float

    def to_json(self) -> Dict[str, object]:
        return {
            "version": 1,
            "counts": {
                "programs": len(self.programs),
                "findings": len(self.findings),
            },
            "elapsed_s": round(self.elapsed_s, 3),
            "updated": self.updated,
            "programs": self.programs,
            "findings": [f.to_json() for f in self.findings],
        }


# ---------------------------------------------------------------------------
# probe environment (shared by every program_specs() hook)
# ---------------------------------------------------------------------------


def probe_mesh(model_axis: int = 1):
    """The tiny CPU mesh every spec builds under: (data=2, model=1) by
    default; model_axis=2 gives the (data=2, model=2) TP probe mesh the
    `.tp` spec variants build under (docs/MESH.md — a collective reorder
    under the 2D mesh must be a reviewed golden diff, not a pod fork).
    The CLI forces a multi-device CPU platform before importing jax
    (tools/proganalyze.py); under pytest, tests/conftest.py already did."""
    from distributed_ddpg_tpu.parallel import mesh as mesh_lib

    need = PROBE_MESH_DEVICES * model_axis
    devices = jax.devices("cpu")
    if len(devices) < need:
        raise ProgramBuildError(
            f"program specs need >= {need} CPU devices for "
            "the probe mesh; run under XLA_FLAGS="
            "--xla_force_host_platform_device_count=8 (the proganalyze "
            "CLI sets this itself)"
        )
    return mesh_lib.make_mesh(
        PROBE_MESH_DEVICES, model_axis, devices=devices[:need]
    )


_probe_seed = 0


@contextlib.contextmanager
def probe_seed(seed: int):
    """Every probe_config() built inside takes `seed`: how the
    seed-constant rule (progrules.seed_constant_findings) builds the
    registry a second time."""
    global _probe_seed
    prev, _probe_seed = _probe_seed, int(seed)
    try:
        yield
    finally:
        _probe_seed = prev


def probe_config(**overrides):
    """Tiny-but-real DDPGConfig for spec builds: every dimension shrunk
    so tracing is milliseconds, nothing else changed — the program
    STRUCTURE (op order, donation, collectives) is what ships."""
    from distributed_ddpg_tpu.config import DDPGConfig

    base = dict(
        env_id="Pendulum-v1",
        batch_size=8,
        actor_hidden=(16, 16),
        critic_hidden=(16, 16),
        replay_capacity=64,
        seed=_probe_seed,
    )
    base.update(overrides)
    return DDPGConfig(**base)


# ---------------------------------------------------------------------------
# tracing: collective order + callback leaks from the jaxpr
# ---------------------------------------------------------------------------


def _canon_axes(params: Dict) -> str:
    axes = params.get("axes")
    if axes is None:
        axes = params.get("axis_name")
    if axes is None:
        return ""
    if not isinstance(axes, (tuple, list)):
        axes = (axes,)
    return ",".join(str(a) for a in axes)


def _walk_jaxpr(jaxpr, collectives: List[str], callbacks: List[str],
                counts: List[int]) -> None:
    """Depth-first, in-equation order — the deterministic canonical order
    of the traced program. Nested jaxprs (pjit, scan, while, cond,
    shard_map, custom_* ...) are found generically through eqn params."""
    for eqn in jaxpr.eqns:
        counts[0] += 1
        name = eqn.primitive.name
        if name in COLLECTIVE_PRIMITIVES:
            axes = _canon_axes(eqn.params)
            collectives.append(f"{name}[{axes}]" if axes else name)
        elif name in CALLBACK_PRIMITIVES:
            callbacks.append(name)
        for val in eqn.params.values():
            for sub in (val if isinstance(val, (list, tuple)) else (val,)):
                inner = getattr(sub, "jaxpr", None)
                if inner is not None and hasattr(inner, "eqns"):
                    _walk_jaxpr(inner, collectives, callbacks, counts)
                elif hasattr(sub, "eqns"):
                    _walk_jaxpr(sub, collectives, callbacks, counts)


def trace_program(built: BuiltProgram, traced=None):
    """(collectives, callbacks, n_eqns) from an abstract trace — no
    compile, no execution. Pass a precomputed `jit(fn).trace(*args)`
    stage to reuse ONE abstract trace across this check and the
    donation-aliasing lowering (tracing dominates the gate's runtime);
    the walk descends nested jaxprs generically, so the traced stage's
    body jaxpr and make_jaxpr's pjit-wrapped one fingerprint alike."""
    if traced is not None:
        closed = traced.jaxpr
    else:
        closed = jax.make_jaxpr(built.fn)(*built.args)
    collectives: List[str] = []
    callbacks: List[str] = []
    counts = [0]
    _walk_jaxpr(closed.jaxpr, collectives, callbacks, counts)
    return collectives, callbacks, counts[0]


def fingerprint(collectives: Sequence[str]) -> str:
    blob = "\n".join(collectives).encode("utf-8")
    return hashlib.sha256(blob).hexdigest()[:16]


# ---------------------------------------------------------------------------
# lowering: donation aliasing
# ---------------------------------------------------------------------------

_MLIR_DTYPES = {
    "float32": "f32", "float64": "f64", "float16": "f16",
    "bfloat16": "bf16", "int64": "i64", "int32": "i32", "int16": "i16",
    "int8": "i8", "uint64": "ui64", "uint32": "ui32", "uint16": "ui16",
    "uint8": "ui8", "bool": "i1",
}


def _leaf_mlir_type(leaf) -> str:
    dt = _MLIR_DTYPES.get(np.dtype(getattr(leaf, "dtype", np.float32)).name,
                          "?")
    shape = tuple(getattr(leaf, "shape", ()))
    return "x".join([str(d) for d in shape] + [dt])


def _main_signature(text: str) -> Tuple[str, str]:
    """(args, results) segments of the lowered module's public @main func
    — the only place XLA records input-output aliasing and donation."""
    i = text.find("@main(")
    if i < 0:
        return "", ""
    depth = 0
    args_seg = None
    for j in range(i + len("@main"), len(text)):
        c = text[j]
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
            if depth == 0:
                args_seg = text[i:j + 1]
                rest = text[j + 1:]
                break
    if args_seg is None:
        return text[i:], ""
    m = re.match(r"\s*->\s*", rest)
    if not m:
        return args_seg, ""
    rest = rest[m.end():]
    if rest.startswith("("):
        depth = 0
        for j, c in enumerate(rest):
            if c == "(":
                depth += 1
            elif c == ")":
                depth -= 1
                if depth == 0:
                    return args_seg, rest[:j + 1]
        return args_seg, rest
    return args_seg, rest.split("{", 1)[0]


def check_donation_aliasing(built: BuiltProgram,
                            traced=None) -> Tuple[int, int, List[str]]:
    """(donated_leaves, aliasable_leaves, missing_types): lower the
    program (no compile) and verify every donated leaf will alias an
    output. Two attribute shapes prove it: `tf.aliasing_output` (jax
    resolved the pairing at lowering — only donated buffers carry it) and
    `jax.buffer_donor` (jax deferred the pairing to XLA — the shard_map/
    sharded-output path), which counts only while an output of the SAME
    tensor type remains to pair with: XLA aliases donor buffers by type
    match, so a donor with no matching output is exactly the silent-2x
    case this check exists for. The comparison is by type multiset —
    positional arg-index mapping is deliberately avoided (lowering may
    hoist closure constants into extra args)."""
    if not built.donated:
        return 0, 0, []
    with warnings.catch_warnings():
        # An unaliased donation warns at lower time; the WARNING is noise
        # here — the structured finding is the signal.
        warnings.simplefilter("ignore")
        # A precomputed trace stage lowers WITHOUT re-tracing — the whole
        # point of threading it through from analyze().
        lowered = (traced.lower() if traced is not None
                   else built.fn.lower(*built.args))
    args_seg, out_seg = _main_signature(lowered.as_text())
    parts = re.split(r"(?=%arg\d+:)", args_seg)
    aliased_types: List[str] = []
    donor_types: List[str] = []
    for p in parts:
        m = re.match(r"%arg\d+: tensor<([^>]*)>", p)
        if not m:
            continue
        if "tf.aliasing_output" in p:
            aliased_types.append(m.group(1))
        elif "jax.buffer_donor" in p:
            donor_types.append(m.group(1))
    out_types = re.findall(r"tensor<([^>]*)>", out_seg)
    donated_leaves: List[str] = []
    for i in built.donated:
        if not 0 <= i < len(built.args):
            # The spec's hand-maintained `donated` tuple drifted from the
            # example args: a silently-skipped index would make the check
            # vacuous for exactly that buffer, so it gates (analyze()
            # reports the raise as a build-error finding).
            raise ProgramBuildError(
                f"donated index {i} out of range for {len(built.args)} "
                "example args — the spec's `donated` tuple drifted from "
                "its jit callsite's donate_argnums"
            )
        donated_leaves.extend(
            _leaf_mlir_type(l) for l in jax.tree.leaves(built.args[i])
        )
    explicit = Counter(aliased_types)
    donor_ok = Counter(donor_types) & (Counter(out_types) - explicit)
    missing = Counter(donated_leaves) - explicit - donor_ok
    missing_list = sorted(t for t, n in missing.items() for _ in range(n))
    n_ok = len(donated_leaves) - sum(missing.values())
    return len(donated_leaves), n_ok, missing_list


# ---------------------------------------------------------------------------
# golden fingerprints
# ---------------------------------------------------------------------------


def golden_path(golden_dir: Path, name: str) -> Path:
    return golden_dir / (name + ".json")


def load_golden(golden_dir: Path, name: str) -> Optional[Dict]:
    p = golden_path(golden_dir, name)
    if not p.is_file():
        return None
    try:
        return json.loads(p.read_text(encoding="utf-8"))
    except (OSError, json.JSONDecodeError):
        return {}


def write_golden(golden_dir: Path, name: str,
                 collectives: Sequence[str]) -> None:
    golden_dir.mkdir(parents=True, exist_ok=True)
    golden_path(golden_dir, name).write_text(
        json.dumps(
            {
                "program": name,
                "collectives": list(collectives),
                "fingerprint": fingerprint(collectives),
            },
            indent=1,
        ) + "\n",
        encoding="utf-8",
    )


# ---------------------------------------------------------------------------
# the analyzer
# ---------------------------------------------------------------------------


def analyze(
    specs: Sequence[ProgramSpec],
    golden_dir: Path,
    update_golden: bool = False,
    only: Optional[Sequence[str]] = None,
    sweep_stale: bool = True,
) -> ProgramReport:
    """Run every check over `specs`. `only` filters by program name
    (exact or fnmatch glob) — a scoped run skips the stale-golden sweep,
    since unmatched goldens belong to programs it never looked at.
    `sweep_stale=False` disables the sweep AND the --update-golden prune
    even unscoped: an alternate registry (the CLI's --specs) covers none
    of the live programs, so against the default golden dir the sweep
    would flag — and the prune would DELETE — every committed golden."""
    t0 = time.perf_counter()
    scoped = only is not None or not sweep_stale
    if only is not None:
        specs = [
            s for s in specs
            if any(fnmatch.fnmatch(s.name, pat) for pat in only)
        ]
    findings: List[ProgramFinding] = []
    programs: List[Dict[str, object]] = []
    updated: List[str] = []
    by_group: Dict[str, List[Tuple[str, Tuple[str, ...]]]] = {}

    for spec in specs:
        try:
            built = spec.build()
            # One abstract trace serves both checks when the program is
            # donated AND jitted (fixture specs may hand a bare callable
            # with donated=() where only make_jaxpr applies).
            traced = (built.fn.trace(*built.args)
                      if built.donated and hasattr(built.fn, "trace")
                      else None)
            collectives, callbacks, n_eqns = trace_program(built, traced)
            donated_leaves, aliased, missing = check_donation_aliasing(
                built, traced)
        except Exception as e:  # a spec that cannot build must gate
            findings.append(ProgramFinding(
                spec.name, "build-error",
                f"program spec failed to build/trace: {e!r:.400}",
            ))
            continue
        fp = fingerprint(collectives)
        programs.append({
            "name": spec.name,
            "owner": spec.owner,
            "beat_group": spec.beat_group,
            "collectives": collectives,
            "fingerprint": fp,
            "eqns": n_eqns,
            "donated_args": list(built.donated),
            "donated_leaves": donated_leaves,
            "aliased_leaves": aliased,
        })
        if spec.beat_group:
            by_group.setdefault(spec.beat_group, []).append(
                (spec.name, tuple(collectives))
            )

        if aliased < donated_leaves:
            findings.append(ProgramFinding(
                spec.name, "donation-aliasing",
                f"{donated_leaves - aliased} of {donated_leaves} donated "
                "buffer leaves failed to alias any output in the lowered "
                f"program (unaliased: {', '.join(missing) or '?'}) — "
                "donation without aliasing is a silent 2x HBM cost on "
                "exactly the buffers it was meant to recycle; align the "
                "donated input's shape/dtype with an output or drop it "
                "from donate_argnums",
            ))
        for cb in sorted(set(callbacks)):
            findings.append(ProgramFinding(
                spec.name, "host-callback",
                f"`{cb}` primitive embedded in the hot program "
                f"({callbacks.count(cb)}x) — a host round-trip inside a "
                "jitted training program couples every pod peer's beat "
                "to one host's Python scheduler; hoist the callback out "
                "of the compiled path (debug prints included)",
            ))

        if update_golden:
            prev = load_golden(golden_dir, spec.name)
            if prev is None or prev.get("collectives") != collectives:
                updated.append(spec.name)
            write_golden(golden_dir, spec.name, collectives)
        else:
            golden = load_golden(golden_dir, spec.name)
            if golden is None:
                findings.append(ProgramFinding(
                    spec.name, "collective-order",
                    "no golden fingerprint committed for this program — "
                    "run `python -m distributed_ddpg_tpu.tools."
                    "proganalyze --update-golden` and review/commit the "
                    "golden diff",
                ))
            elif golden.get("collectives") != collectives:
                findings.append(ProgramFinding(
                    spec.name, "collective-order",
                    "collective order diverged from the committed golden "
                    f"(golden: {golden.get('collectives')} -> traced: "
                    f"{collectives}) — on a pod this is exactly how "
                    "replicas fork into PodPeerLost/exit-76; if the "
                    "reorder is intentional, re-run with --update-golden "
                    "and review the golden diff",
                ))

    for group, members in sorted(by_group.items()):
        if len({seq for _, seq in members}) > 1:
            detail = "; ".join(
                f"{name}: [{', '.join(seq) or 'none'}]"
                for name, seq in members
            )
            findings.append(ProgramFinding(
                members[0][0], "beat-group",
                f"beat group '{group}' variants disagree on collective "
                f"order ({detail}) — these programs dispatch at the SAME "
                "lockstep site, so a pod mixing them forks its device-op "
                "order",
            ))

    if not scoped and not update_golden and golden_dir.is_dir():
        known = {s.name for s in specs}
        for p in sorted(golden_dir.glob("*.json")):
            if p.stem not in known:
                findings.append(ProgramFinding(
                    p.stem, "stale-golden",
                    f"golden file {p.name} matches no registered program "
                    "spec — a renamed/removed program must retire its "
                    "golden (delete it, or re-run --update-golden which "
                    "prunes stale files)",
                ))
    if update_golden and not scoped and golden_dir.is_dir():
        known = {s.name for s in specs}
        for p in sorted(golden_dir.glob("*.json")):
            if p.stem not in known:
                p.unlink()
                updated.append(f"-{p.stem}")

    return ProgramReport(
        findings=findings,
        programs=programs,
        updated=updated,
        elapsed_s=time.perf_counter() - t0,
    )


# ---------------------------------------------------------------------------
# the default registry
# ---------------------------------------------------------------------------

# Modules exposing a program_specs() hook; --changed-only scoping in the
# CLI keys on the owner paths these specs declare.
SPEC_MODULES = (
    "distributed_ddpg_tpu.parallel.learner",
    "distributed_ddpg_tpu.parallel.megastep",
    "distributed_ddpg_tpu.parallel.superstep",
    "distributed_ddpg_tpu.replay.device",
    "distributed_ddpg_tpu.actors.device_pool",
    "distributed_ddpg_tpu.serve.server",
)


def default_specs() -> List[ProgramSpec]:
    """Every registered hot program in the live tree (the subsystem
    program_specs() hooks), name-deduplicated and order-stable."""
    import importlib

    specs: List[ProgramSpec] = []
    for modname in SPEC_MODULES:
        mod = importlib.import_module(modname)
        specs.extend(mod.program_specs())
    names = [s.name for s in specs]
    dupes = [n for n, c in Counter(names).items() if c > 1]
    if dupes:
        raise ValueError(f"duplicate program spec names: {dupes}")
    return specs


def render_human(report: ProgramReport) -> str:
    out = [f.render() for f in report.findings]
    n = len(report.findings)
    if report.updated:
        out.append(f"updated goldens: {', '.join(report.updated)}")
    out.append(
        f"{len(report.programs)} programs, {n} finding"
        f"{'s' if n != 1 else ''} in {report.elapsed_s:.2f}s"
    )
    return "\n".join(out)


def write_report(report: ProgramReport, path: Path) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(report.to_json(), indent=1) + "\n",
                    encoding="utf-8")
