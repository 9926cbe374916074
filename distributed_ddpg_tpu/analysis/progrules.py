"""Program-level static rules: the AST half of the program-contract
analyzer (analysis/programs.py; docs/ANALYSIS.md "Layer 2").

The dynamic analyzer traces the compiled programs; this module holds the
jit-KEY hazards that are visible without tracing anything — shapes that
make XLA recompile the same program over and over, which on a pod means
every replica pays the multi-second compile inside the training loop
(and on the serve path, inside a request deadline). One rule, four
concrete shapes, all of which have shipped somewhere as "why is the TPU
idle 40% of the time":

1. a `jax.jit(...)` (or `partial(jax.jit, ...)` factory) call inside a
   `for`/`while` body — inline, or as a decorator on a def, since a
   decorator executes at definition time, i.e. per iteration — every
   iteration builds a fresh callable, and the jit cache keys on the
   function OBJECT, so each one retraces and recompiles. Worse when the
   closure captures the loop variable: the baked-in Python scalar forces
   one compile per distinct value.
2. a jit built and invoked in one expression inside a function
   (`jax.jit(fn)(x)`): the wrapper is rebuilt — and the program
   retraced — on every call of the enclosing function.
3. an unhashable literal (list/dict/set) passed at a static position of
   a tracked `jax.jit(..., static_argnums=...)` callsite: dispatch
   raises TypeError the first time that path runs — on the pod, at beat
   cadence.
4. a `jax.jit(...)` inside the TRACED body callable of
   `lax.fori_loop` / `lax.while_loop` / `lax.scan` (inline lambda, a
   named def passed as the body, or a jit handed directly as the body
   argument): the body executes under trace, so the nested jit
   re-enters the jit machinery on every (re)composition of the
   enclosing program — the compile-once superstep contract
   (parallel/superstep.py) requires the loop body to stay jit-free,
   with the one jit wrapping the whole loop.

Registered into the same registry as rules.py, so `tools.lint`, the
suppression grammar, and `--rules recompile-hazard` all apply; the
proganalyze CLI runs it alongside the traced checks.

A fifth shape of the same hazard cannot be seen in the source, so it is
no lint rule and `seed_constant_findings` below lowers the registered
programs to find it (importing jax only when called; the CLI runs it
beside the static rule): a value derived from `config.seed` traced into
a hot program as a CONSTANT. The persistent compile cache keys on the
program's text, so such a program compiles anew for every seed of one
configuration — 10 to 41 s of every run of a sweep, where a seed that
reaches the program as an argument (the sampling key, the noise
stream's base key: parallel/learner.py) costs a load from the cache.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from distributed_ddpg_tpu.analysis.engine import (
    Finding,
    LintContext,
    Module,
    Rule,
    register,
)
from distributed_ddpg_tpu.analysis.rules import (
    _DonationScan,
    _int_tuple_kwarg,
    _jit_call,
    dotted,
)


_JIT_NAMES = ("jit", "jax.jit", "pjit", "jax.experimental.pjit.pjit")


def _jit_like_call(node: ast.AST) -> Optional[ast.Call]:
    """jax.jit(...) itself, or the partial(jax.jit, ...) factory shape."""
    jc = _jit_call(node)
    if jc is not None:
        return jc
    if isinstance(node, ast.Call):
        name = dotted(node.func) or ""
        if name in ("partial", "functools.partial") and node.args:
            inner = dotted(node.args[0]) or ""
            if inner in ("jit", "jax.jit"):
                return node
    return None


def _static_positions(call: ast.Call) -> Tuple[int, ...]:
    """Literal static_argnums of a jit call, () when absent/computed."""
    return _int_tuple_kwarg(call, "static_argnums") or ()


class _StaticJitScan:
    """Names bound to jax.jit(..., static_argnums=...) results — the
    static-position twin of rules._DonationScan, kept deliberately
    narrow the same way (plain/annotated assigns, no alias chasing;
    the binding shapes come from _DonationScan._binding)."""

    def __init__(self, nodes: List[ast.AST]):
        self.static: Dict[str, Tuple[int, ...]] = {}
        for node in nodes:
            bind = _DonationScan._binding(node)
            if bind is None:
                continue
            targets, value = bind
            jc = _jit_call(value)
            if jc is None:
                continue
            pos = _static_positions(jc)
            if pos:
                for t in targets:
                    tn = dotted(t)
                    if tn:
                        self.static[tn] = pos


_UNHASHABLE = (ast.List, ast.Dict, ast.Set, ast.ListComp, ast.DictComp,
               ast.SetComp)


# Traced-loop callsites and the arg positions holding traced callables:
# fori_loop(lower, upper, BODY, init); while_loop(COND, BODY, init);
# scan(BODY, init, xs). Bare `scan` is deliberately absent — the name is
# too generic to claim without a lax/jax.lax qualifier (host-side scan
# helpers exist); `fori_loop`/`while_loop` are distinctive enough bare.
_TRACED_LOOP_BODY_ARGS: Dict[str, Tuple[int, ...]] = {}
for _base, _pos in (("fori_loop", (2,)), ("while_loop", (0, 1)),
                    ("scan", (0,))):
    for _prefix in ("lax.", "jax.lax."):
        _TRACED_LOOP_BODY_ARGS[_prefix + _base] = _pos
_TRACED_LOOP_BODY_ARGS["fori_loop"] = (2,)
_TRACED_LOOP_BODY_ARGS["while_loop"] = (0, 1)


def _walk_skipping_deferred(stmt: ast.stmt) -> Iterable[ast.AST]:
    """ast.walk minus the bodies of nested def/lambda: a def or lambda
    inside a loop DEFERS execution, so a jit call in its body runs when
    the helper is called (possibly once — the ProgramSpec-builder
    idiom), not per iteration. Decorators and class bodies still
    descend: both execute at definition time, i.e. per iteration —
    `@jax.jit` on a def in a loop body builds a fresh callable every
    pass exactly like an inline jit call."""
    stack = [stmt]
    while stack:
        node = stack.pop()
        yield node
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            stack.extend(node.decorator_list)
            continue
        if isinstance(node, ast.Lambda):
            continue
        stack.extend(ast.iter_child_nodes(node))


@register
class RecompileHazard(Rule):
    """Jit-key hazards: shapes that silently turn one compile into a
    compile-per-call (module docstring). The finding always names the
    hazard AND the sanctioned idiom — hoist the jit, cache per shape
    (replay/device.py's `_get_insert` dict), or make the static arg
    hashable."""

    name = "recompile-hazard"
    doc = (
        "no jax.jit inside a loop body, no jit-and-call in one "
        "expression inside a function, no unhashable literal at a "
        "static_argnums position, and no jit inside the traced body "
        "callable of lax.fori_loop/while_loop/scan"
    )

    def check_module(self, module: Module, ctx: LintContext) -> Iterable[Finding]:
        if module.tree is None:
            return
        statics = _StaticJitScan(module.nodes).static
        fndefs: Dict[str, ast.FunctionDef] = {
            n.name: n
            for n in module.nodes
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
        }

        def findings():
            for node in module.nodes:
                if isinstance(node, (ast.For, ast.While)):
                    yield from self._scan_loop(module, node)
                elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    yield from self._scan_inline_jit(module, node)
                if isinstance(node, ast.Call):
                    yield from self._check_static_args(module, node, statics)
                    yield from self._scan_traced_body(module, node, fndefs)

        # ast.walk visits nested loops/defs once per ancestor scan — the
        # same hazard must report once. Messages can differ across scans
        # (only the innermost loop's scan sees its loop variable in the
        # closure), so dedup on position and keep the richest message.
        best: Dict[Tuple[int, int], Finding] = {}
        order: List[Tuple[int, int]] = []
        for f in findings():
            key = (f.line, f.col)
            cur = best.get(key)
            if cur is None:
                order.append(key)
                best[key] = f
            elif len(f.message) > len(cur.message):
                best[key] = f
        for key in order:
            yield best[key]

    # -- shape 1: jit built inside a loop body -------------------------

    def _scan_loop(self, module: Module, loop) -> Iterable[Finding]:
        loop_vars: Set[str] = set()
        if isinstance(loop, ast.For):
            for n in ast.walk(loop.target):
                if isinstance(n, ast.Name):
                    loop_vars.add(n.id)
        for stmt in loop.body + loop.orelse:
            for node in _walk_skipping_deferred(stmt):
                # A BARE `@jax.jit` decorator on a def in the loop body is
                # the same hazard with no Call node to match: the decorator
                # executes at definition time, i.e. per iteration. (Call-
                # shaped decorators — `@jax.jit(...)`, `@partial(jax.jit,
                # ...)` — flow through the walk and match below.)
                if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    for dec in node.decorator_list:
                        if (not isinstance(dec, ast.Call)
                                and (dotted(dec) or "") in _JIT_NAMES):
                            yield module.finding(
                                self.name, dec,
                                f"@{dotted(dec)} on a def inside a loop "
                                "body — the decorator runs at definition "
                                "time, so each iteration builds a fresh "
                                "jitted callable that retraces and "
                                "recompiles; hoist the jitted helper out "
                                "of the loop",
                            )
                    continue
                jc = _jit_like_call(node)
                if jc is None or not isinstance(node, ast.Call):
                    continue
                captured = self._captured_loop_var(jc, loop_vars)
                extra = (
                    f" — and the jitted closure captures loop variable "
                    f"`{captured}` as a baked-in Python scalar, one "
                    "recompile per distinct value"
                    if captured else ""
                )
                yield module.finding(
                    self.name, node,
                    "jax.jit() inside a loop body — each iteration builds "
                    "a fresh callable and the jit cache keys on the "
                    "function object, so the same program retraces and "
                    "recompiles every pass; hoist the jit out of the loop "
                    "or cache per static shape (the replay _get_insert "
                    f"dict idiom){extra}",
                )

    @staticmethod
    def _captured_loop_var(jc: ast.Call, loop_vars: Set[str]) -> Optional[str]:
        if not loop_vars or not jc.args:
            return None
        target = jc.args[0]
        if isinstance(target, ast.Lambda):
            for n in ast.walk(target.body):
                if isinstance(n, ast.Name) and n.id in loop_vars:
                    return n.id
        return None

    # -- shape 2: jit-and-invoke in one expression ---------------------

    def _scan_inline_jit(self, module: Module, fn) -> Iterable[Finding]:
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            # Only a DIRECT jax.jit(...) call invoked in place counts:
            # `partial(jax.jit, ...)(fn)` merely builds the wrapper (the
            # sanctioned bind-once factory idiom) — no program is traced
            # by the outer call.
            jc = _jit_call(node.func)
            if jc is not None and isinstance(node.func, ast.Call):
                yield module.finding(
                    self.name, node,
                    "jit built and invoked in one expression "
                    "(`jax.jit(fn)(...)`) inside a function — the wrapper "
                    "is rebuilt and the program retraced on every call of "
                    "the enclosing function; bind the jitted callable "
                    "once (module level or __init__) and dispatch through "
                    "the binding",
                )

    # -- shape 4: jit inside a traced loop body ------------------------

    def _scan_traced_body(self, module: Module, call: ast.Call,
                          fndefs: Dict[str, ast.FunctionDef]
                          ) -> Iterable[Finding]:
        """jax.jit inside the body callable of lax.fori_loop / while_loop
        / scan. The body is TRACED — a nested jit there re-enters the jit
        machinery on every (re)composition of the enclosing program. The
        compile-once superstep (parallel/superstep.py) depends on this
        staying clean: one jit around the whole loop, a jit-free body
        inside it."""
        name = dotted(call.func) or ""
        positions = _TRACED_LOOP_BODY_ARGS.get(name)
        if not positions:
            return
        site = name.rsplit(".", 1)[-1]
        for i in positions:
            if i >= len(call.args):
                continue
            body = call.args[i]
            # The body argument IS a jit: `fori_loop(0, n, jax.jit(f), c)`.
            if _jit_like_call(body) is not None:
                yield module.finding(
                    self.name, body,
                    f"jit-wrapped callable passed as the traced body of "
                    f"lax.{site}() — the loop body executes under trace, "
                    "so the nested jit re-enters the jit cache on every "
                    "composition of the enclosing program; keep the body "
                    "jit-free and jit the function that CONTAINS the loop",
                )
                continue
            # Inline lambda body, or a named def resolved in this module.
            target = None
            if isinstance(body, ast.Lambda):
                target = body.body
            elif isinstance(body, ast.Name) and body.id in fndefs:
                target = fndefs[body.id]
            if target is None:
                continue
            scan_root = (
                [s for s in target.body]
                if isinstance(target, (ast.FunctionDef, ast.AsyncFunctionDef))
                else [target]
            )
            for stmt in scan_root:
                for node in _walk_skipping_deferred(stmt):
                    hazard = None
                    if (isinstance(node, ast.Call)
                            and _jit_like_call(node) is not None):
                        hazard = node
                    elif isinstance(node,
                                    (ast.FunctionDef, ast.AsyncFunctionDef)):
                        for dec in node.decorator_list:
                            if (not isinstance(dec, ast.Call)
                                    and (dotted(dec) or "") in _JIT_NAMES):
                                hazard = dec
                    if hazard is not None:
                        yield module.finding(
                            self.name, hazard,
                            f"jax.jit inside the traced body of "
                            f"lax.{site}() — the body runs under trace, so "
                            "the nested jit re-traces on every composition "
                            "of the enclosing program (and defeats the "
                            "compile-once loop contract); hoist the jit "
                            "out and close over the plain function",
                        )

    # -- shape 3: unhashable literal at a static position --------------

    def _check_static_args(self, module: Module, call: ast.Call,
                           statics: Dict[str, Tuple[int, ...]]
                           ) -> Iterable[Finding]:
        callee = dotted(call.func)
        pos = statics.get(callee or "")
        if not pos:
            return
        for i in pos:
            if i < len(call.args) and isinstance(call.args[i], _UNHASHABLE):
                kind = type(call.args[i]).__name__.lower().replace("comp", " comprehension")
                yield module.finding(
                    self.name, call.args[i],
                    f"{kind} literal passed at static position {i} of "
                    f"{callee}() — static jit args must be hashable "
                    "(dispatch raises TypeError the first time this path "
                    "runs); pass a tuple / frozen value instead",
                )


# -- shape 5: a seed in a hot program's text (lowered, not static) ------


def seed_constant_findings(make_specs=None, seeds: Tuple[int, int] = (0, 1),
                           only: Optional[Sequence[str]] = None) -> List:
    """`seed-constant` findings (analysis.programs.ProgramFinding) over a
    program registry: `make_specs()` (default: the live default_specs())
    is built once under each of `seeds` (programs.probe_seed: every
    probe_config takes it) and each program is lowered, never compiled;
    a program whose two texts differ holds a constant derived from the
    seed. `only` filters by program name (exact or fnmatch glob)."""
    import fnmatch

    from distributed_ddpg_tpu.analysis import programs as prog_lib

    make_specs = make_specs or prog_lib.default_specs
    texts: List[Dict[str, str]] = []
    for seed in seeds:
        by_name: Dict[str, str] = {}
        with prog_lib.probe_seed(seed):
            for spec in make_specs():
                if only is not None and not any(
                    fnmatch.fnmatch(spec.name, pat) for pat in only
                ):
                    continue
                try:
                    built = spec.build()
                    by_name[spec.name] = built.fn.lower(*built.args).as_text()
                except Exception:
                    # analyze() gates on a spec that cannot build; there
                    # is no text of it to compare.
                    continue
        texts.append(by_name)
    first, second = texts
    findings = []
    for name, text in first.items():
        other = second.get(name)
        if other is None or other == text:
            continue
        a, b = text.splitlines(), other.splitlines()
        where = next(
            (x.strip() for x, y in zip(a, b) if x != y),
            f"{len(a)} lines against {len(b)}",
        )
        findings.append(prog_lib.ProgramFinding(
            name, "seed-constant",
            f"the program's lowered text differs between seed {seeds[0]} "
            f"and seed {seeds[1]} (first at: {where:.200}) — a value "
            "derived from config.seed is a constant of the program, so "
            "the persistent compile cache misses for every new seed; "
            "build the value on the host and hand it to the program as "
            "an argument (ShardedLearner._noise_key is the pattern)",
        ))
    return findings
