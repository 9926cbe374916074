"""The repo-specific rule set (docs/ANALYSIS.md has the catalog).

Every rule encodes one architectural invariant a previous PR paid for.
They are deliberately narrow: each matches the concrete AST shape of the
bug class it guards, not a general style opinion — a finding should read
as "this line can reproduce a known outage", never as taste.
"""

from __future__ import annotations

import ast
import re
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from distributed_ddpg_tpu.analysis.engine import (
    Finding,
    LintContext,
    Module,
    Rule,
    register,
)

# ---------------------------------------------------------------------------
# shared AST helpers
# ---------------------------------------------------------------------------


def dotted(node: ast.AST) -> Optional[str]:
    """Render a Name/Attribute chain as 'a.b.c'; None for anything with a
    non-name root (subscripts, calls)."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


_FOLDABLE_BINOPS = {
    ast.Add: lambda a, b: a + b,
    ast.Sub: lambda a, b: a - b,
    ast.Mult: lambda a, b: a * b,
    ast.Div: lambda a, b: a / b if b else None,
}


def numeric_literal(node: ast.AST) -> Optional[float]:
    """The value of a literal int/float expression (incl. unary minus and
    constant-only arithmetic like `10 * 60` — the natural spelling of a
    600 s deadline must not slip past timeout-discipline); None for
    names, calls, and anything genuinely computed."""
    if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
        inner = numeric_literal(node.operand)
        return None if inner is None else -inner
    if isinstance(node, ast.BinOp):
        fold = _FOLDABLE_BINOPS.get(type(node.op))
        left = numeric_literal(node.left)
        right = numeric_literal(node.right)
        if fold is None or left is None or right is None:
            return None
        return fold(left, right)
    if isinstance(node, ast.Constant) and isinstance(node.value, (int, float)) \
            and not isinstance(node.value, bool):
        return float(node.value)
    return None


def keyword_arg(call: ast.Call, name: str) -> Optional[ast.expr]:
    for kw in call.keywords:
        if kw.arg == name:
            return kw.value
    return None


def _in_package_dirs(relpath: str, dirs: Sequence[str]) -> bool:
    return any(relpath.startswith(d + "/") for d in dirs)


# ---------------------------------------------------------------------------
# 1. collective-discipline
# ---------------------------------------------------------------------------

_MULTIHOST_MODULE = "parallel/multihost.py"
_COLLECTIVE_LEAVES = (
    "psum", "pmean", "pmax", "pmin",
    "all_gather", "all_to_all", "ppermute",
)
_COLLECTIVE_LAX = tuple("lax." + leaf for leaf in _COLLECTIVE_LEAVES)
# Modules allowed to BUILD collectives into jitted programs: the mesh /
# learner-program layer and the fused device ops. Everywhere else a raw
# lax collective is either dead code or a host-side hang waiting for a
# deadline that only multihost.py provides.
_COLLECTIVE_BUILDER_DIRS = ("parallel", "ops")


@register
class CollectiveDiscipline(Rule):
    """Every host-initiated DCN collective must ride the audited,
    deadline-guarded entry points in parallel/multihost.py (PR 6): a raw
    multihost_utils / jax.distributed call anywhere else reintroduces the
    eternal-gloo-block failure mode PodPeerLost exists to kill. Raw lax
    collectives (psum & co) are confined to the jit-building layers
    (parallel/, ops/) — outside a jitted program they are a different
    bug (traced-op-outside-trace) with the same fix: go through the
    framework."""

    name = "collective-discipline"
    doc = (
        "DCN collectives only via parallel/multihost.py; raw lax "
        "collectives only in the jit-building layers (parallel/, ops/)"
    )

    def check_module(self, module: Module, ctx: LintContext) -> Iterable[Finding]:
        if module.rulepath == _MULTIHOST_MODULE or module.tree is None:
            return
        # Resolve import bindings first, so `from jax.lax import psum` /
        # `from jax import lax as l` can't smuggle a collective past the
        # spelled-out `lax.psum` match.
        direct: Set[str] = set()
        lax_mods: Set[str] = {"lax", "jax.lax"}
        for node in module.nodes:
            if isinstance(node, ast.ImportFrom):
                if node.module == "jax.lax":
                    for a in node.names:
                        if a.name in _COLLECTIVE_LEAVES:
                            direct.add(a.asname or a.name)
                elif node.module == "jax":
                    for a in node.names:
                        if a.name == "lax" and a.asname:
                            lax_mods.add(a.asname)
            elif isinstance(node, ast.Import):
                for a in node.names:
                    if a.name == "jax.lax" and a.asname:
                        lax_mods.add(a.asname)
        yield from self._walk(module, module.tree, 0, direct, lax_mods)

    def _walk(self, module: Module, node: ast.AST, fn_depth: int,
              direct: Set[str], lax_mods: Set[str]) -> Iterable[Finding]:
        for child in ast.iter_child_nodes(node):
            d = fn_depth
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.Lambda)):
                d += 1
            yield from self._check_node(module, child, fn_depth, direct,
                                        lax_mods)
            yield from self._walk(module, child, d, direct, lax_mods)

    def _check_node(self, module: Module, node: ast.AST, fn_depth: int,
                    direct: Set[str], lax_mods: Set[str]) -> Iterable[Finding]:
        if isinstance(node, ast.Import):
            for alias in node.names:
                if alias.name.startswith("jax.experimental.multihost_utils"):
                    yield module.finding(
                        self.name, node,
                        "import of jax.experimental.multihost_utils "
                        "outside parallel/multihost.py — use "
                        "multihost.allgather_scalar / beat_allgather "
                        "(deadline-guarded, PodPeerLost-typed)",
                    )
        elif isinstance(node, ast.ImportFrom):
            mod = node.module or ""
            names = {a.name for a in node.names}
            if mod.startswith("jax.experimental.multihost_utils") or (
                mod == "jax.experimental" and "multihost_utils" in names
            ):
                yield module.finding(
                    self.name, node,
                    "import of jax.experimental.multihost_utils outside "
                    "parallel/multihost.py — use the audited multihost "
                    "entry points instead",
                )
        elif isinstance(node, ast.Call):
            name = dotted(node.func) or ""
            if name.endswith("distributed.initialize") or \
                    name == "distributed.shutdown" or \
                    name.endswith("jax.distributed.shutdown"):
                yield module.finding(
                    self.name, node,
                    f"{name}() outside parallel/multihost.py — the pod "
                    "bootstrap must stay idempotent and centralized "
                    "(multihost.initialize)",
                )
            elif name.startswith("multihost_utils."):
                yield module.finding(
                    self.name, node,
                    f"raw {name}() call — an unguarded DCN collective "
                    "blocks forever on peer loss; route through "
                    "multihost.allgather_scalar / call_with_deadline",
                )
            else:
                leaf = name.rsplit(".", 1)[-1]
                prefix = name.rsplit(".", 1)[0] if "." in name else ""
                is_collective = (
                    any(name == c or name.endswith("." + c)
                        for c in _COLLECTIVE_LAX)
                    or name in direct
                    or (leaf in _COLLECTIVE_LEAVES and prefix in lax_mods)
                )
                # fn_depth >= 2 ⇒ inside a def nested in another def: the
                # shard_map/jit program-body closure shape, which is a
                # jit-building site wherever it lives.
                if is_collective and not _in_package_dirs(
                    module.rulepath, _COLLECTIVE_BUILDER_DIRS
                ) and fn_depth < 2:
                    yield module.finding(
                        self.name, node,
                        f"raw {leaf}() outside the "
                        "jit-building layers (parallel/, ops/) — "
                        "collectives belong inside the compiled "
                        "learner/mesh programs",
                    )


# ---------------------------------------------------------------------------
# 2. timeout-discipline
# ---------------------------------------------------------------------------

# Literals >= this many seconds are deadlines (must be named knobs);
# smaller literals are poll cadences inside re-checking loops, which are
# the documented idiom (prefetch/batcher condvar ticks).
TIMEOUT_LITERAL_FLOOR_S = 1.0

_BLOCKING_ATTRS = ("result", "get", "wait", "join", "sleep")


@register
class TimeoutDiscipline(Rule):
    """No inline literal deadline on a blocking wait (PR 10: a hardcoded
    `ticket.result(timeout=600)` stalled a wedged pod for 10 silent
    minutes). Deadlines must be named — a config knob, a multihost-derived
    bound (beat_result_timeout_s), or a documented module constant — so
    every wait's budget is auditable in one place. Sub-second literals are
    poll cadences inside re-checking loops and stay allowed."""

    name = "timeout-discipline"
    doc = (
        "no literal timeout >= 1s in .result()/.get()/.wait()/.join()/"
        "time.sleep() — route through a named knob"
    )

    def check_module(self, module: Module, ctx: LintContext) -> Iterable[Finding]:
        if module.tree is None:
            return
        # Bare-name bindings of the blocking callables (`from time import
        # sleep`, `from concurrent.futures import wait`): same semantics
        # as their attribute forms, same rule.
        bare: Dict[str, str] = {}
        for node in module.nodes:
            if isinstance(node, ast.ImportFrom):
                if node.module == "time":
                    for a in node.names:
                        if a.name == "sleep":
                            bare[a.asname or a.name] = "sleep"
                elif node.module == "concurrent.futures":
                    for a in node.names:
                        if a.name == "wait":
                            bare[a.asname or a.name] = "futures_wait"
        for node in module.nodes:
            if not isinstance(node, ast.Call):
                continue
            func = node.func
            if isinstance(func, ast.Name) and func.id in bare:
                sem = bare[func.id]
                kw = keyword_arg(node, "timeout")
                if kw is not None:
                    value = numeric_literal(kw)
                elif sem == "sleep" and node.args:
                    value = numeric_literal(node.args[0])
                elif sem == "futures_wait" and len(node.args) >= 2:
                    value = numeric_literal(node.args[1])
                else:
                    value = None
                if value is not None and value >= TIMEOUT_LITERAL_FLOOR_S:
                    yield module.finding(
                        self.name, node,
                        f"literal {value:g}s timeout in {func.id}() — "
                        "name it (config knob, "
                        "multihost.beat_result_timeout_s, or a documented "
                        "module constant); inline deadlines are how the "
                        "600s silent stall shipped",
                    )
                continue
            if not isinstance(func, ast.Attribute):
                continue
            attr = func.attr
            if attr not in _BLOCKING_ATTRS:
                continue
            value: Optional[float] = None
            kw = keyword_arg(node, "timeout")
            if kw is not None:
                value = numeric_literal(kw)
            elif attr == "get":
                # queue.get's positionals are (block, timeout): the
                # deadline is args[1], and only when args[0] is a literal
                # bool — `d.get(key, default)` must never read as one.
                if len(node.args) >= 2 and \
                        isinstance(node.args[0], ast.Constant) and \
                        isinstance(node.args[0].value, bool):
                    value = numeric_literal(node.args[1])
            elif node.args:
                value = numeric_literal(node.args[0])
            if value is not None and value >= TIMEOUT_LITERAL_FLOOR_S:
                target = dotted(func) or f"<expr>.{attr}"
                yield module.finding(
                    self.name, node,
                    f"literal {value:g}s timeout in {target}() — name it "
                    "(config knob, multihost.beat_result_timeout_s, or a "
                    "documented module constant); inline deadlines are how "
                    "the 600s silent stall shipped",
                )


# ---------------------------------------------------------------------------
# 3. donation-safety
# ---------------------------------------------------------------------------


def _int_tuple_kwarg(call: ast.Call, name: str) -> Optional[Tuple[int, ...]]:
    """Literal int-tuple value of keyword `name` on `call` (scalar, tuple,
    or list literal of ints — donate_argnums/static_argnums shapes), None
    when absent or computed. Shared by the donation-safety rule and
    progrules' recompile-hazard so literal-parsing hardening (constant
    folding etc.) lands in one place."""
    kw = keyword_arg(call, name)
    if kw is None:
        return None
    if isinstance(kw, (ast.Tuple, ast.List)):
        out = []
        for el in kw.elts:
            v = numeric_literal(el)
            if v is None or int(v) != v:
                return None
            out.append(int(v))
        return tuple(out)
    v = numeric_literal(kw)
    return (int(v),) if v is not None and int(v) == v else None


def _donated_positions(call: ast.Call) -> Optional[Tuple[int, ...]]:
    """The literal donate_argnums of a jax.jit(...) call, or None."""
    return _int_tuple_kwarg(call, "donate_argnums")


def _jit_call(node: ast.AST) -> Optional[ast.Call]:
    if isinstance(node, ast.Call):
        name = dotted(node.func) or ""
        if name in ("jit", "jax.jit", "pjit", "jax.experimental.pjit.pjit"):
            return node
    return None


def _own_statements(fn: ast.AST) -> Iterable[ast.stmt]:
    """Every statement in `fn`'s own body, NOT descending into nested
    function/class definitions — a nested helper's `return jax.jit(...)`
    belongs to the helper, not to the enclosing method."""
    stack = list(getattr(fn, "body", []))
    while stack:
        stmt = stack.pop()
        yield stmt
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            continue
        for field in stmt._fields:
            val = getattr(stmt, field, None)
            if not isinstance(val, list):
                continue
            stack.extend(v for v in val if isinstance(v, ast.stmt))
            for v in val:  # except-handlers wrap their own stmt lists
                if isinstance(v, ast.excepthandler):
                    stack.extend(v.body)


class _DonationScan:
    """Per-module registry of 'known donated callsites': names (locals and
    self-attributes) bound — via plain or annotated assignment — to
    jax.jit(..., donate_argnums=...) results, including the
    `donate = partial(jax.jit, donate_argnums=...)` factory idiom AND the
    local-def factory idiom (`def _jit_chunk(fn): return jax.jit(fn,
    donate_argnums=(0, 1, 4))` — the parallel/learner.py shape whose
    multi-arg donation tuples must be tracked through the helper). Values
    map callee -> donated positional indices. Aliases of a tracked name
    (`self.f = self.g`) are NOT chased — deliberately narrow, like every
    rule here."""

    @staticmethod
    def _binding(node: ast.AST) -> Optional[Tuple[List[ast.expr], ast.expr]]:
        """(targets, value) for plain and annotated assignments."""
        if isinstance(node, ast.Assign):
            return node.targets, node.value
        if isinstance(node, ast.AnnAssign) and node.value is not None:
            return [node.target], node.value
        return None

    def __init__(self, nodes: List[ast.AST]):
        self.donated: Dict[str, Tuple[int, ...]] = {}
        factories: Dict[str, Tuple[int, ...]] = {}
        # Two passes so a factory defined after first use still resolves
        # (order in a class body is not execution order).
        for node in nodes:
            # Local-def factory: a helper whose own `return` hands back a
            # jax.jit(..., donate_argnums=...) — `_jit_per_chunk` in
            # parallel/learner.py. Calling it binds the target to the
            # FULL donated tuple (e.g. (0, 1, 4, 9)), so a later read of
            # ANY donated position is flagged, not just arg 0.
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for stmt in _own_statements(node):
                    if isinstance(stmt, ast.Return) and stmt.value is not None:
                        jc = _jit_call(stmt.value)
                        pos = _donated_positions(jc) if jc is not None else None
                        if pos:
                            factories[node.name] = pos
                continue
            bind = self._binding(node)
            if bind is None:
                continue
            targets, value = bind
            call = value if isinstance(value, ast.Call) else None
            if call is None:
                continue
            fname = dotted(call.func) or ""
            if fname in ("partial", "functools.partial") and call.args:
                inner = dotted(call.args[0]) or ""
                if inner in ("jit", "jax.jit"):
                    pos = _donated_positions(call)
                    if pos:
                        for t in targets:
                            tn = dotted(t)
                            if tn:
                                factories[tn] = pos
        for node in nodes:
            bind = self._binding(node)
            if bind is None:
                continue
            targets, bound = bind
            values = [bound]
            if isinstance(bound, ast.IfExp):
                values = [bound.body, bound.orelse]
            for value in values:
                pos: Optional[Tuple[int, ...]] = None
                jc = _jit_call(value)
                if jc is not None:
                    pos = _donated_positions(jc)
                elif isinstance(value, ast.Call):
                    fname = dotted(value.func) or ""
                    pos = factories.get(fname)
                if pos:
                    for t in targets:
                        tn = dotted(t)
                        if tn:
                            self.donated[tn] = pos


@register
class DonationSafety(Rule):
    """A buffer passed at a donated position of a jitted call is DEAD the
    moment the call dispatches — XLA owns (and will overwrite) its memory.
    Reading it afterwards without re-binding is the PR-9 TrainState
    pointer-re-swap bug class: works on CPU, corrupts silently on TPU
    where donation actually aliases. The rule tracks names bound to
    jax.jit(..., donate_argnums=...) within a module and flags any load of
    a donated argument after the call, before a re-bind. Same-statement
    re-binds (`state = step(state)`) are the sanctioned idiom and pass."""

    name = "donation-safety"
    doc = (
        "no read of a variable after it was passed at a donated position "
        "of a known donated-jit callsite, without an intervening re-bind"
    )

    def check_module(self, module: Module, ctx: LintContext) -> Iterable[Finding]:
        if module.tree is None:
            return ()
        scan = _DonationScan(module.nodes)
        if not scan.donated:
            return ()
        findings: List[Finding] = []
        for node in module.nodes:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self._scan_function(module, node, scan.donated, findings)
        return findings

    # -- statement-linear dataflow (single pass, control flow flattened:
    #    conservative about order, silent about loops re-entering — the
    #    bug class this guards is straight-line dispatch code) ----------

    def _scan_function(self, module, fn, donated, findings) -> None:
        dead: Dict[str, Tuple[str, int]] = {}  # name -> (callee, line)
        self._scan_body(module, fn.body, donated, dead, findings)

    def _scan_body(self, module, stmts, donated, dead, findings) -> None:
        for stmt in stmts:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                continue  # nested defs run later, under different state
            if isinstance(stmt, ast.Assign):
                self._scan_expr(module, stmt.value, donated, dead, findings)
                for t in stmt.targets:
                    self._clear_target(t, dead)
            elif isinstance(stmt, ast.AnnAssign):
                if stmt.value is not None:
                    self._scan_expr(module, stmt.value, donated, dead,
                                    findings)
                self._clear_target(stmt.target, dead)
            elif isinstance(stmt, ast.AugAssign):
                self._scan_expr(module, stmt.value, donated, dead, findings)
                self._scan_expr(module, stmt.target, donated, dead, findings)
                self._clear_target(stmt.target, dead)
            elif isinstance(stmt, ast.For):
                self._scan_expr(module, stmt.iter, donated, dead, findings)
                self._clear_target(stmt.target, dead)
                self._scan_body(module, stmt.body, donated, dead, findings)
                self._scan_body(module, stmt.orelse, donated, dead, findings)
            elif isinstance(stmt, ast.If):
                # Branch-aware: a branch that cannot fall through (ends in
                # return/raise/break/continue) keeps its donated-dead set
                # to itself — the guard_enabled early-return idiom must
                # not poison the straight-line path after it.
                self._scan_expr(module, stmt.test, donated, dead, findings)
                body_dead = dict(dead)
                self._scan_body(module, stmt.body, donated, body_dead,
                                findings)
                else_dead = dict(dead)
                self._scan_body(module, stmt.orelse, donated, else_dead,
                                findings)
                dead.clear()
                if not self._terminates(stmt.body):
                    dead.update(body_dead)
                if not self._terminates(stmt.orelse):
                    dead.update(else_dead)
            elif isinstance(stmt, ast.While):
                self._scan_expr(module, stmt.test, donated, dead, findings)
                self._scan_body(module, stmt.body, donated, dead, findings)
                self._scan_body(module, stmt.orelse, donated, dead, findings)
            elif isinstance(stmt, ast.With):
                for item in stmt.items:
                    self._scan_expr(module, item.context_expr, donated, dead,
                                    findings)
                    if item.optional_vars is not None:
                        self._clear_target(item.optional_vars, dead)
                self._scan_body(module, stmt.body, donated, dead, findings)
            elif isinstance(stmt, ast.Try):
                self._scan_body(module, stmt.body, donated, dead, findings)
                for h in stmt.handlers:
                    self._scan_body(module, h.body, donated, dead, findings)
                self._scan_body(module, stmt.orelse, donated, dead, findings)
                self._scan_body(module, stmt.finalbody, donated, dead,
                                findings)
            else:
                for expr in ast.iter_child_nodes(stmt):
                    if isinstance(expr, ast.expr):
                        self._scan_expr(module, expr, donated, dead, findings)

    @staticmethod
    def _terminates(stmts) -> bool:
        """True when the block cannot fall through to the next statement."""
        return bool(stmts) and isinstance(
            stmts[-1], (ast.Return, ast.Raise, ast.Break, ast.Continue)
        )

    def _scan_expr(self, module, expr, donated, dead, findings) -> None:
        if isinstance(expr, ast.Call):
            self._scan_expr(module, expr.func, donated, dead, findings)
            for a in expr.args:
                self._scan_expr(module, a, donated, dead, findings)
            for kw in expr.keywords:
                self._scan_expr(module, kw.value, donated, dead, findings)
            callee = dotted(expr.func)
            pos = donated.get(callee or "")
            if pos:
                for i in pos:
                    if i < len(expr.args):
                        argname = dotted(expr.args[i])
                        if argname:
                            dead[argname] = (callee, expr.lineno)
            return
        name = dotted(expr)
        if name is not None and isinstance(expr, (ast.Name, ast.Attribute)) \
                and isinstance(getattr(expr, "ctx", None), ast.Load):
            for key, (callee, line) in dead.items():
                if name == key or name.startswith(key + "."):
                    findings.append(module.finding(
                        self.name, expr,
                        f"`{name}` read after being passed at a donated "
                        f"position of {callee}() (line {line}) with no "
                        "re-bind — the buffer is deleted/aliased after "
                        "dispatch (the PR-9 TrainState re-swap bug class); "
                        "re-bind the result or snapshot before the call",
                    ))
                    return
        for child in ast.iter_child_nodes(expr):
            if isinstance(child, ast.expr):
                self._scan_expr(module, child, donated, dead, findings)

    def _clear_target(self, target, dead) -> None:
        if isinstance(target, (ast.Tuple, ast.List)):
            for el in target.elts:
                self._clear_target(el, dead)
            return
        if isinstance(target, ast.Starred):
            self._clear_target(target.value, dead)
            return
        name = dotted(target)
        if name:
            for key in [k for k in dead
                        if k == name or k.startswith(name + ".")]:
                del dead[key]


# ---------------------------------------------------------------------------
# 4. typed-error
# ---------------------------------------------------------------------------

_TYPED_ERROR_DIRS: Dict[str, str] = {
    "serve": "ServeOverload / ServeDispatchError / ServeTimeout",
    "transfer": "TransferError",
    "replay": "IngestError / ReplayUsageError",
    "actors": "DeviceActorError / faults.InjectedFault / ValueError",
    "parallel": "PodPeerLost / PrefetchError / PrefetchTimeout",
}


@register
class TypedErrorContract(Rule):
    """Subsystem code may not raise bare RuntimeError/Exception: every
    subsystem has a typed family that callers catch to pick a recovery
    path (degrade-to-local on ServeTimeout, clean pod abort on
    PodPeerLost, bounded restart past IngestError...). A bare
    RuntimeError is caught by nobody's recovery logic and by everybody's
    blanket handler — the worst of both."""

    name = "typed-error"
    doc = (
        "no `raise RuntimeError/Exception` inside serve/, transfer/, "
        "replay/, actors/, parallel/ — use the subsystem's typed family"
    )

    def check_module(self, module: Module, ctx: LintContext) -> Iterable[Finding]:
        if module.tree is None:
            return
        subsystem = module.rulepath.split("/", 1)[0]
        family = _TYPED_ERROR_DIRS.get(subsystem)
        if family is None or "/" not in module.rulepath:
            return
        for node in module.nodes:
            if not isinstance(node, ast.Raise) or node.exc is None:
                continue
            exc = node.exc
            name = dotted(exc.func) if isinstance(exc, ast.Call) else dotted(exc)
            if name in ("RuntimeError", "Exception"):
                yield module.finding(
                    self.name, node,
                    f"raise {name} in {subsystem}/ — use the subsystem's "
                    f"typed error family ({family}) so recovery paths can "
                    "catch it",
                )


# ---------------------------------------------------------------------------
# 5. lock-discipline
# ---------------------------------------------------------------------------

_LOCK_NAMES = ("dispatch_lock",)
# Host-side blocking waits; jax.block_until_ready is deliberately ABSENT:
# holding dispatch_lock across the device barrier IS the donation-safety
# mechanism (replay/device.py drain_pending).
_LOCK_BLOCKING_ATTRS = ("result", "wait", "join", "sleep")
_COLLECTIVE_ENTRYPOINTS = (
    "allgather_scalar", "beat_allgather", "call_with_deadline",
    "startup_barrier", "elect_resume_step", "wait_beat_ticket",
    "process_allgather", "sync_ship",
)


@register
class LockDiscipline(Rule):
    """dispatch_lock serializes device dispatch against the ingest
    shipper's donate-and-swap. Blocking on a host primitive — or worse,
    issuing a pod collective — while holding it deadlocks the trainer the
    first time the other side of the wait needs the lock (and a
    collective under the lock couples a local wedge to every peer's
    deadline). Collectives run BEFORE taking the lock (sync_ship's
    beat_allgather does exactly this)."""

    name = "lock-discipline"
    doc = (
        "no blocking wait (.result/.wait/.join/sleep/queue-shaped .get) "
        "or pod collective under dispatch_lock"
    )

    def check_module(self, module: Module, ctx: LintContext) -> Iterable[Finding]:
        if module.tree is None:
            return
        # Dedupe by location: a dispatch_lock `with` nested inside another
        # one is visited both by the outer scan's recursion and by its own
        # ast.walk hit — the same blocking call must report once.
        seen: Set[Tuple[int, int, str]] = set()
        for node in module.nodes:
            if not isinstance(node, ast.With):
                continue
            if not any(self._is_dispatch_lock(i.context_expr)
                       for i in node.items):
                continue
            for f in self._scan_block(module, node.body):
                key = (f.line, f.col, f.message)
                if key not in seen:
                    seen.add(key)
                    yield f

    def _is_dispatch_lock(self, expr: ast.expr) -> bool:
        name = dotted(expr)
        if name and any(name == n or name.endswith("." + n)
                        for n in _LOCK_NAMES):
            return True
        # The learner takes the same lock through its helper
        # (parallel/learner.py _ingest_lock(device_replay)).
        if isinstance(expr, ast.Call):
            fname = dotted(expr.func) or ""
            return fname.endswith("_ingest_lock")
        return False

    def _scan_block(self, module: Module, stmts) -> Iterable[Finding]:
        for stmt in stmts:
            yield from self._scan_node(module, stmt)

    def _scan_node(self, module: Module, node: ast.AST) -> Iterable[Finding]:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            return  # deferred execution: not under the lock
        if isinstance(node, ast.Call):
            name = dotted(node.func) or ""
            leaf = name.rsplit(".", 1)[-1]
            is_block = False
            bound: Optional[float] = None
            if isinstance(node.func, ast.Attribute):
                attr = node.func.attr
                kw = keyword_arg(node, "timeout")
                if attr in _LOCK_BLOCKING_ATTRS:
                    is_block = True
                    bound = numeric_literal(kw) if kw is not None else (
                        numeric_literal(node.args[0]) if node.args else None
                    )
                elif attr == "get":
                    # queue.get shapes only — a bare call, a literal-bool
                    # block flag, or keyword-only args. dict.get(key, ...)
                    # always passes a non-bool key first and never waits.
                    bool_flag = bool(
                        node.args
                        and isinstance(node.args[0], ast.Constant)
                        and isinstance(node.args[0].value, bool)
                    )
                    is_block = not node.args or bool_flag
                    if bool_flag and node.args[0].value is False:
                        is_block = False  # block=False: a poll
                    blk = keyword_arg(node, "block")
                    if isinstance(blk, ast.Constant) and blk.value is False:
                        is_block = False
                    if kw is not None:
                        bound = numeric_literal(kw)
                    elif bool_flag and len(node.args) >= 2:
                        bound = numeric_literal(node.args[1])
            if is_block:
                # .result(timeout=0.0) / .get(timeout=0.0) is a poll.
                if bound is None or bound != 0.0:
                    yield module.finding(
                        self.name, node,
                        f"blocking {name or leaf}() under dispatch_lock — "
                        "the shipper/learner on the other side of this "
                        "wait needs the lock; wait outside the critical "
                        "section",
                    )
            elif leaf in _COLLECTIVE_ENTRYPOINTS or \
                    name.startswith("multihost."):
                yield module.finding(
                    self.name, node,
                    f"collective {name or leaf}() under dispatch_lock "
                    "— a peer-coupled wait under a local lock wedges "
                    "the pod; gather first, then take the lock "
                    "(sync_ship's beat_allgather ordering)",
                )
        for child in ast.iter_child_nodes(node):
            yield from self._scan_node(module, child)


# ---------------------------------------------------------------------------
# 6. observability-drift
# ---------------------------------------------------------------------------

_FIELD_RE = re.compile(r"^[a-z][a-z0-9_]*_[a-z0-9_]+$")


def _doc_field_patterns(doc_text: str) -> List[re.Pattern]:
    """Compile the doc's field tokens into matchers. Tokens may use the
    doc shorthand `a_b_c/d/e` (suffix alternatives) and `<cls>` template
    segments (match any one field segment)."""
    patterns: List[re.Pattern] = []
    for token in re.findall(r"[a-z][a-z0-9_/<>]*", doc_text):
        for cand in _expand_slash(token):
            if "<" in cand:
                rx = re.escape(cand)
                # re.escape stopped escaping <> in Python 3.7: accept the
                # template marker with or without the backslashes.
                rx = re.sub(r"\\?<[a-z_]+\\?>", r"[a-z0-9_]+", rx)
                patterns.append(re.compile(rx + r"$"))
    return patterns


def _expand_slash(token: str) -> List[str]:
    """`a_b_c/d/e` → [a_b_c, a_b_d, a_b_e]: each alternative replaces the
    base's LAST segment, whatever its own segment count — the doc row
    `transfer_pool_buffers/fence_waits` covers transfer_pool_fence_waits."""
    if "/" not in token:
        return [token]
    parts = token.split("/")
    base = parts[0]
    out = [base]
    segs = base.split("_")
    for p in parts[1:]:
        if not p:
            continue
        out.append("_".join(segs[:-1] + [p]) if len(segs) > 1 else p)
    return out


def _doc_mentions(field: str, plain_tokens: Set[str],
                  patterns: List[re.Pattern]) -> bool:
    if field in plain_tokens:
        return True
    return any(p.match(field) for p in patterns)


@register
class ObservabilityDrift(Rule):
    """The metrics schema, its documentation, and its renderer must move
    together: every field family a `*Stats` class emits in metrics.py
    needs a row in docs/OBSERVABILITY.md and a renderer reference in
    tools/runs.py — an undocumented counter is write-only telemetry
    (exactly how the replay_*/pod_* families drifted before this rule).
    Folded in: every fault component registered in faults.py must appear
    in docs/RESILIENCE.md's failure matrix, so the chaos grammar and the
    recovery documentation cannot diverge."""

    name = "observability-drift"
    doc = (
        "metrics.py *Stats fields must appear in docs/OBSERVABILITY.md "
        "and tools/runs.py; faults.py components must appear in "
        "docs/RESILIENCE.md's failure matrix"
    )

    def check_project(self, ctx: LintContext) -> Iterable[Finding]:
        yield from self._check_stats_fields(ctx)
        yield from self._check_fault_components(ctx)

    # -- metrics fields ------------------------------------------------

    def _check_stats_fields(self, ctx: LintContext) -> Iterable[Finding]:
        metrics = ctx.module("metrics.py")
        if metrics is None or metrics.tree is None or ctx.docs_root is None:
            # No docs tree at all (bare file set): doc-coupled checks stay
            # silent — only a MISSING file inside an existing docs dir is
            # a finding.
            return
        doc_text = ctx.doc_text("OBSERVABILITY.md")
        runs = ctx.module("tools/runs.py")
        if doc_text is None:
            yield Finding(
                rule=self.name, path=metrics.relpath, line=1, col=0,
                message="docs/OBSERVABILITY.md not found next to the "
                        "package — the JSONL schema has no documentation "
                        "to check against",
            )
            return
        plain_tokens = {
            t for tok in re.findall(r"[a-z][a-z0-9_/<>]*", doc_text)
            for t in _expand_slash(tok) if "<" not in t
        }
        patterns = _doc_field_patterns(doc_text)
        runs_text = runs.text if runs is not None else ""

        for cls in metrics.tree.body:
            if not isinstance(cls, ast.ClassDef) or \
                    not cls.name.endswith("Stats"):
                continue
            fields = self._snapshot_fields(cls)
            families: Set[str] = set()
            for field, node in fields:
                families.add(field.split("_", 1)[0] + "_")
                if not _doc_mentions(field, plain_tokens, patterns):
                    # exact: the snapshot dict is ONE simple statement —
                    # statement-span suppression matching would let a
                    # single per-field escape cover every sibling field's
                    # future drift. The comment must sit on the key's line.
                    yield metrics.finding(
                        self.name, node,
                        f"{cls.name} emits `{field}` but "
                        "docs/OBSERVABILITY.md has no row for it — "
                        "document the field (or its `<cls>` template) in "
                        "the JSONL schema table",
                        exact=True,
                    )
            for fam in sorted(families):
                if runs_text and fam not in runs_text:
                    # Anchored to the class HEADER line only (not the
                    # ClassDef's full span): a field-level suppression
                    # inside the body must never mask this class-level
                    # finding via span matching.
                    yield Finding(
                        rule=self.name, path=metrics.relpath,
                        line=cls.lineno, col=cls.col_offset,
                        message=(
                            f"{cls.name}'s `{fam}*` family has no renderer "
                            "reference in tools/runs.py — summarize/compare "
                            "would silently drop the whole family"
                        ),
                    )

    def _snapshot_fields(self, cls: ast.ClassDef) -> List[Tuple[str, ast.AST]]:
        """Literal string keys of dicts built inside the class's
        snapshot() method — the emitted JSONL field names. f-string keys
        (per-class templates) are covered by the doc's `<cls>` rows and
        skipped here."""
        out: List[Tuple[str, ast.AST]] = []
        for item in cls.body:
            if isinstance(item, ast.FunctionDef) and item.name == "snapshot":
                for node in ast.walk(item):
                    if isinstance(node, ast.Dict):
                        for k in node.keys:
                            if isinstance(k, ast.Constant) and \
                                    isinstance(k.value, str) and \
                                    _FIELD_RE.match(k.value):
                                out.append((k.value, k))
                    elif isinstance(node, ast.Subscript) and \
                            isinstance(node.ctx, ast.Store) and \
                            isinstance(node.slice, ast.Constant) and \
                            isinstance(node.slice.value, str) and \
                            _FIELD_RE.match(node.slice.value):
                        out.append((node.slice.value, node))
        return out

    # -- fault components ----------------------------------------------

    def _check_fault_components(self, ctx: LintContext) -> Iterable[Finding]:
        faults = ctx.module("faults.py")
        if faults is None or faults.tree is None or ctx.docs_root is None:
            return
        doc_text = ctx.doc_text("RESILIENCE.md")
        components: List[Tuple[str, ast.AST]] = []
        for node in faults.tree.body:
            if isinstance(node, ast.Assign) and any(
                isinstance(t, ast.Name) and t.id == "COMPONENTS"
                for t in node.targets
            ):
                if isinstance(node.value, (ast.Tuple, ast.List)):
                    for el in node.value.elts:
                        if isinstance(el, ast.Constant) and \
                                isinstance(el.value, str):
                            components.append((el.value, el))
        if not components:
            return
        if doc_text is None:
            yield Finding(
                rule=self.name, path=faults.relpath, line=1, col=0,
                message="docs/RESILIENCE.md not found — the fault grammar "
                        "has no failure matrix to check against",
            )
            return
        # The matrix section: from its heading to the next same-level one.
        m = re.search(r"^## Failure matrix.*?(?=^## )", doc_text,
                      re.MULTILINE | re.DOTALL)
        matrix = m.group(0) if m else doc_text
        for comp, node in components:
            if not re.search(rf"\b{re.escape(comp)}\s*:", matrix):
                # exact, like the snapshot-field findings: COMPONENTS is
                # one tuple statement — a suppression on one entry's line
                # must not cover its siblings.
                yield faults.finding(
                    self.name, node,
                    f"fault component `{comp}` (faults.py COMPONENTS) has "
                    "no `"
                    f"{comp}:...` spec row in docs/RESILIENCE.md's "
                    "failure matrix — every injectable fault needs its "
                    "detection/recovery/artifact row",
                    exact=True,
                )


# ---------------------------------------------------------------------------
# 8. exit-code-literal
# ---------------------------------------------------------------------------

# The typed codes (exits.py). Untyped statuses (sys.exit(1), argparse's
# 2) are not the contract's business and stay unflagged.
_TYPED_EXIT_CODES = frozenset({70, 75, 76, 77, 78, 79})
_EXITS_MODULE = "exits.py"
_EXIT_CALL_LEAVES = ("exit", "_exit", "SystemExit")


@register
class ExitCodeLiteral(Rule):
    """The typed exit codes (70/75/76/77/78/79) are a cross-process
    CONTRACT: train, the watchdog, the chaos children, and the pod
    supervisor all key recovery decisions off them (docs/RESILIENCE.md
    exit-code matrix). Before exits.py they lived as scattered literals
    — and one drifted copy turns a shrink-ready exit (relaunch smaller,
    adopt the slices) into an unknown crash (relaunch blindly). Every
    typed exit must go through the distributed_ddpg_tpu.exits constants;
    only exits.py itself may spell the numbers."""

    name = "exit-code-literal"
    doc = (
        "no bare typed exit-code literal (70/75/76/77/78/79) in "
        "sys.exit/os._exit/SystemExit or EXIT_*-named assignments "
        "outside exits.py — import distributed_ddpg_tpu.exits"
    )

    def check_module(self, module: Module, ctx: LintContext) -> Iterable[Finding]:
        if module.tree is None or module.rulepath == _EXITS_MODULE:
            return
        for node in module.nodes:
            if isinstance(node, ast.Call):
                name = dotted(node.func) or ""
                leaf = name.rsplit(".", 1)[-1]
                if leaf not in _EXIT_CALL_LEAVES or not node.args:
                    continue
                val = node.args[0]
                if (
                    isinstance(val, ast.Constant)
                    and isinstance(val.value, int)
                    and not isinstance(val.value, bool)
                    and val.value in _TYPED_EXIT_CODES
                ):
                    yield module.finding(
                        self.name, node,
                        f"bare typed exit code {val.value} in "
                        f"{name or leaf}() — import the named constant "
                        "from distributed_ddpg_tpu.exits "
                        "(docs/RESILIENCE.md exit-code matrix)",
                    )
            elif isinstance(node, ast.Assign):
                val = node.value
                if not (
                    isinstance(val, ast.Constant)
                    and isinstance(val.value, int)
                    and not isinstance(val.value, bool)
                    and val.value in _TYPED_EXIT_CODES
                ):
                    continue
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name) and "EXIT" in tgt.id.upper():
                        yield module.finding(
                            self.name, node,
                            f"local exit-code constant {tgt.id} = "
                            f"{val.value} shadows the one-place contract "
                            "— import it from distributed_ddpg_tpu.exits",
                        )
