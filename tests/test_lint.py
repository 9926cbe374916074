"""Invariant lint engine tests (distributed_ddpg_tpu/analysis/;
docs/ANALYSIS.md): known-good/known-bad fixture pairs per rule under
tests/lint_fixtures/, the suppression grammar, the JSON output schema,
the CLI exit-code contract, the gate scripts — and the self-run pinning
the live tree clean, fast (<5 s), and jax-free.

Everything here is tier-1: pure-stdlib engine, no backend, no device.
"""

import json
import subprocess
import sys
import time
from collections import Counter
from pathlib import Path

import pytest

from distributed_ddpg_tpu.analysis import RULES, run_lint
from distributed_ddpg_tpu.analysis.engine import (
    BAD_SUPPRESSION,
    PARSE_ERROR,
    UNUSED_SUPPRESSION,
    render_human,
    write_json,
)
from distributed_ddpg_tpu.tools import lint as lint_cli
from distributed_ddpg_tpu.tools import runs as runs_cli

TESTS = Path(__file__).resolve().parent
REPO = TESTS.parent
PKG = REPO / "distributed_ddpg_tpu"
FIX = TESTS / "lint_fixtures"

EXPECTED_RULES = {
    "collective-discipline",
    "timeout-discipline",
    "donation-safety",
    "typed-error",
    "lock-discipline",
    "observability-drift",
    "recompile-hazard",
    "exit-code-literal",
}


def lint_tree(name, **kw):
    root = FIX / name
    docs = root / "docs"
    return run_lint(root, docs_root=docs if docs.is_dir() else None, **kw)


# ---------------------------------------------------------------------------
# registry + fixture trees
# ---------------------------------------------------------------------------


def test_rule_registry_has_the_contract_rules():
    names = {r.name for r in RULES}
    assert EXPECTED_RULES <= names
    # Unique names: the suppression grammar and --rules filter key on them.
    assert len([r.name for r in RULES]) == len(names)
    assert all(r.doc for r in RULES)


def test_clean_tree_is_silent():
    result = lint_tree("clean")
    assert result.findings == []
    assert result.files >= 10


def test_dirty_tree_fires_every_rule_with_expected_counts():
    result = lint_tree("dirty")
    counts = Counter(f.rule for f in result.findings)
    assert counts == {
        "collective-discipline": 6,
        "timeout-discipline": 7,
        "donation-safety": 3,
        "typed-error": 2,
        "lock-discipline": 4,
        "observability-drift": 3,
        "recompile-hazard": 5,
        "exit-code-literal": 3,
    }
    # Nothing in the dirty tree is suppressed — every finding gates.
    assert len(result.unsuppressed) == len(result.findings) == 33


def test_dirty_tree_known_bad_locations():
    by_rule = {}
    for f in lint_tree("dirty").findings:
        by_rule.setdefault(f.rule, []).append(f)
    # donation-safety names the dead variable and the donating callee.
    msgs = [f.message for f in by_rule["donation-safety"]]
    assert any("`state`" in m and "step()" in m for m in msgs)
    assert any("`batch`" in m and "apply_batch()" in m for m in msgs)
    # The local-def factory idiom tracks the FULL multi-arg donate tuple:
    # reading position 4 (not just arg 0) after dispatch is flagged.
    assert any("`priorities`" in m and "chunk_step()" in m for m in msgs)
    # recompile-hazard covers all five jit-key hazard shapes.
    prog_msgs = [f.message for f in by_rule["recompile-hazard"]]
    assert any("loop body" in m and "`k`" in m for m in prog_msgs)
    assert any("@jax.jit on a def inside a loop body" in m for m in prog_msgs)
    assert any("one expression" in m for m in prog_msgs)
    assert any("static position 1" in m for m in prog_msgs)
    assert any("traced body of lax.fori_loop" in m for m in prog_msgs)
    # timeout-discipline reports the literal it saw.
    assert any("600s" in f.message for f in by_rule["timeout-discipline"])
    # observability-drift covers both metric drift and fault-grammar drift.
    paths = {f.path for f in by_rule["observability-drift"]}
    assert paths == {"metrics.py", "faults.py"}
    assert any("ghost" in f.message for f in by_rule["observability-drift"])
    # lock-discipline: the lambda body itself is never the finding — only
    # the sibling wait AFTER the deferred callback (bad_after_deferred).
    lock_lines = {f.line for f in by_rule["lock-discipline"]
                  if f.path == "serve/locks.py"}
    assert len(lock_lines) == 4
    # ...and the blocking queue.get is among them, by name.
    assert any("q.get()" in f.message for f in by_rule["lock-discipline"])
    # exit-code-literal: both the call form and the shadowing assignment.
    exit_msgs = [f.message for f in by_rule["exit-code-literal"]]
    assert any("78" in m and "_exit()" in m for m in exit_msgs)
    assert any("_EXIT_CODE" in m and "70" in m for m in exit_msgs)
    assert {f.path for f in by_rule["exit-code-literal"]} == {"runner.py"}


def test_doc_coupled_checks_silent_without_a_docs_tree(tmp_path):
    # Bare file set, no docs dir: doc-coupled rules stay silent — but an
    # existing docs dir MISSING a file is a finding.
    (tmp_path / "metrics.py").write_text(
        "class FooStats:\n"
        "    def snapshot(self):\n"
        "        return {\"foo_thing\": 1}\n"
    )
    (tmp_path / "faults.py").write_text('COMPONENTS = ("worker",)\n')
    assert run_lint(tmp_path, docs_root=None).findings == []
    docs = tmp_path / "docs"
    docs.mkdir()
    missing = run_lint(tmp_path, docs_root=docs).unsuppressed
    assert missing and all("not found" in f.message for f in missing)


def test_expand_slash_replaces_only_the_last_segment():
    from distributed_ddpg_tpu.analysis.rules import _expand_slash

    assert _expand_slash("transfer_pool_buffers/fence_waits") == [
        "transfer_pool_buffers", "transfer_pool_fence_waits",
    ]
    assert _expand_slash("replay_exchange_ms_p50/p95") == [
        "replay_exchange_ms_p50", "replay_exchange_ms_p95",
    ]


def test_rules_filter_scopes_the_run():
    result = lint_tree("dirty", rule_names=["timeout-discipline"])
    assert {f.rule for f in result.findings} == {"timeout-discipline"}
    assert len(result.findings) == 7
    assert result.rules == ["timeout-discipline"]


# ---------------------------------------------------------------------------
# suppression grammar
# ---------------------------------------------------------------------------


def test_reasoned_suppressions_suppress_inline_and_comment_only():
    result = run_lint(FIX / "suppress", paths=[FIX / "suppress" / "ok.py"])
    assert result.unsuppressed == []
    suppressed = [f for f in result.findings if f.suppressed]
    assert len(suppressed) == 2  # inline + comment-only coverage
    assert all(f.suppression_reason.startswith("fixture") for f in suppressed)


def test_reasonless_suppression_keeps_the_finding_and_is_reported():
    result = run_lint(FIX / "suppress", paths=[FIX / "suppress" / "bad.py"])
    rules = [f.rule for f in result.unsuppressed]
    assert "timeout-discipline" in rules  # the finding stays live
    assert BAD_SUPPRESSION in rules       # and the bad escape is its own
    assert UNUSED_SUPPRESSION not in rules


def test_unused_suppression_is_reported():
    result = run_lint(FIX / "suppress", paths=[FIX / "suppress" / "unused.py"])
    assert [f.rule for f in result.unsuppressed] == [UNUSED_SUPPRESSION]


def test_grammar_inside_a_docstring_is_not_a_suppression():
    result = run_lint(
        FIX / "suppress", paths=[FIX / "suppress" / "docstring.py"]
    )
    rules = [f.rule for f in result.unsuppressed]
    assert rules == ["timeout-discipline"]  # live — and no unused-suppression


def test_rules_subset_does_not_report_foreign_suppressions():
    # Under a --rules subset, suppressions of inactive rules cannot be
    # proven stale — only a full-registry run may call them unused.
    result = run_lint(
        FIX / "suppress", paths=[FIX / "suppress" / "ok.py"],
        rule_names=["lock-discipline"],
    )
    assert result.findings == []


def test_suppression_of_unknown_rule_is_reported(tmp_path):
    src = tmp_path / "typo.py"
    src.write_text("X = 1  # lint: ok(donation-safty): typo'd rule name\n")
    result = run_lint(tmp_path, paths=[src])
    assert [f.rule for f in result.unsuppressed] == [BAD_SUPPRESSION]
    assert "unknown rule" in result.unsuppressed[0].message


def test_malformed_suppression_is_reported(tmp_path):
    src = tmp_path / "malformed.py"
    src.write_text(
        "import time\n\n\n"
        "def f():\n"
        "    time.sleep(5)  # lint: ok(timeout-discipline) forgot colon\n"
    )
    result = run_lint(tmp_path, paths=[src])
    rules = sorted(f.rule for f in result.unsuppressed)
    assert rules == [BAD_SUPPRESSION, "timeout-discipline"]
    assert any("malformed" in f.message for f in result.unsuppressed)


def test_suppression_matches_anywhere_in_the_statement_span(tmp_path):
    # A multi-line call's only room for the comment may be its closing
    # line; the finding anchors to the call's FIRST line but the span
    # covers the whole statement.
    src = tmp_path / "span.py"
    src.write_text(
        "import time\n\n\n"
        "def f():\n"
        "    time.sleep(\n"
        "        5,\n"
        "    )  # lint: ok(timeout-discipline): fixture reason\n"
    )
    result = run_lint(tmp_path, paths=[src])
    assert result.unsuppressed == []
    assert [f.suppressed for f in result.findings] == [True]
    assert result.findings[0].line == 5
    assert result.findings[0].end_line == 7


def test_suppression_covers_expression_anchored_finding_in_statement(tmp_path):
    # donation-safety anchors to the READ expression, which may sit lines
    # above the only place with room for the comment (the closing paren).
    # The suppression span is the whole enclosing simple statement — and
    # a covered finding must not double-report as unused-suppression.
    src = tmp_path / "donate.py"
    src.write_text(
        "import jax\n\n"
        "step = jax.jit(lambda s: s, donate_argnums=(0,))\n\n\n"
        "def run(state, combine):\n"
        "    out = step(state)\n"
        "    r = combine(\n"
        "        state,\n"
        "    )  # lint: ok(donation-safety): fixture reason\n"
        "    return r, out\n"
    )
    result = run_lint(tmp_path, paths=[src])
    assert result.unsuppressed == [], "\n".join(
        f.render() for f in result.unsuppressed
    )
    assert [f.rule for f in result.findings] == ["donation-safety"]
    assert result.findings[0].suppressed


def test_field_suppression_does_not_cover_sibling_fields(tmp_path):
    # A *Stats snapshot dict is ONE simple statement; if suppressions
    # matched the statement span, one per-field escape would silently
    # cover every sibling field's future drift. Field findings are exact:
    # the comment suppresses its own line's key only.
    (tmp_path / "docs").mkdir()
    (tmp_path / "docs" / "OBSERVABILITY.md").write_text(
        "| foo | `foo_documented` | docs |\n"
    )
    (tmp_path / "tools").mkdir()
    (tmp_path / "tools" / "runs.py").write_text("FAMILIES = ['foo_']\n")
    (tmp_path / "metrics.py").write_text(
        "class FooStats:\n"
        "    def snapshot(self):\n"
        "        return {\n"
        "            'foo_documented': 1,\n"
        "            'foo_undoc_a': 2,  "
        "# lint: ok(observability-drift): fixture reason\n"
        "            'foo_undoc_b': 3,\n"
        "        }\n"
    )
    result = run_lint(tmp_path, docs_root=tmp_path / "docs")
    live = [f for f in result.findings if not f.suppressed]
    assert [f.rule for f in live] == ["observability-drift"]
    assert "foo_undoc_b" in live[0].message
    sup = [f for f in result.findings if f.suppressed]
    assert len(sup) == 1 and "foo_undoc_a" in sup[0].message


def test_directory_scans_skip_test_trees(tmp_path):
    # The rules enforce NON-test hot-path discipline: linting a repo root
    # must not drown in test-code waits or the deliberately dirty fixture
    # trees. An explicitly named test file still lints.
    (tmp_path / "tests").mkdir()
    bad = "import time\n\n\ndef f():\n    time.sleep(600)\n"
    (tmp_path / "tests" / "test_waits.py").write_text(bad)
    (tmp_path / "tests" / "conftest.py").write_text(bad)
    (tmp_path / "mod.py").write_text("X = 1\n")
    result = run_lint(tmp_path)
    assert result.files == 1
    assert result.findings == []
    explicit = run_lint(
        tmp_path, paths=[tmp_path / "tests" / "test_waits.py"]
    )
    assert [f.rule for f in explicit.findings] == ["timeout-discipline"]


def test_nested_dispatch_lock_reports_each_violation_once(tmp_path):
    src = tmp_path / "nested.py"
    src.write_text(
        "def f(a, b):\n"
        "    with a.dispatch_lock:\n"
        "        with b.dispatch_lock:\n"
        "            b.q.get()\n"
    )
    result = run_lint(tmp_path, paths=[src])
    assert len(result.findings) == 1
    assert result.findings[0].rule == "lock-discipline"


def test_donation_safety_tracks_annotated_assignments(tmp_path):
    src = tmp_path / "ann.py"
    src.write_text(
        "import jax\n"
        "from typing import Callable\n\n\n"
        "class L:\n"
        "    def setup(self):\n"
        "        self.step: Callable = jax.jit(_step, donate_argnums=(0,))\n\n"
        "    def run(self, state):\n"
        "        out = self.step(state)\n"
        "        return state.params\n"
    )
    result = run_lint(tmp_path, paths=[src])
    assert [f.rule for f in result.findings] == ["donation-safety"]
    assert "`state.params`" in result.findings[0].message


def test_field_suppression_does_not_mask_class_level_renderer_drift(tmp_path):
    # The family-renderer finding anchors to the class HEADER line, so a
    # reasoned field-level suppression inside the body cannot swallow it
    # via statement-span matching.
    (tmp_path / "metrics.py").write_text(
        "class FooStats:\n"
        "    def snapshot(self):\n"
        "        return {\n"
        '            "foo_thing": 1,'
        "  # lint: ok(observability-drift): fixture reason\n"
        "        }\n"
    )
    docs = tmp_path / "docs"
    docs.mkdir()
    (docs / "OBSERVABILITY.md").write_text("no rows\n")
    (docs / "RESILIENCE.md").write_text("## Failure matrix\n")
    (tmp_path / "tools").mkdir()
    (tmp_path / "tools" / "runs.py").write_text("# renders nothing\n")
    result = run_lint(tmp_path, docs_root=docs)
    live = [f.message for f in result.unsuppressed]
    assert any("no renderer reference" in m for m in live), live


def test_one_comment_may_cover_several_rules(tmp_path):
    src = tmp_path / "multi.py"
    src.write_text(
        "import time\n\n\n"
        "def f(t):\n"
        "    time.sleep(5)  "
        "# lint: ok(timeout-discipline, lock-discipline): fixture reason\n"
    )
    result = run_lint(tmp_path, paths=[src])
    assert result.unsuppressed == []  # suppressed, and no unused report
    assert [f.suppressed for f in result.findings] == [True]


# ---------------------------------------------------------------------------
# engine mechanics: parse errors, JSON schema, human rendering
# ---------------------------------------------------------------------------


def test_parse_error_is_a_finding_not_a_crash(tmp_path):
    bad = tmp_path / "broken.py"
    bad.write_text("def f(:\n")
    result = run_lint(tmp_path, paths=[bad])
    assert [f.rule for f in result.findings] == [PARSE_ERROR]
    assert result.unsuppressed  # a non-parsing file gates


def test_json_schema(tmp_path):
    result = lint_tree("dirty")
    out = tmp_path / "findings.json"
    write_json(result, out)
    obj = json.loads(out.read_text())
    assert obj["version"] == 1
    assert set(obj["counts"]) == {"files", "findings", "suppressed"}
    assert obj["counts"]["findings"] == 33
    assert obj["counts"]["suppressed"] == 0
    assert sorted(obj["rules"]) == sorted(r.name for r in RULES)
    assert isinstance(obj["elapsed_s"], float)
    for f in obj["findings"]:
        assert set(f) == {"rule", "path", "line", "col", "end_line",
                          "message", "suppressed", "suppression_reason"}
        assert "/" not in f["path"] or "\\" not in f["path"]


def test_human_rendering_has_locations_and_summary():
    result = lint_tree("dirty")
    text = render_human(result)
    assert "transfer/waits.py:" in text
    assert text.splitlines()[-1].endswith("s")  # "... in N.NNs" summary


# ---------------------------------------------------------------------------
# CLI exit-code contract
# ---------------------------------------------------------------------------


def test_cli_exit_0_on_clean_tree(capsys):
    rc = lint_cli.main([
        str(FIX / "clean"), "--root", str(FIX / "clean"),
        "--docs", str(FIX / "clean" / "docs"), "--quiet",
    ])
    assert rc == 0
    assert "0 findings" in capsys.readouterr().out


def test_cli_exit_2_on_dirty_tree(capsys):
    rc = lint_cli.main([
        str(FIX / "dirty"), "--root", str(FIX / "dirty"),
        "--docs", str(FIX / "dirty" / "docs"),
    ])
    assert rc == 2
    err = capsys.readouterr().err
    assert "lint: FAIL" in err


def test_cli_usage_errors_exit_1(capsys, tmp_path):
    assert lint_cli.main(["--rules", "no-such-rule"]) == 1
    assert lint_cli.main([str(FIX / "does-not-exist")]) == 1
    # A path matching no .py files must error, not pass as a clean run.
    (tmp_path / "README.md").write_text("no python here\n")
    assert lint_cli.main([str(tmp_path), "--root", str(tmp_path)]) == 1
    assert "no Python files" in capsys.readouterr().err


def test_cli_subpath_target_keeps_package_anchoring(capsys):
    # Linting one file inside the package must anchor rule path-scoping
    # to the PACKAGE root: parallel/multihost.py stays the exempt module,
    # not a freshly-rooted "multihost.py" full of collective findings.
    rc = lint_cli.main([str(PKG / "parallel" / "multihost.py"), "--quiet"])
    assert rc == 0, capsys.readouterr().out


def test_cli_repo_anchored_root_keeps_rule_scoping(capsys):
    # --root <repo> makes every relpath start with distributed_ddpg_tpu/;
    # rulepath strips the package prefix so the multihost exemption,
    # typed-error subsystem scoping, and metrics.py lookups still hold.
    rc = lint_cli.main([
        "--root", str(REPO), "--docs", str(REPO / "docs"),
        str(PKG), "--quiet",
    ])
    assert rc == 0, capsys.readouterr().out


def test_cli_path_outside_root_is_a_usage_error(tmp_path, capsys):
    stray = tmp_path / "stray.py"
    stray.write_text("X = 1\n")
    rc = lint_cli.main([str(stray), "--root", str(PKG)])
    assert rc == 1
    assert "outside the lint root" in capsys.readouterr().err


def test_cli_list_rules(capsys):
    assert lint_cli.main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for name in EXPECTED_RULES:
        assert name in out


# ---------------------------------------------------------------------------
# tools.runs lint subcommand (the CI-box digest renderer)
# ---------------------------------------------------------------------------


def test_runs_lint_renders_fail_digest(tmp_path, capsys):
    out = tmp_path / "findings.json"
    write_json(lint_tree("dirty"), out)
    rc = runs_cli.main(["lint", str(out)])
    assert rc == 2
    text = capsys.readouterr().out
    assert "LINT FAIL" in text
    assert "timeout-discipline" in text
    assert "transfer/waits.py:" in text


def test_runs_lint_renders_pass_digest(tmp_path, capsys):
    out = tmp_path / "findings.json"
    write_json(lint_tree("clean"), out)
    rc = runs_cli.main(["lint", str(out)])
    assert rc == 0
    assert "LINT PASS" in capsys.readouterr().out


def test_runs_lint_missing_file_exits_1(tmp_path, capsys):
    assert runs_cli.main(["lint", str(tmp_path / "nope.json")]) == 1


def test_runs_lint_non_object_json_exits_1(tmp_path, capsys):
    trunc = tmp_path / "trunc.json"
    trunc.write_text("[]\n")
    assert runs_cli.main(["lint", str(trunc)]) == 1
    assert "not a findings object" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# self-run: the shipped tree is clean, fast, and jax-free
# ---------------------------------------------------------------------------


def test_self_run_live_tree_is_clean_and_fast():
    # CPU time, not wall clock: the <5s budget is about the engine's own
    # cost, and the CI box's documented contention (CHANGES.md PR 9:
    # ~60% wall slowdowns under load) must not turn tier-1 red on it.
    t0 = time.process_time()
    result = run_lint(PKG, docs_root=REPO / "docs")
    elapsed = time.process_time() - t0
    assert result.unsuppressed == [], "\n".join(
        f.render() for f in result.unsuppressed
    )
    # Suppressions in the live tree must all carry reasons (engine enforces)
    # and there are known, documented ones — not zero, not an explosion.
    assert 0 < sum(f.suppressed for f in result.findings) < 20
    assert elapsed < 5.0, f"lint took {elapsed:.1f}s (budget 5s)"


def test_cli_never_imports_jax():
    # A clean interpreter (not this conftest-jax'd one): the engine must
    # lint the fixture trees without jax ever landing in sys.modules.
    code = (
        "import sys\n"
        "from distributed_ddpg_tpu.tools import lint\n"
        f"rc = lint.main([{str(FIX / 'clean')!r}, '--root', "
        f"{str(FIX / 'clean')!r}, '--docs', "
        f"{str(FIX / 'clean' / 'docs')!r}, '--quiet'])\n"
        "assert rc == 0, rc\n"
        "assert 'jax' not in sys.modules, 'lint imported jax'\n"
    )
    subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, check=True, timeout=60,
    )


# ---------------------------------------------------------------------------
# gate scripts
# ---------------------------------------------------------------------------


def test_lint_gate_script_passes_fixture_tree(tmp_path):
    proc = subprocess.run(
        ["bash", str(REPO / "scripts" / "lint_gate.sh"), "--quiet",
         "--root", str(FIX / "clean"), "--docs",
         str(FIX / "clean" / "docs"), str(FIX / "clean")],
        env={"PATH": "/usr/bin:/bin:/usr/local/bin",
             "LINT_JSON": str(tmp_path / "findings.json")},
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr
    assert (tmp_path / "findings.json").is_file()


def test_lint_gate_script_fails_on_findings(tmp_path):
    proc = subprocess.run(
        ["bash", str(REPO / "scripts" / "lint_gate.sh"), "--quiet",
         "--root", str(FIX / "dirty"), "--docs",
         str(FIX / "dirty" / "docs"), str(FIX / "dirty")],
        env={"PATH": "/usr/bin:/bin:/usr/local/bin",
             "LINT_JSON": str(tmp_path / "findings.json")},
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 2, (proc.stdout, proc.stderr)
    assert "tools.runs lint" in proc.stderr  # points at the digest renderer


def test_lint_gate_script_skips_without_analysis_package(tmp_path):
    # Old baselines predate the linter: the gate must SKIP, not fail.
    scripts = tmp_path / "scripts"
    scripts.mkdir()
    gate = scripts / "lint_gate.sh"
    gate.write_text((REPO / "scripts" / "lint_gate.sh").read_text())
    proc = subprocess.run(
        ["bash", str(gate)],
        env={"PATH": "/usr/bin:/bin:/usr/local/bin"},
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 0
    assert "SKIP" in proc.stderr


# ---------------------------------------------------------------------------
# --changed-only (the sub-second pre-commit mode; docs/ANALYSIS.md)
# ---------------------------------------------------------------------------


def _git(repo, *args):
    subprocess.run(
        ["git", "-C", str(repo), "-c", "user.name=t", "-c",
         "user.email=t@t", *args],
        check=True, capture_output=True, timeout=30,
    )


@pytest.fixture()
def lint_repo(tmp_path):
    """A tiny git repo: one clean file, one file carrying the 5 known
    recompile-hazard findings — both committed, so HEAD is the baseline."""
    repo = (tmp_path / "repo").resolve()
    (repo / "replay").mkdir(parents=True)
    (repo / "replay" / "donate.py").write_text(
        (FIX / "clean" / "replay" / "donate.py").read_text(),
        encoding="utf-8",
    )
    (repo / "progs.py").write_text(
        (FIX / "dirty" / "progs.py").read_text(), encoding="utf-8"
    )
    _git(repo, "init", "-q")
    _git(repo, "add", "-A")
    _git(repo, "commit", "-q", "-m", "seed")
    return repo


def test_changed_only_nothing_changed(lint_repo, capsys):
    rc = lint_cli.main(["--changed-only", "HEAD", "--root", str(lint_repo)])
    assert rc == 0
    assert "nothing to lint" in capsys.readouterr().out


def test_changed_only_scopes_to_the_diff(lint_repo, capsys):
    # progs.py carries 5 recompile-hazard findings, but only the CLEAN
    # file changed: the scoped run must not see them.
    donate = lint_repo / "replay" / "donate.py"
    donate.write_text(donate.read_text() + "\n# touched\n",
                      encoding="utf-8")
    rc = lint_cli.main(["--changed-only", "HEAD", "--root", str(lint_repo)])
    assert rc == 0
    capsys.readouterr()
    # Once the dirty file changes too, its findings gate the scoped run.
    progs = lint_repo / "progs.py"
    progs.write_text(progs.read_text() + "\n# touched\n", encoding="utf-8")
    rc = lint_cli.main(["--changed-only", "HEAD", "--root", str(lint_repo)])
    assert rc == 2
    assert "recompile-hazard" in capsys.readouterr().out


def test_changed_only_sees_untracked_files(lint_repo):
    # A new file must lint BEFORE its first commit.
    (lint_repo / "replay" / "fresh.py").write_text(
        (FIX / "dirty" / "progs.py").read_text(), encoding="utf-8"
    )
    rc = lint_cli.main(["--changed-only", "HEAD", "--root", str(lint_repo)])
    assert rc == 2


def test_changed_only_bad_ref_errors(lint_repo, capsys):
    rc = lint_cli.main(
        ["--changed-only", "no-such-ref", "--root", str(lint_repo)]
    )
    assert rc == 1
    assert "--changed-only" in capsys.readouterr().err


def test_recompile_hazard_nested_loop_reports_once(tmp_path):
    # ast.walk scans the inner loop once per ancestor loop; the hazard
    # must still report once, keeping the richer (captured-loop-var)
    # message.
    (tmp_path / "nested.py").write_text(
        "import jax\n\n\n"
        "def f(xs):\n"
        "    for i in range(2):\n"
        "        for k in range(3):\n"
        "            g = jax.jit(lambda x: x * k)\n"
        "            xs = g(xs)\n"
        "    return xs\n",
        encoding="utf-8",
    )
    result = run_lint(tmp_path, rule_names=["recompile-hazard"])
    msgs = [f.message for f in result.findings]
    assert len(msgs) == 1
    assert "captures loop variable `k`" in msgs[0]


def test_recompile_hazard_skips_deferred_builders(tmp_path):
    # A def (or lambda) inside a loop DEFERS execution — the
    # ProgramSpec-builder idiom must not gate; and partial(jax.jit, ...)
    # invoked inline only BUILDS the wrapper (the sanctioned bind-once
    # factory), it traces nothing.
    (tmp_path / "deferred.py").write_text(
        "import jax\n"
        "from functools import partial\n\n\n"
        "def make_specs(fns):\n"
        "    specs = []\n"
        "    for fn in fns:\n"
        "        def build(fn=fn):\n"
        "            return jax.jit(fn)\n"
        "        specs.append(build)\n"
        "        deferred = lambda: jax.jit(fn)\n"
        "        specs.append(deferred)\n"
        "    return specs\n\n\n"
        "class Holder:\n"
        "    def __init__(self, step):\n"
        "        self.step = partial(jax.jit, donate_argnums=(0,))(step)\n",
        encoding="utf-8",
    )
    result = run_lint(tmp_path, rule_names=["recompile-hazard"])
    assert [f.message for f in result.findings] == []


def test_changed_only_intersects_explicit_paths(lint_repo, capsys):
    # Explicit path args compose as a FILTER within the changed set: a
    # pre-commit hook scoped to one subsystem must not fail on unrelated
    # changed files elsewhere in the tree.
    for name in ("replay/donate.py", "progs.py"):
        p = lint_repo / name
        p.write_text(p.read_text() + "\n# touched\n", encoding="utf-8")
    rc = lint_cli.main(
        ["--changed-only", "HEAD", "--root", str(lint_repo),
         str(lint_repo / "replay")]
    )
    assert rc == 0  # the dirty progs.py changed too, but is out of scope
    capsys.readouterr()
    rc = lint_cli.main(
        ["--changed-only", "HEAD", "--root", str(lint_repo),
         str(lint_repo / "progs.py")]
    )
    assert rc == 2
    assert "recompile-hazard" in capsys.readouterr().out


def test_changed_only_explicit_scope_nothing_changed(lint_repo, capsys):
    # Only the out-of-scope file changed: the scoped run lints nothing
    # and says so (exit 0), instead of failing on the unrelated change.
    progs = lint_repo / "progs.py"
    progs.write_text(progs.read_text() + "\n# touched\n", encoding="utf-8")
    rc = lint_cli.main(
        ["--changed-only", "HEAD", "--root", str(lint_repo),
         str(lint_repo / "replay")]
    )
    assert rc == 0
    assert "nothing to lint" in capsys.readouterr().out


def test_git_changed_files_diff_relative_config(lint_repo):
    # Under `git config diff.relative true`, `git diff --name-only` from
    # a subdir prints SUBDIR-relative paths: the diff must run at the
    # toplevel so joining against it stays correct — a mis-join here
    # silently lints nothing and reads as green.
    from distributed_ddpg_tpu.analysis.engine import git_changed_files

    _git(lint_repo, "config", "diff.relative", "true")
    donate = lint_repo / "replay" / "donate.py"
    donate.write_text(donate.read_text() + "\n# touched\n", encoding="utf-8")
    changed = git_changed_files(lint_repo / "replay", "HEAD")
    assert changed == [str(donate)]


def test_git_changed_files_untracked_from_subdir(lint_repo):
    # `git ls-files --others` prints cwd-relative paths: untracked files
    # must still resolve when the lint root sits DEEPER than the git
    # toplevel (the default package-root invocation).
    from distributed_ddpg_tpu.analysis.engine import git_changed_files

    fresh = lint_repo / "replay" / "fresh.py"
    fresh.write_text("x = 1\n", encoding="utf-8")
    changed = git_changed_files(lint_repo / "replay", "HEAD")
    assert changed == [str(fresh)]
