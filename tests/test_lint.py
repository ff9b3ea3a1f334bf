"""tpulint (tools/lint.py, docs/LINTING.md) — tier-1 enforcement.

The clean-tree test IS the enforcement point: every future PR runs the
whole static-analysis suite by default.  The fixture corpus
(tests/lint_fixtures/) proves each rule actually fires, line-exact, and
that pragmas/selectors/JSON output behave.
"""

import json
import os
import re
import subprocess
import sys

import pytest

pytestmark = pytest.mark.lint

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "lint_fixtures")
RULES = {"env-flag-registry", "atomic-write", "traced-purity",
         "parity-hazard", "lock-discipline", "docs-sync"}

if REPO not in sys.path:
    sys.path.insert(0, REPO)


_CLI_CACHE = {}


def run_cli(*args):
    """One subprocess per distinct arg vector (the CLI is pure over an
    unchanged tree; several tests share the two canonical runs)."""
    if args in _CLI_CACHE:
        return _CLI_CACHE[args]
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "lint.py"), *args],
        capture_output=True, text=True, cwd=REPO)
    verdict = None
    lines = r.stdout.strip().splitlines()
    if lines:
        try:
            verdict = json.loads(lines[-1])
        except ValueError:
            verdict = None
    _CLI_CACHE[args] = (r, verdict)
    return r, verdict


# ------------------------------------------------------------ the real tree

def test_repo_tree_is_clean():
    """THE gate: the shipped tree has zero violations and exits 0."""
    r, verdict = run_cli()
    assert r.returncode == 0, r.stdout + r.stderr
    assert verdict is not None and verdict["ok"] is True
    assert verdict["violations"] == 0
    assert set(verdict["rules"]) == RULES


# -------------------------------------------------------------- the corpus

def seeded_lines():
    """rule -> {(rel_path, line)} from the '# SEED <rule>' markers."""
    out = {}
    for dirpath, _dirs, files in os.walk(FIXTURES):
        for fn in sorted(files):
            if not fn.endswith(".py"):
                continue
            p = os.path.join(dirpath, fn)
            rel = os.path.relpath(p, REPO)
            for i, line in enumerate(open(p), start=1):
                m = re.search(r"#\s*SEED\s+([a-z\-]+)", line)
                if m:
                    out.setdefault(m.group(1), set()).add((rel, i))
    return out


def test_fixture_corpus_every_rule_fires_line_exact():
    """Exit 1 on the corpus; every rule fires by name on EXACTLY the
    seeded (file, line) set — no misses, no false positives."""
    r, verdict = run_cli("tests/lint_fixtures")
    assert r.returncode == 1, r.stdout + r.stderr
    assert verdict["ok"] is False
    assert set(verdict["by_rule"]) == RULES

    reported = {}
    for line in r.stdout.splitlines():
        m = re.match(r"(\S+?):(\d+): \[([a-z\-]+)\]", line)
        if m:
            reported.setdefault(m.group(3), set()).add(
                (m.group(1), int(m.group(2))))
    seeds = seeded_lines()
    assert set(seeds) == RULES, "corpus must seed every rule"
    for rule in RULES:
        assert reported.get(rule) == seeds[rule], (
            f"{rule}: reported {sorted(reported.get(rule, ()))} != "
            f"seeded {sorted(seeds[rule])}")


def test_pragmas_silence_violations():
    """pragma_ok.py re-seeds env/write/traced violations behind line and
    file pragmas and must come back clean."""
    r, verdict = run_cli("tests/lint_fixtures/pragma_ok.py")
    assert r.returncode == 0, r.stdout + r.stderr
    assert verdict["violations"] == 0


def test_only_and_ignore_selectors():
    r, verdict = run_cli("tests/lint_fixtures", "--only", "atomic-write")
    assert r.returncode == 1
    assert set(verdict["by_rule"]) == {"atomic-write"}
    assert verdict["rules"] == ["atomic-write"]

    r2, verdict2 = run_cli("tests/lint_fixtures",
                           "--ignore", "atomic-write,traced-purity")
    assert r2.returncode == 1
    assert "atomic-write" not in verdict2["by_rule"]
    assert "traced-purity" not in verdict2["by_rule"]
    assert verdict2["by_rule"]  # others still fire


def test_unknown_rule_selector_exits_2():
    r, _ = run_cli("--only", "no-such-rule")
    assert r.returncode == 2
    assert "no-such-rule" in r.stderr


def test_missing_path_exits_2():
    """A typo'd path must NOT come back '0 files clean, exit 0'."""
    r, _ = run_cli("lightgbm_tpu/no_such_dir")
    assert r.returncode == 2
    assert "no_such_dir" in r.stderr
    r2, _ = run_cli("README.md")        # exists, but not lintable
    assert r2.returncode == 2


def test_unparseable_file_exits_2(tmp_path):
    """Null bytes / broken syntax are unusable input (exit 2 with a
    message), never a silent traceback or a fake 'violations' run."""
    bad = tmp_path / "bad.py"
    bad.write_bytes(b"x = 1\x00\n")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "lint.py"),
         str(bad)], capture_output=True, text=True, cwd=REPO)
    assert r.returncode == 2, r.stdout + r.stderr
    assert "cannot load tree" in r.stderr


def test_traced_rule_covers_kwonly_and_posonly_params(tmp_path):
    """static_argnums maps over posonly+positional order; kw-only params
    are traced unless named in static_argnames."""
    fixture = tmp_path / "kern.py"
    fixture.write_text(
        "import jax\n"
        "import functools\n"
        "@functools.partial(jax.jit, static_argnums=(0,))\n"
        "def k(cfg, /, x, *, scale):\n"
        "    a = float(x)\n"          # traced -> flagged
        "    b = float(scale)\n"      # kw-only traced -> flagged
        "    if cfg:\n"               # static_argnums=(0,) -> cfg static
        "        a = a + 1\n"
        "    return a + b\n")
    from tools.lint import Project, SourceFile, run_lint, select_rules
    sf = SourceFile(str(fixture), "kern.py", fixture.read_text())
    vs = run_lint(Project([sf], root=REPO),
                  select_rules(only=["traced-purity"]))
    lines = sorted(v.line for v in vs)
    assert lines == [5, 6], [v.render() for v in vs]


def test_json_verdict_schema():
    """The last stdout line is machine-readable with the documented
    keys/types (CI parses this)."""
    for args in ((), ("tests/lint_fixtures",)):
        r, verdict = run_cli(*args)
        assert verdict is not None, r.stdout
        assert verdict["tool"] == "tpulint"
        assert isinstance(verdict["files"], int) and verdict["files"] > 0
        assert isinstance(verdict["rules"], list)
        assert isinstance(verdict["violations"], int)
        assert isinstance(verdict["by_rule"], dict)
        assert isinstance(verdict["ok"], bool)
        assert verdict["ok"] == (verdict["violations"] == 0)
        assert sum(verdict["by_rule"].values()) == verdict["violations"]


# ------------------------------------------------------- checker unit tests

def lint_paths(*paths, only=None):
    from tools.lint import load_project, run_lint, select_rules
    project = load_project(root=REPO, paths=list(paths))
    return run_lint(project, select_rules(only=only))


def test_lock_rule_negative_class_is_clean():
    """DisciplinedQueue (annotation + Condition alias + guarded-by-caller
    helper) must produce no lock-discipline findings."""
    vs = [v for v in lint_paths("tests/lint_fixtures/bad_locks.py",
                                only=["lock-discipline"])
          if "DisciplinedQueue" in v.message]
    assert vs == []


def test_traced_rule_static_and_partial_params_exempt():
    """static_argnames and functools.partial-bound params may drive
    Python branches; only genuinely traced params are flagged."""
    vs = lint_paths("tests/lint_fixtures/bad_traced.py",
                    only=["traced-purity"])
    assert not any(v.line > 30 for v in vs), \
        [v.render() for v in vs]  # build_partial/static_ok stay clean


def test_env_registry_is_complete_and_documented():
    """Programmatic twin of the clean-tree run: every registered flag
    carries a default+consumer+doc and its docfile mentions it."""
    from lightgbm_tpu.utils import envflags
    assert len(envflags.FLAGS) >= 38
    for flag in envflags.all_flags():
        assert flag.doc and flag.consumer and flag.docfile, flag.name
        doc = open(os.path.join(REPO, flag.docfile)).read()
        assert flag.name in doc, \
            f"{flag.name} missing from {flag.docfile}"
    # registry-backed accessor honors env + default
    assert envflags.get("LGBM_TPU_FUSED") == "1"
    with pytest.raises(KeyError):
        envflags.get("LGBM_TPU_NOT_A_FLAG_EVER")


def test_run_lint_api_agrees_with_cli():
    """The Python API (``load_project`` + ``run_lint``) holds the project
    clean and agrees with the two cached CLI runs on the dirty corpus."""
    from tools.lint import load_project, run_lint
    project = load_project(root=REPO)
    violations = run_lint(project)
    assert violations == []
    # dirty-tree path (CLI agreement already asserted above)
    _r, verdict = run_cli("tests/lint_fixtures")
    assert verdict["violations"] > 0


def test_gen_parameters_doc_shim_unchanged():
    """The standalone entrypoint still honors --check (exit 0, current)
    after the fold-in; the docs-sync rule shares its implementation."""
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools",
                                      "gen_parameters_doc.py"), "--check"],
        capture_output=True, text=True)
    assert r.returncode == 0, r.stderr + r.stdout
    from tools.lint import params_doc
    code, messages = params_doc.check()
    assert code == 0 and any("current" in m for m in messages)
