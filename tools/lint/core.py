"""tpulint core: file model, pragma handling, rule registry, runner.

tpulint is the project-native static-analysis suite: ~6 AST checkers
enforcing the invariants the codebase bets on but no generic linter
knows about (env-flag registry, atomic-write discipline, traced-code
purity, MXU parity conventions, lock discipline, docs/metrics sync).
``tools/lint.py`` is the CLI; ``tests/test_lint.py`` runs the suite over
the real tree in tier-1 so every PR is linted by default.

Suppression pragmas (docs/LINTING.md):

- ``# tpulint: disable=<rule>[,<rule>...]`` trailing on a line silences
  those rules for violations REPORTED on that line (``all`` silences
  every rule).  Allowlisting a real violation should come with a short
  justification in the same comment.
- ``# tpulint: disable-file=<rule>[,...]`` anywhere in a file silences
  the rules for the whole file.
"""

from __future__ import annotations

import ast
import dataclasses
import os
import re
from typing import Dict, List, Optional, Sequence, Set

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

_PRAGMA_RE = re.compile(
    r"#\s*tpulint:\s*(disable|disable-file)\s*=\s*([A-Za-z0-9_,\- ]+)")


@dataclasses.dataclass(frozen=True)
class Violation:
    """One finding: rule id, repo-relative path, 1-based line, text."""

    rule: str
    path: str
    line: int
    message: str

    def render(self) -> str:
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}"


class SourceFile:
    """One parsed Python file plus its pragma tables."""

    def __init__(self, path: str, rel: str, text: str):
        self.path = path
        self.rel = rel
        self.text = text
        self.lines = text.splitlines()
        self.tree = ast.parse(text, filename=rel)
        self.line_disables: Dict[int, Set[str]] = {}
        self.file_disables: Set[str] = set()
        for i, line in enumerate(self.lines, start=1):
            m = _PRAGMA_RE.search(line)
            if not m:
                continue
            rules = {r.strip() for r in m.group(2).split(",") if r.strip()}
            if m.group(1) == "disable-file":
                self.file_disables |= rules
            else:
                self.line_disables.setdefault(i, set()).update(rules)

    def suppressed(self, v: Violation) -> bool:
        for s in (self.file_disables,
                  self.line_disables.get(v.line, ())):
            if v.rule in s or "all" in s:
                return True
        return False


class Project:
    """The scanned file set plus repo-level context for repo rules."""

    def __init__(self, files: Sequence[SourceFile], root: str = REPO,
                 full_tree: bool = False):
        self.files = list(files)
        self.root = root
        # full_tree: the default whole-repo scan — repo-level checks that
        # need the complete picture (stale registry entries, the
        # Parameters.rst sync) only run here, never on a path subset
        self.full_tree = full_tree
        self._by_rel = {f.rel: f for f in self.files}

    def file(self, rel: str) -> Optional[SourceFile]:
        return self._by_rel.get(rel)

    def read_doc(self, rel: str) -> str:
        try:
            with open(os.path.join(self.root, rel)) as fh:
                return fh.read()
        except OSError:
            return ""


class Rule:
    """One checker.  Subclasses set ``name``/``doc`` and implement
    ``check(project) -> [Violation]`` (pragma filtering happens in the
    runner, not in rules)."""

    name: str = ""
    doc: str = ""

    def check(self, project: Project) -> List[Violation]:
        raise NotImplementedError


# ------------------------------------------------------------ file walking

# the default scan set: the library, the operator tools and the graft
# entry; tests/ seed env vars and raw writes on purpose and are
# excluded (pass paths explicitly to lint them)
DEFAULT_ROOTS = ("lightgbm_tpu", "tools", "__graft_entry__.py")
_SKIP_DIRS = {"__pycache__", ".git", ".pytest_cache", "build", "dist"}


def iter_py_files(root: str, paths: Optional[Sequence[str]] = None):
    """Yield absolute paths of .py files under ``paths`` (default:
    DEFAULT_ROOTS) relative to ``root``."""
    rels = list(paths) if paths else list(DEFAULT_ROOTS)
    for rel in rels:
        p = rel if os.path.isabs(rel) else os.path.join(root, rel)
        if not os.path.exists(p):
            # a typo'd path must NOT come back "0 files clean, exit 0"
            raise OSError(f"no such path: {rel}")
        if os.path.isfile(p):
            if not p.endswith(".py"):
                raise OSError(f"not a Python file: {rel}")
            yield p
            continue
        for dirpath, dirnames, filenames in os.walk(p):
            dirnames[:] = sorted(d for d in dirnames
                                 if d not in _SKIP_DIRS)
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    yield os.path.join(dirpath, fn)


def load_project(root: str = REPO,
                 paths: Optional[Sequence[str]] = None) -> Project:
    files = []
    for p in iter_py_files(root, paths):
        rel = os.path.relpath(p, root)
        with open(p, encoding="utf-8") as fh:
            text = fh.read()
        files.append(SourceFile(p, rel, text))
    return Project(files, root=root, full_tree=not paths)


# ------------------------------------------------------------------ runner

def all_rules() -> List[Rule]:
    from . import rules_docs, rules_env, rules_locks  # noqa: PLC0415
    from . import rules_parity, rules_traced, rules_write
    return [rules_env.EnvFlagRegistryRule(),
            rules_write.AtomicWriteRule(),
            rules_traced.TracedPurityRule(),
            rules_parity.ParityHazardRule(),
            rules_locks.LockDisciplineRule(),
            rules_docs.DocsSyncRule()]


def select_rules(only: Optional[Sequence[str]] = None,
                 ignore: Optional[Sequence[str]] = None) -> List[Rule]:
    rules = all_rules()
    known = {r.name for r in rules}
    for sel in list(only or []) + list(ignore or []):
        if sel not in known:
            raise ValueError(
                f"unknown rule {sel!r}; known: {', '.join(sorted(known))}")
    if only:
        rules = [r for r in rules if r.name in set(only)]
    if ignore:
        rules = [r for r in rules if r.name not in set(ignore)]
    return rules


def run_lint(project: Project,
             rules: Optional[Sequence[Rule]] = None) -> List[Violation]:
    """Run ``rules`` (default: all) over ``project``; returns pragma-
    filtered violations sorted by (path, line, rule)."""
    out: Set[Violation] = set()
    for rule in (rules if rules is not None else all_rules()):
        for v in rule.check(project):
            f = project.file(v.path)
            if f is not None and f.suppressed(v):
                continue
            out.add(v)
    return sorted(out, key=lambda v: (v.path, v.line, v.rule, v.message))


# --------------------------------------------------------------- AST utils

def dotted_name(node: ast.AST) -> Optional[str]:
    """'os.environ.get' for Attribute/Name chains, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def str_const(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


def module_str_constants(tree: ast.Module) -> Dict[str, str]:
    """Module-level ``NAME = "literal"`` assignments — lets checkers
    resolve ``os.environ.get(_TRACE_ENV)`` through the constant."""
    out: Dict[str, str] = {}
    for node in tree.body:
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name):
            s = str_const(node.value)
            if s is not None:
                out[node.targets[0].id] = s
    return out
