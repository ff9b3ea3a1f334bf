"""tpulint CLI — run the project-native static-analysis suite.

Usage (``python tools/lint.py`` and ``python -m tools.lint`` are
equivalent)::

    python tools/lint.py                     # lint the default tree
    python tools/lint.py lightgbm_tpu/ops    # lint a path subset
    python tools/lint.py --only atomic-write,env-flag-registry
    python tools/lint.py --ignore lock-discipline
    python tools/lint.py --list-rules

Output: one human line per violation (``path:line: [rule] message``),
then a LAST-LINE JSON verdict (the same contract tools/obs_doctor.py
follows)::

    {"tool": "tpulint", "files": N, "violations": M,
     "by_rule": {"atomic-write": 2, ...}, "ok": false}

Exit codes: 0 clean, 1 violations found, 2 unusable input (unknown
rule selector, missing path, unparseable file).  Rules, pragmas and the
how-to-add-a-checker recipe: docs/LINTING.md.
"""

from __future__ import annotations

import argparse
import json
import sys

from .core import REPO, all_rules, load_project, run_lint, select_rules


def _csv(value):
    return [s.strip() for s in value.split(",") if s.strip()]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="tpulint", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("paths", nargs="*",
                    help="files/dirs to lint (default: the repo tree)")
    ap.add_argument("--only", type=_csv, default=None,
                    help="comma-separated rule names to run exclusively")
    ap.add_argument("--ignore", type=_csv, default=None,
                    help="comma-separated rule names to skip")
    ap.add_argument("--root", default=REPO,
                    help="repo root for relative paths and docs lookups")
    ap.add_argument("--list-rules", action="store_true",
                    help="print rule names + one-line docs and exit")
    args = ap.parse_args(argv)

    if args.list_rules:
        for r in all_rules():
            print(f"{r.name}: {r.doc}")
        return 0

    try:
        rules = select_rules(only=args.only, ignore=args.ignore)
    except ValueError as e:
        print(str(e), file=sys.stderr)
        return 2
    try:
        project = load_project(root=args.root,
                               paths=args.paths or None)
    # ValueError: null bytes in source (ast.parse); UnicodeDecodeError:
    # non-UTF-8 file — both are unusable input, not "violations found"
    except (OSError, SyntaxError, ValueError, UnicodeDecodeError) as e:
        print(f"cannot load tree: {e}", file=sys.stderr)
        return 2

    violations = run_lint(project, rules)
    for v in violations:
        print(v.render())
    by_rule = {}
    for v in violations:
        by_rule[v.rule] = by_rule.get(v.rule, 0) + 1
    ok = not violations
    if ok:
        print(f"tpulint: {len(project.files)} files clean "
              f"({len(rules)} rules)")
    else:
        print(f"tpulint: {len(violations)} violation(s) in "
              f"{len(set(v.path for v in violations))} file(s)")
    print(json.dumps({"tool": "tpulint", "files": len(project.files),
                      "rules": sorted(r.name for r in rules),
                      "violations": len(violations),
                      "by_rule": dict(sorted(by_rule.items())),
                      "ok": ok}))
    return 0 if ok else 1
