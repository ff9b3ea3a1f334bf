"""atomic-write: durable writes route through ``file_io.write_atomic``.

Every persistence claim in the tree (checkpoint bundles, blockstore
manifests, AOT exports, flight bundles) rests on the
temp-sibling + fsync + ``os.replace`` discipline in
``lightgbm_tpu/utils/file_io.write_atomic`` — a reader never observes a
truncated file.  A raw ``open(path, "w")`` silently opts out of that
contract, so this rule flags every builtin ``open`` (and seam-routed
``open_file``) call whose mode writes (``w``/``a``/``x``, text or
binary) anywhere in the scanned tree.

Both seam spellings pass: ``write_atomic(path, data)`` for in-memory
payloads and the streaming ``with open_atomic(path, mode):`` for
payloads too large to assemble (binary caches, per-row output).
Genuinely non-durable writes (tmp probe output, lock sentinels) are
allowlisted per line with a justification::

    with open(tmp, "w") as f:  # tpulint: disable=atomic-write — tmp probe

``utils/file_io.py`` itself is exempt: it IS the seam.
"""

from __future__ import annotations

import ast
from typing import List, Optional

from .core import Project, Rule, Violation, dotted_name, str_const

_EXEMPT_RELS = ("lightgbm_tpu/utils/file_io.py",)
_OPENERS = {"open", "open_file", "io.open"}


def _write_mode(call: ast.Call) -> Optional[str]:
    """The mode string when this open()-style call writes, else None."""
    mode = None
    if len(call.args) >= 2:
        mode = str_const(call.args[1])
    for kw in call.keywords:
        if kw.arg == "mode":
            mode = str_const(kw.value)
    if mode and any(c in mode for c in "wax"):
        return mode
    return None


class AtomicWriteRule(Rule):
    name = "atomic-write"
    doc = ("raw open(..., 'w'/'a'/'x') writes must route through "
           "utils.file_io.write_atomic (pragma-allowlist non-durable "
           "tmp output with a justification)")

    def check(self, project: Project) -> List[Violation]:
        out: List[Violation] = []
        for f in project.files:
            if f.rel in _EXEMPT_RELS:
                continue
            for node in ast.walk(f.tree):
                if not isinstance(node, ast.Call):
                    continue
                callee = dotted_name(node.func)
                if callee not in _OPENERS:
                    continue
                mode = _write_mode(node)
                if mode is None:
                    continue
                if "a" in mode and not any(c in mode for c in "wx"):
                    # appends have no atomic equivalent (the seam is
                    # whole-file replace); the remedy differs
                    out.append(Violation(
                        self.name, f.rel, node.lineno,
                        f"append-mode {callee}(..., {mode!r}) cannot "
                        "ride the atomic seam; restructure to "
                        "whole-file rewrites through write_atomic/"
                        "open_atomic, or pragma with a justification "
                        "if the log is genuinely non-durable"))
                    continue
                out.append(Violation(
                    self.name, f.rel, node.lineno,
                    f"raw {callee}(..., {mode!r}) write bypasses the "
                    "utils.file_io atomic seam (write_atomic for "
                    "in-memory payloads, open_atomic to stream); a "
                    "crash here can leave a truncated file behind"))
        return out
