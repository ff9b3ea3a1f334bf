"""env-flag-registry: every env gate must be declared and documented.

Three failure modes, each named after the offending flag:

1. a string literal matching the flag grammar
   (``LGBM_TPU_*`` / ``LIGHTGBM_TPU_*`` / ``LGBT_*``)
   appears in scanned code but not in
   ``lightgbm_tpu/utils/envflags.FLAGS`` — an unregistered knob;
2. a registered flag's name is absent from its declared doc file — an
   undocumented knob;
3. (full-tree scans only) a registered flag appears nowhere in the
   scanned code — a stale registry entry.

Scanning LITERALS rather than only ``os.environ`` call expressions is
deliberate: it also catches flags routed through helper wrappers
(``_env_float("LGBM_TPU_ICI_GBPS")``), ladder dicts
(``{"LGBM_TPU_PACK": ...}``) and ``os.environ.update`` payloads —
anywhere a knob name is spelled, it must be a registered knob.
"""

from __future__ import annotations

import ast
import importlib.util
import os
import re
import sys
from typing import Dict, List, Tuple

from .core import Project, Rule, Violation

_FLAG_RE = re.compile(
    r"^(LGBM_TPU_|LIGHTGBM_TPU_|LGBT_)[A-Z0-9_]+$")

# the registry itself spells every name; the lint package spells the
# prefixes and fixture names in rule docs/tests
_EXEMPT_RELS = ("lightgbm_tpu/utils/envflags.py",)
_EXEMPT_PREFIXES = ("tools/lint/", "tools/lint.py")


def load_registry(root: str) -> Dict[str, object]:
    """Load ``root``'s envflags registry BY PATH — never through the
    import cache, so linting another checkout (or a fixture tree) reads
    that tree's registry, not whichever one this process imported
    first.  envflags.py is stdlib-only with no package-relative imports
    by contract, which is what makes standalone execution safe."""
    path = os.path.join(root, "lightgbm_tpu", "utils", "envflags.py")
    if not os.path.exists(path):
        raise ImportError(f"no envflags registry at {path}")
    spec = importlib.util.spec_from_file_location("_tpulint_envflags",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    # dataclasses resolves the class namespace through sys.modules at
    # definition time; a later load of a different root overwrites the
    # slot, which is exactly the per-root freshness we want
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return dict(mod.FLAGS)


class EnvFlagRegistryRule(Rule):
    name = "env-flag-registry"
    doc = ("every LGBM_TPU_*/LIGHTGBM_TPU_*/LGBT_* literal must be "
           "registered in lightgbm_tpu/utils/envflags.py and documented "
           "in its declared doc file")

    def check(self, project: Project) -> List[Violation]:
        try:
            flags = load_registry(project.root)
        except ImportError:
            # scanning a tree without the registry module: every
            # matching literal is by definition unregistered
            flags = {}
        out: List[Violation] = []
        seen: Dict[str, List[Tuple[str, int]]] = {}
        for f in project.files:
            if f.rel in _EXEMPT_RELS or \
                    f.rel.startswith(_EXEMPT_PREFIXES):
                continue
            for node in ast.walk(f.tree):
                if not (isinstance(node, ast.Constant)
                        and isinstance(node.value, str)):
                    continue
                name = node.value
                if not _FLAG_RE.match(name):
                    continue
                seen.setdefault(name, []).append((f.rel, node.lineno))
                if name not in flags:
                    out.append(Violation(
                        self.name, f.rel, node.lineno,
                        f"env flag {name} is not registered in "
                        "lightgbm_tpu/utils/envflags.py (add an EnvFlag "
                        "entry with default, consumer and doc anchor)"))
        # registered but undocumented / stale.  Word-boundary match: a
        # short flag must not pass because a longer flag it prefixes
        # (LGBM_TPU_STREAM vs LGBM_TPU_STREAM_BLOCK_ROWS) is documented
        reg_file = "lightgbm_tpu/utils/envflags.py"
        doc_cache: Dict[str, str] = {}
        for name, flag in sorted(flags.items()):
            docfile = flag.docfile
            if docfile not in doc_cache:
                doc_cache[docfile] = project.read_doc(docfile)
            if not re.search(r"(?<![A-Z0-9_])" + re.escape(name)
                             + r"(?![A-Z0-9_])", doc_cache[docfile]):
                out.append(Violation(
                    self.name, reg_file, 1,
                    f"env flag {name} is registered but undocumented: "
                    f"its name does not appear in {docfile}"))
            if project.full_tree and name not in seen:
                out.append(Violation(
                    self.name, reg_file, 1,
                    f"env flag {name} is registered but read nowhere in "
                    "the tree — delete the stale entry"))
        return out
