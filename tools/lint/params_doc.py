"""docs/Parameters.rst generation + staleness check.

Moved here from ``tools/gen_parameters_doc.py`` (now a thin shim) so the
tpulint ``docs-sync`` rule and the standalone CLI share ONE
implementation.  reference: helpers/parameter_generator.py generates
config_auto.cpp AND docs/Parameters.rst from structured comments in
config.h; here the source of truth is the ``Config`` dataclass and
``_ALIASES`` dict in ``lightgbm_tpu/config.py``.
"""

from __future__ import annotations

import dataclasses
import io
import os
import re
import sys
from typing import List, Optional, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
OUT = os.path.join(REPO, "docs", "Parameters.rst")


# one sentence under a field's line, where the type and default alone
# would mislead
NOTES = {
    "tpu_round_width": "a cap: the rounds grower chooses each round's "
                       "width under it, from what its last rounds committed",
}


def _config(root: str = REPO):
    if root not in sys.path:
        sys.path.insert(0, root)
    from lightgbm_tpu.config import _ALIASES, Config  # noqa: PLC0415
    return Config, _ALIASES


def _sections(root: str = REPO):
    """(field name -> section title) from the explicit ``# section:
    <name>`` sentinels that structure the dataclass body — explicit, so
    an ordinary short comment can never silently spawn a garbage doc
    section."""
    src = open(os.path.join(root, "lightgbm_tpu", "config.py")).read()
    body = src.split("class Config:", 1)[1]
    section = "Core Parameters"
    out = {}
    for line in body.splitlines():
        m = re.match(r"\s*#\s*section:\s*(.+?)\s*$", line)
        if m:
            section = m.group(1).strip().title() + " Parameters"
            continue
        f = re.match(r"\s{4}(\w+)\s*:\s*\w", line)
        if f:
            out[f.group(1)] = section
    return out


def generate(root: str = REPO) -> str:
    Config, _ALIASES = _config(root)
    fields = dataclasses.fields(Config)
    sec_of = _sections(root)
    aliases_of = {}
    for alias, canon in _ALIASES.items():
        if alias != canon:
            aliases_of.setdefault(canon, []).append(alias)

    buf = io.StringIO()
    w = buf.write
    w("Parameters\n==========\n\n")
    w("Generated from ``lightgbm_tpu/config.py`` by "
      "``tools/gen_parameters_doc.py`` — do not edit by hand.\n"
      "The reference analogue is ``docs/Parameters.rst`` generated from "
      "``config.h`` by ``helpers/parameter_generator.py``.\n\n")
    current = None
    for f in fields:
        sec = sec_of.get(f.name, "Other Parameters")
        if sec != current:
            w(f"\n{sec}\n{'-' * len(sec)}\n\n")
            current = sec
        default = f.default
        if default is dataclasses.MISSING:
            default = (f.default_factory()
                       if f.default_factory is not dataclasses.MISSING
                       else "")
        typename = getattr(f.type, "__name__", str(f.type))
        w(f"- ``{f.name}``: {typename}, default ``{default!r}``")
        al = aliases_of.get(f.name)
        if al:
            w(f", aliases: {', '.join('``%s``' % a for a in sorted(al))}")
        w("\n")
        if f.name in NOTES:
            w(f"\n  {NOTES[f.name]}\n\n")
    return buf.getvalue()


def check(out_path: Optional[str] = None,
          root: str = REPO) -> Tuple[int, List[str]]:
    """(exit code, messages) for the staleness check — 0 current, 1
    stale.  Missing Config fields are named FIRST: "stale" alone sends
    people diffing; a field added without regenerating should fail by
    name."""
    if out_path is None:
        out_path = os.path.join(root, "docs", "Parameters.rst")
    Config, _ = _config(root)
    text = generate(root)
    on_disk = open(out_path).read() if os.path.exists(out_path) else ""
    missing = [f.name for f in dataclasses.fields(Config)
               if f"``{f.name}``" not in on_disk]
    if missing:
        return 1, [f"{out_path} is missing Config fields: "
                   f"{', '.join(missing)}; regenerate with "
                   "python tools/gen_parameters_doc.py"]
    if on_disk != text:
        return 1, [f"{out_path} is stale: regenerate with "
                   "python tools/gen_parameters_doc.py"]
    return 0, [f"{out_path} is current"]
