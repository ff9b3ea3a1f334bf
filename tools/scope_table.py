#!/usr/bin/env python3
"""Device time of a traced training window by the round program's named
scopes (``lgbm.gradients`` ... ``lgbm.score_update``; a dotted scope such
as ``lgbm.rank.pairs`` is one name).

    python3 tools/scope_table.py <trace.xplane.pb> <hlo.txt> [<hlo.txt> ...]

The trace is one kept from a ``--trace 1`` run of the benchmark
(``BENCH_KEEP_TRACE=<dir>``); the HLO texts are the optimized modules of
the same run (``XLA_FLAGS="--xla_dump_to=<dir> --xla_dump_hlo_as_text"``
on a run that compiles, or ``compiled.as_text()``).  A device event is an
executed HLO instruction; its scope is the innermost ``lgbm.*`` component
of that instruction's ``op_name`` in the HLO text, joined by instruction
name.  Own times (an op's duration less its direct children's) are summed
inside the harness's ``bench.*`` annotations, as
``benchmark/lib/trace_reduce.py`` sums them, so the rows other than the
Pallas kernels add up to ``grower_xla_ms_per_tree`` x trees.  Prints one
JSON object.  This is the by-hand recipe the benchmark's reducer needs to
read scopes itself (PERF.md section 7).
"""
import json
import re
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmark.lib import trace_reduce as tr  # noqa: E402

INSTRUCTION = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s.*\bop_name="([^"]*)"')
SCOPE = re.compile(r"lgbm\.[a-z_]+(?:\.[a-z_]+)*")


def scopes_by_instruction(texts):
    """{instruction name: innermost lgbm scope or None} over HLO texts.
    A name defined in two modules with different scopes maps to None."""
    out = {}
    for text in texts:
        for line in text.splitlines():
            m = INSTRUCTION.match(line)
            if not m:
                continue
            found = SCOPE.findall(m.group(2))
            scope = found[-1] if found else None
            name = m.group(1)
            if name in out and out[name] != scope:
                scope = None
            out[name] = scope
    return out


def split(xplane, texts):
    scope_of = scopes_by_instruction(texts)
    planes = tr.read_planes(xplane)
    spans = [(s, s + d) for pname, lines in planes.items()
             if not tr.DEVICE_PLANE.match(pname)
             for events in lines.values() for n, s, d in events
             if n.startswith(tr.ANNOTATION_PREFIX)]
    lo, hi = min(s for s, _ in spans), max(e for _, e in spans)
    dev = sorted(p for p in planes if tr.DEVICE_PLANE.match(p))[0]
    events = [(n, s, d) for n, s, d in planes[dev].get(tr.OPS_LINE, [])
              if s + d > lo and s < hi]
    by_scope, kernels, ops = {}, 0.0, {}
    for name, own in tr.self_times(events):
        if tr.KERNEL_NAME.search(name):
            kernels += own
            continue
        scope = scope_of.get(name.split("%", 1)[-1]) or "unscoped"
        by_scope[scope] = by_scope.get(scope, 0.0) + own
        per = ops.setdefault(scope, {})
        per[name] = per.get(name, 0.0) + own
    order = sorted(by_scope, key=lambda k: -by_scope[k])
    return {"window_s": (hi - lo) / 1e9,
            "kernel_s": kernels / 1e9,
            "xla_s": sum(by_scope.values()) / 1e9,
            "by_scope_s": {k: by_scope[k] / 1e9 for k in order},
            "top_ops_s": {k: [[n, v / 1e9] for n, v in sorted(
                ops[k].items(), key=lambda kv: -kv[1])[:5]] for k in order}}


if __name__ == "__main__":
    if len(sys.argv) < 3:
        sys.exit(__doc__)
    print(json.dumps(split(sys.argv[1],
                           [Path(p).read_text() for p in sys.argv[2:]]),
                     indent=1))
