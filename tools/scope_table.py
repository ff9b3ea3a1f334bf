#!/usr/bin/env python3
"""A kept training trace split two ways: device time by the round program's
named scopes, or the device's idle time by what the host was doing.

    python3 tools/scope_table.py <trace.xplane.pb> <hlo.txt> [<hlo.txt> ...]
    python3 tools/scope_table.py --idle <trace.xplane.pb[.gz]>

The trace is one kept from a ``--trace 1`` run of the benchmark
(``BENCH_KEEP_TRACE=<dir>``), or any ``jax.profiler`` session around
``lgb.train``.

Scopes (``lgbm.gradients`` ... ``lgbm.score_update``; a dotted scope such
as ``lgbm.rank.pairs`` is one name): the HLO texts are the optimized
modules of the same run (``XLA_FLAGS="--xla_dump_to=<dir>
--xla_dump_hlo_as_text"`` on a run that compiles, or
``compiled.as_text()``).  A device event is an executed HLO instruction;
its scope is the innermost ``lgbm.*`` component of that instruction's
``op_name`` in the HLO text, joined by instruction name.  Own times (an
op's duration less its direct children's) are summed inside the harness's
``bench.*`` annotations, as ``benchmark/lib/trace_reduce.py`` sums them, so
the rows other than the Pallas kernels add up to
``grower_xla_ms_per_tree`` x trees.

Idle (``--idle``): the window is the harness's ``bench.*`` annotations, as
the reducer's (the program's ``lgbm.*`` seams where the trace has no
harness); the first chip's idle gaps in it are cut where a host
annotation (``lgbm.*`` or ``bench.*``) begins or ends, and each piece is
booked to the innermost annotation covering it (``by_innermost_s``: the
pieces add up to ``idle_s``) and to every annotation name covering it
(``under_s``: ``lgbm.engine.eval`` holds the evaluation's pull and
metrics too).  Where nothing covers a piece it reads ``outside_spans``.

Prints one JSON object.  This is the by-hand recipe the benchmark's reducer
needs to read scopes and name idle time itself (PERF.md section 7).
"""
import bisect
import json
import re
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmark.lib import trace_reduce as tr  # noqa: E402

INSTRUCTION = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s.*\bop_name="([^"]*)"')
SCOPE = re.compile(r"lgbm\.[a-z_]+(?:\.[a-z_]+)*")
HOST_PREFIXES = ("lgbm.", tr.ANNOTATION_PREFIX)
OUTSIDE = "outside_spans"


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


def host_annotations(planes):
    """[(name, start_ns, end_ns)] of the ``lgbm.*`` and ``bench.*``
    annotations on every host thread."""
    return [(n, s, s + d) for pname, lines in planes.items()
            if not tr.DEVICE_PLANE.match(pname)
            for events in lines.values() for n, s, d in events
            if n.startswith(HOST_PREFIXES)]


def _covers(spans, cuts):
    """For each elementary segment [cuts[i], cuts[i+1]): (innermost name,
    names covering it), by one sweep over the spans' edges."""
    edges = sorted([(s, 1, k) for k, (_, s, _e) in enumerate(spans)]
                   + [(e, 0, k) for k, (_, _s, e) in enumerate(spans)])
    active, out, j = {}, [], 0
    for t in cuts[:-1]:
        while j < len(edges) and edges[j][0] <= t:
            _, opening, k = edges[j]
            if opening:
                active[k] = spans[k]
            else:
                active.pop(k, None)
            j += 1
        if active:
            inner = min(active.values(), key=lambda v: v[2] - v[1])[0]
            out.append((inner, {v[0] for v in active.values()}))
        else:
            out.append((OUTSIDE, {OUTSIDE}))
    return out


def idle(xplane):
    planes = tr.read_planes(xplane)
    spans = host_annotations(planes)
    harness = [sp for sp in spans if sp[0].startswith(tr.ANNOTATION_PREFIX)]
    frame = harness or spans
    if not frame:
        raise ValueError(f"{xplane}: no lgbm.* or bench.* annotation")
    lo, hi = min(s for _, s, _ in frame), max(e for _, _, e in frame)
    dev = sorted(p for p in planes if tr.DEVICE_PLANE.match(p))[0]
    busy_ns, merged = tr._union(
        [(s, s + d) for _, s, d in planes[dev].get(tr.OPS_LINE, [])
         if s + d > lo and s < hi])
    busy_ns, merged = tr._union(
        [(max(s, lo), min(e, hi)) for s, e in merged])
    inside = [sp for sp in spans if sp[2] > lo and sp[1] < hi]
    cuts = sorted({lo, hi} | {t for _, s, e in inside for t in (s, e)
                              if lo < t < hi})
    cover = _covers(inside, cuts)
    by_inner, under = {}, {}
    for s, e in tr._complement(merged, lo, hi):
        i = bisect.bisect_right(cuts, s) - 1
        while s < e:
            t = min(e, cuts[i + 1])
            inner, names = cover[i]
            by_inner[inner] = by_inner.get(inner, 0.0) + (t - s) / 1e9
            for n in names:
                under[n] = under.get(n, 0.0) + (t - s) / 1e9
            s, i = t, i + 1

    def ranked(d):
        return dict(sorted(d.items(), key=lambda kv: -kv[1]))

    return {"window_s": (hi - lo) / 1e9, "busy_s": busy_ns / 1e9,
            "idle_s": (hi - lo - busy_ns) / 1e9,
            "by_innermost_s": ranked(by_inner), "under_s": ranked(under)}


if __name__ == "__main__":
    if len(sys.argv) == 3 and sys.argv[1] == "--idle":
        print(json.dumps(idle(sys.argv[2]), indent=1))
    elif len(sys.argv) >= 3 and not sys.argv[1].startswith("--"):
        print(json.dumps(split(sys.argv[1],
                               [Path(p).read_text() for p in sys.argv[2:]]),
                         indent=1))
    else:
        sys.exit(__doc__)
