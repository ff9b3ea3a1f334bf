"""From a JAX profiler trace (``.xplane.pb``) to the few numbers the
per-layer metrics read.  Needs nothing but ``jax.profiler.ProfileData``.

What a v5e trace looks like (looked at by hand on this PR's first traced
chip run; ``tests/data/trace_small.xplane.pb`` is such a trace): one plane
per chip named ``/device:TPU:<i>`` whose line ``XLA Ops`` holds one event
per executed HLO op (the line ``XLA Modules`` holds one per program, and
``Steps`` one per step), and a plane ``/host:CPU`` whose lines are host
threads, where ``jax.profiler.TraceAnnotation`` spans appear by name.  A
Pallas (Mosaic) kernel is an ``XLA Ops`` event whose opcode is
``custom-call`` and whose target is ``tpu_custom_call``.  An event's name is the op's whole HLO text (kilobytes for
a ``while`` with a long tuple), so names are cut to ``<opcode> %<name>``
(``custom-call:<target> %<name>`` for custom calls).
``XLA Ops`` events nest: a ``while`` spans its body's ops, a ``fusion``
its parts.  Busy time is the union of the intervals; an op's own time is
its duration less its direct children's.

``reduce_trace`` returns a dict:

- ``window_s``      first to last device-or-annotation event, in seconds
- ``devices``       per chip: ``busy_s`` (union of op intervals),
                    ``ops`` {name: own seconds}, ``kernel_s`` (custom calls),
                    ``collective_s``, ``collective_exposed_s``, ``modules``
                    {program name: [runs, seconds]} from the line
                    ``XLA Modules`` (``jit_<function>``, the fingerprint in
                    brackets cut off)
- ``modules``       the first chip's ``modules``, the twenty with most time
- ``busy_s``        mean of the chips' ``busy_s``
- ``device_ops``    the ten ops with most time, [[name, seconds], ...]
- ``idle_gaps``     device-idle seconds by what the host was doing,
                    [[annotation, seconds], ...] (ten largest)
- ``annotations``   {name: [count, seconds]} of the harness's own spans
"""
import bisect
import re
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
MODULE_ID = re.compile(r"\(\d+\)$")
KERNEL_NAME = re.compile(r"^custom-call:tpu_custom_call ")
COLLECTIVE_NAME = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)")
OPCODE = re.compile(r"[\]\}\)] ([a-z][\w-]*)\(")
CALL_TARGET = re.compile(r'custom_call_target="([^"]+)"')
ANNOTATION_PREFIX = "bench."


def find_xplane(trace_dir):
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def _union(intervals):
    """Total length and merged list of [start, end) intervals (ns)."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def _overlap(merged_a, merged_b):
    """Length of the intersection of two merged interval lists."""
    i = j = 0
    total = 0
    while i < len(merged_a) and j < len(merged_b):
        s = max(merged_a[i][0], merged_b[j][0])
        e = min(merged_a[i][1], merged_b[j][1])
        if e > s:
            total += e - s
        if merged_a[i][1] < merged_b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _complement(merged, lo, hi):
    gaps = []
    cur = lo
    for s, e in merged:
        if s > cur:
            gaps.append([cur, s])
        cur = max(cur, e)
    if hi > cur:
        gaps.append([cur, hi])
    return gaps


def short_name(text):
    """``<opcode> %<name>`` of an op's HLO text; other names unchanged."""
    if not text.startswith("%") or " = " not in text:
        return text[:120]
    name, rest = text.split(" = ", 1)
    m = OPCODE.search(rest)
    opcode = m.group(1) if m else "op"
    if opcode == "custom-call":
        # a Mosaic kernel's target is tpu_custom_call; AllocateBuffer and
        # ConcatBitcast are the compiler's zero-time bookkeeping
        t = CALL_TARGET.search(rest)
        opcode += ":" + (t.group(1) if t else "unknown")
    return f"{opcode} {name}"[:120]


def self_times(events):
    """[(name, own_ns)] of nested (name, start, dur) events: each event's
    duration less that of the events directly inside it."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    own = [e[2] for e in events]
    stack = []
    for i in order:
        _, s, d = events[i]
        while stack and events[stack[-1]][1] + events[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= d
        stack.append(i)
    return [(events[i][0], max(own[i], 0.0)) for i in range(len(events))]


def read_planes(path):
    """{plane name: {line name: [(event name, start_ns, dur_ns), ...]}}."""
    import gzip

    from jax.profiler import ProfileData
    path = str(path)
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as fh:
            data = ProfileData.from_serialized_xspace(fh.read())
    else:
        data = ProfileData.from_file(path)
    out = {}
    for plane in data.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        lines = {}
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            lines.setdefault(line.name, []).extend(
                (short_name(ev.name), float(ev.start_ns),
                 float(ev.duration_ns)) for ev in line.events)
        out[plane.name] = lines
    return out


def reduce_planes(planes):
    devices = []
    all_ops = {}
    spans = []          # (name, start, end) of the harness's annotations
    for pname, lines in planes.items():
        if DEVICE_PLANE.match(pname):
            continue
        for events in lines.values():
            spans.extend((n, s, s + d) for n, s, d in events
                         if n.startswith(ANNOTATION_PREFIX))
    lo = min([s for _, s, _ in spans], default=None)
    hi = max([e for _, _, e in spans], default=None)
    for pname in sorted(p for p in planes if DEVICE_PLANE.match(p)):
        events = planes[pname].get(OPS_LINE, [])
        if lo is not None:
            events = [(n, s, d) for n, s, d in events
                      if s + d > lo and s < hi]
        ops = {}
        for n, d in self_times(events):
            ops[n] = ops.get(n, 0.0) + d
        busy_ns, merged = _union([(s, s + d) for _, s, d in events])
        kern = [(s, s + d) for n, s, d in events if KERNEL_NAME.search(n)]
        coll = [(s, s + d) for n, s, d in events
                if COLLECTIVE_NAME.search(n)]
        compute = [(s, s + d) for n, s, d in events
                   if not COLLECTIVE_NAME.search(n)]
        coll_ns, coll_merged = _union(coll)
        _, compute_merged = _union(compute)
        modules = {}
        for n, s, d in planes[pname].get(MODULES_LINE, []):
            if lo is None or (s + d > lo and s < hi):
                m = modules.setdefault(MODULE_ID.sub("", n), [0, 0.0])
                m[0] += 1
                m[1] += d / 1e9
        devices.append({
            "plane": pname, "busy_s": busy_ns / 1e9,
            "ops": {n: d / 1e9 for n, d in ops.items()},
            "kernel_s": sum(e - s for s, e in kern) / 1e9,
            "kernel_calls": len(kern),
            "collective_s": coll_ns / 1e9,
            "collective_exposed_s":
                (coll_ns - _overlap(coll_merged, compute_merged)) / 1e9,
            "modules": modules, "_merged": merged,
        })
        for n, d in ops.items():
            all_ops[n] = all_ops.get(n, 0.0) + d / 1e9
    if not devices:
        raise ValueError("the trace holds no /device:TPU:<i> plane")
    starts = [d["_merged"][0][0] for d in devices if d["_merged"]]
    ends = [d["_merged"][-1][1] for d in devices if d["_merged"]]
    if lo is None:
        lo, hi = min(starts, default=0.0), max(ends, default=0.0)
    # idle gaps of the first chip, attributed to the innermost harness
    # span that covers them
    cuts = sorted({t for _, st, en in spans for t in (st, en)})
    gaps = []
    for s, e in _complement(devices[0]["_merged"], lo, hi):
        # cut a gap where a span begins or ends, so that each piece has
        # one innermost span
        inner = cuts[bisect.bisect_right(cuts, s):bisect.bisect_left(cuts, e)]
        edges = [s] + inner + [e]
        gaps.extend(zip(edges[:-1], edges[1:]))
    by_host = {}
    for s, e in gaps:
        mid = 0.5 * (s + e)
        cover = [(en - st, n) for n, st, en in spans if st <= mid < en]
        name = min(cover)[1] if cover else "outside_harness_spans"
        by_host[name] = by_host.get(name, 0.0) + (e - s) / 1e9
    ann = {}
    for n, s, e in spans:
        c = ann.setdefault(n, [0, 0.0])
        c[0] += 1
        c[1] += (e - s) / 1e9
    nchips = len(devices)
    for d in devices:
        del d["_merged"]
    top = sorted(all_ops.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": (hi - lo) / 1e9,
        "devices": devices,
        "busy_s": sum(d["busy_s"] for d in devices) / nchips,
        "device_ops": [[n, s / nchips] for n, s in top],
        "idle_gaps": [[n, s] for n, s in
                      sorted(by_host.items(), key=lambda kv: -kv[1])[:10]],
        "annotations": ann,
        "modules": dict(sorted(devices[0]["modules"].items(),
                               key=lambda kv: -kv[1][1])[:20]),
    }


def idle_share_percent(reduced):
    """Share of the traced window in which no op ran on the device."""
    if reduced["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])


def reduce_trace(trace_dir_or_file):
    path = Path(trace_dir_or_file)
    if path.is_dir():
        path = find_xplane(path)
    return reduce_planes(read_planes(path))
