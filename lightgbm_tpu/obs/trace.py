"""Structured tracing: a thread-safe span recorder emitting Chrome
trace-event / Perfetto-compatible JSON.

The timer subsystem (utils/timer.py) answers "how much total time went
into section X"; this module answers "WHEN did each occurrence run, on
which thread, nested under what" — the difference between a table and a
timeline.  Spans are recorded through the hot seams of the whole stack
(engine iteration loop, macro-chunk dispatch + host fetch, grower trace
construction, checkpoint save/load, ``resilient_allgather`` attempts,
serving batcher admission -> dispatch -> completion) and dump as one JSON
file that chrome://tracing or ui.perfetto.dev loads directly.

``span(name, **args)`` is the ONE seam API of the program.  Every span
enters a ``jax.profiler.TraceAnnotation("lgbm." + name)``, so it lies on
the profiler's clock beside the device ops whenever a profiler session
runs (``jax.profiler.start_trace`` around ``lgb.train``); with no session
an annotation is a flag check, which is what "tracing off" costs.  A
span opened with ``ring=True`` (the coarse seams: once a round, a chunk,
a construct — never once a row or a leaf) also lands as one record in
the always-on flight ring (``obs/flight.py``): name, start, duration,
``parent`` (the span it ran under, a thread-local stack) and ``it`` (the
boosting iteration, inherited from the enclosing span when not given).
``timer="<tag>"`` feeds the seam's seconds to ``utils.timer.global_timer``
under that tag, so one ``with`` serves both.

Gate of the Chrome-JSON recorder: ``LIGHTGBM_TPU_TRACE`` — unset/"0"
disables (a seam then records no event; there is one span
implementation, and ``Tracer.span`` hands out the same seam bound to its
own tracer); "1" enables recording; any other value enables AND names
the file the trace is dumped to at interpreter exit.
``global_tracer.dump(path)`` dumps on demand.

Event format (Chrome trace-event "JSON object format"): complete events
``{"name", "ph": "X", "ts", "dur", "pid", "tid", "args"}`` with ``ts``/
``dur`` in microseconds since the tracer's epoch, plus instant events
(``"ph": "i"``) for point-in-time facts (planner verdicts, measured HBM
peaks, request admissions).  Events are timestamp-sorted at dump time.

One clock: the epoch is ``Tracer.epoch_ns``, an absolute
``time.perf_counter_ns()`` value, in the dump's ``otherData`` and in
every flight bundle's fingerprint (``trace_epoch_ns``).  A seam takes its
start and end inside its annotation, so a ring record and its ``lgbm.``
annotation differ by the annotation's own entry and exit, and
``ring_offset_ns`` finds the one constant that lays the ring on a
profiler trace's host clock.

Because device work is asynchronous under jit, spans measure HOST time:
dispatch cost lands in the dispatch span and device time surfaces in
whichever span first blocks on a result (the same decomposition
``global_timer`` reports, now with per-occurrence timing).  A span that
only dispatches holds host time only.  This module is stdlib-only at
import; ``jax.profiler`` is looked up at the first span and its absence
is tolerated.
"""

from __future__ import annotations

import atexit
import bisect
import json
import os
import threading
import time
from typing import List, Optional

from ..utils.timer import global_timer

_TRACE_ENV = "LIGHTGBM_TPU_TRACE"
_MAX_EVENTS_ENV = "LIGHTGBM_TPU_TRACE_MAX_EVENTS"
# generous default: ~1M events is hundreds of MB of JSON before a long
# pod run would ever hit it, but it IS a bound — the in-process span
# list can no longer grow without limit (drops are counted, never silent)
_DEFAULT_MAX_EVENTS = 1_000_000

# the flight recorder's ring sink (obs/flight.py installs itself via
# set_flight_sink at import).  Kept as a module global so trace.py never
# imports flight.py (no cycle); None = no recorder armed.
_flight_sink = None


def set_flight_sink(sink) -> None:
    """Install (or clear, with None) the flight-recorder ring that tees
    recorded span/instant events.  Called by obs/flight.py."""
    global _flight_sink
    _flight_sink = sink


def _max_events_env() -> int:
    try:
        v = int(os.environ.get(_MAX_EVENTS_ENV, _DEFAULT_MAX_EVENTS))
    except ValueError:
        return _DEFAULT_MAX_EVENTS
    return v if v > 0 else _DEFAULT_MAX_EVENTS


class Tracer:
    """Thread-safe span/instant recorder with Chrome-trace export."""

    def __init__(self, enabled: Optional[bool] = None,
                 max_events: Optional[int] = None):
        if enabled is None:
            v = os.environ.get(_TRACE_ENV, "")
            enabled = bool(v) and v != "0"
        self.enabled = enabled
        # bounded in-process event list (LIGHTGBM_TPU_TRACE_MAX_EVENTS):
        # beyond the cap new events are DROPPED and counted, so a long
        # pod run cannot grow the span list without bound
        self.max_events = (int(max_events) if max_events is not None
                           else _max_events_env())
        self.dropped = 0
        self._events: List[dict] = []
        self._lock = threading.Lock()
        self._pid = os.getpid()
        # the epoch every ``ts`` counts from, as an absolute
        # ``time.perf_counter_ns()`` value: with it a ring record (or a
        # dumped event) lands on any clock the process also read
        # (``ring_offset_ns`` finds the profiler's)
        self.epoch_ns = time.perf_counter_ns()
        self._epoch = self.epoch_ns / 1e9
        # only the process tracer tees into the flight ring (scratch
        # tracers in tests must not pollute the process forensics)
        self._flight_tee = False

    # ------------------------------------------------------------- control

    def enable(self) -> None:
        self.enabled = True

    def disable(self) -> None:
        self.enabled = False

    def reset(self) -> None:
        with self._lock:
            self._events.clear()
            self.dropped = 0

    # ----------------------------------------------------------- recording

    def span(self, name: str, **args):
        """``with tracer.span("grow_tree", leaves=255): ...`` — the same
        seam as the module's ``span``, recording into THIS tracer."""
        return _Seam(self, name, args)

    def instant(self, name: str, **args) -> None:
        """Point-in-time event (Chrome "i" phase, thread scope)."""
        if not self.enabled:
            return
        ev = {"name": name, "ph": "i", "s": "t", "pid": self._pid,
              "tid": threading.get_ident(),
              "ts": (time.perf_counter() - self._epoch) * 1e6}
        if args:
            ev["args"] = args
        self._append(ev)

    def _event(self, name: str, t0: float, t1: float, args: dict) -> dict:
        ev = {"name": name, "ph": "X", "pid": self._pid,
              "tid": threading.get_ident(),
              "ts": (t0 - self._epoch) * 1e6,
              "dur": (t1 - t0) * 1e6}
        if args:
            ev["args"] = args
        return ev

    def _record(self, name: str, t0: float, t1: float, args: dict) -> None:
        self._append(self._event(name, t0, t1, args))

    def _append(self, ev: dict) -> None:
        dropped_now = None
        with self._lock:
            if len(self._events) >= self.max_events:
                self.dropped += 1
                dropped_now = self.dropped
            else:
                self._events.append(ev)
        sink = _flight_sink
        if sink is not None and self._flight_tee:
            # the flight ring is bounded by construction, so it still
            # sees events the capped span list dropped
            sink.feed(ev)
        if dropped_now is not None:
            # visible both process-wide (gauge) and in the trace dump
            # (an instant is appended at export, see to_chrome_trace)
            from .metrics import global_registry
            global_registry.gauge("trace_events_dropped").set(dropped_now)

    # -------------------------------------------------------------- export

    def events(self) -> List[dict]:
        with self._lock:
            return list(self._events)

    def to_chrome_trace(self, events: Optional[List[dict]] = None) -> dict:
        """Loadable-by-chrome://tracing dict: timestamp-sorted events plus
        a process-name metadata record.  ``events`` restricts the export
        to a subset (e.g. one phase's slice of a shared tracer)."""
        evs = sorted(self.events() if events is None else events,
                     key=lambda e: e.get("ts", 0.0))
        meta = [{"name": "process_name", "ph": "M", "pid": self._pid,
                 "tid": 0, "ts": 0.0,
                 "args": {"name": "lightgbm-tpu"}}]
        if self.dropped:
            evs = evs + [{
                "name": "trace_events_dropped", "ph": "i", "s": "p",
                "pid": self._pid, "tid": 0,
                "ts": (evs[-1]["ts"] if evs else 0.0),
                "args": {"dropped": self.dropped,
                         "max_events": self.max_events}}]
        return {"traceEvents": meta + evs, "displayTimeUnit": "ms",
                "otherData": {"epoch_perf_counter_ns": self.epoch_ns}}

    def dump(self, path: str, events: Optional[List[dict]] = None) -> str:
        """Write the Chrome-trace JSON to ``path`` (atomic); returns it."""
        from ..utils.file_io import write_atomic
        write_atomic(path, json.dumps(self.to_chrome_trace(events)))
        return str(path)

    def mark(self) -> int:
        """Current event count — pass the returned mark to ``since`` to
        slice later events (per-stage export from a shared tracer)."""
        with self._lock:
            return len(self._events)

    def since(self, mark: int) -> List[dict]:
        with self._lock:
            return list(self._events[mark:])


global_tracer = Tracer()
global_tracer._flight_tee = True


_tls = threading.local()
_annotation = None      # jax.profiler.TraceAnnotation; False = no jax here


def _annotation_cls():
    global _annotation
    if _annotation is None:
        try:
            from jax.profiler import TraceAnnotation
            _annotation = TraceAnnotation
        except ImportError:
            _annotation = False
    return _annotation


class _Seam:
    """One live seam of the program (see the module docstring): a
    profiler annotation always, a flight-ring record when ``ring``, a
    ``global_timer`` section when ``timer``, a Chrome-trace event in its
    tracer when that records (always closed: an exception inside the span
    tags ``args["error"]``, so span trees stay well-nested under raises).
    Holds HOST time: a seam that only dispatches ends when the dispatch
    returns, not when the device does."""

    __slots__ = ("_tracer", "name", "args", "timer", "ring", "seconds",
                 "_ann", "_t0", "_parent")

    def __init__(self, tracer: Tracer, name: str, args: dict, timer=None,
                 ring: bool = False):
        self._tracer = tracer
        self.name = name
        self.args = args
        self.timer = timer
        self.ring = ring

    def set(self, **args) -> "_Seam":
        """Attach attributes mid-span (e.g. sums known at the end)."""
        self.args.update(args)
        return self

    def __enter__(self) -> "_Seam":
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        self._parent = None
        if stack:
            self._parent, it = stack[-1]
            if it is not None:
                self.args.setdefault("it", it)
        stack.append((self.name, self.args.get("it")))
        cls = _annotation_cls()
        self._ann = cls("lgbm." + self.name) if cls else None
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        t1 = time.perf_counter()
        self.seconds = t1 - self._t0     # for a caller that sums its seams
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        _tls.stack.pop()
        if self.timer is not None and global_timer.enabled:
            global_timer.add(self.timer, self.seconds)
        tracer = self._tracer
        recording = tracer.enabled
        sink = _flight_sink if self.ring and not recording else None
        if recording or sink is not None:
            args = self.args
            if self._parent is not None:
                args["parent"] = self._parent
            if exc_type is not None:
                args["error"] = exc_type.__name__
            if recording:     # the process tracer tees into the flight ring
                tracer._record(self.name, self._t0, t1, args)
            else:
                sink.feed(tracer._event(self.name, self._t0, t1, args))
        return False


def span(name: str, *, timer: Optional[str] = None, ring: bool = False,
         **args):
    """The seam API: ``with span("macro.dispatch", ring=True, it=it0,
    timer="TreeLearner::Train(dispatch)"): ...`` (module docstring)."""
    return _Seam(global_tracer, name, args, timer, ring)


def instant(name: str, **args) -> None:
    global_tracer.instant(name, **args)
    if not global_tracer.enabled and _flight_sink is not None:
        # instants are rare (planner verdicts, HBM peaks, admissions) and
        # exactly the point-in-time facts a forensic bundle needs — keep
        # feeding the always-on flight ring with tracing off
        _flight_sink.note_instant(name, args)


def trace_enabled() -> bool:
    return global_tracer.enabled


def trace_path() -> Optional[str]:
    """The exit-dump path named by ``LIGHTGBM_TPU_TRACE``, if any."""
    v = os.environ.get(_TRACE_ENV, "")
    if v and v.lower() not in ("0", "1", "on", "true"):
        return v
    return None


def span_coverage(events: List[dict], root_name: str) -> Optional[float]:
    """Fraction of the longest ``root_name`` span's wall-clock covered by
    the union of every other span overlapping it — the "does the span
    tree account for the stage?" number tools/obs_dump.py reports."""
    roots = [e for e in events
             if e.get("name") == root_name and e.get("ph") == "X"]
    if not roots:
        return None
    root = max(roots, key=lambda e: e.get("dur", 0.0))
    lo, hi = root["ts"], root["ts"] + root["dur"]
    if hi <= lo:
        return None
    ivals = []
    for e in events:
        if e is root or e.get("ph") != "X":
            continue
        s = max(e["ts"], lo)
        t = min(e["ts"] + e.get("dur", 0.0), hi)
        if t > s:
            ivals.append((s, t))
    ivals.sort()
    covered, cur_s, cur_t = 0.0, None, None
    for s, t in ivals:
        if cur_t is None or s > cur_t:
            if cur_t is not None:
                covered += cur_t - cur_s
            cur_s, cur_t = s, t
        else:
            cur_t = max(cur_t, t)
    if cur_t is not None:
        covered += cur_t - cur_s
    return covered / (hi - lo)


def ring_offset_ns(ring_events: List[dict], host_events,
                   epoch_ns: Optional[int] = None,
                   tol_ns: float = 1e6) -> Optional[dict]:
    """The constant that lays ring records on a profiler trace:
    ``trace_ns = epoch_ns + ts * 1e3 + offset_ns`` for a record's start.

    ``ring_events`` are complete records as the flight ring (or a bundle's
    ``ring``, or a Chrome dump) holds them, ``ts`` in us since the tracer's
    epoch (``epoch_ns``: ``global_tracer.epoch_ns`` for this process; a
    bundle's ``fingerprint["trace_epoch_ns"]``).  ``host_events`` are
    ``(name, start_ns, dur_ns)`` of any trace's host plane.  A record is
    matched to an ``"lgbm." + name`` annotation by name and order: the
    offset shared by the most (record, annotation) pairs of one name within
    ``tol_ns`` fixes the alignment, then each record takes the annotation
    nearest to it.  Returns ``{"offset_ns", "worst_ns", "matched",
    "unmatched", "no_annotation"}``: the median offset over matched pairs,
    the largest deviation from it, the records inside the trace's span that
    found no annotation of their name, and the names of records that have
    none in the trace at all (``grower.tree`` is a record, not a seam);
    ``None`` when no record matched."""
    if epoch_ns is None:
        epoch_ns = global_tracer.epoch_ns
    starts = {}
    lo = hi = None
    for name, s, d in host_events:
        if name.startswith("lgbm."):
            starts.setdefault(name[5:], []).append(float(s))
            lo = s if lo is None else min(lo, s)
            hi = s + d if hi is None else max(hi, s + d)
    recs = {}
    missing = set()
    for e in ring_events:
        if e.get("ph") != "X":
            continue
        if e["name"] in starts:
            recs.setdefault(e["name"], []).append(
                epoch_ns + float(e["ts"]) * 1e3)
        else:
            missing.add(e["name"])
    diffs = sorted(a - r for name, rs in recs.items()
                   for r in rs for a in starts[name])
    if not diffs:
        return None
    best, j = (0, 0.0), 0
    for i, d in enumerate(diffs):        # densest window of width tol_ns
        while diffs[j] < d - tol_ns:
            j += 1
        if i - j + 1 > best[0]:
            best = (i - j + 1, diffs[(i + j) // 2])
    guess = best[1]
    offsets, unmatched = [], 0
    for name, rs in recs.items():
        free = sorted(starts[name])
        for r in sorted(rs):
            k = bisect.bisect_left(free, r + guess)
            near = [x for x in free[max(k - 1, 0):k + 1]
                    if abs(x - r - guess) <= tol_ns]
            if near:
                a = min(near, key=lambda x: abs(x - r - guess))
                free.remove(a)
                offsets.append(a - r)
            elif lo <= r + guess <= hi:
                unmatched += 1
    if not offsets:
        return None
    offsets.sort()
    mid = offsets[len(offsets) // 2]
    return {"offset_ns": mid,
            "worst_ns": max(abs(x - mid) for x in offsets),
            "matched": len(offsets), "unmatched": unmatched,
            "no_annotation": sorted(missing)}


@atexit.register
def _dump_at_exit() -> None:
    p = trace_path()
    if p and global_tracer.enabled and global_tracer.events():
        try:
            global_tracer.dump(p)
        except OSError:
            pass
