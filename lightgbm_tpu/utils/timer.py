"""Hierarchical wall-clock timing.

reference: Common::Timer + RAII FunctionTimer (include/LightGBM/utils/
common.h:1026-1110), compile-time gated by -DUSE_TIMETAG and dumped at exit
through the single ``global_timer`` (application.cpp:30, tags through the
hot paths e.g. serial_tree_learner.cpp:150,232,262,322; gbdt.cpp:153,211).

Here the gate is runtime: set ``LIGHTGBM_TPU_TIMETAG=1`` in the environment
(or call ``global_timer.enable()``) and every tagged section accumulates
(count, total seconds) under its name; the table prints at interpreter exit
sorted by total time, like Timer::Print.  Disabled, a tagged section costs
one attribute check.

Machine-readable exit dump: ``LIGHTGBM_TPU_TIMETAG=json`` emits a JSON
object to stderr instead of the table; ``LIGHTGBM_TPU_TIMETAG=json:<path>``
writes it to ``<path>`` — so tools and CI journal timer totals
instead of scraping the human table.  ``publish()`` mirrors the totals
into the unified process metrics registry (``obs.metrics``,
docs/OBSERVABILITY.md) as ``timer.<name>.{calls,total_s}`` gauges.

Because device work is asynchronous under jit, host-side sections measure
dispatch + the points where the host blocks (fetching tree arrays, metric
values) — the same wall-clock decomposition the reference reports, with
"device program" time showing up in the section that first blocks on it.
"""

from __future__ import annotations

import atexit
import os
import sys
import threading
import time
from contextlib import contextmanager


class Timer:
    """Accumulating named wall-clock sections (thread-safe)."""

    def __init__(self, enabled: bool | None = None):
        if enabled is None:
            # any non-empty value but "0" enables ("1" = table at exit,
            # "json"/"json:<path>" = machine-readable exit dump)
            enabled = os.environ.get("LIGHTGBM_TPU_TIMETAG", "") \
                not in ("", "0")
        self.enabled = enabled
        self._acc: dict = {}          # name -> [count, total_seconds]
        self._lock = threading.Lock()

    def enable(self) -> None:
        self.enabled = True

    def disable(self) -> None:
        self.enabled = False

    def reset(self) -> None:
        with self._lock:
            self._acc.clear()

    def add(self, name: str, seconds: float) -> None:
        with self._lock:
            slot = self._acc.setdefault(name, [0, 0.0])
            slot[0] += 1
            slot[1] += seconds

    @contextmanager
    def section(self, name: str):
        """``with global_timer.section("GBDT::TrainOneIter"): ...``
        (reference: FunctionTimer RAII guard, common.h:1091-1110)."""
        if not self.enabled:
            yield
            return
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.add(name, time.perf_counter() - t0)

    def items(self):
        with self._lock:
            return {k: tuple(v) for k, v in self._acc.items()}

    def to_dict(self) -> dict:
        """JSON-ready totals: name -> {calls, total_s, mean_ms}."""
        return {
            name: {"calls": cnt, "total_s": round(total, 6),
                   "mean_ms": round(total / cnt * 1e3, 6) if cnt else 0.0}
            for name, (cnt, total) in self.items().items()
        }

    def dump_json(self, path=None) -> str:
        """The machine-readable form of ``print``; writes to ``path``
        when given, returns the JSON string either way."""
        import json
        s = json.dumps({"timers": self.to_dict()}, indent=1, sort_keys=True)
        if path:
            from .file_io import write_atomic
            write_atomic(path, s)
        return s

    def publish(self, registry=None) -> dict:
        """Mirror the totals into the unified process metrics registry
        (default: ``obs.metrics.global_registry``) as
        ``timer.<name>.calls`` / ``timer.<name>.total_s`` gauges, so
        tools journal them with the rest of the snapshot instead
        of scraping stderr.  Returns the mirrored totals."""
        if registry is None:
            from ..obs.metrics import global_registry as registry
        items = self.items()
        for name, (cnt, total) in items.items():
            registry.gauge(f"timer.{name}.calls").set(cnt)
            registry.gauge(f"timer.{name}.total_s").set(round(total, 6))
        return items

    def print(self, file=None) -> None:
        """reference: Timer::Print (common.h:1054-1070)."""
        if file is None:
            file = sys.stderr
        rows = sorted(self.items().items(), key=lambda kv: -kv[1][1])
        if not rows:
            return
        width = max(len(k) for k, _ in rows)
        print("LightGBM-TPU timers (name, calls, total s, mean ms):",
              file=file)
        for name, (cnt, total) in rows:
            print(f"  {name:<{width}}  {cnt:>8}  {total:>10.3f}  "
                  f"{total / cnt * 1e3:>10.3f}", file=file)


global_timer = Timer()


def function_timer(name: str, timer: Timer = global_timer):
    """Decorator form (reference FunctionTimer wraps whole functions)."""

    def wrap(fn):
        import functools

        @functools.wraps(fn)
        def inner(*args, **kwargs):
            if not timer.enabled:
                return fn(*args, **kwargs)
            t0 = time.perf_counter()
            try:
                return fn(*args, **kwargs)
            finally:
                timer.add(name, time.perf_counter() - t0)

        return inner

    return wrap


@atexit.register
def _print_at_exit() -> None:
    if not global_timer.enabled:
        return
    mode = os.environ.get("LIGHTGBM_TPU_TIMETAG", "")
    if mode == "json" or mode.startswith("json:"):
        # an empty path ("json:") falls back to stderr, never silence
        path = (mode[5:] or None) if mode.startswith("json:") else None
        try:
            s = global_timer.dump_json(path)
            if path is None:
                print(s, file=sys.stderr)
        except OSError:
            global_timer.print()
    else:
        global_timer.print()
