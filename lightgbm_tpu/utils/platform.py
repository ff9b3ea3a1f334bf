"""Process-environment helpers: the virtual CPU mesh and the compile cache.

The program runs two ways.  On the chip, JAX finds the TPU by itself and
nothing here is involved but the compile cache.  On the CPU — tests,
multi-device dry runs, CI — a process asks for N virtual devices through
``JAX_PLATFORMS=cpu`` and
``XLA_FLAGS=--xla_force_host_platform_device_count=N``; JAX reads the
first when it is imported and the second when its backend starts, so
both must be in the environment before that (``tests/conftest.py`` sets
them at its top; ``cpu_mesh_env`` builds the environment of a child).
"""
from __future__ import annotations

import collections
import os
import threading
import time

_DEVICE_COUNT_FLAG = "--xla_force_host_platform_device_count"


def cpu_mesh_env(n_devices: int = 8, base=None) -> dict:
    """Environment for a child process that must run on N virtual CPU
    devices."""
    env = dict(os.environ if base is None else base)
    env["JAX_PLATFORMS"] = "cpu"
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if not f.startswith(_DEVICE_COUNT_FLAG)]
    flags.append(f"{_DEVICE_COUNT_FLAG}={n_devices}")
    env["XLA_FLAGS"] = " ".join(flags)
    return env


def force_cpu_inprocess(n_devices: int = 8) -> None:
    """Pin THIS process's JAX to N virtual CPU devices.  Must run before
    the first backend access (a tool's ``main`` calls it first thing)."""
    env = cpu_mesh_env(n_devices)
    os.environ["JAX_PLATFORMS"] = env["JAX_PLATFORMS"]
    os.environ["XLA_FLAGS"] = env["XLA_FLAGS"]
    import jax
    jax.config.update("jax_platforms", "cpu")


# ----------------------------------------------------------------------
# the persistent compile cache: ONE rule, for lgb.train / cv / serve, the
# multi driver and chip_smoke.py alike
# ----------------------------------------------------------------------

def _repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def compile_cache_dir() -> str:
    """Where compiled programs persist: ``JAX_COMPILATION_CACHE_DIR`` when
    the environment sets it, else the fixed ``<checkout>/.jax_cache``.
    The path is part of the cache key, so it never carries a temporary
    name, a pid or a time.  The serving AOT store (``serving/``,
    fleet/aot.py) hangs off the same directory."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
            or os.path.join(_repo_root(), ".jax_cache"))


def enable_compile_cache(family=None) -> str:
    """Turn the persistent XLA compilation cache on for THIS process and
    return its directory.  Idempotent; on by default.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX's own handling of it
    is the whole story and the program sets no directory.  Where it is
    not, the cache goes to ``compile_cache_dir()``.  The min-entry
    thresholds drop either way, so every program that took real compile
    time is banked regardless of blob size (the default 1 MiB floor
    would skip most of this repo's per-iteration programs).

    ``family`` ("train", "serving") keys the warmth GAUGES by program
    family so a cold start is attributable: the train family's warmth
    counts JIT blobs only, the serving family's counts its AOT export
    store, and the reserved subtree (``serving/``) never inflates
    another family's count.
    """
    import jax

    from ..obs.metrics import global_registry
    d = compile_cache_dir()
    os.makedirs(d, exist_ok=True)
    entries = compile_cache_entries(d)
    global_registry.gauge("compile_cache_entries_at_init").set(entries)
    global_registry.gauge("compile_cache_warm").set(entries > 0)
    if family:
        fam_entries = entries
        if family == "serving":
            fam_entries = compile_cache_entries_by_family(d).get(
                "serving_aot", 0)
        global_registry.gauge(
            f"compile_cache_entries_at_init:{family}").set(fam_entries)
        global_registry.gauge(
            f"compile_cache_warm:{family}").set(fam_entries > 0)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip():
        jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.2)
    _count_compiles()
    return d


_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_READ_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_compile_listener = None


def _count_compiles() -> None:
    """Register (once a process) the listener on ``jax.monitoring`` that
    says what a start cost: registry counters ``compile_trace_seconds``,
    ``compile_lower_seconds``, ``compile_backend_seconds`` (real backend
    compiles), ``compile_cache_read_seconds`` (persistent-cache hits) and
    ``compile_programs_total`` (programs compiled or read).
    JAX times a cache hit's read inside its backend-compile event, and an
    inner jitted function's trace inside the trace that called it (the
    inner event ends first); both are taken out, so the four seconds are
    disjoint and add up."""
    global _compile_listener
    if _compile_listener is not None:
        return
    import jax.monitoring as monitoring

    from ..obs.metrics import global_registry
    # per thread: the cache read since its last backend event, and the
    # (start, seconds) of its outermost traces so far
    reading = threading.local()

    def on_duration(event, seconds, **_kw):
        if event == _TRACE_EVENT:
            tops = getattr(reading, "tops", None)
            if tops is None:
                tops = reading.tops = collections.deque(maxlen=1 << 16)
            start = time.monotonic() - seconds
            inner = 0.0
            while tops and tops[-1][0] >= start - 1e-4:
                inner += tops.pop()[1]
            tops.append((start, seconds))
            global_registry.counter("compile_trace_seconds").inc(
                max(seconds - inner, 0.0))
        elif event == _LOWER_EVENT:
            global_registry.counter("compile_lower_seconds").inc(seconds)
        elif event == _CACHE_READ_EVENT:
            reading.s = getattr(reading, "s", 0.0) + seconds
            global_registry.counter(
                "compile_cache_read_seconds").inc(seconds)
        elif event == _BACKEND_EVENT:
            read, reading.s = getattr(reading, "s", 0.0), 0.0
            global_registry.counter("compile_programs_total").inc()
            global_registry.counter("compile_backend_seconds").inc(
                max(seconds - read, 0.0))

    # made now, so that a start that read or compiled nothing says 0
    for name in ("compile_trace_seconds", "compile_lower_seconds",
                 "compile_backend_seconds", "compile_cache_read_seconds",
                 "compile_programs_total"):
        global_registry.counter(name)
    monitoring.register_event_duration_secs_listener(on_duration)
    _compile_listener = on_duration


# reserved non-JIT subtree of the cache dir: the serving AOT export
# store (fleet/aot.py) lives BESIDE the XLA blob pool and must never
# count as JIT warmth
_CACHE_RESERVED_SUBDIRS = ("serving",)


def compile_cache_entries(path=None) -> int:
    """Number of banked XLA JIT blobs under the cache dir (0 when it does
    not exist yet) — the cold-vs-warm discriminator.

    Counts the JIT pool ONLY: the reserved ``serving/`` (AOT exports)
    subtree is excluded, so a serving-only prior run cannot make a
    training cold start report warm."""
    d = path or compile_cache_dir()
    total = 0
    for root, dirs, files in os.walk(d):
        if root == d:
            dirs[:] = [s for s in dirs if s not in _CACHE_RESERVED_SUBDIRS]
        total += len(files)
    return total


def compile_cache_entries_by_family(path=None) -> dict:
    """Entry counts under the cache dir, keyed by what each entry IS:
    ``jit`` for the shared XLA blob pool and ``serving_aot`` for the
    exported-program store (``<dir>/serving``, fleet/aot.py)."""
    d = path or compile_cache_dir()
    out = {"jit": compile_cache_entries(d)}
    sub = os.path.join(d, "serving")
    if os.path.isdir(sub):
        out["serving_aot"] = sum(len(files) for _, _, files in os.walk(sub))
    return out
