"""The harness's own spans: seconds on the host clock around calls that
end in a device sync, mirrored into the profiler's trace by name."""
import time
from contextlib import contextmanager


class Spans:
    def __init__(self):
        self.seconds = {}     # name -> total seconds
        self.samples = {}     # name -> [seconds, ...]

    @contextmanager
    def span(self, name):
        from jax.profiler import TraceAnnotation
        t0 = time.perf_counter()
        with TraceAnnotation("bench." + name):
            yield
        dt = time.perf_counter() - t0
        self.add(name, dt)

    def add(self, name, dt):
        """Seconds measured by the caller (a span that opens in one
        callback and closes in another)."""
        self.seconds[name] = self.seconds.get(name, 0.0) + dt
        self.samples.setdefault(name, []).append(dt)


class CompileCounter:
    """Counts compilations (and persistent-cache loads) while ``active``,
    through JAX's monitoring hook: the window has to see none."""

    def __init__(self):
        import jax.monitoring as monitoring
        self.active = False
        self.count = 0
        self.total = 0
        self.names = []
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, _secs, **_kw):
        if "backend_compile" in name or "cache_retrieval" in name:
            self.total += 1
            if self.active:
                self.count += 1
                self.names.append(name)
