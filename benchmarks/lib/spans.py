"""The benchmark's own spans, kept in memory for the length of a run. A span is (name, start, end) on ``time.perf_counter``;
with ``annotate`` on, each span also goes into the profiler's trace
(``jax.profiler.TraceAnnotation``) so that an idle gap on the device
can be given to what the host was doing in it."""

import contextlib
import time


class Spans:
    def __init__(self):
        self.spans = []      # (name, start_s, end_s)
        self.annotate = False

    @contextlib.contextmanager
    def span(self, name):
        ctx = contextlib.nullcontext()
        if self.annotate:
            import jax

            ctx = jax.profiler.TraceAnnotation("bench." + name)
        t0 = time.perf_counter()
        with ctx:
            try:
                yield
            finally:
                self.spans.append((name, t0, time.perf_counter()))

    def durations(self, name, since=0.0):
        """Seconds of every span ``name`` that started at or after
        ``since``."""
        return [e - s for n, s, e in self.spans if n == name and s >= since]
