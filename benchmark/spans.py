"""Host spans of the benchmark's own: kept in memory, and, while a device trace
is open, also written into the profiler's trace (``TraceAnnotation``) so that
idle gaps on the device can be laid against what the host was doing."""
from __future__ import annotations

import contextlib
import time


class Spans:
    def __init__(self):
        self.rows: list[tuple[str, float, float]] = []
        self.annotate = False

    @contextlib.contextmanager
    def span(self, name: str):
        ann = None
        if self.annotate:
            import jax
            ann = jax.profiler.TraceAnnotation("bench/" + name)
            ann.__enter__()
        t0 = time.monotonic()
        try:
            yield
        finally:
            t1 = time.monotonic()
            if ann is not None:
                ann.__exit__(None, None, None)
            self.rows.append((name, t0, t1))

    def total(self, name: str, since: float = 0.0) -> float:
        return sum(b - a for n, a, b in self.rows if n == name and a >= since)

    def count(self, name: str, since: float = 0.0) -> int:
        return sum(1 for n, a, _ in self.rows if n == name and a >= since)
